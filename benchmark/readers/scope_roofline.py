"""A kernel's share of its roofline: the least time the chip could take for
what ``needs(cfg, batch, loop)["scopes"][scope]`` says the mathematics asks
(the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s, ``harness/
peaks.json``) over the device time a step spent under that scope, every
operation counted once (``scope_once``'s reduction), in %. None where there is nothing to read:
no chip, a program without the scope, a model whose ``needs`` names none."""

from benchmark.readers import scope_once


def read(ctx, scope: str):
    env, peaks, steps = ctx["env"], ctx["peaks"], ctx["trace"]["steps"]
    by_scope = scope_once.parsed(ctx)
    if peaks is None or not steps or not by_scope or not by_scope.get(scope):
        return None
    asked = env.model.needs(env.cfg, env.mix["batch"], env.mix["loop"]).get("scopes", {})
    if scope not in asked:
        return None
    by_flops = asked[scope]["flops"] / peaks["flops_per_s"]
    by_bytes = asked[scope]["bytes"] / peaks["bytes_per_s"]
    took_s = by_scope[scope] / steps
    env.info("scope_roofline", scope=scope, bound="bytes" if by_bytes >= by_flops else "flops",
             by_flops_ms=by_flops * 1e3, by_bytes_ms=by_bytes * 1e3, took_ms=took_s * 1e3)
    return 100.0 * max(by_flops, by_bytes) / took_s
