"""The least time the chip could take for a step over the device time it
took: the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s, from the
model's ``needs()``; an information line says which bound holds."""


def read(ctx):
    env, trace, peaks = ctx["env"], ctx["trace"], ctx["peaks"]
    if peaks is None or not trace["events"] or not trace["steps"]:
        return None
    needs = env.model.needs(env.cfg, env.mix["batch"], env.mix["loop"])
    by_flops = needs["flops"] / peaks["flops_per_s"]
    by_bytes = needs["bytes"] / peaks["bytes_per_s"]
    least = max(by_flops, by_bytes)
    step_s = trace["op_s"] / trace["steps"]
    env.info("roofline", bound="bytes" if by_bytes >= by_flops else "flops",
             least_ms=least * 1e3, by_flops_ms=by_flops * 1e3, by_bytes_ms=by_bytes * 1e3,
             step_device_ms=step_s * 1e3, **needs)
    return 100.0 * least / step_s
