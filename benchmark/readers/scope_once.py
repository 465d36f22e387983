"""Device time by the program's scopes with every operation counted ONCE.

On a TPU the profiler's ``XLA Ops`` line holds, beside the operations of a
``while``'s body, an event for the ``while`` itself that spans them all
(PR 27: the chunk loop of the delta rule is 36 ms a layer, the loop over
the expert tiles 5 ms). ``trace_device`` and ``trace_scope`` sum both, so
a looped scope reads up to twice what the chip spent there, and fusing the
loop away would show as a gain that is half the second count going. This
reader leaves out every ``while`` or ``conditional`` event that encloses
the event after it (a loop's own event, and an outer loop's around an
inner one) and sums the rest as
``trace_scope`` does, by the innermost ``tfr.`` scope of an operation's
``op_name`` or of its body:

    scopes=[...]     ms a step under any of these scopes
    quantity="all"   ms a step under any scope or none: the scopes and the
                     unscoped time add up to it, and it is the chip's busy
                     time a step (``trace_device``'s union)

One information line, ``[scopes_once]``: seconds by scope, and the
enclosing events left out with their seconds. None where there is nothing
to read: no chip, or a program without scopes (this PR's parent).
"""

from __future__ import annotations

from collections import defaultdict

from benchmark.harness import trace_reduce
from benchmark.readers import trace_scope


CONTROL_FLOW = ("while", "conditional")  # XLA names an instruction after its opcode


def leaves(ops: list, names: dict) -> tuple:
    """(the [(metadata id, start, duration)] counted, the control-flow events
    left out because the event after them lies inside them). ``ops`` are one
    chip's ``XLA Ops`` events, which run one after the other except that a
    loop's own event spans its body's; ``names`` {metadata id: display name}."""
    ordered = sorted(ops, key=lambda e: (e[1], -e[2]))
    kept, enclosing = [], []
    for this, after in zip(ordered, ordered[1:] + [None]):
        spans_next = after is not None and after[1] + after[2] / 2 < this[1] + this[2]
        if spans_next and names.get(this[0], "").startswith(CONTROL_FLOW):
            enclosing.append(this)
        else:
            kept.append(this)
    return kept, enclosing


def seconds_once(planes: dict) -> tuple:
    """({scope or None: seconds}, {display name: seconds} of the enclosing
    events left out) over the first chip's ``XLA Ops``."""
    chips = sorted(p for p in planes if p.startswith(trace_reduce.DEVICE_PLANE))
    if not chips:
        return {}, {}
    chip, modules = planes[chips[0]], trace_scope.hlo_modules(planes)
    scope_by_event = {}
    for mid, record in chip["events"].items():
        scope = trace_scope.scope_of(record.get("tf_op"))
        if scope is None and "tf_op" not in record and record.get("program_id") in modules:
            scope = trace_scope.body_scope(modules[record["program_id"]], record["display_name"])
        scope_by_event[mid] = scope
    kept, enclosing = leaves(chip["lines"].get(trace_reduce.OPS_LINE, []),
                             {mid: r.get("display_name", "") for mid, r in chip["events"].items()})
    by_scope, left_out = defaultdict(float), defaultdict(float)
    for mid, _, duration_ns in kept:
        by_scope[scope_by_event.get(mid)] += duration_ns / 1e9
    for mid, _, duration_ns in enclosing:
        left_out[chip["events"].get(mid, {}).get("display_name", "?")] += duration_ns / 1e9
    return dict(by_scope), dict(left_out)


def parsed(ctx):
    """{scope or None: seconds} of the run's trace, read once a run."""
    if "scope_once" not in ctx:
        path = trace_reduce.find_trace(ctx["env"].trace_dir)
        ctx["scope_once"] = None
        if path is not None:
            with open(path, "rb") as f:
                by_scope, left_out = seconds_once(trace_scope.parse_xspace(f.read()))
            ctx["scope_once"] = by_scope
            ctx["env"].info("scopes_once", seconds={str(k): v for k, v in by_scope.items()},
                            enclosing_left_out=sorted(left_out.items(), key=lambda kv: -kv[1])[:8])
    return ctx["scope_once"]


def read(ctx, scopes=(), quantity: str = "ms_per_step"):
    by_scope, steps = parsed(ctx), ctx["trace"]["steps"]
    if not by_scope or not steps or not any(by_scope):  # no chip, or no scope anywhere
        return None
    if quantity == "ms_per_step":
        return sum(by_scope.get(s, 0.0) for s in scopes) / steps * 1e3
    if quantity == "all":
        return sum(by_scope.values()) / steps * 1e3
    raise ValueError(f"unknown quantity {quantity!r}")
