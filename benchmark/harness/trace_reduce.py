"""From one profiler trace (``*.xplane.pb``) to the device's numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. On a TPU v5e the
trace has one plane a chip, ``/device:TPU:<n>``, whose line ``XLA Ops``
holds one event for each operation the chip ran and whose line ``XLA
Modules`` holds one event for each program it ran; the host's plane
``/host:CPU`` holds a line a thread, with the benchmark's own
``TraceAnnotation`` spans among its events. All share one clock.

    op_s      sum of the operations' durations, averaged over the chips
    busy_s    the union of their intervals, averaged over the chips
    steps     how often the program with the most device time ran
    top_ops   [[name, seconds], ...] by total time, largest first
    idle_gaps [[host span open on the loop's thread, seconds], ...] the
              longest gaps between operations on the first chip, each
              named by the benchmark's span that covered its middle
    host      {event name on the host's plane: [count, total seconds]}: the
              benchmark's spans and the program's own (``tfr:decode``, ...)

The device's clock and the host's agree to about a millisecond in these
traces (the first ``split_wire`` ran 0.9 ms before the span that dispatched
it began), so a gap shorter than that may be named by its neighbour.

``benchmark/tests/test_trace_reduce.py`` checks this on a recorded trace.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

#: the spans the step loop opens on its own thread (harness/window.py, loops/)
LOOP_SPANS = ("wait_batch", "dispatch_split", "dispatch_step", "observe")
DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def union_seconds(intervals) -> float:
    """Length of the union of (start, end) pairs."""
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps_between(intervals):
    """(start, end) of every gap between merged (start, end) pairs."""
    out, end = [], None
    for a, b in sorted(intervals):
        if end is not None and a > end:
            out.append((end, a))
        end = b if end is None else max(end, b)
    return out


def load_planes(path: str) -> dict:
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = defaultdict(list)
        for line in plane.lines:
            for ev in line.events:
                lines[line.name].append((ev.name, float(ev.start_ns), float(ev.duration_ns)))
        planes[plane.name] = dict(lines)
    return planes


def reduce_planes(planes: dict, window_s: float, top: int = 10, gaps: int = 5) -> dict:
    chips = sorted(p for p in planes if p.startswith(DEVICE_PLANE))
    out = {"events": 0, "op_s": 0.0, "busy_s": 0.0, "steps": 0, "window_s": window_s,
           "top_ops": [], "idle_gaps": [], "chips": len(chips), "host": {}}
    for plane, lines in planes.items():
        if plane.startswith("/host:"):
            for events in lines.values():
                for name, _, d in events:
                    entry = out["host"].setdefault(name, [0, 0.0])
                    entry[0] += 1
                    entry[1] += d / 1e9
    if not chips:
        return out
    by_name = defaultdict(float)
    for chip in chips:
        ops = planes[chip].get(OPS_LINE, [])
        out["events"] += len(ops)
        out["op_s"] += sum(d for _, _, d in ops) / 1e9 / len(chips)
        out["busy_s"] += union_seconds((s, s + d) for _, s, d in ops) / 1e9 / len(chips)
        for name, _, d in ops:
            by_name[name] += d / 1e9 / len(chips)
    out["top_ops"] = [[n, s] for n, s in sorted(by_name.items(), key=lambda kv: -kv[1])[:top]]
    modules = defaultdict(lambda: [0, 0.0])
    for name, _, d in planes[chips[0]].get(MODULES_LINE, []):
        modules[name][0] += 1
        modules[name][1] += d
    if modules:
        out["steps"] = max(modules.values(), key=lambda cd: cd[1])[0]
    # the loop's spans, wherever the host's plane keeps that thread
    spans = [
        (s, s + d, name)
        for plane, lines in planes.items() if not plane.startswith(DEVICE_PLANE)
        for events in lines.values()
        for name, s, d in events if name in LOOP_SPANS
    ]
    first = planes[chips[0]].get(OPS_LINE, [])
    longest = sorted(gaps_between((s, s + d) for _, s, d in first),
                     key=lambda ab: ab[0] - ab[1])[:gaps]
    for a, b in longest:
        mid = (a + b) / 2
        covering = [name for s, e, name in spans if s <= mid < e]
        out["idle_gaps"].append([covering[0] if covering else "no_span", (b - a) / 1e9])
    return out


def find_trace(trace_dir: str):
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce_dir(trace_dir: str, window_s: float) -> dict:
    path = find_trace(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return reduce_planes(load_planes(path), window_s)
