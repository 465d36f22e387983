"""What the program's host log (``tpu_tfrecord.tracing.host_events``) holds
of a run: every hand-off of the feed (``tfr:*``), every pause of the host
(``host:pause``) and every collection of 1 ms or more (``host:gc``), on the
window's own ``time.perf_counter()`` clock, profiler or not.

``part``: ``window`` (every part of the window), ``untraced`` (every part
but the first when the run has two: a ``--trace 1`` run, whose first part the
profiler covered; None when it has one) or ``setup`` (what ended before the
first window opened). ``what`` over the records called ``names``:

    sum_ms, max_ms   their durations (0 where the part holds none)
    median_ms        likewise (None where it holds none)
    sum_s            sum_ms in seconds
    busy_pct         thread-seconds under them for each second of the part, a
                     record cut to the part (the blocked shares)
    per_ms           their durations summed, for each record called ``per``

All but ``busy_pct`` take the records that BEGAN in the part (``setup``: that
ended before the window). None where the program keeps no such log (a commit
before it had one) or never started its watch (a rehearsal does not call
``compile_cache.enable()``), and None where the bounded ring has dropped
records the part may have held.

The first call of a run prints ``[host_log]`` (records, dropped, the window's
pauses and collections with their fields, set-up's longest pauses with what
the compile log had open at their middle) and, where the run left a trace,
``[idle_host]``: for the five longest idle gaps of the first chip, every record
of the log open at the gap's middle, the log's clock mapped to the trace's by
the spans both hold (``tfr:h2d``).
"""

from __future__ import annotations

import statistics

import numpy as np

from benchmark.harness import trace_reduce

#: the span both clocks hold once a batch, by which one is mapped to the other
ANCHOR = "tfr:h2d"


def parts_of(windows, part: str):
    """[(begin, end), ...] of ``part`` on the windows' clock, or None."""
    if part == "window":
        return list(windows)
    if part == "untraced":
        return list(windows[1:]) or None
    if part == "setup":
        return [(float("-inf"), windows[0][0])]
    raise ValueError(f"host_log knows no part {part!r}")


def clock_offset(trace_s, log_s):
    """(seconds to add to the log's clock to get the trace's, spans matched,
    how far the matched spans disagree) from the begins of the same spans on
    both clocks, the log's a superset in order; None where they cannot be
    matched. The shift whose differences agree best is the match."""
    trace_s, log_s = np.sort(np.asarray(trace_s, float)), np.sort(np.asarray(log_s, float))
    n = len(trace_s)
    if n == 0 or len(log_s) < n:
        return None
    best = None
    for shift in range(len(log_s) - n + 1):
        apart = trace_s - log_s[shift:shift + n]
        disagree = float(apart.max() - apart.min())
        if best is None or disagree < best[2]:
            best = (float(np.median(apart)), n, disagree)
    return best


def idle_host(planes: dict, records, traced, gaps: int = 5):
    """{"offset_s", "matched", "disagree_us", "gaps": [[gap ms, [[name, ms it
    had been open at the gap's middle, its ms], ...]], ...]} or None where the
    trace holds no chip or the clocks cannot be matched."""
    chips = sorted(p for p in planes if p.startswith(trace_reduce.DEVICE_PLANE))
    if not chips:
        return None
    anchors = []
    for name, plane in planes.items():
        if name.startswith("/host:"):
            wanted = {mid for mid, r in plane["events"].items() if r["name"] == ANCHOR}
            anchors += [s / 1e9 for events in plane["lines"].values()
                        for mid, s, _ in events if mid in wanted]
    near = [r.begin for r in records
            if r.name == ANCHOR and traced[0] - 2.0 <= r.begin < traced[1] + 2.0]
    found = clock_offset(anchors, near)
    if found is None:
        return None
    offset, matched, disagree = found
    ops = planes[chips[0]]["lines"].get(trace_reduce.OPS_LINE, [])
    longest = sorted(trace_reduce.gaps_between((s, s + d) for _, s, d in ops),
                     key=lambda ab: ab[0] - ab[1])[:gaps]
    out = []
    for a, b in longest:
        mid = (a + b) / 2e9 - offset
        open_ = [[r.name, (mid - r.begin) * 1e3, (r.end - r.begin) * 1e3]
                 for r in records if r.begin <= mid < r.end]
        out.append([(b - a) / 1e6, sorted(open_, key=lambda row: -row[1])])
    return {"offset_s": offset, "matched": matched, "disagree_us": disagree * 1e6, "gaps": out}


def _lines(ctx, records, dropped: int) -> None:
    """The run's ``[host_log]`` and ``[idle_host]`` lines."""
    from benchmark.readers import trace_scope
    from tpu_tfrecord import compile_cache
    from tpu_tfrecord.metrics import METRICS

    env, windows = ctx["env"], ctx["measured"]["windows"]
    inside = [r for r in records if any(t0 <= r.begin < t1 for t0, t1 in windows)]
    by_name = {}
    for r in records:
        by_name[r.name] = by_name.get(r.name, 0) + 1
    building = compile_cache.events(until=windows[0][0]) or []

    def told(r):
        return {"at_s": r.begin - windows[0][0], "ms": (r.end - r.begin) * 1e3, **(r.args or {})}

    def told_of_setup(r):  # and what the compile log had open at the pause's middle
        mid = (r.begin + r.end) / 2
        during = sorted({f"{e.phase}:{e.fun}" for e in building if e.begin <= mid < e.end})
        return {**told(r), "during": during}

    collections = sorted((r for r in inside if r.name == "host:gc"),
                         key=lambda r: r.begin - r.end)
    before = sorted((r for r in records if r.name == "host:pause" and r.end < windows[0][0]),
                    key=lambda r: r.begin - r.end)
    every = METRICS.stage("host.gc")  # the young ones too; the watch folds them in once a second
    env.info("host_log", records=len(records), dropped=dropped, by_name=by_name,
             window_records=len(inside),
             pauses=[told(r) for r in inside if r.name == "host:pause"],
             collections=len(collections), collections_longest=[told(r) for r in collections[:5]],
             collections_of_any_length={"count": every.records, "seconds": every.seconds},
             setup_pauses=len(before), setup_pause_s=sum(r.end - r.begin for r in before),
             setup_pauses_longest=[told_of_setup(r) for r in before[:5]])
    path = trace_reduce.find_trace(env.trace_dir) if env.traced else None
    if path is not None:
        with open(path, "rb") as f:
            planes = trace_scope.parse_xspace(f.read())
        found = idle_host(planes, records, (env.traced["t0"], env.traced["t1"]))
        if found is not None:
            env.info("idle_host", **found)


def _log(ctx):
    """The run's records and where the ring's memory begins, read once a run
    and kept on ``ctx``; None where there is no log to read."""
    if "host_log" not in ctx:
        from tpu_tfrecord import tracing

        events = getattr(tracing, "host_events", None)
        ctx["host_log"] = None
        if events is not None and tracing.watching():
            records, dropped = events(), tracing.host_log_dropped()
            # a record is written as its region closes, so what the ring let
            # go ended before everything it still holds
            kept_from = min((r.end for r in records), default=float("inf")) if dropped \
                else float("-inf")
            ctx["host_log"] = (records, kept_from)
            if "env" in ctx:
                _lines(ctx, records, dropped)
    return ctx["host_log"]


def read(ctx, what: str, names=None, per=None, part: str = "window"):
    log = _log(ctx)
    spans = parts_of(ctx["measured"]["windows"], part)
    if log is None or spans is None:
        return None
    records, kept_from = log
    if spans[0][0] < kept_from:
        return None
    names = set(names or ())
    if part == "setup":
        began = [r for r in records if r.end < spans[0][1]]
    else:
        began = [r for r in records if any(t0 <= r.begin < t1 for t0, t1 in spans)]
    durations = [r.end - r.begin for r in began if r.name in names]
    if what == "sum_ms":
        return sum(durations) * 1e3
    if what == "sum_s":
        return sum(durations)
    if what == "max_ms":
        return max(durations, default=0.0) * 1e3
    if what == "median_ms":
        return statistics.median(durations) * 1e3 if durations else None
    if what == "per_ms":
        count = sum(1 for r in began if r.name == per)
        return sum(durations) / count * 1e3 if count else None
    if what == "busy_pct":
        under = sum(max(0.0, min(r.end, t1) - max(r.begin, t0))
                    for r in records if r.name in names for t0, t1 in spans)
        return 100.0 * under / sum(t1 - t0 for t0, t1 in spans)
    raise ValueError(f"host_log cannot read {what!r}")
