"""What the program's compile log (``tpu_tfrecord.compile_cache.events``)
holds of set-up: the records that ended before the first window opened, on
the window's own ``time.perf_counter()`` clock. What the float32 reference
compiles after the window is left out.

``phase``: ``trace`` | ``lower`` | ``backend`` | ``cache_read`` |
``kernel_trace``, or ``all``. ``what``: ``seconds`` (the records' union: a
function traced inside another's trace, or a cache read inside its compile,
counts each moment once), ``count`` (records), ``misses`` (``backend`` records
whose program the persistent cache did not hold). None where the program
keeps no such log (a commit before it had one) or never started it (a
rehearsal does not call ``compile_cache.enable()``)."""


def union_seconds(spans) -> float:
    total, reach = 0.0, float("-inf")
    for begin, end in sorted(spans):
        total += max(0.0, end - max(begin, reach))
        reach = max(reach, end)
    return total


def read(ctx, phase: str, what: str):
    from tpu_tfrecord import compile_cache

    events = getattr(compile_cache, "events", None)
    found = events(until=ctx["measured"]["windows"][0][0]) if events is not None else None
    if found is None:
        return None
    if phase != "all":
        found = [r for r in found if r.phase == phase]
    if what == "seconds":
        return union_seconds((r.begin, r.end) for r in found)
    if what == "misses":
        return sum(1 for r in found if r.cache == "miss")
    if what == "count":
        return len(found)
    raise ValueError(f"compile_events cannot read {what!r}")
