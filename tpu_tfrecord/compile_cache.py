"""Where XLA's persistent compilation cache lives, and what building the
programs cost — one owner.

Every entry point that jits (``chip_smoke.py``, ``benchmark/run.py``, the
examples, ``serving.main``) calls :func:`enable` before its first compile.
The directory is placed from OUTSIDE: where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it by itself and this module sets nothing; where it is
not, the cache goes to ``<checkout>/.jax_cache`` — a fixed, git-ignored
path (the path is part of the cache key, so a directory that moves never
hits; no temp name, pid or time ever goes into it). ``tests/conftest.py``
does not call this: tests compile tiny programs and must not depend on
what an earlier run left on disk.

:func:`enable` also starts the compile log. JAX reports, in-process and by
program name, every trace of a jitted function, every lowering to an MLIR
module, every backend compile (or the read of the persistent cache that
stands in for one) and every cache hit (``jax.monitoring``). Each becomes
one :class:`Event` on the ``time.perf_counter()`` clock in a bounded log,
is folded into ``metrics.METRICS`` (stages ``compile.trace``,
``compile.lower``, ``compile.backend``, ``compile.cache_read``; counters
``compile.cache_hits``, ``compile.cache_misses``) and, where the flight
recorder is on, goes to ``telemetry.record_span`` under the stage's name.
:class:`kernel_trace` does the same for the Python block that builds one
Pallas kernel while a program is traced (stages ``kernel.trace.<kernel>``,
phase ``kernel_trace``). :func:`events` and :func:`summary` read the log:
what a slow start was spent on, program by program, and which program a
window recompiled. :func:`enable` also starts the host watch
(``tracing.watch_host``): what the host's pauses and the collector took of
the same start is in the host log, on the same clock.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, NamedTuple, Optional

from tpu_tfrecord import telemetry, tracing
from tpu_tfrecord.metrics import METRICS, timed

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The fixed fallback: ``.jax_cache`` beside the package, i.e. at the root
#: of the checkout (listed in .gitignore).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)

#: Records the log keeps; older ones are dropped and counted. A run of the
#: benchmark's largest token cell makes 8,400 (nine in ten of them jitted
#: ``jnp`` helpers traced inside a program's trace), and its readers ask for
#: set-up's records when the run is over.
LOG_CAPACITY = 16384

#: JAX's time-span events -> the log's phase.
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
#: JAX's cache events -> what they say of the program being compiled on the
#: thread. A request that is neither hit nor (yet) written is a miss too:
#: JAX fires ``cache_misses`` only for an executable it goes on to store.
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_misses": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
#: What JAX calls the program of a lowering or a compile whose function has
#: no ``__name__`` (``jax.jit(functools.partial(step, ...))``); its trace has
#: the function's own name, and a jit's phases follow each other on a thread.
_UNNAMED = "<unknown>"
_FOLLOWS = {"lower": "trace", "backend": "lower"}


class Event(NamedTuple):
    """One record of the compile log. ``begin`` / ``end`` are
    ``time.perf_counter()`` seconds; ``cache`` is ``hit`` / ``miss`` / ``off``
    on a ``backend`` record and None on every other."""

    phase: str  # trace | lower | backend | cache_read | kernel_trace
    fun: str
    begin: float
    end: float
    thread: int
    cache: Optional[str] = None


class _Log:
    def __init__(self, capacity: int):
        self.lock = threading.Lock()
        self.records: deque = deque(maxlen=capacity)
        self.dropped = 0
        # JAX stamps its spans with time.time(); the log is on perf_counter
        self.offset = time.perf_counter() - time.time()
        # thread -> {"cache": hit | miss, "read": (begin, end)}: what JAX said
        # between a program's lowering and the close of its backend span
        self.pending: Dict[int, dict] = {}
        self.listener_s = 0.0

    def counted_inside(self, phase: str, thread: int, begin: float) -> float:
        """Seconds records of ``phase`` on ``thread`` that began at or after
        ``begin`` already gave their stage: a traced function that calls a
        jitted one closes the inner span first, inside its own."""
        inside = 0.0
        for rec, counted in reversed(self.records):
            if rec.phase == phase and rec.thread == thread:
                if rec.end <= begin:
                    break
                inside += counted
        return inside

    def named_before(self, phase: str, thread: int, begin: float) -> Optional[str]:
        """The program of ``thread``'s latest ``phase`` record that ended by
        ``begin``."""
        for rec, _ in reversed(self.records):
            if rec.phase == phase and rec.thread == thread and rec.end <= begin:
                return rec.fun
        return None

    def append(self, rec: Event, counted: float) -> None:
        if len(self.records) == self.records.maxlen:
            self.dropped += 1
        self.records.append((rec, counted))


_LOG: Optional[_Log] = None


def _record(phase: str, fun: str, begin: float, end: float, stage: str, fold: bool = True) -> None:
    """One span into the log, the flight recorder and, with ``fold``,
    ``stage``'s totals (:class:`kernel_trace` has counted its own)."""
    log, thread = _LOG, threading.get_ident()
    if log is None:
        return
    with log.lock:
        if fun == _UNNAMED and phase in _FOLLOWS:
            fun = log.named_before(_FOLLOWS[phase], thread, begin) or fun
        said = log.pending.pop(thread, {}) if phase == "backend" else {}
        cache = said.get("cache", "off") if phase == "backend" else None
        spans = [(Event(phase, fun, begin, end, thread, cache), stage, fold)]
        if "read" in said:
            spans.insert(0, (Event("cache_read", fun, *said["read"], thread),
                             "compile.cache_read", True))
        counted = []
        for rec, _, folded in spans:
            inside = log.counted_inside(rec.phase, thread, rec.begin) if folded else 0.0
            counted.append(max(rec.end - rec.begin - inside, 0.0))
            log.append(rec, counted[-1])
    for (rec, name, folded), seconds in zip(spans, counted):
        if folded:
            METRICS.add(name, records=1, seconds=seconds, latency=rec.end - rec.begin)
        telemetry.record_span(name, int(rec.begin * 1e9), int((rec.end - rec.begin) * 1e9),
                              fun=rec.fun)
    if cache in ("hit", "miss"):
        METRICS.count("compile.cache_hits" if cache == "hit" else "compile.cache_misses")


def _program(module_name: str) -> str:
    """A lowering's and a compile's module name (``jit(f)``) as its trace
    names the function (``f``)."""
    for api in ("jit", "pmap"):
        if module_name.startswith(api + "(") and module_name.endswith(")"):
            return module_name[len(api) + 1:-1]
    return module_name


def _on_span(event: str, start_time: float, end_time: float, **kw) -> None:
    log, phase = _LOG, _PHASES.get(event)
    if log is None or phase is None:
        return
    t0 = time.perf_counter()
    fun = str(kw.get("fun_name", "?"))
    _record(phase, fun if phase == "trace" else _program(fun),
            start_time + log.offset, end_time + log.offset, "compile." + phase)
    log.listener_s += time.perf_counter() - t0


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    log = _LOG
    if log is None or event != _CACHE_READ:
        return
    end = time.perf_counter()
    with log.lock:
        log.pending.setdefault(threading.get_ident(), {})["read"] = (end - duration_secs, end)
    log.listener_s += time.perf_counter() - end


def _on_event(event: str, **kw) -> None:
    log, said = _LOG, _CACHE_EVENTS.get(event)
    if log is None or said is None:
        return
    t0 = time.perf_counter()
    with log.lock:
        pending = log.pending.setdefault(threading.get_ident(), {})
        if pending.get("cache") != "hit":
            pending["cache"] = said
    log.listener_s += time.perf_counter() - t0


def enable() -> str:
    """Turn the persistent compilation cache on and start the compile log;
    returns the cache's directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set the directory is not set in code
    (JAX's own config reads the variable); otherwise
    ``jax_compilation_cache_dir`` becomes :data:`DEFAULT_DIR`. Either way
    the cache key includes the programs' metadata. The log's listeners are
    registered once, however often this is called; so is the host watch
    (``tracing.watch_host``: the witness for the host's pauses and the
    collector's clock, in the host log from before the first compile)."""
    global _LOG
    import jax

    tracing.watch_host()
    if _LOG is None:
        _LOG = _Log(LOG_CAPACITY)
        jax.monitoring.register_event_time_span_listener(_on_span)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
    # The programs' scope names (tracing.ANNOTATIONS) are metadata, which
    # JAX leaves out of the cache key by default: an executable cached
    # before a scope was added or moved would come back without it, and a
    # profiler capture of that run would show the old names.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


class kernel_trace(timed):
    """``metrics.timed`` around the call that builds one Pallas kernel
    (``kernel_trace("kernel.trace.mla_attn")``), also written to the compile
    log under phase ``kernel_trace`` with the kernel's name. The block runs in
    Python while a program is traced, never at run time: it times the trace of
    the kernel's body and counts the programs built around the kernel (a
    kernel called eagerly, as a test does, times its run too)."""

    def __exit__(self, exc_type, exc, tb) -> None:
        super().__exit__(exc_type, exc, tb)
        _record("kernel_trace", self.stage.rsplit(".", 1)[-1], self._t0,
                time.perf_counter(), self.stage, fold=False)


def events(since: Optional[float] = None, until: Optional[float] = None) -> Optional[List[Event]]:
    """The log's records that ended in ``[since, until)`` (``perf_counter``
    seconds; None: unbounded), oldest first; None where :func:`enable` never
    ran. Over a measured window this names what a count of compiles only
    counts."""
    log = _LOG
    if log is None:
        return None
    with log.lock:
        records = [rec for rec, _ in log.records]
    return sorted((r for r in records if (since is None or r.end >= since)
                   and (until is None or r.end < until)), key=lambda r: r.end)


def _union_s(spans: Iterable[Event]) -> float:
    """Seconds covered by the records, each moment once."""
    total, reach = 0.0, float("-inf")
    for begin, end in sorted((r.begin, r.end) for r in spans):
        total += max(0.0, end - max(begin, reach))
        reach = max(reach, end)
    return total


def _outermost(spans: List[Event]) -> List[Event]:
    """The records no later record of their phase and thread holds inside."""
    kept: Dict[tuple, List[Event]] = {}
    for rec in spans:
        lane = kept.setdefault((rec.phase, rec.thread), [])
        while lane and lane[-1].begin >= rec.begin:
            lane.pop()
        lane.append(rec)
    return sorted((r for lane in kept.values() for r in lane), key=lambda r: r.end)


def summary(since: Optional[float] = None, until: Optional[float] = None) -> Optional[dict]:
    """What :func:`events` of the interval add up to; None where
    :func:`enable` never ran.

    ``seconds``: by phase and ``all`` (every phase together), each moment
    counted once; ``hits`` / ``misses``: backend records so marked;
    ``programs``: a row a program ``{fun, trace_s, lower_s, backend_s,
    cache}``, largest first (a function traced inside another's trace is part
    of that program's ``trace_s`` and has no row); ``dropped``: records the
    bounded log has let go; ``listener_s``: what keeping the log has cost."""
    found = events(since, until)
    if found is None:
        return None
    seconds = {phase: _union_s(r for r in found if r.phase == phase)
               for phase in ("trace", "lower", "backend", "cache_read", "kernel_trace")}
    seconds["all"] = _union_s(found)
    rows, building = [], {}
    for rec in _outermost([r for r in found if r.phase in ("trace", "lower", "backend")]):
        key, field = (rec.thread, rec.fun), rec.phase + "_s"
        row = building.get(key)
        if row is None or (rec.phase != "backend" and (row[field] or row["lower_s"])):
            row = building[key] = {"fun": rec.fun, "trace_s": 0.0, "lower_s": 0.0,
                                   "backend_s": 0.0, "cache": None}
            rows.append(row)
        row[field] += rec.end - rec.begin
        if rec.phase == "backend":
            row["cache"] = rec.cache
            del building[key]
    rows.sort(key=lambda r: -(r["trace_s"] + r["lower_s"] + r["backend_s"]))
    backends = [r for r in found if r.phase == "backend"]
    return {"seconds": seconds,
            "hits": sum(r.cache == "hit" for r in backends),
            "misses": sum(r.cache == "miss" for r in backends),
            "programs": rows, "dropped": _LOG.dropped, "listener_s": _LOG.listener_s}
