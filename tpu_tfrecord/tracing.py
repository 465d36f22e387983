"""Profiling hooks: the program's own names on the profiler's trace AND in
the host log, a witness for the host's pauses, and a host-clock duty-cycle
estimate.

The reference has no observability of its own (SURVEY.md §5: tracing ABSENT
— it rides on Spark's UI). Here the input pipeline is the product, so it can
explain itself:

- ``trace(name, **args)``: one call a site writes two clocks. It opens a
  ``jax.profiler.TraceAnnotation`` around a host-side region, so the region
  lands in a profiler capture on the same clock as the device's operations,
  and for the host spans of ``ANNOTATIONS`` (``HOST_SPANS``: every name with
  a ``:``) it also appends ``(name, begin, end, thread, args)`` on
  ``time.perf_counter()`` to the HOST LOG when the region closes: the
  process's one bounded ring, ``telemetry.RECORDER``, through its
  always-recorded path. The ring is written whether or not a profiler runs,
  whether or not the flight recorder is enabled, and with no profiler module
  at all, so the feed can be read over a whole run
  (``host_events(since, until)``, ``host_log_dropped()``); the benchmark
  reads it on the chip (``benchmark/readers/host_log.py``).
- ``put_or_wait`` / ``get_or_wait``: a bounded queue's hand-off, with the
  time a thread sat blocked on it under one span (through ``trace``: in
  both clocks).
- ``watch_host()``: started by ``compile_cache.enable()``, once a process. A
  daemon thread that waits 20 ms at a time and, when it wakes more than 50
  ms late, writes ``host:pause`` to the log with what the operating system
  says of the interval (run-queue wait, steal, major faults, involuntary
  switches, pressure stalls); a pause of a second or more is also a
  ``tfrecord.host_pause`` warning on the package logger with a one-word
  cause. The same call times the collector (``gc.callbacks``): every
  collection counts into ``METRICS`` stage ``host.gc``, one of 1 ms or more
  is a ``host:gc`` record, and each is a ``TraceAnnotation`` too, so the
  profiler's timeline names it.
- ``ANNOTATIONS``: every ``tfr:*`` / ``host:*`` host span and ``tfr.*``
  device scope (``jax.named_scope`` in ``models/dlrm.py``, ``models/lm.py``,
  ``models/moe.py``) the package emits — the one
  place the names live; ``benchmark/layer_metrics`` and PERF.md quote them.
- ``DutyCycle``: estimates the BASELINE.md north-star secondary metric — the
  fraction of wall time the device spends computing vs waiting on input —
  from step/wait timestamps recorded in the training loop.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import queue
import resource
import threading
import time
from typing import Callable, Dict, List, NamedTuple, Optional

from tpu_tfrecord import telemetry
from tpu_tfrecord.metrics import METRICS, logger

#: name -> what it covers. ``tfr:`` and ``host:`` names are host spans (one
#: per batch or rarer, never per record), written to the profiler's timeline
#: and to the host log; ``tfr.`` names are scopes inside the jitted
#: programs (DLRM's, the pattern LM's), found in the ``op_name`` of the
#: device's operations.
#: ``<queue>`` is ``batch`` (the dataset's prefetch queue), ``host``
#: (HostPrefetcher's) or ``device`` (DeviceIterator's transfer thread's).
ANNOTATIONS = {
    "tfr:open": "opening a shard",
    "tfr:cache": "serving a chunk from the columnar cache",
    "tfr:decode": "frame scan + CRC + decode + hash of one chunk; rows, bytes",
    "tfr:pack": "host_batch_from_columnar or pack_mixed on one batch; rows, bytes out",
    "tfr:pack_tokens": "TokenPacker (bin modes) placing one reader batch's documents; docs in, "
                       "rows and tokens out",
    "tfr:noise": "TokenPacker(noise=) noising one closing batch for a block-diffusion model: a level a "
                 "block of each document, its tokens masked with that probability; rows, positions, masked",
    "tfr:h2d": "make_global_batch: the dispatch of one batch's copy; rows, bytes",
    "tfr:h2d_land": "the transfer thread's wait for that copy to land",
    "tfr:blocked.batch": "the decode thread's put waited on a full prefetch queue",
    "tfr:starved.batch": "the dataset's consumer found its prefetch queue empty",
    "tfr:blocked.host": "HostPrefetcher's thread waited on its full queue",
    "tfr:starved.host": "HostPrefetcher's consumer found its queue empty",
    "tfr:blocked.device": "the transfer thread waited on its full queue",
    "tfr:starved.device": "DeviceIterator's consumer found its queue empty",
    "host:pause": "the host watch woke late: begin is when it should have woken, end when it did; "
                  "late_s and what the operating system says of the interval (runqueue_s, steal_s, "
                  "major_faults, involuntary_switches, pressure_cpu_s, pressure_memory_s, "
                  "pressure_io_s: each where its file exists), cause (host log only: it is known "
                  "when it is over)",
    "host:gc": "one collection of the cyclic collector (the host log keeps those of 1 ms or more); "
               "generation, collected",
    "tfr.write.encode": "writer: host span (named before the ':' convention) around encode",
    "tfr.write.compress": "writer: host span around compress",
    "tfr.write.io": "writer: host span around the file write",
    "tfr.write.commit": "writer: host span around the commit",
    "tfr.table_cast": "no program opens it since PR 25; kept because the benchmark's recorded "
                      "trace (benchmark/tests/test_trace_scope.py) holds its scopes to this table",
    "tfr.gather": "the embedding rows gathered from the table, rounded to the activation dtype",
    "tfr.bottom_mlp": "bottom MLP (backward ops carry it inside transpose(jvp(..)))",
    "tfr.interaction": "feature interaction",
    "tfr.top_mlp": "top MLP to the logits",
    "tfr.dense_update": "sparse_train_step: optimizer update of the MLPs",
    "tfr.dedup_sort": "sparse_train_step: keys sorted, each run's key sorted to its slot, row gradients reordered",
    "tfr.segment_sum": "sparse_train_step: duplicate keys' gradients summed, each run's sum at its slot",
    "tfr.accum_update": "sparse_train_step: AdaGrad accumulator scatter over all slots; its "
                        "read-back and rsqrt per block of slots, inside the loop over the "
                        "blocks that hold runs",
    "tfr.table_scatter": "sparse_train_step: the row updates scattered into the table, per block "
                         "of slots, inside the loop over the blocks that hold runs",
    "tfr.embed": "pattern LM (models.lm.score): the token rows gathered from the embedding",
    "tfr.gqa": "pattern LM: the softmax layer without a window (norm, projections, Q/K norm, the "
               "attention call: on a TPU attention.flash_attention_widths, the 8 K/V heads read as they lie and "
               "block pairs that share no document skipped, blockwise attention elsewhere; gate, out, branch norm)",
    "tfr.swa_proj": "pattern LM: a sliding-window layer's norm, five projections, Q/K norm, rotary "
                    "turns, gate, out, branch norm",
    "tfr.swa_attn": "pattern LM: a sliding-window layer's attention call alone (the band of block "
                    "pairs inside the window, inside each document)",
    "tfr.kda_proj": "pattern LM: the delta-rule layer's projections, decay, beta, gates, norm, out",
    "tfr.kda_conv": "pattern LM: the short convolution, SiLU and unit norm of q, k, v",
    "tfr.kda_scan": "pattern LM: the chunked gated delta rule (models.linear_attn)",
    "tfr.gdn_proj": "pattern LM: the gated delta-net layer's norm on its way in (where the pattern has "
                    "one), six projections (q, k, v, the output gate z, the decay's and beta's one a "
                    "head), decay, beta in (0, 1) or (0, 2), head norm, gate (2 sigmoid(z) or silu(z)), "
                    "out, branch norm",
    "tfr.gdn_conv": "pattern LM: the gated delta-net layer's short convolution, SiLU and unit norm "
                    "of q and k at their own heads and of v (empty on a TPU: the kernel prepares them)",
    "tfr.gdn_scan": "pattern LM: the gated delta-net layer's recurrence call alone (models.linear_attn "
                    "under one decay a head and token, key heads shared by their value heads, a state "
                    "[d_k, d_v] that need not be square)",
    "tfr.ssm_proj": "pattern LM: the state-space layer's norm, the three products of its one projection in "
                    "(gate, [x | B | C], step), the step's softplus and the decay, the skip, the gate "
                    "before the grouped norm, out",
    "tfr.ssm_conv": "pattern LM: the state-space layer's one short convolution with its bias and SiLU "
                    "over all channels of [x | B | C]",
    "tfr.ssm_scan": "pattern LM: the state-space layer's recurrence call alone (models.linear_attn.ssm_chunked: "
                    "one decay and one step a head and token, B and C read by group from where they lie)",
    "tfr.mla_proj": "pattern LM: the latent-attention layer's norm, query projection, latent "
                    "projection and norm, expansion to keys and values, rotary turns, the output's "
                    "sigmoid gate where the layer has one, out",
    "tfr.mla_attn": "pattern LM: the latent-attention layer's attention call alone (192-wide "
                    "queries and keys against 128-wide values inside each document)",
    "tfr.dsa_proj": "pattern LM: a latent-attention layer's indexer: its queries from the query "
                    "latent, its one key a token through a LayerNorm, rotary turns, the heads' weights",
    "tfr.dsa_index": "pattern LM: the indexer's scores over every causal pair of a document, each "
                     "query's exact k-th largest, and the mask of the keys it keeps",
    "tfr.dense_ffn": "pattern LM: a layer's dense gated feed-forward part (pre-norm, gate, up, down)",
    "tfr.moe_route": "held experts: pre-norm, scores over all experts, top-k, visits sorted by expert",
    "tfr.moe_experts": "held experts: the loop over the tiles of visits to the experts held here",
    "tfr.moe_shared": "held experts: the shared expert, every token",
    "tfr.lm_head": "pattern LM: final norm, the head's log-probabilities (models.head: on a TPU one "
                   "kernel whose float32 logits stay in VMEM a tile at a time, else blocks of them), "
                   "the sampled positions' logits",
}


#: The names the host log keeps: the host spans of ``ANNOTATIONS``. The
#: writer's ``tfr.write.*`` (named before the ':' convention) and a training
#: loop's ``train.step`` go to the profiler's timeline alone, as they did:
#: the flight recorder has spans of its own for them.
HOST_SPANS = frozenset(name for name in ANNOTATIONS if ":" in name)

_perf_ns = time.perf_counter_ns

_PROF = None
_PROF_CHECKED = False


def _profiler():
    global _PROF, _PROF_CHECKED
    if not _PROF_CHECKED:
        _PROF_CHECKED = True
        try:
            import jax.profiler as prof

            _PROF = prof
        except Exception:  # pragma: no cover - jax always present in this repo  # graftlint: swallow(no jax profiler available: tracing disabled)
            _PROF = None
    return _PROF


class _NullTrace:
    """Shared no-op context manager for the profiler-less path."""

    __slots__ = ()

    def __enter__(self) -> "_NullTrace":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **args) -> None:
        return None


_NULL_TRACE = _NullTrace()


class _HostSpan:
    """One open host span: the profiler's annotation (the shared no-op
    where there is no profiler module) and, on exit, one record of the
    host log."""

    __slots__ = ("name", "args", "_annotation", "_t0")

    def __init__(self, name: str, args: dict, prof) -> None:
        self.name = name
        self.args = args or None
        self._annotation = prof.TraceAnnotation(name, **args) if prof is not None else _NULL_TRACE

    def __enter__(self) -> "_HostSpan":
        self._annotation.__enter__()
        self._t0 = _perf_ns()
        return self

    def set_metadata(self, **args) -> None:
        self._annotation.set_metadata(**args)
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)

    def __exit__(self, *exc) -> None:
        t1 = _perf_ns()
        self._annotation.__exit__(*exc)
        telemetry.RECORDER.log(self.name, self._t0, t1 - self._t0, self.args)


def trace(name: str, **args):
    """Annotate a host-side region on the profiler timeline and, for a name
    of ``HOST_SPANS``, in the host log.

    A slotted context object, never a generator: ``trace`` sits on
    per-chunk hot paths (decode, cache serve, write stages), where the old
    ``@contextlib.contextmanager`` layer allocated a generator per call.
    A name outside ``HOST_SPANS`` gets the profiler's TraceAnnotation
    itself (it IS a context manager), or a shared no-op with jax
    unavailable: zero allocation per call. ``args`` become the event's
    stats in a capture and the record's ``args`` in the log; what is only
    known at the end of the region goes through the returned object's
    ``set_metadata(**args)``."""
    prof = _profiler()
    if name in HOST_SPANS:
        return _HostSpan(name, args, prof)
    if prof is None:
        return _NULL_TRACE
    return prof.TraceAnnotation(name, **args)


# -- the host log ---------------------------------------------------------------


class HostEvent(NamedTuple):
    """One record of the host log. ``begin`` / ``end`` are
    ``time.perf_counter()`` seconds; ``args`` what the site gave ``trace``
    and ``set_metadata`` (None where it gave nothing)."""

    name: str
    begin: float
    end: float
    thread: int
    args: Optional[dict]


def host_events(since: Optional[float] = None, until: Optional[float] = None) -> List[HostEvent]:
    """The host log's records that BEGAN in ``[since, until)``
    (``perf_counter`` seconds; None: unbounded), oldest first. A span still
    open is not in the log: a record is written when its region closes."""
    found = []
    for name, t0_ns, dur_ns, thread, attrs, ph in telemetry.RECORDER.spans():
        if ph != "X" or name not in HOST_SPANS:
            continue
        begin = t0_ns / 1e9
        if (since is None or begin >= since) and (until is None or begin < until):
            found.append(HostEvent(name, begin, (t0_ns + dur_ns) / 1e9, thread, attrs))
    found.sort(key=lambda r: r.begin)
    return found


def host_log_dropped() -> int:
    """Records the bounded ring has let go, oldest first (0: ``host_events``
    holds everything the process ever wrote)."""
    return telemetry.RECORDER.dropped


# -- the host watch ---------------------------------------------------------------


def _first_numbers(path: str, line_starts: str = "") -> Optional[List[str]]:
    """The fields after ``line_starts`` on the first line of ``path`` that
    starts with it; None where the file (or the line) is absent."""
    try:
        with open(path) as f:
            for line in f:
                if line.startswith(line_starts):
                    return line[len(line_starts):].split()
    except OSError:
        return None
    return None


def _runqueue_s() -> Optional[float]:
    # /proc/self is the thread group's leader: the main thread. Field 2 is
    # the nanoseconds it sat runnable on a run queue, waiting for a CPU.
    fields = _first_numbers("/proc/self/schedstat")
    return int(fields[1]) / 1e9 if fields and len(fields) >= 2 else None


def _steal_s() -> Optional[float]:
    # "cpu  user nice system idle iowait irq softirq steal ...", in ticks,
    # over all CPUs: time the hypervisor ran something else on ours.
    fields = _first_numbers("/proc/stat", "cpu ")
    return int(fields[7]) / os.sysconf("SC_CLK_TCK") if fields and len(fields) >= 8 else None


def _pressure_s(what: str) -> Callable[[], Optional[float]]:
    def read() -> Optional[float]:
        # "some avg10=0.00 avg60=0.00 avg300=0.00 total=<microseconds>"
        fields = _first_numbers(f"/proc/pressure/{what}", "some ")
        total = [f for f in fields or () if f.startswith("total=")]
        return int(total[0][len("total="):]) / 1e6 if total else None

    return read


def _rusage(field: str) -> Callable[[], Optional[float]]:
    return lambda: float(getattr(resource.getrusage(resource.RUSAGE_SELF), field))


#: field of a ``host:pause`` record -> the reader of its running total (None
#: where this machine has no such file). A record holds each total's rise
#: over the pause, against a snapshot the watch refreshes once a quiet second.
HOST_READERS: Dict[str, Callable[[], Optional[float]]] = {
    "runqueue_s": _runqueue_s,
    "steal_s": _steal_s,
    "major_faults": _rusage("ru_majflt"),
    "involuntary_switches": _rusage("ru_nivcsw"),
    "pressure_cpu_s": _pressure_s("cpu"),
    "pressure_memory_s": _pressure_s("memory"),
    "pressure_io_s": _pressure_s("io"),
}

#: the fields, in seconds, that can explain a pause -> the cause they name
_CAUSES = {"steal_s": "steal", "runqueue_s": "runqueue", "pressure_cpu_s": "runqueue",
           "pressure_memory_s": "memory", "pressure_io_s": "io"}


def pause_cause(late_s: float, fields: Dict[str, float], gc_s: float = 0.0) -> str:
    """One word for a pause of ``late_s`` seconds: ``gc`` where collections
    cover at least half of it, else the largest field (seconds) that
    explains at least half, else ``unknown`` (a stopped guest that accounts
    no steal, an extension holding the interpreter's lock)."""
    if gc_s >= late_s / 2:
        return "gc"
    seconds, name = max(((fields.get(f, 0.0), f) for f in _CAUSES), default=(0.0, None))
    return _CAUSES[name] if name is not None and seconds >= late_s / 2 else "unknown"


class HostWatch:
    """The witness for the host's pauses and the collector's clock (module
    docstring). ``clock``, ``wait`` and ``readers`` are arguments so that a
    test makes it late by hand; ``step`` is one turn of the thread's loop."""

    INTERVAL_S = 0.02  # what a quiet wait lasts
    LATE_S = 0.05      # a wake later than this is a pause
    WARN_S = 1.0       # a pause this long is a warning on the package logger
    REFRESH_S = 1.0    # how old the operating system's snapshot may grow
    GC_RECORD_NS = 1_000_000  # a collection this long (1 ms) is a record of the log

    def __init__(self, clock: Callable[[], float] = time.perf_counter,
                 wait: Optional[Callable[[float], object]] = None,
                 readers: Optional[Dict[str, Callable[[], Optional[float]]]] = None) -> None:
        self._stop = threading.Event()
        self._clock = clock
        self._wait = wait if wait is not None else self._stop.wait
        self._readers = dict(HOST_READERS if readers is None else readers)
        self._thread: Optional[threading.Thread] = None
        self._snapshot = self._read()
        self._snapshot_at = clock()
        self._gc_t0 = 0
        self._gc_annotation = None
        self._gc_count = 0
        self._gc_ns = 0
        self._gc_folded = (0, 0)
        self._fold_lock = threading.Lock()

    # -- the pauses

    def _read(self) -> Dict[str, float]:
        found = {}
        for field, reader in self._readers.items():
            try:
                value = reader()
            except (OSError, ValueError, IndexError):
                value = None
            if value is not None:
                found[field] = value
        return found

    def step(self) -> Optional[HostEvent]:
        """Wait once; the ``host:pause`` written if the wake was late."""
        due = self._clock() + self.INTERVAL_S
        self._wait(self.INTERVAL_S)
        now = self._clock()
        if now - due > self.LATE_S:
            return self._pause(due, now)
        if now - self._snapshot_at >= self.REFRESH_S:
            self._snapshot, self._snapshot_at = self._read(), now
            self.fold_gc()
        return None

    def _pause(self, due: float, now: float) -> HostEvent:
        after, late = self._read(), now - due
        fields = {f: round(after[f] - before, 6) for f, before in self._snapshot.items()
                  if f in after}
        self._snapshot, self._snapshot_at = after, now
        gc_s = sum(min(r.end, now) - max(r.begin, due)
                   for r in host_events(until=now) if r.name == "host:gc" and r.end > due)
        args = {"late_s": round(late, 6), **fields, "cause": pause_cause(late, fields, gc_s)}
        telemetry.RECORDER.log("host:pause", int(due * 1e9), int(late * 1e9), args)
        METRICS.add("host.pause", records=1, seconds=late, latency=late)
        if late >= self.WARN_S:
            logger.warning("tfrecord.host_pause %s", json.dumps(args, sort_keys=True))
        return HostEvent("host:pause", due, now, threading.get_ident(), args)

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self.step()
            except Exception:  # graftlint: swallow(the witness must never take the pipeline down)
                METRICS.count("host.pause.errors")
                self._stop.wait(self.INTERVAL_S)  # a step that fails at once must not spin

    # -- the collector

    def _on_gc(self, phase: str, info: dict) -> None:
        # Collections neither nest nor overlap (the interpreter's lock is
        # held throughout), so one stamp a process is enough. A young
        # collection (generation 0) looks at the 700-odd containers made
        # since the last one, a microsecond's work many thousand times a
        # second while a program is traced: it costs two stamps and two
        # additions here and opens no annotation.
        if phase == "start":
            if info["generation"]:
                prof = _profiler()
                if prof is not None:
                    self._gc_annotation = prof.TraceAnnotation(
                        "host:gc", generation=info["generation"])
                    self._gc_annotation.__enter__()
            self._gc_t0 = _perf_ns()
            return
        t0, self._gc_t0 = self._gc_t0, 0
        if not t0:
            return  # installed between a collection's start and its stop
        dur_ns = _perf_ns() - t0
        if self._gc_annotation is not None:
            self._gc_annotation.__exit__(None, None, None)
            self._gc_annotation = None
        self._gc_count += 1
        self._gc_ns += dur_ns
        if dur_ns >= self.GC_RECORD_NS:
            telemetry.RECORDER.log("host:gc", t0, dur_ns, {
                "generation": info["generation"], "collected": info["collected"]})

    def fold_gc(self) -> None:
        """Every collection since the last call and their seconds into
        ``METRICS`` stage ``host.gc`` (the watch's thread calls it once a
        quiet second and when it stops; a reader that wants the totals to
        the moment calls it first)."""
        with self._fold_lock:
            count, ns = self._gc_count, self._gc_ns
            d_count, d_ns = count - self._gc_folded[0], ns - self._gc_folded[1]
            self._gc_folded = (count, ns)
        if d_count:
            METRICS.add("host.gc", records=d_count, seconds=d_ns / 1e9)

    # -- lifetime

    def start(self) -> "HostWatch":
        if self._thread is None:
            gc.callbacks.append(self._on_gc)
            self._thread = threading.Thread(target=self._run, daemon=True, name="tfr-host-watch")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=2.0)
        self.fold_gc()


_WATCH: Optional[HostWatch] = None
_WATCH_LOCK = threading.Lock()


def watch_host() -> HostWatch:
    """Start the process's host watch (thread and ``gc.callbacks`` entry),
    once however often it is called. ``compile_cache.enable()`` calls it, so
    every entry point has it before its first compile; ``tests/conftest.py``
    does not, so tier-1 runs without it."""
    global _WATCH
    with _WATCH_LOCK:
        if _WATCH is None:
            _WATCH = HostWatch().start()
        return _WATCH


def unwatch_host() -> None:
    """Stop and forget the process's host watch (a test's tear-down; a
    program has no reason to)."""
    global _WATCH
    with _WATCH_LOCK:
        watch, _WATCH = _WATCH, None
    if watch is not None:
        watch.stop()


def watching() -> bool:
    """Has ``watch_host()`` run in this process? (Without it the log holds
    the feed's spans and no ``host:*`` record: a reader of pauses and
    collections then has nothing to say, not a zero.)"""
    return _WATCH is not None


#: what ``get_or_wait`` returns once ``stop`` is set
STOPPED = object()


def put_or_wait(q: "queue.Queue", item, stop, span: str) -> bool:
    """Put ``item`` on a bounded queue unless ``stop`` is (or gets) set;
    False then. A put that finds room opens no span; one that finds the
    queue full waits under ONE ``span``, however many polls it takes."""
    if stop.is_set():
        return False
    try:
        q.put_nowait(item)
        return True
    except queue.Full:
        pass
    with trace(span):
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
    return False


def get_or_wait(q: "queue.Queue", stop, span: str):
    """Next item of a queue, or ``STOPPED`` once ``stop`` is set. An item
    that is already there opens no span; an empty queue is waited on under
    ONE ``span``."""
    if stop.is_set():
        return STOPPED
    try:
        return q.get_nowait()
    except queue.Empty:
        pass
    with trace(span):
        while not stop.is_set():
            try:
                return q.get(timeout=0.1)
            except queue.Empty:
                continue
    return STOPPED


class DutyCycle:
    """Track device busy vs input-wait time in a training loop.

    Usage::

        duty = DutyCycle()
        for batch in it:
            with duty.wait():     # host blocked on input pipeline
                gb = make_global_batch(...)
            with duty.step():     # device computing (block_until_ready inside)
                loss = step(gb)
        print(duty.value())       # busy / (busy + wait)
    """

    def __init__(self):
        self.busy_seconds = 0.0
        self.wait_seconds = 0.0

    @contextlib.contextmanager
    def step(self):
        t0 = time.perf_counter()
        yield
        self.busy_seconds += time.perf_counter() - t0

    @contextlib.contextmanager
    def wait(self):
        t0 = time.perf_counter()
        yield
        self.wait_seconds += time.perf_counter() - t0

    def value(self) -> Optional[float]:
        total = self.busy_seconds + self.wait_seconds
        return self.busy_seconds / total if total > 0 else None
