"""Pipeline flight recorder: span tracing, latency histograms, telemetry
pulse, and the producer/consumer bound-ness verdict.

The reference has no observability of its own (SURVEY.md §5: tracing ABSENT
— it rides on Spark's UI), and `Metrics` only aggregates per-stage TOTALS:
after an epoch you know decode took N seconds, but not the latency
distribution, which shard was the straggler, or whether the pipeline was
producer- or consumer-bound at any moment. tf.data's auto-tuning and the
tf.data service rest on exactly this kind of per-op timing instrumentation
(PAPERS.md) — a slow epoch should be explainable without attaching a
profiler. Four pieces:

- **Span tracing** (``SpanRecorder``): a thread-safe, bounded ring buffer
  of (name, begin, duration, thread, attrs) records, written through the
  ``span(name, **attrs)`` context manager and ``instant(name, **attrs)``
  point events. Opt-in via ``TFRecordOptions(trace="on")`` — when off, the
  module-level ``span()``/``instant()`` return a shared no-op without
  taking any lock (one attribute read on the hot path). Exportable as
  Chrome trace-event JSON (``to_chrome_trace``/``save_chrome_trace``) —
  loadable in Perfetto / chrome://tracing. The same ring is the HOST LOG:
  ``SpanRecorder.log`` is its always-recorded path, which
  ``tracing.trace`` takes for the feed's ``tfr:*`` hand-offs and the host
  watch for ``host:pause`` / ``host:gc`` whether or not the recorder is
  enabled (``tracing.host_events`` reads them back; one call a site
  writes the ring and the jax-profiler timeline).

- **Latency histograms** (``Histogram``): log-bucketed (~19% geometric
  buckets → quantile relative error ≤ ~10%), folded into ``Metrics`` via
  ``Metrics.observe``/the ``timed`` context manager, so every timed stage
  (shard open, slab read, chunk decode, cache serve, write/commit) grows a
  p50/p90/p99 next to its totals and stragglers stop hiding inside means.

- **Telemetry pulse** (``Pulse``): a background reporter emitting one
  machine-parseable JSON line per interval — per-interval stage
  throughputs, cumulative counters, histogram quantiles, gauges (prefetch
  queue depth, in-flight decode workers, backpressure occupancy), and the
  bound-ness verdict. Opt-in via ``TFRecordOptions(pulse_interval_s=...)``;
  an optional stdlib-HTTP Prometheus text endpoint
  (``TFRecordOptions(telemetry_port=...)`` / ``ensure_exporter``) serves
  the same registry for scraping. Pulse ticks are also the pipeline's
  ACTUATION points: registered observers (``add_observer``) see each
  payload before it is emitted and may merge fields into the line — the
  closed-loop autotuner (tpu_tfrecord.autotune) runs this way, so every
  knob decision lands in the same trace as the interval it was made from.

- **Bound-ness verdict** (``boundness_verdict``): computed from the
  prefetch queue's average fill fraction, sampled by the consumer. A queue
  that is nearly always FULL means decode keeps ahead of the consumer —
  the pipeline is consumer-bound (the device/training step is the
  bottleneck; the BASELINE.md goal state). Nearly always EMPTY means the
  consumer drains batches faster than decode produces them —
  producer-bound (speed up the input pipeline).

The offline complement is ``tools/tfrecord_doctor.py report DATA_DIR``:
run N batches with tracing on and print the stage breakdown, slowest
shards, straggler ratio, and the verdict.

This module deliberately imports nothing from the rest of the package at
module level (stdlib only; the default-registry lookups import
``metrics`` lazily), so every layer — metrics, io, cache, stall — can
import it without cycles.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import socket
import threading
import time
import uuid
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

__all__ = [
    "Histogram",
    "SpanRecorder",
    "Pulse",
    "RECORDER",
    "TraceContext",
    "current_context",
    "adopt",
    "adopt_from_env",
    "span",
    "instant",
    "record_span",
    "record_instant",
    "enable",
    "disable",
    "boundness_verdict",
    "verdict_from_metrics",
    "OccupancyEma",
    "quantiles_ms",
    "merge_chrome_traces",
    "atomic_write_bytes",
    "prometheus_text",
    "ensure_exporter",
    "serve_text_endpoint",
    "exporter_address",
    "shutdown_exporter",
]


# ---------------------------------------------------------------------------
# Latency histograms
# ---------------------------------------------------------------------------


class Histogram:
    """Log-bucketed latency histogram with quantile estimation.

    Buckets grow geometrically by ``2**0.25`` (~19% per bucket) from a
    100 ns floor, spanning 100 ns .. ~1.9 h in 144 fixed buckets — so one
    histogram is a flat int list, O(1) to observe and cheap to snapshot.
    Quantiles interpolate at the log-midpoint of the selected bucket and
    clamp to the observed [min, max], bounding the relative error at
    ``sqrt(2**0.25) - 1`` ≈ 9.1% (pinned against a reference sort in
    tests/test_telemetry.py).

    NOT internally locked: the owner (``Metrics``) serializes access under
    its own lock so one observation costs one lock acquisition total.
    """

    _MIN = 1e-7  # 100 ns floor: anything faster is bucket 0
    _LOG2_GROWTH = 0.25  # buckets grow by 2**0.25 per step
    _NBUCKETS = 144  # 144 * 0.25 = 36 octaves above _MIN (~1.9 h)

    __slots__ = ("counts", "count", "total", "min", "max", "exemplars")

    def __init__(self) -> None:
        self.counts = [0] * self._NBUCKETS
        self.count = 0
        self.total = 0.0
        self.min = math.inf
        self.max = 0.0
        # bucket index -> (trace_id, span_id, value): the LAST exemplar
        # observed into that bucket. Bounded by construction (one entry
        # per populated bucket, <= _NBUCKETS) and carried bucket-exactly
        # through state()/merge_state() so fleet merges keep the pointer
        # from a tail bucket to the trace that filled it.
        self.exemplars: Dict[int, Tuple[str, str, float]] = {}

    def bucket_index(self, value: float) -> int:
        if value <= self._MIN:
            return 0
        return min(
            self._NBUCKETS - 1,
            1 + int(math.log2(value / self._MIN) / self._LOG2_GROWTH),
        )

    @classmethod
    def bucket_le(cls, idx: int) -> float:
        """Inclusive upper bound (seconds) of bucket ``idx`` — the ``le``
        label when a bucket is rendered on a Prometheus page."""
        if idx <= 0:
            return cls._MIN
        return cls._MIN * 2 ** (idx * cls._LOG2_GROWTH)

    def observe(
        self,
        value: float,
        exemplar: Optional[Tuple[str, str]] = None,
    ) -> None:
        idx = self.bucket_index(value)
        self.counts[idx] += 1
        self.count += 1
        self.total += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value
        if exemplar is not None:
            self.exemplars[idx] = (
                str(exemplar[0]), str(exemplar[1]), float(value)
            )

    def exemplar_at(self, q: float) -> Optional[Dict[str, Any]]:
        """The exemplar nearest the quantile-``q`` bucket: the exemplar of
        the highest populated bucket at or below where ``quantile(q)``
        lands (tail observations overwrite last-wins, so for q near 1 this
        is 'the trace that filled the top bucket'). None when no exemplar
        was ever attached at or below that bucket."""
        if self.count == 0 or not self.exemplars:
            return None
        rank = q * self.count
        cum = 0
        target = self._NBUCKETS - 1
        for idx, c in enumerate(self.counts):
            cum += c
            if cum >= rank and c:
                target = idx
                break
        best = None
        for idx, ex in self.exemplars.items():
            if idx <= target and (best is None or idx > best):
                best = idx
        if best is None:
            return None
        trace_id, span_id, value = self.exemplars[best]
        return {
            "bucket": best,
            "trace_id": trace_id,
            "span_id": span_id,
            "value": value,
        }

    def quantile(self, q: float) -> Optional[float]:
        """Estimated value at quantile ``q`` in [0, 1] (None when empty)."""
        if self.count == 0:
            return None
        rank = q * self.count
        cum = 0
        for idx, c in enumerate(self.counts):
            cum += c
            if cum >= rank and c:
                if idx == 0:
                    est = self._MIN
                else:
                    # log-midpoint of the bucket [g**(idx-1), g**idx) * _MIN
                    est = self._MIN * 2 ** ((idx - 0.5) * self._LOG2_GROWTH)
                return min(max(est, self.min), self.max)
        return self.max

    def quantiles(self) -> Dict[str, float]:
        """The standard p50/p90/p99 snapshot (seconds), plus count/mean."""
        if self.count == 0:
            return {}
        return {
            "p50_s": self.quantile(0.50),
            "p90_s": self.quantile(0.90),
            "p99_s": self.quantile(0.99),
            "count": self.count,
            "mean_s": self.total / self.count,
        }

    # -- cross-process export/merge ------------------------------------------
    #
    # The bucket layout is FIXED (same floor, growth, count in every
    # process), so per-process histograms merge exactly: bucket counts
    # add, min/max fold — the merged histogram is bucket-identical to one
    # histogram fed every process's observations (pinned by a property
    # test in tests/test_fleet.py). This is what makes cluster-level
    # quantiles from per-process spool snapshots honest rather than an
    # average-of-quantiles approximation.

    def state(self) -> Dict[str, Any]:
        """JSON-serializable snapshot: sparse bucket counts + count/total/
        min/max. The layout params ride along so a merge across versions
        with a different bucket geometry fails loudly instead of blending
        incompatible buckets."""
        state: Dict[str, Any] = {
            "buckets": {
                str(i): c for i, c in enumerate(self.counts) if c
            },
            "count": self.count,
            "total": self.total,
            "min": self.min if self.count else None,
            "max": self.max,
            "layout": [self._MIN, self._LOG2_GROWTH, self._NBUCKETS],
        }
        if self.exemplars:
            # omitted when empty: pre-exemplar snapshots and exemplar-free
            # histograms serialize byte-identically to before
            state["exemplars"] = {
                str(i): [t, s, v]
                for i, (t, s, v) in sorted(self.exemplars.items())
            }
        return state

    def merge_state(self, state: Dict[str, Any]) -> None:
        """Fold one ``state()`` snapshot in (exact: fixed shared buckets)."""
        if not isinstance(state, dict):
            raise TypeError(
                f"histogram state must be a mapping, got {type(state).__name__}"
            )
        layout = state.get("layout")
        if layout is not None and list(layout) != [
            self._MIN, self._LOG2_GROWTH, self._NBUCKETS,
        ]:
            raise ValueError(
                f"histogram bucket layout mismatch: {layout} vs "
                f"{[self._MIN, self._LOG2_GROWTH, self._NBUCKETS]}"
            )
        buckets = state.get("buckets") or {}
        if not isinstance(buckets, dict):
            raise TypeError(
                f"histogram buckets must be a mapping, got {type(buckets).__name__}"
            )
        for idx, c in buckets.items():
            i = int(idx)
            if not 0 <= i < self._NBUCKETS:
                # a negative index would silently wrap into the tail bucket
                raise ValueError(f"histogram bucket index out of range: {i}")
            self.counts[i] += int(c)
        self.count += int(state.get("count", 0))
        self.total += float(state.get("total", 0.0))
        smin = state.get("min")
        if smin is not None and smin < self.min:
            self.min = smin
        smax = state.get("max")
        if smax is not None and smax > self.max:
            self.max = smax
        exemplars = state.get("exemplars") or {}
        if not isinstance(exemplars, dict):
            raise TypeError(
                f"histogram exemplars must be a mapping, got "
                f"{type(exemplars).__name__}"
            )
        for idx, ex in exemplars.items():
            i = int(idx)
            if not 0 <= i < self._NBUCKETS:
                raise ValueError(f"exemplar bucket index out of range: {i}")
            trace_id, span_id, value = ex
            # last-wins across merge order; bucket COUNTS are untouched,
            # so exemplar-carrying states merge to the same quantiles as
            # exemplar-free ones
            self.exemplars[i] = (str(trace_id), str(span_id), float(value))

    @classmethod
    def from_states(cls, states: Iterable[Dict[str, Any]]) -> "Histogram":
        hist = cls()
        for st in states:
            hist.merge_state(st)
        return hist


# ---------------------------------------------------------------------------
# Cross-process trace context
# ---------------------------------------------------------------------------

#: Environment variable carrying a serialized TraceContext from a parent
#: process to its children (doctor subprocesses, multihost workers, future
#: data-service workers). ``adopt_from_env`` reads it; ``TraceContext.to_env``
#: produces the value to put in a child's environment.
TRACE_CONTEXT_ENV = "TFR_TRACE_CONTEXT"


def _new_id() -> str:
    return uuid.uuid4().hex[:16]


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """Identity of one process's slice of a distributed trace.

    ``trace_id`` is shared by every process participating in one logical
    run (a multihost job, a dispatcher + its decode workers); ``span_id``
    is this process's own root id, and ``parent_span_id`` names the root
    of the process that spawned/coordinated it (None at the root). role/
    host/pid identify the process for humans and for the spool aggregator
    — merged Perfetto timelines label tracks ``role@host:pid``.

    Plain JSON-serializable value: ``to_json``/``from_json`` round-trip
    it; ``to_env``/``adopt_from_env`` ship it across a process spawn via
    the ``TFR_TRACE_CONTEXT`` environment variable, the child minting its
    own span id and stamping its own host/pid (ids propagate, identities
    never do)."""

    trace_id: str
    span_id: str
    parent_span_id: Optional[str] = None
    role: str = "main"
    host: str = ""
    pid: int = 0

    @staticmethod
    def new(role: str = "main") -> "TraceContext":
        """A fresh root context for this process."""
        return TraceContext(
            trace_id=_new_id(),
            span_id=_new_id(),
            parent_span_id=None,
            role=role,
            host=socket.gethostname(),
            pid=os.getpid(),
        )

    def child(self, role: str) -> "TraceContext":
        """A context for a process THIS one spawns: same trace, new span
        id, this context's span as the parent. host/pid are left for the
        child to stamp at adoption (they describe the child, and the
        parent cannot know them)."""
        return TraceContext(
            trace_id=self.trace_id,
            span_id=_new_id(),
            parent_span_id=self.span_id,
            role=role,
            host="",
            pid=0,
        )

    def with_role(self, role: str) -> "TraceContext":
        return dataclasses.replace(self, role=role)

    def to_json(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(obj: Dict[str, Any]) -> "TraceContext":
        known = {f.name for f in dataclasses.fields(TraceContext)}
        return TraceContext(**{k: v for k, v in obj.items() if k in known})

    def to_env(self) -> Dict[str, str]:
        """{TFR_TRACE_CONTEXT: payload} to merge into a child process's
        environment — the child's ``adopt_from_env`` joins this trace."""
        return {TRACE_CONTEXT_ENV: json.dumps(self.to_json(), sort_keys=True)}

    def label(self) -> str:
        """The human track label merged timelines use: ``role@host:pid``."""
        return f"{self.role}@{self.host}:{self.pid}"


def current_context() -> TraceContext:
    """The process's trace context — created (and cached on the global
    recorder) on first use, so pulse lines and spool snapshots always
    carry host/pid/role even when nobody propagated a context in."""
    ctx = RECORDER.context
    if ctx is None:
        ctx = RECORDER.adopt(TraceContext.new())
    return ctx


def adopt(ctx: TraceContext) -> TraceContext:
    """Adopt ``ctx`` as this process's identity on the global recorder
    (host/pid re-stamped to the adopting process — identities never
    propagate, only ids do)."""
    return RECORDER.adopt(ctx)


def _adopt_child_of(obj: Any, role: Optional[str]) -> TraceContext:
    """Adopt a context that joins the trace ``obj`` (a parsed TraceContext
    JSON object) describes: keep the parent's trace id, record the
    parent's span as our parent, mint our own span id (host/pid stamped by
    ``adopt``). Raises on malformed payloads — callers own the degrade
    policy."""
    if not isinstance(obj, dict):
        # valid JSON that is not an object ('null', '[1]', '"x"')
        # is just as malformed as unparseable bytes
        raise ValueError(f"not a JSON object: {obj!r}")
    parent = TraceContext.from_json(obj)
    ctx = TraceContext(
        trace_id=parent.trace_id,
        span_id=_new_id(),
        parent_span_id=parent.span_id,
        role=role if role is not None else parent.role,
    )
    return RECORDER.adopt(ctx)


def adopt_from_env(
    role: Optional[str] = None, environ: Optional[Dict[str, str]] = None
) -> TraceContext:
    """Join the trace a parent process shipped via ``TFR_TRACE_CONTEXT``:
    the child keeps the parent's trace id, records the parent's span id as
    its parent, and mints its own span id / host / pid. Without the env
    var this is a fresh root context — subprocesses can call it
    unconditionally."""
    environ = os.environ if environ is None else environ
    raw = environ.get(TRACE_CONTEXT_ENV)
    if raw:
        try:
            return _adopt_child_of(json.loads(raw), role)
        except (ValueError, TypeError, KeyError, AttributeError):
            pass  # a malformed payload must not take the pipeline down
    return RECORDER.adopt(TraceContext.new(role if role is not None else "main"))


def adopt_child_from_json(
    obj: Any, role: Optional[str] = None
) -> TraceContext:
    """Join the trace of a coordinator that handed us its context over a
    WIRE payload rather than a spawn environment — the data-service worker
    adopting the dispatcher's trace at registration. Same semantics as
    ``adopt_from_env`` (ids propagate, identities never do); a malformed
    payload degrades to a fresh root, never raises."""
    try:
        return _adopt_child_of(obj, role)
    except (ValueError, TypeError, KeyError, AttributeError):
        return RECORDER.adopt(
            TraceContext.new(role if role is not None else "main")
        )


def atomic_write_bytes(path: str, data: bytes) -> None:
    """Write ``data`` to ``path`` via tmp-file + atomic rename, so a crash
    mid-write never leaves a truncated/corrupt artifact behind for a
    reader (the spool aggregator, Perfetto) to choke on. The tmp name is
    pid-suffixed: two processes racing on one path each land a complete
    file, last rename wins."""
    tmp = f"{path}.tmp-{os.getpid()}-{_new_id()[:8]}"
    try:
        with open(tmp, "wb") as fh:
            fh.write(data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


# ---------------------------------------------------------------------------
# Span tracing
# ---------------------------------------------------------------------------


class _NoopSpan:
    """The shared disabled-path context manager: no state, no lock."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs) -> None:
        return None


_NOOP = _NoopSpan()


class _SpanCtx:
    """One live span: records (name, begin, duration, tid, attrs) into its
    recorder on exit. An exception propagating through the span marks it
    ``failed=1`` — error latency stays attributed to its stage."""

    __slots__ = ("_rec", "name", "attrs", "_t0")

    def __init__(self, rec: "SpanRecorder", name: str, attrs: Optional[dict]):
        self._rec = rec
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        """Attach attrs discovered mid-span (row counts, byte counts)."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __enter__(self) -> "_SpanCtx":
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur = time.perf_counter_ns() - self._t0
        attrs = self.attrs
        if exc_type is not None:
            attrs = dict(attrs or (), failed=1)
        self._rec._record(self.name, self._t0, dur, attrs, "X")
        return None


class SpanRecorder:
    """Thread-safe bounded ring buffer of span/instant records.

    ``capacity`` bounds memory for arbitrarily long epochs: the buffer
    keeps the most recent ``capacity`` records and counts what it dropped
    (``dropped``) — a flight recorder, not an archive. ``enabled`` is a
    plain attribute read on the hot path; when False, the module-level
    ``span()``/``instant()`` return the shared no-op without touching this
    object's lock (pinned by tests/test_telemetry.py).
    """

    def __init__(self, capacity: int = 65536, enabled: bool = False):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self.enabled = enabled
        self._lock = threading.Lock()
        # ring storage: fixed-size list + running sequence number
        self._ring: List[Optional[tuple]] = [None] * capacity
        self._seq = 0
        self.dropped = 0
        #: Adopted TraceContext (None until the process identifies itself
        #: via ``adopt``/``current_context``). Purely metadata: recording
        #: never reads it, so the hot path is unchanged.
        self.context: Optional[TraceContext] = None

    def adopt(self, ctx: TraceContext) -> TraceContext:
        """Adopt ``ctx`` as this recorder's process identity, re-stamping
        host/pid to the adopting process (a shipped context carries the
        PARENT's ids plus a role — never another process's identity)."""
        host = socket.gethostname()
        pid = os.getpid()
        if ctx.host != host or ctx.pid != pid:
            ctx = dataclasses.replace(ctx, host=host, pid=pid)
        self.context = ctx
        return ctx

    # -- recording -----------------------------------------------------------

    def span(self, name: str, **attrs) -> "_SpanCtx | _NoopSpan":
        if not self.enabled:
            return _NOOP
        return _SpanCtx(self, name, attrs or None)

    def instant(self, name: str, **attrs) -> None:
        if not self.enabled:
            return
        self._record(name, time.perf_counter_ns(), 0, attrs or None, "i")

    def _record(
        self,
        name: str,
        t0_ns: int,
        dur_ns: int,
        attrs: Optional[dict] = None,
        ph: str = "X",
        tid: Optional[int] = None,
    ) -> None:
        # ``tid`` override: per-request spans (serving) record onto a
        # synthetic lane per request id so concurrent requests render as
        # parallel tracks in Perfetto instead of overlapping X events on
        # one thread's track
        if tid is None:
            tid = threading.get_ident()
        with self._lock:
            seq = self._seq
            self._seq = seq + 1
            if seq >= self.capacity:
                self.dropped += 1
            self._ring[seq % self.capacity] = (name, t0_ns, dur_ns, tid, attrs, ph)

    #: The always-recorded path, ``log(name, t0_ns, dur_ns, attrs=None)``: one
    #: already-measured span into the ring whether or not ``enabled`` (which
    #: goes on governing ``span``/``instant``/``record_span``). For the names
    #: the host log owns (``tracing.HOST_SPANS``), one a batch or rarer. The
    #: ring's one writer under its public name: no call in between.
    log = _record

    # -- reading -------------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return min(self._seq, self.capacity)

    def spans(self) -> List[tuple]:
        """Snapshot of retained records, oldest first:
        (name, t0_ns, dur_ns, tid, attrs, ph)."""
        with self._lock:
            seq = self._seq
            if seq <= self.capacity:
                return [r for r in self._ring[:seq]]
            start = seq % self.capacity
            return [
                r
                for r in (self._ring[start:] + self._ring[:start])
                if r is not None
            ]

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self.capacity
            self._seq = 0
            self.dropped = 0

    # -- export --------------------------------------------------------------

    def to_chrome_trace(self) -> Dict[str, Any]:
        """The retained records as a Chrome trace-event JSON object
        (https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU
        — the format Perfetto and chrome://tracing load). Durations are
        complete ("X") events; point events are instants ("i").

        Leads with process/thread metadata ("M") records — the process
        track is named from the adopted TraceContext (``role@host:pid``)
        and live pipeline threads get their Python thread names — so a
        ``merge_chrome_traces`` fusion of K per-process files renders as K
        labeled tracks in one Perfetto timeline. The adopted context also
        rides the top-level ``traceContext`` key (extra top-level keys are
        legal in the format), which is how the merger correlates files
        from different hosts that happen to reuse a pid."""
        ctx = self.context
        pid = ctx.pid if ctx is not None and ctx.pid else os.getpid()
        pname = ctx.label() if ctx is not None else f"tfrecord:{pid}"
        events: List[Dict[str, Any]] = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": pname},
            }
        ]
        thread_names = {t.ident: t.name for t in threading.enumerate()}
        seen_tids = set()
        spans = self.spans()
        for rec in spans:
            tid = rec[3]
            if tid in seen_tids:
                continue
            seen_tids.add(tid)
            name = thread_names.get(tid)
            if name:  # best-effort: exited threads keep their bare ident
                events.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": pid,
                        "tid": tid,
                        "args": {"name": name},
                    }
                )
        for name, t0_ns, dur_ns, tid, attrs, ph in spans:
            ev: Dict[str, Any] = {
                "name": name,
                "cat": "tfrecord",
                "ph": ph,
                "ts": t0_ns / 1000.0,  # microseconds
                "pid": pid,
                "tid": tid,
            }
            if ph == "X":
                ev["dur"] = dur_ns / 1000.0
            else:
                ev["s"] = "t"  # thread-scoped instant
            if attrs:
                ev["args"] = attrs
            events.append(ev)
        out: Dict[str, Any] = {"traceEvents": events, "displayTimeUnit": "ms"}
        if ctx is not None:
            out["traceContext"] = ctx.to_json()
        return out

    def save_chrome_trace(self, path: str) -> None:
        """Export atomically (tmp + rename): a crash mid-dump must never
        leave a truncated JSON for Perfetto or the trace merger to choke
        on."""
        atomic_write_bytes(
            path, json.dumps(self.to_chrome_trace()).encode("utf-8")
        )


#: Records the process's ring keeps; older ones are overwritten and counted
#: (``dropped``). The host log (``SpanRecorder.log``) is always on, so the
#: ring has to hold a whole run of the densest benchmark cell twice over:
#: ``criteo_mlperf.score`` wrote 34,006 records from process start to the end
#: of its run (a 20 s window of 185 batches a second at 9 records a batch, the
#: ingest check's epoch before it; my chip run, PR 51), a token cell 4,100-4,400.
RING_CAPACITY = 131072

#: Process-global flight recorder — spans come from dataset iterators,
#: prefetch workers, writer pipeline threads, and the stall guard, so the
#: ring is shared (one timeline). ``TFRecordOptions(trace="on")`` enables it
#: at dataset/writer construction; it stays on until ``disable()``. The host
#: log's records land in it regardless (``SpanRecorder.log``).
RECORDER = SpanRecorder(capacity=RING_CAPACITY)


def span(name: str, **attrs):
    """Record a duration span on the global recorder; a shared no-op (no
    lock, no allocation beyond the caller's kwargs) when tracing is off."""
    rec = RECORDER
    if not rec.enabled:
        return _NOOP
    return _SpanCtx(rec, name, attrs or None)


def instant(name: str, **attrs) -> None:
    """Record a point event (stall, hedge, retry, watchdog restart)."""
    rec = RECORDER
    if rec.enabled:
        rec._record(name, time.perf_counter_ns(), 0, attrs or None, "i")


def record_span(
    name: str, t0_ns: int, dur_ns: int, tid: Optional[int] = None, **attrs
) -> None:
    """Record an already-measured duration span — for callers that time a
    region manually and only know its extent after the fact (the
    consumer-side ``batch`` wait, which must not mark a terminal
    StopIteration as a failed span). ``tid`` places the span on a
    synthetic lane (serving's per-request tracks) instead of the calling
    thread's."""
    rec = RECORDER
    if rec.enabled:
        rec._record(name, t0_ns, dur_ns, attrs or None, "X", tid=tid)


def record_instant(
    name: str, t0_ns: int, tid: Optional[int] = None, **attrs
) -> None:
    """Record a point event at an explicit timestamp (``instant`` stamps
    now) — for shed/expiry markers that must land on the same clock and
    lane as the request spans around them."""
    rec = RECORDER
    if rec.enabled:
        rec._record(name, t0_ns, 0, attrs or None, "i", tid=tid)


def enable() -> SpanRecorder:
    RECORDER.enabled = True
    return RECORDER


def disable() -> None:
    RECORDER.enabled = False


def merge_chrome_traces(out_path: str, in_paths: Iterable[str]) -> Dict[str, Any]:
    """Fuse K per-process Chrome trace files (``save_chrome_trace``
    output, or any trace-event JSON object) into ONE Perfetto timeline
    with one labeled track per process, written atomically to
    ``out_path`` and returned.

    Processes are distinguished by pid, which is only unique per host:
    two files whose events share a pid but whose ``traceContext`` names a
    different host/root-span are given a fresh pid so their tracks never
    interleave. Files missing a ``process_name`` metadata record (traces
    from older recorders, hand-built files) get one synthesized from
    their context label or filename — every pid in the merged timeline
    renders as a named track. Unreadable/malformed inputs raise
    (ValueError/OSError): a silently dropped process would make the fused
    timeline lie."""
    files = []
    for path in in_paths:
        with open(path, "rb") as fh:
            try:
                obj = json.load(fh)
            except ValueError as e:
                raise ValueError(f"{path}: not valid JSON: {e}") from None
        if not isinstance(obj, dict) or not isinstance(
            obj.get("traceEvents"), list
        ):
            raise ValueError(f"{path}: not a Chrome trace-event JSON object")
        files.append((path, obj))
    events: List[Dict[str, Any]] = []
    contexts: List[Dict[str, Any]] = []
    owner: Dict[int, tuple] = {}  # output pid -> identity that holds it
    max_pid = 0
    for _, obj in files:
        for ev in obj["traceEvents"]:
            if isinstance(ev.get("pid"), int):
                max_pid = max(max_pid, ev["pid"])
    for idx, (path, obj) in enumerate(files):
        ctx = obj.get("traceContext")
        if not isinstance(ctx, dict):
            ctx = None
        if ctx is not None:
            contexts.append(ctx)
        # identity: same host + same root span = same process (a pid alone
        # collides across hosts); context-less files are their own identity
        ident_base = (
            (ctx.get("host"), ctx.get("pid"), ctx.get("span_id"))
            if ctx is not None
            else (os.path.basename(path), idx)
        )
        named = {
            ev.get("pid", 0)
            for ev in obj["traceEvents"]
            if ev.get("ph") == "M" and ev.get("name") == "process_name"
        }
        remap: Dict[int, int] = {}
        file_events: List[Dict[str, Any]] = []
        for ev in obj["traceEvents"]:
            pid = ev.get("pid", 0)
            out_pid = remap.get(pid)
            if out_pid is None:
                ident = ident_base + (pid,)
                out_pid = pid
                if owner.get(out_pid, ident) != ident:
                    max_pid += 1
                    out_pid = max_pid
                owner[out_pid] = ident
                remap[pid] = out_pid
                if pid not in named:
                    label = (
                        f"{ctx.get('role', 'proc')}@{ctx.get('host', '?')}:{pid}"
                        if ctx is not None
                        else os.path.basename(path)
                    )
                    events.append(
                        {
                            "name": "process_name",
                            "ph": "M",
                            "pid": out_pid,
                            "tid": 0,
                            "args": {"name": label},
                        }
                    )
            if out_pid != pid:
                ev = dict(ev, pid=out_pid)
            file_events.append(ev)
        events.extend(file_events)
    merged: Dict[str, Any] = {"traceEvents": events, "displayTimeUnit": "ms"}
    if contexts:
        merged["traceContexts"] = contexts
    atomic_write_bytes(out_path, json.dumps(merged).encode("utf-8"))
    return merged


# ---------------------------------------------------------------------------
# Bound-ness verdict
# ---------------------------------------------------------------------------

#: Gauge the consumer-side iterator maintains: EMA of the prefetch queue's
#: fill fraction sampled at each batch get (see io/dataset.py).
OCCUPANCY_GAUGE = "prefetch.occupancy"


def boundness_verdict(occupancy: Optional[float]) -> str:
    """Producer/consumer verdict from a queue fill fraction in [0, 1].

    ≥ 0.66: the queue is mostly full — the producer (decode) keeps ahead,
    so the CONSUMER is the bottleneck (``consumer_bound``; for a training
    loop this is the goal state: the device never waits on input).
    ≤ 0.33: mostly empty — the consumer drains faster than decode refills
    (``producer_bound``: speed up the input pipeline — more workers,
    cache, faster store). Between: ``balanced``. None: ``unknown`` (no
    samples yet)."""
    if occupancy is None:
        return "unknown"
    if occupancy >= 0.66:
        return "consumer_bound"
    if occupancy <= 0.33:
        return "producer_bound"
    return "balanced"


def verdict_from_metrics(metrics=None, gauge: str = OCCUPANCY_GAUGE) -> str:
    """The verdict for a metrics registry's occupancy gauge (the process
    default registry when ``metrics`` is None)."""
    if metrics is None:
        from tpu_tfrecord.metrics import METRICS as metrics  # noqa: N813
    return boundness_verdict(metrics.gauge_value(gauge))


# ---------------------------------------------------------------------------
# Training verdict (the trainer-side twin of the bound-ness verdict)
# ---------------------------------------------------------------------------

#: The step-phase decomposition the training harness records
#: (examples/_harness.py StepPhases): disjoint wall-clock partitions of one
#: train step. Stage names are ``train.<phase>``; windowed phase shares are
#: published as ``train.share.<phase>`` gauges so the spool/doctor can read
#: a trainer's recent regime, not its lifetime average.
TRAIN_PHASES = ("data_wait", "h2d", "compute", "ckpt")
TRAIN_STAGE_PREFIX = "train."
TRAIN_SHARE_PREFIX = "train.share."

#: Verdict thresholds: a step spending >= this fraction on checkpointing
#: is ckpt_bound; >= this fraction on input (data_wait + h2d) is
#: input_bound (the tf.data-style diagnosis that drives elastic scaling —
#: an input_bound trainer wants more decode capacity, a compute_bound one
#: is the goal state).
TRAIN_CKPT_BOUND_SHARE = 0.25
TRAIN_INPUT_BOUND_SHARE = 0.5


def training_verdict(shares: Optional[Dict[str, float]]) -> str:
    """``input_bound`` / ``compute_bound`` / ``ckpt_bound`` / ``unknown``
    from a step-phase share mapping (keys = TRAIN_PHASES entries, values
    fractions of step wall time; missing phases read as 0).

    Checkpointing is checked first: a trainer drowning in ckpt writes is
    ckpt_bound even when its input pipeline is also slow — the fix (async
    or less frequent checkpoints) is different from "add decode workers",
    so the louder-signal phase wins. ``unknown`` when no shares exist."""
    if not shares or sum(shares.values()) <= 0:
        return "unknown"
    if shares.get("ckpt", 0.0) >= TRAIN_CKPT_BOUND_SHARE:
        return "ckpt_bound"
    input_share = shares.get("data_wait", 0.0) + shares.get("h2d", 0.0)
    if input_share >= TRAIN_INPUT_BOUND_SHARE:
        return "input_bound"
    return "compute_bound"


#: Queue depth (waiting requests) at or above this fraction of the
#: serving tier's admission bound reads as queue pressure — requests are
#: arriving faster than slots free, so the p99 miss is an ADMISSION
#: problem (shed more / add a replica), not a model-speed problem.
SERVE_QUEUE_BOUND_FILL = 0.5


def serving_verdict(
    p99_ms: Optional[float],
    queue_depth: Optional[float],
    slo_p99_ms: float,
    max_queue: int = 16,
) -> str:
    """Latency-SLO verdict for the serving tier (the inference-side twin
    of the bound-ness verdict): ``meeting_slo`` when per-request p99 is
    within ``slo_p99_ms``; on a miss, ``queue_bound`` when the waiting
    queue sits at ≥ ``SERVE_QUEUE_BOUND_FILL`` of the admission bound
    (latency is queueing delay — shed harder or scale out) else
    ``compute_bound`` (the compiled step itself is too slow for the SLO —
    a smaller model/bigger mesh problem no replica count fixes).
    ``unknown`` when no requests have completed yet."""
    if p99_ms is None:
        return "unknown"
    if p99_ms <= slo_p99_ms:
        return "meeting_slo"
    depth = 0.0 if queue_depth is None else float(queue_depth)
    if depth >= SERVE_QUEUE_BOUND_FILL * max(1, int(max_queue)):
        return "queue_bound"
    return "compute_bound"


class OccupancyEma:
    """Shared smoothing for the bound-ness occupancy gauges: one EMA
    (alpha 0.2 — the verdict reflects the recent regime, not the epoch's
    warmup) feeding one named gauge. Used by the consumer iterator
    (``prefetch.occupancy``) and the write slab pipeline
    (``write.occupancy``), so both verdicts read identically-smoothed
    signals."""

    __slots__ = ("gauge", "alpha", "value")

    def __init__(self, gauge: str, alpha: float = 0.2):
        self.gauge = gauge
        self.alpha = alpha
        self.value: Optional[float] = None

    def update(self, fill: float, metrics=None) -> float:
        v = self.value
        self.value = (
            fill if v is None else (1.0 - self.alpha) * v + self.alpha * fill
        )
        if metrics is None:
            from tpu_tfrecord.metrics import METRICS as metrics  # noqa: N813
        metrics.gauge(self.gauge, self.value)
        return self.value


#: Histogram families that hold DIMENSIONLESS values (fractions/ratios —
#: the in-jit model diagnostics the training harness folds each step),
#: not seconds: every ms-renderer must skip them, or a dropped-token
#: fraction of 0.02 would print as "20ms of latency" on the fleet page.
DIMENSIONLESS_HIST_PREFIXES = ("moe.", "pipeline.")


def is_latency_hist(name: str) -> bool:
    return not name.startswith(DIMENSIONLESS_HIST_PREFIXES)


def quantiles_ms(source: Dict[str, Dict[str, float]]) -> Dict[str, Dict[str, float]]:
    """Convert a ``Metrics.quantiles()`` mapping — or any mapping whose
    entries carry ``p50_s``/``p90_s``/``p99_s`` (``snapshot()`` stage
    entries qualify) — into the shared milliseconds shape the pulse,
    ``bench_write.py`` and doctor lines all emit, so their field sets cannot drift
    apart. Entries without quantiles are skipped, as are the
    DIMENSIONLESS diagnostic histograms (their values are fractions;
    rendering them as milliseconds would lie)."""
    out: Dict[str, Dict[str, float]] = {}
    for name, q in sorted(source.items()):
        if not q or "p50_s" not in q or not is_latency_hist(name):
            continue
        entry = {
            "p50_ms": round(q["p50_s"] * 1e3, 3),
            "p90_ms": round(q["p90_s"] * 1e3, 3),
            "p99_ms": round(q["p99_s"] * 1e3, 3),
        }
        if "count" in q:
            entry["count"] = q["count"]
        elif "hist_count" in q:
            entry["count"] = int(q["hist_count"])
        out[name] = entry
    return out


# ---------------------------------------------------------------------------
# Telemetry pulse
# ---------------------------------------------------------------------------


class Pulse:
    """Periodic one-line-JSON telemetry reporter.

    Every ``interval_s`` the pulse thread emits one machine-parseable dict
    through ``emit`` (default: a ``tfrecord.pulse {json}`` INFO line on the
    package logger — the same fleet-log convention as
    ``log_salvage_event``). Stage throughputs are PER-INTERVAL deltas
    (records/bytes produced this interval over the interval wall time), so
    a stall shows up as the pulse going to zero, not as a slowly decaying
    lifetime average; counters, gauges, and histogram quantiles are
    cumulative snapshots. ``tick()`` is public so tests and the doctor can
    force a pulse without waiting out the interval."""

    def __init__(
        self,
        interval_s: float,
        metrics=None,
        emit: Optional[Callable[[Dict[str, Any]], None]] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        if interval_s <= 0:
            raise ValueError("interval_s must be > 0")
        if metrics is None:
            from tpu_tfrecord.metrics import METRICS as metrics  # noqa: N813
        self.interval_s = interval_s
        self.metrics = metrics
        self.emit = emit if emit is not None else _log_pulse
        self._clock = clock
        self._prev_totals: Dict[str, Tuple[int, int, int, float]] = {}
        self._prev_t = clock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._observers: List[Callable[[Dict[str, Any]], Optional[Dict]]] = []

    def add_observer(
        self, fn: Callable[[Dict[str, Any]], Optional[Dict]]
    ) -> "Pulse":
        """Register a per-tick observer. Each tick, after the payload is
        computed and before it is emitted, every observer is called with
        the payload; a returned dict is merged into the emitted line. The
        autotune controller runs this way (its decisions land in the same
        pulse line that carries the interval they were made from).
        Observer exceptions are swallowed — telemetry (and tuning riding
        on it) must never take the pipeline down."""
        self._observers.append(fn)
        return self

    def start(self) -> "Pulse":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="tfr-pulse"
            )
            self._thread.start()
        return self

    def stop(self, final: bool = True) -> None:
        """Stop the thread; ``final`` emits one last pulse covering the
        tail interval so short epochs still leave a line behind.
        Idempotent: a second stop (iterator close + GC finalizer) does
        nothing."""
        already = self._stop.is_set()
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
            self._thread = None
        if final and not already:
            try:
                self.tick()
            except Exception:  # graftlint: swallow(final tail tick is best-effort at stop)
                pass

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:  # graftlint: swallow(telemetry must never take the pipeline down)
                # telemetry must never take the pipeline down
                pass

    def tick(self) -> Dict[str, Any]:
        """Compute and emit one pulse line; returns the emitted dict."""
        now = self._clock()
        dt = max(now - self._prev_t, 1e-9)
        self._prev_t = now
        totals = self.metrics.raw_totals()
        stages: Dict[str, Dict[str, float]] = {}
        counters: Dict[str, int] = {}
        for name, (records, nbytes, batches, seconds) in sorted(totals.items()):
            prev = self._prev_totals.get(name, (0, 0, 0, 0.0))
            d_rec = records - prev[0]
            d_bytes = nbytes - prev[1]
            if seconds == 0.0 and nbytes == 0:
                # a pure count()-style event counter (read.retries,
                # cache.hits, *.errors): cumulative total + interval delta
                counters[name] = records
                if d_rec:
                    counters[name + ".delta"] = d_rec
                continue
            stages[name] = {
                "records_per_sec": round(d_rec / dt, 1),
                "bytes_per_sec": round(d_bytes / dt, 1),
                "records": records,
            }
        self._prev_totals = totals
        gauges = self.metrics.gauges()
        quantiles = quantiles_ms(self.metrics.quantiles())
        ctx = current_context()
        payload = {
            "event": "pulse",
            "ts": round(time.time(), 3),
            "interval_s": round(dt, 3),
            # process identity: in a fleet (every process pulsing into one
            # log stream) a line is unattributable without host/pid/role,
            # and trace_id correlates the line with the merged timeline
            "proc": {
                "host": ctx.host,
                "pid": ctx.pid,
                "role": ctx.role,
                "trace_id": ctx.trace_id,
            },
            "stages": stages,
            "counters": counters,
            "gauges": {k: round(v, 4) for k, v in sorted(gauges.items())},
            "quantiles": quantiles,
            "verdict": boundness_verdict(gauges.get(OCCUPANCY_GAUGE)),
        }
        for fn in list(self._observers):
            try:
                extra = fn(payload)
                if extra:
                    payload.update(extra)
            except Exception:
                # observers must never take the pipeline down — but a
                # crashing controller silently freezing the knobs must
                # not be invisible either: the error counter lands in
                # this very pulse's counters on the NEXT tick
                try:
                    self.metrics.count("pulse.observer_errors")
                except Exception:  # graftlint: swallow(the observer_errors counter itself failed)
                    pass
        self.emit(payload)
        return payload


def _log_pulse(payload: Dict[str, Any]) -> None:
    from tpu_tfrecord.metrics import logger

    logger.info("tfrecord.pulse %s", json.dumps(payload, sort_keys=True))


# ---------------------------------------------------------------------------
# Prometheus text endpoint (stdlib HTTP only)
# ---------------------------------------------------------------------------


def escape_label_value(v: Any) -> str:
    """Prometheus label-value escaping: a value containing a quote,
    backslash, or newline must not break the exposition format."""
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


def append_family(
    lines: List[str], fam: str, ftype: str, samples: List[str]
) -> None:
    """Append one metric family to an exposition page. The format requires
    every sample of one family to form a single contiguous block under its
    # TYPE line — interleaving families per stage makes strict parsers
    (promtool, OpenMetrics scrapes) reject the page as duplicate families,
    so both the process page and the fleet's federated page build each
    family's samples in full before appending through here."""
    if samples:
        lines.append(f"# TYPE {fam} {ftype}")
        lines.extend(samples)


def summary_family_lines(
    fam: str, labeled_quantiles: Iterable[Tuple[str, Dict[str, float]]]
) -> List[str]:
    """Samples for a p50/p90/p99 summary family from ``quantiles()``-shaped
    dicts: per entry, one ``fam{<labels>,quantile="q"} v`` line per
    quantile plus the ``fam_count{<labels>}`` line."""
    samples: List[str] = []
    for label, q in labeled_quantiles:
        if not q:
            continue
        for key, quant in (("p50_s", "0.5"), ("p90_s", "0.9"), ("p99_s", "0.99")):
            samples.append(f'{fam}{{{label},quantile="{quant}"}} {q[key]:.9f}')
        samples.append(f'{fam}_count{{{label}}} {q["count"]}')
    return samples


def prometheus_text(metrics=None) -> str:
    """The registry in Prometheus text exposition format: stage totals as
    counters, gauges as gauges, histogram quantiles as a summary-style
    family. Stage/gauge names ride in label values (where dots are legal),
    so the metric-family names stay fixed and dashboards survive new
    stages."""
    if metrics is None:
        from tpu_tfrecord.metrics import METRICS as metrics  # noqa: N813
    totals = sorted(metrics.raw_totals().items())
    lines: List[str] = []

    def family(fam: str, ftype: str, samples: List[str]) -> None:
        append_family(lines, fam, ftype, samples)

    family(
        "tfrecord_stage_records_total",
        "counter",
        [
            f'tfrecord_stage_records_total{{stage="{n}"}} {r}'
            for n, (r, _b, _bt, _s) in totals
        ],
    )
    family(
        "tfrecord_stage_bytes_total",
        "counter",
        [
            f'tfrecord_stage_bytes_total{{stage="{n}"}} {b}'
            for n, (_r, b, _bt, _s) in totals
            if b
        ],
    )
    family(
        "tfrecord_stage_seconds_total",
        "counter",
        [
            f'tfrecord_stage_seconds_total{{stage="{n}"}} {s:.6f}'
            for n, (_r, _b, _bt, s) in totals
            if s
        ],
    )
    family(
        "tfrecord_gauge",
        "gauge",
        [
            f'tfrecord_gauge{{name="{name}"}} {value:.6g}'
            for name, value in sorted(metrics.gauges().items())
        ],
    )
    family(
        "tfrecord_latency_seconds",
        "summary",
        summary_family_lines(
            "tfrecord_latency_seconds",
            (
                (f'stage="{name}"', q)
                for name, q in sorted(metrics.quantiles().items())
            ),
        ),
    )
    # Exemplars as a dedicated gauge family (value = the exemplared
    # observation, seconds) instead of OpenMetrics `# {...}` suffixes —
    # the pinned text-format 0.0.4 parse of this page would reject the
    # suffix syntax. `le` is the bucket's upper bound, so a tail sample
    # here is clickable back to its trace/span ids.
    family(
        "tfrecord_latency_exemplar_seconds",
        "gauge",
        [
            "tfrecord_latency_exemplar_seconds{"
            f'stage="{escape_label_value(name)}",'
            f'le="{Histogram.bucket_le(int(idx)):.6g}",'
            f'trace_id="{escape_label_value(t)}",'
            f'span_id="{escape_label_value(s)}"'
            "} " + f"{v:.6g}"
            for name, state in sorted(metrics.hist_states().items())
            if is_latency_hist(name)
            for idx, (t, s, v) in sorted(
                (state.get("exemplars") or {}).items(), key=lambda kv: int(kv[0])
            )
        ],
    )
    return "\n".join(lines) + "\n"


_EXPORTERS: Dict[int, Any] = {}
_EXPORTERS_LOCK = threading.Lock()


def ensure_exporter(port: int, metrics=None):
    """Start (or return the already-running) Prometheus text endpoint on
    ``port`` — process-wide, idempotent per port, daemon-threaded. ``port``
    0 binds an ephemeral port; the bound address is logged at startup and
    queryable via ``exporter_address(port)`` (keyed by the REQUESTED port,
    as is ``shutdown_exporter`` — pass 0 back, not the ephemeral number).
    Serves ``/metrics`` (and ``/`` as an alias); anything else 404s.
    Stdlib ``http.server`` only — no new dependencies. A port that cannot
    be bound (already taken by another process) logs a warning and returns
    None — telemetry must never take the pipeline down."""
    if metrics is None:
        from tpu_tfrecord.metrics import METRICS as metrics  # noqa: N813

    reg = metrics
    return serve_text_endpoint(port, lambda: prometheus_text(reg))


def serve_text_endpoint(
    port: int, render: Callable[[], str], kind: str = "process"
):
    """The stdlib-HTTP plumbing under ``ensure_exporter``, parameterized
    on the page renderer so other registries (the fleet aggregator's
    federated page, tpu_tfrecord.fleet) serve through the same idempotent
    per-port server table without duplicating it. Same contract:
    idempotent per requested port; unbindable port warns and returns
    None. A port already serving a DIFFERENT page kind (e.g. a
    ``telemetry_port=0`` process exporter claimed key 0 and a fleet
    aggregator now asks for 0) also warns and returns None — returning
    the existing server would let the caller report success while every
    scrape silently gets the wrong page."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from tpu_tfrecord.metrics import logger

    with _EXPORTERS_LOCK:
        server = _EXPORTERS.get(port)
        if server is not None:
            served = getattr(server, "_tfr_kind", "process")
            if served != kind:
                logger.warning(
                    "tfrecord.telemetry endpoint for requested port %d "
                    "already serves the %r page; NOT replacing it with the "
                    "requested %r page — use a different port",
                    port, served, kind,
                )
                return None  # callers must see the failure, not a server
            return server

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 — http.server API
                if self.path.split("?", 1)[0] not in ("/metrics", "/"):
                    self.send_response(404)
                    self.end_headers()
                    return
                body = render().encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4; charset=utf-8"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args) -> None:  # quiet: telemetry, not access logs
                return

        try:
            server = ThreadingHTTPServer(("127.0.0.1", port), _Handler)
        except OSError as e:
            # a taken port (two processes sharing one config) must not
            # take the pipeline down — telemetry is never load-bearing
            logger.warning(
                "tfrecord.telemetry prometheus endpoint on port %d "
                "unavailable (%s); continuing without it", port, e,
            )
            return None
        server.daemon_threads = True
        server._tfr_kind = kind
        threading.Thread(
            target=server.serve_forever, daemon=True, name="tfr-prometheus"
        ).start()
        _EXPORTERS[port] = server
        host, bound = server.server_address[:2]
        logger.info(
            "tfrecord.telemetry prometheus endpoint on http://%s:%d/metrics",
            host, bound,
        )
        return server


def exporter_address(port: int) -> Optional[Tuple[str, int]]:
    """(host, bound_port) of the exporter started for REQUESTED ``port``
    (the public way to learn which ephemeral port ``telemetry_port=0``
    actually bound), or None when none is running."""
    with _EXPORTERS_LOCK:
        server = _EXPORTERS.get(port)
    return server.server_address[:2] if server is not None else None


def shutdown_exporter(port: int) -> None:
    """Stop the exporter started for REQUESTED ``port`` (tests; production
    leaves it up). For an ephemeral exporter pass 0 — the key is the port
    you asked for, not the one the OS picked."""
    with _EXPORTERS_LOCK:
        server = _EXPORTERS.pop(port, None)
    if server is not None:
        server.shutdown()
        server.server_close()
