"""Throughput counters and structured logging.

The reference has no observability of its own (SURVEY.md §5: tracing ABSENT,
metrics ride on Spark's UI). Here per-stage counters are first-class because
records/sec and bytes/sec into the device ARE the north-star metric
(BASELINE.md). Counters are cheap (updated at batch granularity, never per
record) and thread-safe.

Three value kinds live in one registry (distinct storage, one lock):

- **stages/counters** (``add``/``count``): monotonic per-stage totals —
  records, bytes, batches, seconds. ``count()`` is the pure-event spelling
  (the count rides the ``records`` field).
- **gauges** (``gauge``): last-written instantaneous values — prefetch
  queue depth, in-flight workers, backpressure occupancy. First-class
  since PR 5 (previously anything instantaneous had to abuse ``count``).
- **latency histograms** (``observe`` / ``timed``): log-bucketed
  per-op latency distributions (tpu_tfrecord.telemetry.Histogram) so
  p50/p90/p99 sit next to the totals and stragglers stop hiding inside
  means. ``timed`` feeds them automatically — one observation per timed
  block, same lock acquisition as the totals update. ``add``/``observe``
  take an optional ``exemplar=(trace_id, span_id)`` that tags the
  observation's bucket (the pointer from a fleet p99 back to the request
  trace that produced it — see telemetry.Histogram.exemplar_at).

Cumulative registries compose upward: the fleet spool ships
``raw_totals()`` + ``hist_states()`` per interval, and the SLO engine
(tpu_tfrecord.slo.SloEngine) folds those cumulative snapshots into its
bounded ring of windowed samples for multi-window burn-rate alerts —
this registry stays cheap and monotonic, windowing lives downstream.

Every name passed to these calls must be registered in
``tpu_tfrecord.vocabulary`` (the single owner of the metric/span name
vocabulary) and documented in the README's generated vocabulary block —
``tools/graftlint`` enforces both directions, so a dashboard keyed on a
documented name can never silently read zero because the code spells it
differently.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from tpu_tfrecord.telemetry import Histogram

logger = logging.getLogger("tpu_tfrecord")


@dataclass
class StageStats:
    records: int = 0
    bytes: int = 0
    batches: int = 0
    seconds: float = 0.0

    def throughput(self) -> Dict[str, float]:
        dt = self.seconds or 1e-9
        return {
            "records_per_sec": self.records / dt,
            "bytes_per_sec": self.bytes / dt,
            "records": self.records,
            "bytes": self.bytes,
            "batches": self.batches,
            "seconds": self.seconds,
        }


class Metrics:
    """Registry of per-stage counters (read, decode, h2d, write, ...),
    gauges, and latency histograms."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stages: Dict[str, StageStats] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, Histogram] = {}

    def add(
        self,
        stage: str,
        records: int = 0,
        nbytes: int = 0,
        seconds: float = 0.0,
        latency: Optional[float] = None,
        exemplar: Optional[Tuple[str, str]] = None,
    ) -> None:
        """Accumulate into a stage's totals. ``latency`` additionally folds
        one observation into the stage's latency histogram under the SAME
        lock acquisition (``timed`` passes its elapsed time here, so every
        timed stage grows a p50/p90/p99 for free). ``exemplar`` is an
        optional (trace_id, span_id) attached to the latency observation's
        bucket (telemetry.Histogram exemplars): the pointer from a fleet
        p99 back to the request trace that produced it."""
        with self._lock:
            st = self._stages.setdefault(stage, StageStats())
            st.records += records
            st.bytes += nbytes
            st.batches += 1
            st.seconds += seconds
            if latency is not None:
                hist = self._hists.get(stage)
                if hist is None:
                    hist = self._hists[stage] = Histogram()
                hist.observe(latency, exemplar=exemplar)

    def count(self, stage: str, n: int = 1) -> None:
        """Increment a pure event counter (the ``records`` field carries the
        count). Used by the robustness counters: ``read.corrupt_records``,
        ``read.resyncs``, ``read.retries``, ``read.skipped_shards``,
        ``write.commit_retries``, the stall counters (``read.stalls``,
        ``read.deadline_misses``, ``read.hedges``, ``read.hedge_wins``,
        ``read.watchdog_restarts``), the epoch-cache counters
        (``cache.hits``, ``cache.misses``, ``cache.bytes_written``,
        ``cache.evictions``, ``cache.corrupt_fallbacks`` — mmap-served
        chunk throughput lands in the ``cache.serve`` stage), the
        per-stage error counters ``<stage>.errors`` (bumped by ``timed``
        when an exception propagates through it), the backpressure
        counters ``read.backpressure_waits``/``write.backpressure_waits``,
        the autotune decision counter ``autotune.adjustments`` (each
        controller knob move — the current knob VALUES live in the
        ``autotune.<knob>`` gauges), the cluster-spool counters
        (``fleet.spool_writes`` = snapshots landed in the telemetry spool,
        ``fleet.spool_errors`` = snapshot attempts that failed — spooling
        is telemetry, it never raises into the pipeline), and the
        training flight recorder's ``train.steps`` (one per completed
        harness step — the step-phase decomposition itself rides the
        ``train.data_wait``/``train.h2d``/``train.compute``/``train.ckpt``
        /``train.step`` STAGES with latency histograms, the windowed
        phase shares ride ``train.share.<phase>`` gauges, and the in-jit
        model diagnostics ride the ``moe.dropped_fraction``/
        ``moe.gate_entropy``/``moe.expert_imbalance``/
        ``pipeline.bubble_fraction`` gauges + histograms).

        INSTANTANEOUS values (queue depths, occupancies, in-flight worker
        counts) belong in ``gauge()``, not here — a counter only goes up.

        Thread-safety audit (counters are bumped from prefetch workers,
        stall-guard workers, the watchdog, and writer pipeline threads):
        every mutation — add/count/gauge/observe — and every read —
        counter/stage/gauge_value/snapshot/raw_totals/gauges/quantiles —
        takes ``self._lock``, so concurrent updates never lose increments
        (pinned by tests/test_chaos.py::TestMetricsThreadSafety and
        tests/test_telemetry.py::TestGauges). The one contract callers
        must keep: a StageStats object returned by ``stage()`` is a live
        reference — read its fields, never mutate them outside this class
        (all in-tree callers only read)."""
        self.add(stage, records=n)

    def counter(self, stage: str) -> int:
        """Current value of a ``count()``-style counter (0 if never hit)."""
        with self._lock:
            st = self._stages.get(stage)
            return st.records if st is not None else 0

    # -- gauges (instantaneous values, last write wins) ----------------------

    def gauge(self, name: str, value: float) -> None:
        """Set an instantaneous gauge (prefetch queue depth, in-flight
        workers, backpressure occupancy). Last write wins — gauges answer
        "what is it NOW", counters answer "how much so far"."""
        with self._lock:
            self._gauges[name] = float(value)

    def gauge_value(self, name: str, default: Optional[float] = None) -> Optional[float]:
        with self._lock:
            return self._gauges.get(name, default)

    def gauges(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._gauges)

    # -- latency histograms --------------------------------------------------

    def observe(
        self,
        stage: str,
        seconds: float,
        exemplar: Optional[Tuple[str, str]] = None,
    ) -> None:
        """Fold one latency observation into ``stage``'s histogram without
        touching its throughput totals (for ops timed inline rather than
        through ``timed``). ``exemplar`` optionally tags the observation's
        bucket with a (trace_id, span_id) — see ``add``."""
        with self._lock:
            hist = self._hists.get(stage)
            if hist is None:
                hist = self._hists[stage] = Histogram()
            hist.observe(seconds, exemplar=exemplar)

    def quantiles(self, prefix: Optional[str] = None) -> Dict[str, Dict[str, float]]:
        """Per-stage latency quantile snapshot (p50/p90/p99 seconds +
        count/mean); ``prefix`` filters like ``snapshot``."""
        with self._lock:
            return {
                name: hist.quantiles()
                for name, hist in self._hists.items()
                if prefix is None
                or name == prefix
                or name.startswith(prefix + ".")
            }

    def hist_states(self) -> Dict[str, dict]:
        """One-lock copy of every stage histogram's mergeable state
        (telemetry.Histogram.state — sparse bucket counts). The spool
        writer (tpu_tfrecord.fleet) ships these across processes; fixed
        shared bucket layout means the aggregator's merge is EXACT, so
        cluster p99s are real quantiles, not averages of quantiles."""
        with self._lock:
            return {name: hist.state() for name, hist in self._hists.items()}

    def stage(self, stage: str) -> StageStats:
        with self._lock:
            return self._stages.setdefault(stage, StageStats())

    def snapshot(self, prefix: Optional[str] = None) -> Dict[str, Dict[str, float]]:
        """Per-stage throughput map; ``prefix`` filters to one stage family
        (e.g. ``'write'`` -> write, write.encode, write.compress, write.io
        — the breakdown ``bench_write.py`` reports).

        Key stability contract (``bench_write.py`` and the tests read these): stage entries keep
        the exact keys they always had (records_per_sec, bytes_per_sec,
        records, bytes, batches, seconds). Stages with a latency histogram
        additionally carry ``p50_s``/``p90_s``/``p99_s``/``hist_count``;
        gauges appear under their own names as ``{"gauge": value}`` —
        distinct shapes, so consumers that iterate stages should select on
        the keys they need (``"seconds" in entry``)."""
        with self._lock:
            out: Dict[str, Dict[str, float]] = {}
            for name, st in self._stages.items():
                if (
                    prefix is not None
                    and name != prefix
                    and not name.startswith(prefix + ".")
                ):
                    continue
                entry = st.throughput()
                hist = self._hists.get(name)
                if hist is not None and hist.count:
                    q = hist.quantiles()
                    entry["p50_s"] = q["p50_s"]
                    entry["p90_s"] = q["p90_s"]
                    entry["p99_s"] = q["p99_s"]
                    entry["hist_count"] = q["count"]
                out[name] = entry
            for name, value in self._gauges.items():
                if (
                    prefix is not None
                    and name != prefix
                    and not name.startswith(prefix + ".")
                ):
                    continue
                out[name] = {"gauge": value}
            return out

    def raw_totals(self) -> Dict[str, Tuple[int, int, int, float]]:
        """One-lock copy of every stage's raw totals as (records, bytes,
        batches, seconds) — the delta source for telemetry.Pulse and the
        Prometheus exporter."""
        with self._lock:
            return {
                name: (st.records, st.bytes, st.batches, st.seconds)
                for name, st in self._stages.items()
            }

    def reset(self) -> None:
        with self._lock:
            self._stages.clear()
            self._gauges.clear()
            self._hists.clear()
            # the fleet spool's wall-window epoch (tpu_tfrecord.fleet
            # stamps it on this registry) describes the totals just
            # cleared — a restarted registry restarts the window
            self.__dict__.pop("_spool_epoch", None)


# Process-global default registry.
METRICS = Metrics()


def log_salvage_event(**fields) -> None:
    """One structured warning per salvage/skip event (corrupt frame found,
    resync landed, shard dropped): a single machine-parseable JSON line on
    the package logger, keyed by path/offset/kind. Fleet log pipelines can
    alert on these without scraping free-form text."""
    logger.warning(
        "tfrecord.salvage %s", json.dumps(fields, sort_keys=True, default=str)
    )


class timed:
    """Context manager adding elapsed wall time (and counts) to a stage,
    plus one latency-histogram observation per block.

    An exception propagating through the block still records the elapsed
    time AND bumps ``<stage>.errors`` — per-stage error rates are visible
    in the pulse/doctor output instead of failed work silently vanishing
    from the timings (the pre-PR-5 ``__exit__(*exc)`` swallowed the
    exception info)."""

    def __init__(self, stage: str, metrics: Optional[Metrics] = None):
        self.stage = stage
        self.metrics = metrics or METRICS
        self.records = 0
        self.bytes = 0

    def __enter__(self) -> "timed":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dt = time.perf_counter() - self._t0
        self.metrics.add(
            self.stage,
            records=self.records,
            nbytes=self.bytes,
            seconds=dt,
            latency=dt,
        )
        if exc_type is not None:
            self.metrics.count(self.stage + ".errors")
