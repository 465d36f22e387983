"""Cluster flight recorder tests (ISSUE 7): trace-context propagation,
telemetry spool + aggregation, trace merging, and the fleet doctor.

Tier 1 (no devices). Unit tests drive private Metrics/TelemetrySpool
instances with injected clocks; the integration tests spawn real
subprocesses (tests/fleet_worker.py) that read concurrently while
spooling into one directory, then check the aggregated picture against
the per-process ground truth EXACTLY — sums, histogram buckets, labels,
liveness.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from tpu_tfrecord import fleet, telemetry
from tpu_tfrecord.fleet import (
    TelemetryAggregator,
    TelemetrySpool,
    read_spool,
)
from tpu_tfrecord.metrics import METRICS, Metrics
from tpu_tfrecord.schema import LongType, StringType, StructField, StructType
from tpu_tfrecord.telemetry import (
    Histogram,
    TraceContext,
    atomic_write_bytes,
    merge_chrome_traces,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "fleet_worker.py")
DOCTOR = os.path.join(REPO, "tools", "tfrecord_doctor.py")

SCHEMA = StructType(
    [
        StructField("id", LongType(), nullable=False),
        StructField("s", StringType()),
    ]
)


def write_dataset(path, n_shards=3, rows_per_shard=40):
    import tpu_tfrecord.io as tfio

    for s in range(n_shards):
        tfio.write(
            [[i, f"s{i}"] for i in range(s * rows_per_shard, (s + 1) * rows_per_shard)],
            SCHEMA,
            str(path),
            mode="append" if s else "overwrite",
        )
    return str(path)


@pytest.fixture(autouse=True)
def _clean_process_globals():
    """The trace context and metrics registry are process-global; every
    test starts and ends with both pristine so identity assertions are
    order-independent."""
    telemetry.disable()
    telemetry.RECORDER.clear()
    telemetry.RECORDER.context = None
    METRICS.reset()
    yield
    telemetry.disable()
    telemetry.RECORDER.clear()
    telemetry.RECORDER.context = None
    METRICS.reset()


# ---------------------------------------------------------------------------
# TraceContext
# ---------------------------------------------------------------------------


class TestTraceContext:
    def test_new_stamps_identity(self):
        ctx = TraceContext.new(role="dispatcher")
        assert ctx.trace_id and ctx.span_id and ctx.trace_id != ctx.span_id
        assert ctx.parent_span_id is None
        assert ctx.role == "dispatcher"
        assert ctx.pid == os.getpid()
        assert ctx.host
        assert ctx.label() == f"dispatcher@{ctx.host}:{ctx.pid}"

    def test_child_shares_trace_not_identity(self):
        root = TraceContext.new()
        child = root.child("decode_worker")
        assert child.trace_id == root.trace_id
        assert child.parent_span_id == root.span_id
        assert child.span_id != root.span_id
        # host/pid are the CHILD's to stamp at adoption
        assert child.host == "" and child.pid == 0

    def test_json_round_trip(self):
        ctx = TraceContext.new(role="trainer")
        assert TraceContext.from_json(json.loads(json.dumps(ctx.to_json()))) == ctx
        # unknown keys from a newer writer are ignored, not fatal
        obj = dict(ctx.to_json(), future_field=1)
        assert TraceContext.from_json(obj) == ctx

    def test_adopt_restamps_host_pid(self):
        foreign = TraceContext(
            trace_id="t" * 16, span_id="s" * 16, host="elsewhere", pid=1
        )
        adopted = telemetry.adopt(foreign)
        assert adopted.trace_id == foreign.trace_id
        assert adopted.pid == os.getpid()
        assert adopted.host != "elsewhere"
        assert telemetry.current_context() is adopted

    def test_current_context_is_sticky(self):
        a = telemetry.current_context()
        assert telemetry.current_context() is a

    def test_adopt_from_env_joins_parent_trace(self):
        parent = TraceContext.new(role="parent")
        ctx = telemetry.adopt_from_env(role="worker", environ=parent.to_env())
        assert ctx.trace_id == parent.trace_id
        assert ctx.parent_span_id == parent.span_id
        assert ctx.span_id != parent.span_id
        assert ctx.role == "worker"
        assert ctx.pid == os.getpid()

    def test_adopt_from_env_without_or_bad_payload_is_fresh_root(self):
        ctx = telemetry.adopt_from_env(environ={})
        assert ctx.parent_span_id is None
        telemetry.RECORDER.context = None
        ctx2 = telemetry.adopt_from_env(
            environ={telemetry.TRACE_CONTEXT_ENV: "{not json"}
        )
        assert ctx2.parent_span_id is None
        assert ctx2.trace_id != ctx.trace_id
        # valid JSON that is not an object is just as malformed: a worker
        # calling adopt_from_env unconditionally must never crash on it
        for payload in ("null", "[1, 2]", '"x"', "42"):
            telemetry.RECORDER.context = None
            ctx3 = telemetry.adopt_from_env(
                environ={telemetry.TRACE_CONTEXT_ENV: payload}
            )
            assert ctx3.parent_span_id is None, payload


# ---------------------------------------------------------------------------
# Histogram state export / exact merge
# ---------------------------------------------------------------------------


class TestHistogramMerge:
    def _observations(self, seed, n):
        import random

        rng = random.Random(seed)
        out = []
        for _ in range(n):
            # span the bucket range: sub-floor, micro, milli, multi-second
            out.append(rng.choice([5e-8, 1e-6, 1e-4, 3e-3, 0.05, 1.7]) *
                       (1.0 + rng.random()))
        return out

    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
    def test_merged_equals_concatenated_exactly(self, seed):
        """The property the whole aggregation story rests on: K per-process
        histograms merged bucket-wise are IDENTICAL (bucket counts, count,
        min/max — not approximately, exactly) to one histogram fed the
        concatenated observations, so cluster quantiles are real."""
        import random

        rng = random.Random(seed * 1000 + 7)
        obs = self._observations(seed, 400)
        parts = [Histogram() for _ in range(3)]
        reference = Histogram()
        for v in obs:
            parts[rng.randrange(3)].observe(v)
            reference.observe(v)
        merged = Histogram.from_states(
            [json.loads(json.dumps(p.state())) for p in parts]
        )
        assert merged.counts == reference.counts  # exact bucket equality
        assert merged.count == reference.count
        assert merged.min == reference.min
        assert merged.max == reference.max
        assert merged.total == pytest.approx(reference.total)
        mq, rq = merged.quantiles(), reference.quantiles()
        assert mq.pop("mean_s") == pytest.approx(rq.pop("mean_s"))
        assert mq == rq

    def test_state_is_sparse_and_json_safe(self):
        h = Histogram()
        h.observe(0.001)
        h.observe(0.001)
        st = json.loads(json.dumps(h.state()))
        assert st["count"] == 2
        assert sum(int(c) for c in st["buckets"].values()) == 2
        assert len(st["buckets"]) == 1  # sparse: only touched buckets

    def test_empty_states_merge_to_empty(self):
        merged = Histogram.from_states([Histogram().state()] * 3)
        assert merged.count == 0
        assert merged.quantiles() == {}

    def test_layout_mismatch_raises(self):
        h = Histogram()
        bad = Histogram().state()
        bad["layout"] = [1e-7, 0.5, 72]
        with pytest.raises(ValueError, match="layout"):
            h.merge_state(bad)

    def test_bucket_index_out_of_range_raises(self):
        # a negative index would silently wrap into the tail bucket and
        # corrupt the cluster quantiles instead of flagging the bad spool
        h = Histogram()
        bad = Histogram().state()
        bad["buckets"] = {"-3": 2}
        with pytest.raises(ValueError, match="out of range"):
            h.merge_state(bad)
        bad["buckets"] = {"1000000": 1}
        with pytest.raises(ValueError, match="out of range"):
            h.merge_state(bad)
        assert h.count == 0

    def test_non_mapping_state_raises(self):
        h = Histogram()
        with pytest.raises(TypeError, match="mapping"):
            h.merge_state([1, 2, 3])
        bad = Histogram().state()
        bad["buckets"] = [4]
        with pytest.raises(TypeError, match="mapping"):
            h.merge_state(bad)


# ---------------------------------------------------------------------------
# Atomic artifact writes
# ---------------------------------------------------------------------------


class TestAtomicWrite:
    def test_write_and_no_tmp_residue(self, tmp_path):
        path = tmp_path / "x.json"
        atomic_write_bytes(str(path), b"abc")
        atomic_write_bytes(str(path), b"defg")  # overwrite is atomic too
        assert path.read_bytes() == b"defg"
        assert os.listdir(tmp_path) == ["x.json"]

    def test_failed_write_leaves_previous_file(self, tmp_path, monkeypatch):
        path = tmp_path / "x.json"
        atomic_write_bytes(str(path), b"good")
        real_replace = os.replace

        def boom(src, dst):
            raise OSError("disk gone")

        monkeypatch.setattr(os, "replace", boom)
        with pytest.raises(OSError):
            atomic_write_bytes(str(path), b"bad")
        monkeypatch.setattr(os, "replace", real_replace)
        assert path.read_bytes() == b"good"
        assert os.listdir(tmp_path) == ["x.json"]  # tmp cleaned up

    def test_save_chrome_trace_is_atomic(self, tmp_path, monkeypatch):
        rec = telemetry.SpanRecorder(enabled=True)
        with rec.span("decode"):
            pass
        out = tmp_path / "trace.json"
        rec.save_chrome_trace(str(out))
        assert json.load(open(out))["traceEvents"]

        def boom(src, dst):
            raise OSError("crash mid-export")

        monkeypatch.setattr(os, "replace", boom)
        with pytest.raises(OSError):
            rec.save_chrome_trace(str(out))
        monkeypatch.undo()
        # the previous complete export survives, no torn file
        assert json.load(open(out))["traceEvents"]
        assert os.listdir(tmp_path) == ["trace.json"]


# ---------------------------------------------------------------------------
# Trace merging
# ---------------------------------------------------------------------------


def _fake_trace(path, ctx, span_name, pid=None):
    rec = telemetry.SpanRecorder(enabled=True)
    rec.context = ctx
    with rec.span(span_name):
        pass
    doc = rec.to_chrome_trace()
    if pid is not None:  # simulate another host reusing a pid number
        for ev in doc["traceEvents"]:
            ev["pid"] = pid
        doc["traceContext"] = dict(doc["traceContext"], pid=pid)
    atomic_write_bytes(str(path), json.dumps(doc).encode())
    return doc


class TestMergeChromeTraces:
    def test_merge_keeps_one_named_track_per_process(self, tmp_path):
        ctxs = [
            TraceContext(
                trace_id="t" * 16, span_id=f"s{i}" * 4, role=f"r{i}",
                host="hostA", pid=1000 + i,
            )
            for i in range(3)
        ]
        paths = []
        for i, ctx in enumerate(ctxs):
            p = tmp_path / f"p{i}.json"
            _fake_trace(p, ctx, f"decode{i}", pid=ctx.pid)
            paths.append(str(p))
        out = tmp_path / "merged.json"
        merged = merge_chrome_traces(str(out), paths)
        doc = json.load(open(out))  # valid JSON on disk
        assert doc == json.loads(json.dumps(merged))
        pids = {e["pid"] for e in doc["traceEvents"]}
        assert len(pids) == 3
        named = {
            e["pid"]: e["args"]["name"]
            for e in doc["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
        }
        assert set(named) == pids  # every pid track is labeled
        assert named[1001] == "r1@hostA:1001"
        # all three files' spans survived
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"decode0", "decode1", "decode2"} <= names

    def test_pid_collision_across_hosts_remapped(self, tmp_path):
        a = TraceContext(trace_id="t" * 16, span_id="a" * 8, role="w",
                         host="hostA", pid=7)
        b = TraceContext(trace_id="t" * 16, span_id="b" * 8, role="w",
                         host="hostB", pid=7)
        pa, pb = tmp_path / "a.json", tmp_path / "b.json"
        _fake_trace(pa, a, "spanA", pid=7)
        _fake_trace(pb, b, "spanB", pid=7)
        merged = merge_chrome_traces(
            str(tmp_path / "m.json"), [str(pa), str(pb)]
        )
        ev_a = [e for e in merged["traceEvents"] if e["name"] == "spanA"][0]
        ev_b = [e for e in merged["traceEvents"] if e["name"] == "spanB"][0]
        assert ev_a["pid"] != ev_b["pid"]  # tracks never interleave
        labels = {
            e["args"]["name"]
            for e in merged["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
        }
        assert {"w@hostA:7", "w@hostB:7"} <= labels

    def test_contextless_file_gets_synthesized_label(self, tmp_path):
        raw = {"traceEvents": [
            {"name": "x", "ph": "X", "ts": 0, "dur": 1, "pid": 3, "tid": 1}
        ]}
        p = tmp_path / "legacy.json"
        p.write_text(json.dumps(raw))
        merged = merge_chrome_traces(str(tmp_path / "m.json"), [str(p)])
        meta = [
            e for e in merged["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
        ]
        assert meta and meta[0]["args"]["name"] == "legacy.json"

    def test_malformed_input_raises_not_drops(self, tmp_path):
        good = tmp_path / "good.json"
        good.write_text(json.dumps({"traceEvents": []}))
        bad = tmp_path / "bad.json"
        bad.write_text("{torn")
        with pytest.raises(ValueError, match="bad.json"):
            merge_chrome_traces(str(tmp_path / "m.json"), [str(good), str(bad)])
        notatrace = tmp_path / "list.json"
        notatrace.write_text("[1, 2]")
        with pytest.raises(ValueError, match="list.json"):
            merge_chrome_traces(str(tmp_path / "m.json"), [str(notatrace)])
        with pytest.raises(OSError):
            merge_chrome_traces(
                str(tmp_path / "m.json"), [str(tmp_path / "missing.json")]
            )


# ---------------------------------------------------------------------------
# Telemetry spool
# ---------------------------------------------------------------------------


def _spool(tmp_path, metrics, clock, interval=1.0, role="reader", pid=None,
           host="testhost"):
    ctx = TraceContext(
        trace_id="t" * 16, span_id=os.urandom(4).hex(), role=role,
        host=host, pid=os.getpid() if pid is None else pid,
    )
    return TelemetrySpool(
        str(tmp_path), role=role, interval_s=interval, metrics=metrics,
        context=ctx, clock=clock,
    )


class TestSpool:
    def test_tick_writes_newest_cumulative_snapshot(self, tmp_path):
        m = Metrics()
        now = [100.0]
        sp = _spool(tmp_path, m, lambda: now[0], interval=0.5)
        m.add("decode", records=10, nbytes=64, seconds=0.25, latency=0.25)
        sp.tick()
        m.add("decode", records=5, nbytes=32, seconds=0.1, latency=0.1)
        m.gauge("prefetch.occupancy", 0.5)
        now[0] = 101.0
        sp.tick()
        snap = read_spool(sp.path)
        assert snap is not None
        assert snap.lines == 2 and snap.skipped_lines == 0
        assert snap.seq == 2  # newest line wins
        assert snap.stages["decode"][0] == 15  # cumulative, not delta
        assert snap.stages["decode"][1] == 96
        assert snap.gauges["prefetch.occupancy"] == 0.5
        assert snap.heartbeat == 101.0
        assert snap.role == "reader" and snap.host == "testhost"
        assert snap.hists["decode"]["count"] == 2
        assert m.counter("fleet.spool_writes") == 2

    def test_counters_and_stages_partition(self, tmp_path):
        # pure counters (no bytes/seconds) land in `counters`, timed
        # stages in `stages` — the aggregator sums them separately
        m = Metrics()
        m.count("read.stalls", 3)
        m.add("decode", records=4, seconds=0.2)
        sp = _spool(tmp_path, m, lambda: 1.0)
        snap_line = sp.snapshot()
        assert snap_line["counters"] == {"read.stalls": 3}
        assert list(snap_line["stages"]) == ["decode"]

    def test_torn_line_skipped_not_fatal(self, tmp_path):
        m = Metrics()
        m.add("decode", records=7, seconds=0.1)
        sp = _spool(tmp_path, m, lambda: 5.0)
        sp.tick()
        with open(sp.path, "ab") as fh:
            fh.write(b'{"event": "spool", "tor')  # simulated torn append
        snap = read_spool(sp.path)
        assert snap is not None
        assert snap.skipped_lines == 1
        assert snap.stages["decode"][0] == 7

    def test_no_valid_lines_returns_none(self, tmp_path):
        p = tmp_path / f"x{fleet.SPOOL_SUFFIX}"
        p.write_text("garbage\n{also: torn\n")
        assert read_spool(str(p)) is None
        assert read_spool(str(tmp_path / "missing")) is None

    def test_history_bounded(self, tmp_path):
        m = Metrics()
        sp = TelemetrySpool(
            str(tmp_path), interval_s=1.0, metrics=m,
            context=TraceContext.new(), max_lines=4, clock=lambda: 1.0,
        )
        for _ in range(10):
            sp.tick()
        with open(sp.path) as fh:
            lines = [l for l in fh.read().splitlines() if l.strip()]
        assert len(lines) == 4
        assert json.loads(lines[-1])["seq"] == 10

    def test_tick_never_raises(self, tmp_path, monkeypatch):
        m = Metrics()
        sp = _spool(tmp_path, m, lambda: 1.0)

        def boom(path, data):
            raise OSError("spool dir vanished")

        monkeypatch.setattr(fleet, "atomic_write_bytes", boom)
        sp.tick()  # must not raise: spooling is telemetry
        assert m.counter("fleet.spool_errors") == 1

    def test_thread_ticks_and_final_snapshot(self, tmp_path):
        m = Metrics()
        m.add("decode", records=1, seconds=0.01)
        sp = TelemetrySpool(
            str(tmp_path), interval_s=0.05, metrics=m,
            context=TraceContext.new(role="reader"),
        )
        sp.start()
        deadline = time.time() + 5.0
        while time.time() < deadline:
            snap = read_spool(sp.path)
            if snap is not None and snap.seq >= 2:
                break
            time.sleep(0.02)
        m.add("decode", records=9, seconds=0.01)
        sp.stop(final=True)
        sp.stop(final=True)  # idempotent
        snap = read_spool(sp.path)
        assert snap.stages["decode"][0] == 10  # final tick caught the tail

    def test_default_role_keeps_adopted_context_role(self, tmp_path):
        # a worker that adopted role="decode_worker" (adopt_from_env /
        # adopt_shared_trace_context) must not have it clobbered by the
        # spool when telemetry_role is unset (options.py documents the
        # default as "the current trace-context role")
        telemetry.adopt(TraceContext.new(role="decode_worker"))
        sp = TelemetrySpool(str(tmp_path), metrics=Metrics())
        assert sp.context.role == "decode_worker"
        assert telemetry.current_context().role == "decode_worker"
        # an explicit role still re-adopts — that's the option's job
        sp2 = TelemetrySpool(str(tmp_path), role="trainer", metrics=Metrics())
        assert sp2.context.role == "trainer"

    def test_acquire_release_refcount(self, tmp_path):
        d = str(tmp_path / "spool")
        a = fleet.acquire_spool(d, interval_s=60.0)
        b = fleet.acquire_spool(d, interval_s=60.0)
        assert a is b  # one spool per (process, dir)
        fleet.release_spool(d)
        assert not a._stop.is_set()  # still referenced
        fleet.release_spool(d)
        assert a._stop.is_set()
        assert read_spool(a.path) is not None  # final snapshot landed
        fleet.release_spool(d)  # unmatched release ignored

    def test_remote_scheme_spool_dir_rejected(self, tmp_path):
        # abspath would silently mangle "gs://bucket/spool" into a private
        # local dir on every host: workers look healthy, aggregator finds
        # an empty fleet — reject loudly at both ends instead
        with pytest.raises(ValueError, match="local path"):
            fleet.TelemetrySpool("gs://bucket/spool", metrics=Metrics())
        with pytest.raises(ValueError, match="local path"):
            fleet.acquire_spool("s3://bucket/spool", interval_s=60.0)
        with pytest.raises(ValueError, match="local path"):
            fleet.TelemetryAggregator("gs://bucket/spool")

    def test_snapshot_follows_late_adopted_context(self, tmp_path):
        # adopt_shared_trace_context may run AFTER the spooling iterator
        # is constructed — later snapshots must stamp the shared trace id,
        # or trace_id-scoped aggregation silently drops the process
        m = Metrics()
        m.add("decode", records=1, seconds=0.1)
        sp = TelemetrySpool(str(tmp_path), metrics=m, clock=lambda: 1.0)
        early = sp.snapshot()
        shared = telemetry.adopt(
            TraceContext.new(role="worker").with_role("worker")
        )
        assert early["job"]["trace_id"] != shared.trace_id
        late = sp.snapshot()
        assert late["job"]["trace_id"] == shared.trace_id
        assert late["job"]["role"] == "worker"
        # an explicitly injected context stays pinned (test seam)
        pinned = _spool(tmp_path, m, lambda: 1.0)
        telemetry.adopt(TraceContext.new(role="other"))
        assert pinned.snapshot()["job"]["trace_id"] == "t" * 16


# ---------------------------------------------------------------------------
# Aggregator
# ---------------------------------------------------------------------------


def _write_process(tmp_path, clock, pid, role="reader", decode=(10, 100, 0.5),
                   counters=(), latencies=(), occupancy=None, interval=1.0):
    m = Metrics()
    m.add("decode", records=decode[0], nbytes=decode[1], seconds=decode[2])
    for name, v in counters:
        m.count(name, v)
    for lat in latencies:
        m.observe("decode", lat)
    if occupancy is not None:
        m.gauge(telemetry.OCCUPANCY_GAUGE, occupancy)
    sp = _spool(tmp_path, m, clock, interval=interval, role=role, pid=pid)
    sp.tick()
    return m


class TestAggregator:
    def test_counters_and_stages_sum_exactly(self, tmp_path):
        now = [50.0]
        clock = lambda: now[0]  # noqa: E731
        _write_process(tmp_path, clock, pid=1, decode=(10, 100, 0.5),
                       counters=[("read.stalls", 3)])
        _write_process(tmp_path, clock, pid=2, decode=(20, 300, 1.5),
                       counters=[("read.stalls", 4), ("read.hedges", 1)])
        _write_process(tmp_path, clock, pid=3, decode=(5, 50, 0.25))
        snap = TelemetryAggregator(str(tmp_path), clock=clock).aggregate()
        assert len(snap.processes) == 3 and not snap.dead
        assert snap.counters["read.stalls"] == 7
        assert snap.counters["read.hedges"] == 1
        # fleet.spool_writes is itself spooled (each process wrote once...
        # but the tick that WROTE the line ran before the counter bumped,
        # so the newest landed line says 0 until the next tick)
        assert snap.stages["decode"][0] == 35
        assert snap.stages["decode"][1] == 450
        assert snap.stages["decode"][3] == pytest.approx(2.25)

    def test_histograms_merge_bucket_exactly(self, tmp_path):
        import random

        rng = random.Random(11)
        now = [10.0]
        clock = lambda: now[0]  # noqa: E731
        all_obs = []
        for pid in (1, 2, 3):
            obs = [rng.uniform(1e-5, 2.0) for _ in range(100)]
            all_obs.extend(obs)
            _write_process(tmp_path, clock, pid=pid, latencies=obs)
        reference = Histogram()
        for v in all_obs:
            reference.observe(v)
        snap = TelemetryAggregator(str(tmp_path), clock=clock).aggregate()
        assert snap.hists["decode"].counts == reference.counts
        mq, rq = snap.quantiles()["decode"], reference.quantiles()
        assert mq.pop("mean_s") == pytest.approx(rq.pop("mean_s"))
        assert mq == rq

    def test_stale_heartbeat_flags_dead(self, tmp_path):
        """Liveness with an injected clock: a process is alive through
        2x its own declared interval and dead one tick past it."""
        now = [1000.0]
        clock = lambda: now[0]  # noqa: E731
        _write_process(tmp_path, clock, pid=1, interval=1.0)
        now[0] = 1001.0
        _write_process(tmp_path, clock, pid=2, interval=1.0)
        agg = TelemetryAggregator(str(tmp_path), clock=clock)
        snap = agg.aggregate()
        assert not snap.dead  # ages 1.0 and 0.0: both within 2x interval
        now[0] = 1002.0  # pid 1's age is exactly the 2.0 bar: still alive
        snap = agg.aggregate()
        assert not snap.dead
        now[0] = 1002.5  # pid 1 at 2.5 > 2.0: dead; pid 2 at 1.5: alive
        snap = agg.aggregate()
        assert [p.pid for p in snap.dead] == [1]
        assert [p.pid for p in snap.alive] == [2]
        # a dead process's totals still count — they happened
        assert snap.stages["decode"][0] == 20
        # explicit override beats the per-process default
        snap = TelemetryAggregator(
            str(tmp_path), stale_after_s=10.0, clock=clock
        ).aggregate()
        assert not snap.dead

    def test_cluster_verdict_from_alive_occupancy(self, tmp_path):
        now = [10.0]
        clock = lambda: now[0]  # noqa: E731
        _write_process(tmp_path, clock, pid=1, occupancy=0.9)
        _write_process(tmp_path, clock, pid=2, occupancy=0.8)
        snap = TelemetryAggregator(str(tmp_path), clock=clock).aggregate()
        assert snap.occupancy == pytest.approx(0.85)
        assert snap.verdict == "consumer_bound"
        # a dead process's occupancy must not poison the verdict
        now[0] = 100.0
        _write_process(tmp_path, clock, pid=3, occupancy=0.0)
        snap = TelemetryAggregator(str(tmp_path), clock=clock).aggregate()
        assert [p.pid for p in snap.alive] == [3]
        assert snap.occupancy == pytest.approx(0.0)
        assert snap.verdict == "producer_bound"

    def test_corrupt_hist_state_loses_stage_not_fleet(self, tmp_path):
        # one process spooled histogram states with a foreign bucket
        # layout (version skew) or garbage indices: its buckets are
        # dropped, its counters still sum, the fleet picture survives —
        # and the doctor reports instead of dying with a traceback
        now = [10.0]
        clock = lambda: now[0]  # noqa: E731
        _write_process(tmp_path, clock, pid=1, latencies=[0.01, 0.02])
        _write_process(tmp_path, clock, pid=2, latencies=[0.03])
        spool_file = os.path.join(tmp_path, f"testhost-2{fleet.SPOOL_SUFFIX}")
        obj = json.loads(open(spool_file).read().splitlines()[-1])
        obj["hists"]["decode"]["layout"] = [1e-7, 0.5, 72]
        with open(spool_file, "w") as fh:
            fh.write(json.dumps(obj) + "\n")
        snap = TelemetryAggregator(str(tmp_path), clock=clock).aggregate()
        assert len(snap.processes) == 2
        assert snap.hists["decode"].count == 2  # pid 1's buckets only
        assert snap.stages["decode"][0] == 20  # counters unaffected
        proc = subprocess.run(
            [sys.executable, DOCTOR, "fleet", str(tmp_path),
             "--stale-after", "3600"],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, (proc.stdout, proc.stderr)

    def test_empty_dir_and_unreadable_dir(self, tmp_path):
        snap = TelemetryAggregator(str(tmp_path), clock=lambda: 0.0).aggregate()
        assert snap.processes == [] and snap.verdict == "unknown"
        with pytest.raises(OSError):
            TelemetryAggregator(
                str(tmp_path / "missing"), clock=lambda: 0.0
            ).processes()

    def test_federated_page_parses_with_official_parser(self, tmp_path):
        parser = pytest.importorskip("prometheus_client.parser")
        now = [10.0]
        clock = lambda: now[0]  # noqa: E731
        _write_process(tmp_path, clock, pid=1, role="reader",
                       decode=(10, 100, 0.5), latencies=[0.01, 0.02],
                       occupancy=0.4, counters=[("read.stalls", 2)])
        _write_process(tmp_path, clock, pid=2, role="trainer",
                       decode=(20, 200, 1.0), latencies=[0.03])
        agg = TelemetryAggregator(str(tmp_path), clock=clock)
        families = {
            f.name: f
            for f in parser.text_string_to_metric_families(agg.prometheus_text())
        }
        up = families["tfrecord_process_up"]
        by_pid = {s.labels["pid"]: s for s in up.samples}
        assert set(by_pid) == {"1", "2"}
        assert by_pid["1"].labels["role"] == "reader"
        assert by_pid["1"].labels["host"] == "testhost"
        assert all(s.value == 1.0 for s in up.samples)
        recs = families["tfrecord_stage_records"]
        decode = {
            s.labels["pid"]: s.value
            for s in recs.samples
            if s.labels["stage"] == "decode"
        }
        assert decode == {"1": 10.0, "2": 20.0}  # per-process, sum in PromQL
        stalls = [
            s for s in recs.samples if s.labels["stage"] == "read.stalls"
        ]
        assert stalls and stalls[0].value == 2.0
        lat = families["tfrecord_fleet_latency_seconds"]
        cnt = [s for s in lat.samples if s.name.endswith("_count")]
        assert cnt and cnt[0].value == 3.0  # cluster-exact merged histogram

    def test_federated_http_endpoint(self, tmp_path):
        import urllib.request

        now = [10.0]
        _write_process(tmp_path, lambda: now[0], pid=1)
        agg = TelemetryAggregator(str(tmp_path), clock=lambda: now[0])
        server = agg.serve(0)
        try:
            host, port = telemetry.exporter_address(0)
            assert port == server.server_address[1]
            body = urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=5
            ).read().decode()
            assert "tfrecord_process_up" in body
        finally:
            telemetry.shutdown_exporter(0)

    def test_serve_refuses_port_already_serving_other_kind(self, tmp_path):
        # the per-port table must not hand a fleet caller the PROCESS
        # exporter's server: scrapes would succeed while fleet liveness
        # families silently never appear
        _write_process(tmp_path, lambda: 10.0, pid=1)
        exporter = telemetry.ensure_exporter(0, metrics=Metrics())
        assert exporter is not None
        try:
            agg = TelemetryAggregator(str(tmp_path), clock=lambda: 10.0)
            assert agg.serve(0) is None  # collision: failure is visible
        finally:
            telemetry.shutdown_exporter(0)

    def test_clean_shutdown_never_flagged_dead(self, tmp_path):
        """A final (stop()) snapshot marks the process FINISHED: however
        stale its heartbeat gets, it stays out of the dead list — a
        completed job must not read as a mass kill. A process with no
        final marker at the same staleness goes dead."""
        now = [1000.0]
        clock = lambda: now[0]  # noqa: E731
        m1 = Metrics()
        m1.add("decode", records=10, nbytes=100, seconds=0.5)
        sp1 = _spool(tmp_path, m1, clock, interval=1.0, pid=1)
        sp1.tick()
        sp1.stop(final=True)  # clean goodbye
        m2 = Metrics()
        m2.add("decode", records=20, nbytes=200, seconds=1.0)
        _spool(tmp_path, m2, clock, interval=1.0, pid=2).tick()  # no goodbye
        now[0] = 2000.0  # both heartbeats ancient
        snap = TelemetryAggregator(str(tmp_path), clock=clock).aggregate()
        assert [p.pid for p in snap.alive] == [1]
        assert snap.alive[0].final
        assert [p.pid for p in snap.dead] == [2]
        # finished totals still count
        assert snap.stages["decode"][0] == 30

    def test_finished_process_occupancy_excluded_while_any_run(self, tmp_path):
        """A finished process's frozen exit occupancy must not dilute the
        live verdict — but with NOTHING running, the fleet is a
        post-mortem and the exit states are the right evidence."""
        now = [100.0]
        clock = lambda: now[0]  # noqa: E731
        m1 = Metrics()
        m1.add("decode", records=1, nbytes=1, seconds=0.1)
        m1.gauge(telemetry.OCCUPANCY_GAUGE, 1.0)
        sp1 = _spool(tmp_path, m1, clock, pid=1)
        sp1.tick()
        sp1.stop(final=True)  # finished at occupancy 1.0
        m2 = Metrics()
        m2.add("decode", records=1, nbytes=1, seconds=0.1)
        m2.gauge(telemetry.OCCUPANCY_GAUGE, 0.1)
        sp2 = _spool(tmp_path, m2, clock, pid=2)
        sp2.tick()  # still running, starved
        snap = TelemetryAggregator(str(tmp_path), clock=clock).aggregate()
        assert snap.occupancy == pytest.approx(0.1)
        assert snap.verdict == "producer_bound"
        sp2.stop(final=True)  # now everything finished: post-mortem mean
        snap = TelemetryAggregator(str(tmp_path), clock=clock).aggregate()
        assert snap.occupancy == pytest.approx(0.55)

    def test_trace_id_scopes_reused_spool_dir(self, tmp_path):
        """A reused spool dir holds a previous run's files; the trace_id
        filter merges one run only."""
        now = [10.0]
        clock = lambda: now[0]  # noqa: E731
        _write_process(tmp_path, clock, pid=1)  # trace id "t"*16
        stale = json.dumps({
            "event": "spool", "v": 1, "seq": 7, "ts": 1.0, "interval_s": 1.0,
            "job": {"host": "old", "pid": 1, "role": "r",
                    "heartbeat": 1.0, "trace_id": "previousrun00000"},
            "counters": {}, "stages": {"decode": [99, 0, 0, 1.0]},
            "gauges": {}, "hists": {},
        })
        (tmp_path / f"old-1{fleet.SPOOL_SUFFIX}").write_text(stale + "\n")
        unscoped = TelemetryAggregator(str(tmp_path), clock=clock).aggregate()
        assert unscoped.stages["decode"][0] == 109  # mixed: disclosure only
        scoped = TelemetryAggregator(
            str(tmp_path), clock=clock, trace_id="t" * 16
        ).aggregate()
        assert [p.pid for p in scoped.processes] == [1]
        assert scoped.stages["decode"][0] == 10

    def test_doctor_names_unmatched_trace_id_filter(self, tmp_path):
        # a typo'd/stale --trace-id against a dir FULL of spool files must
        # not claim "no spool files found" — that sends the operator to
        # debug a missing directory instead of the filter
        _write_process(tmp_path, lambda: 10.0, pid=1)  # trace id "t"*16
        proc = subprocess.run(
            [sys.executable, DOCTOR, "fleet", str(tmp_path),
             "--trace-id", "nosuchtrace00000"],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 2
        err = json.loads(proc.stdout.strip().splitlines()[-1])
        assert "nosuchtrace00000" in err["error"]
        assert err["spool_files"] == 1
        assert err["trace_ids_present"] == ["t" * 16]

    def test_snapshot_carries_spool_start_for_wall_throughput(self, tmp_path):
        """`created` (spool start, writer's clock) survives the round
        trip: heartbeat - created is the wall window the doctor divides
        records by (busy seconds sum across threads and would understate
        parallel workers)."""
        now = [100.0]
        m = Metrics()
        m.add("decode", records=50, nbytes=0, seconds=7.5)  # busy > wall
        sp = _spool(tmp_path, m, lambda: now[0])
        now[0] = 105.0
        sp.tick()
        snap = read_spool(sp.path)
        assert snap.created == pytest.approx(100.0)
        assert snap.heartbeat == pytest.approx(105.0)
        assert snap.heartbeat - snap.created == pytest.approx(5.0)
        # the epoch sticks to the METRICS REGISTRY, not the spool
        # instance: a release + re-acquire over the same (cumulative)
        # registry keeps the original window instead of restarting it
        # under lifetime totals and overstating the rate
        sp.stop(final=True)
        now[0] = 200.0
        sp2 = _spool(tmp_path, m, lambda: now[0])
        sp2.tick()
        snap = read_spool(sp2.path)
        assert snap.created == pytest.approx(100.0)
        # a registry reset restarts the window with the totals
        m.reset()
        now[0] = 300.0
        sp3 = _spool(tmp_path, m, lambda: now[0])
        sp3.tick()
        assert read_spool(sp3.path).created == pytest.approx(300.0)

    def test_malformed_line_skipped_not_fatal(self, tmp_path):
        """A line that parses as JSON but fails field coercion (version
        skew, hand edits) loses that LINE, not the file and not the fleet:
        the newest remaining valid line wins and aggregation proceeds."""
        now = [10.0]
        clock = lambda: now[0]  # noqa: E731
        _write_process(tmp_path, clock, pid=1, decode=(10, 100, 0.5))
        bad_file = tmp_path / f"evil-9{fleet.SPOOL_SUFFIX}"
        good = json.dumps({
            "event": "spool", "v": 1, "seq": 1, "ts": 10.0,
            "interval_s": 1.0,
            "job": {"host": "h", "pid": 9, "role": "r", "heartbeat": 10.0},
            "counters": {}, "stages": {"decode": [5, 50, 0, 0.25]},
            "gauges": {}, "hists": {},
        })
        for bad in (
            '{"event": "spool", "seq": 2, "job": {"pid": "abc"}}',
            '{"event": "spool", "seq": 3, "job": {"heartbeat": "x"}}',
            '{"event": "spool", "seq": 4, "stages": {"decode": [1]}}',
            '{"event": "spool", "seq": 5, "counters": {"c": "NaNope"}}',
        ):
            bad_file.write_text(good + "\n" + bad + "\n")
            snap = TelemetryAggregator(str(tmp_path), clock=clock).aggregate()
            assert {p.pid for p in snap.processes} == {1, 9}, bad
            nine = [p for p in snap.processes if p.pid == 9][0]
            assert nine.seq == 1 and nine.skipped_lines == 1, bad
            assert snap.stages["decode"][0] == 15, bad

    def test_label_values_escaped_on_federated_page(self, tmp_path):
        """role/host are user strings: quotes/backslashes/newlines must be
        escaped so the page still parses with the official parser."""
        parser = pytest.importorskip("prometheus_client.parser")
        now = [10.0]
        clock = lambda: now[0]  # noqa: E731
        _write_process(
            tmp_path, clock, pid=1, role='w"1\\x\ny',
        )
        agg = TelemetryAggregator(str(tmp_path), clock=clock)
        families = {
            f.name: f
            for f in parser.text_string_to_metric_families(agg.prometheus_text())
        }
        up = families["tfrecord_process_up"]
        assert up.samples[0].labels["role"] == 'w"1\\x\ny'

    def test_acquire_spool_mismatched_join_warns(self, tmp_path, caplog):
        """Joining an existing spool dir with a different role/interval
        keeps the existing spool's settings and says so."""
        import logging

        from tpu_tfrecord.fleet import acquire_spool, release_spool

        d = str(tmp_path / "sp")
        acquire_spool(d, role="a", interval_s=30.0)
        try:
            with caplog.at_level(logging.WARNING, logger="tpu_tfrecord"):
                sp = acquire_spool(d, role="b", interval_s=0.5)
            assert sp.interval_s == 30.0 and sp.context.role == "a"
            msgs = " ".join(r.message for r in caplog.records)
            assert "interval" in msgs and "role" in msgs
        finally:
            release_spool(d)
            release_spool(d)


# ---------------------------------------------------------------------------
# Options plumbing
# ---------------------------------------------------------------------------


class TestOptions:
    def test_defaults_off(self):
        from tpu_tfrecord.options import TFRecordOptions

        o = TFRecordOptions.from_map()
        assert o.telemetry_spool_dir is None
        assert o.spool_interval_s is None
        assert o.telemetry_role is None

    def test_parsing_and_validation(self, tmp_path):
        from tpu_tfrecord.options import TFRecordOptions

        o = TFRecordOptions.from_map(
            telemetry_spool_dir=str(tmp_path), spool_interval_s=0.5,
            telemetry_role="decode_worker",
        )
        assert o.telemetry_spool_dir == str(tmp_path)
        assert o.spool_interval_s == 0.5
        assert o.telemetry_role == "decode_worker"
        camel = TFRecordOptions.from_map(
            telemetrySpoolDir=str(tmp_path), spoolIntervalS="2",
            telemetryRole="t",
        )
        assert camel.spool_interval_s == 2.0
        with pytest.raises(ValueError, match="spool_interval_s"):
            TFRecordOptions.from_map(spool_interval_s=0)
        with pytest.raises(ValueError, match="telemetry_role"):
            TFRecordOptions.from_map(telemetry_role="")

    def test_dataset_scheme_spool_dir_rejected(self, sandbox):
        # the iterator must not abspath "gs://..." before the spool's
        # scheme guard sees it — that would silently spool into a local
        # '<cwd>/gs:/bucket/spool' dir instead of raising
        from tpu_tfrecord.io.dataset import TFRecordDataset

        data = write_dataset(sandbox / "ds", n_shards=1, rows_per_shard=4)
        ds = TFRecordDataset(
            data, batch_size=4, schema=SCHEMA, num_epochs=1,
            drop_remainder=False, telemetry_spool_dir="gs://bucket/spool",
        )
        with pytest.raises(ValueError, match="local path"):
            with ds.batches():
                pass
        assert not fleet._SPOOLS

    def test_dataset_spools_while_iterating(self, sandbox, tmp_path):
        from tpu_tfrecord.io.dataset import TFRecordDataset

        data = write_dataset(sandbox / "ds", n_shards=2, rows_per_shard=30)
        spool_dir = str(tmp_path / "spool")
        ds = TFRecordDataset(
            data, batch_size=16, schema=SCHEMA, num_epochs=1,
            drop_remainder=False, telemetry_spool_dir=spool_dir,
            spool_interval_s=0.05, telemetry_role="reader",
        )
        rows = 0
        with ds.batches() as it:
            for cb in it:
                rows += cb.num_rows
        assert rows == 60
        # the iterator's close released the refcount: final snapshot landed
        snaps = TelemetryAggregator(spool_dir, clock=time.time).processes()
        assert len(snaps) == 1
        assert snaps[0].role == "reader"
        assert snaps[0].stages["decode"][0] == 60
        assert not fleet._SPOOLS  # registry drained


# ---------------------------------------------------------------------------
# Multi-process integration (the acceptance test)
# ---------------------------------------------------------------------------


def _spawn_worker(data, spool_dir, env, role="reader", trace_out=None,
                  linger=0.0, interval=0.1):
    cmd = [
        sys.executable, WORKER, data, spool_dir,
        "--role", role, "--epochs", "2", "--batch-size", "16",
        "--interval", str(interval),
    ]
    if trace_out:
        cmd += ["--trace-out", trace_out]
    if linger:
        cmd += ["--linger", str(linger)]
    return subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env,
    )


class TestFleetIntegration:
    def test_three_workers_aggregate_exactly(self, sandbox, tmp_path):
        """K=3 subprocesses read concurrently while spooling into one dir:
        the aggregated decode count equals the per-process sum EXACTLY,
        every process carries the parent's trace id, the federated page
        parses with per-process labels, the fleet doctor exits 0, and the
        merged Chrome trace has one named track per pid."""
        data = write_dataset(sandbox / "ds", n_shards=3, rows_per_shard=40)
        spool_dir = str(tmp_path / "spool")
        parent_ctx = TraceContext.new(role="test_parent")
        env = {
            **os.environ, "JAX_PLATFORMS": "cpu", **parent_ctx.to_env(),
        }
        traces = [str(tmp_path / f"trace-{i}.json") for i in range(3)]
        procs = [
            _spawn_worker(data, spool_dir, env, role=f"reader{i}",
                          trace_out=traces[i])
            for i in range(3)
        ]
        outs = []
        for p in procs:
            out, err = p.communicate(timeout=240)
            assert p.returncode == 0, (out, err)
            outs.append(json.loads(out.splitlines()[-1]))

        # every worker read the whole dataset twice
        assert all(o["rows"] == 240 for o in outs)
        # trace propagation: all three joined the parent's trace
        assert {o["trace_id"] for o in outs} == {parent_ctx.trace_id}
        assert {o["parent_span_id"] for o in outs} == {parent_ctx.span_id}

        # exact aggregation: merged decode records == sum of per-process
        agg = TelemetryAggregator(spool_dir)
        snap = agg.aggregate()
        assert len(snap.processes) == 3
        expected = sum(o["decode_records"] for o in outs)
        assert snap.stages["decode"][0] == expected == 720
        roles = sorted(p.role for p in snap.processes)
        assert roles == ["reader0", "reader1", "reader2"]
        assert sorted(p.pid for p in snap.processes) == sorted(
            o["pid"] for o in outs
        )

        # federated page parses with the official parser, labeled per pid
        parser = pytest.importorskip("prometheus_client.parser")
        families = {
            f.name: f
            for f in parser.text_string_to_metric_families(agg.prometheus_text())
        }
        recs = families["tfrecord_stage_records"]
        decode = {
            int(s.labels["pid"]): s.value
            for s in recs.samples
            if s.labels["stage"] == "decode"
        }
        assert decode == {o["pid"]: float(o["decode_records"]) for o in outs}

        # fleet doctor: exit 0 with per-proc lines and a cluster verdict
        proc = subprocess.run(
            [sys.executable, DOCTOR, "fleet", spool_dir,
             "--stale-after", "3600"],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, (proc.stdout, proc.stderr)
        lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
        proc_lines = [l for l in lines if l["event"] == "proc"]
        (fleet_line,) = [l for l in lines if l["event"] == "fleet"]
        assert len(proc_lines) == 3
        assert all(l["alive"] for l in proc_lines)
        assert all(l["records_per_sec"] for l in proc_lines)
        assert fleet_line["stages"]["decode"]["records"] == 720
        assert fleet_line["alive"] == 3 and fleet_line["dead"] == []
        assert fleet_line["verdict"] in (
            "producer_bound", "consumer_bound", "balanced", "unknown"
        )
        assert fleet_line["trace_ids"] == [parent_ctx.trace_id]

        # merged timeline: valid trace-event JSON, 3 named pid tracks
        merged_path = str(tmp_path / "merged.json")
        proc = subprocess.run(
            [sys.executable, DOCTOR, "merge-trace", merged_path] + traces,
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, (proc.stdout, proc.stderr)
        summary = json.loads(proc.stdout.splitlines()[-1])
        assert summary["event"] == "merged_trace" and summary["pids"] >= 3
        doc = json.load(open(merged_path))
        pids = {
            e["pid"] for e in doc["traceEvents"] if e.get("ph") != "M"
        }
        assert pids == {o["pid"] for o in outs}
        named = {
            e["pid"]
            for e in doc["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
        }
        assert pids <= named  # one named track per pid
        assert any(e["name"] == "tfr:decode" for e in doc["traceEvents"])

    def test_killed_worker_flagged_stale(self, sandbox, tmp_path):
        """SIGKILL a demonstrably-alive worker: the aggregator flags it
        dead once its heartbeat age passes the staleness bar (2x its
        declared interval), and the doctor reports it in the dead list."""
        data = write_dataset(sandbox / "ds", n_shards=1, rows_per_shard=20)
        spool_dir = str(tmp_path / "spool")
        os.makedirs(spool_dir)  # don't race the worker's own makedirs
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        interval = 0.2
        p = _spawn_worker(data, spool_dir, env, role="victim",
                          linger=120.0, interval=interval)
        try:
            agg = TelemetryAggregator(spool_dir)
            deadline = time.time() + 120.0
            alive_seen = False
            while time.time() < deadline:
                snap = agg.aggregate()
                if snap.alive and snap.alive[0].stages.get("decode"):
                    alive_seen = True
                    break
                time.sleep(0.05)
            assert alive_seen, (p.poll(), p.stderr.read() if p.poll() else "")
            p.kill()
            p.wait(timeout=30)
            # dead within ~one heartbeat interval past the 2x bar
            deadline = time.time() + 10 * interval
            flagged = None
            while time.time() < deadline:
                snap = agg.aggregate()
                if snap.dead:
                    flagged = snap.dead[0]
                    break
                time.sleep(interval / 4)
            assert flagged is not None, "killed worker never flagged stale"
            assert flagged.role == "victim"
            # its totals still count after death
            assert snap.stages["decode"][0] == flagged.stages["decode"][0]
            proc = subprocess.run(
                [sys.executable, DOCTOR, "fleet", spool_dir],
                capture_output=True, text=True, env=env,
            )
            assert proc.returncode == 0
            lines = [
                json.loads(l) for l in proc.stdout.splitlines() if l.strip()
            ]
            (fleet_line,) = [l for l in lines if l["event"] == "fleet"]
            assert fleet_line["dead"] and fleet_line["dead"][0]["role"] == "victim"
        finally:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
