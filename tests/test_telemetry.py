"""Flight recorder tests (ISSUE 5): span tracing, histograms, pulse,
gauges, the off-path fast path, and the bottleneck doctor.

Tier 1 (no devices). The recorder under test is the process-global
``telemetry.RECORDER`` wherever the wiring is exercised end-to-end
(options -> dataset -> spans), and private SpanRecorder instances where
the contract is about the data structure itself.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from tpu_tfrecord import telemetry
from tpu_tfrecord.metrics import METRICS, Metrics, timed
from tpu_tfrecord.options import TFRecordOptions
from tpu_tfrecord.schema import LongType, StringType, StructField, StructType
from tpu_tfrecord.telemetry import (
    Histogram,
    Pulse,
    SpanRecorder,
    boundness_verdict,
    prometheus_text,
)

SCHEMA = StructType(
    [
        StructField("id", LongType(), nullable=False),
        StructField("s", StringType()),
    ]
)


def write_dataset(path, n_shards=3, rows_per_shard=50):
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
def _clean_global_recorder():
    """Every test starts and ends with the global recorder off and empty —
    the recorder is process-global, so leakage between tests would make
    span assertions order-dependent."""
    telemetry.disable()
    telemetry.RECORDER.clear()
    METRICS.reset()
    yield
    telemetry.disable()
    telemetry.RECORDER.clear()
    METRICS.reset()


class TestSpanRecorder:
    def test_span_records_name_duration_tid(self):
        rec = SpanRecorder(enabled=True)
        with rec.span("outer", shard="a"):
            time.sleep(0.002)
        (span,) = rec.spans()
        name, t0, dur, tid, attrs, ph = span
        assert name == "outer"
        assert ph == "X"
        assert dur >= 2_000_000  # >= 2ms in ns
        assert tid == threading.get_ident()
        assert attrs == {"shard": "a"}

    def test_span_nesting(self):
        rec = SpanRecorder(enabled=True)
        with rec.span("outer"):
            with rec.span("inner"):
                time.sleep(0.001)
        # inner exits (and records) first; outer encloses it
        inner, outer = rec.spans()
        assert inner[0] == "inner" and outer[0] == "outer"
        assert outer[1] <= inner[1]  # outer began first
        assert outer[1] + outer[2] >= inner[1] + inner[2]  # and ended last

    def test_set_attrs_mid_span(self):
        rec = SpanRecorder(enabled=True)
        with rec.span("decode", shard="x") as sp:
            sp.set(rows=128)
        (span,) = rec.spans()
        assert span[4] == {"shard": "x", "rows": 128}

    def test_exception_marks_failed(self):
        rec = SpanRecorder(enabled=True)
        with pytest.raises(ValueError):
            with rec.span("decode"):
                raise ValueError("boom")
        (span,) = rec.spans()
        assert span[4] == {"failed": 1}

    def test_instant_event(self):
        rec = SpanRecorder(enabled=True)
        rec.instant("read.stall", path="p")
        (ev,) = rec.spans()
        assert ev[0] == "read.stall" and ev[5] == "i" and ev[2] == 0

    def test_thread_interleaving(self):
        rec = SpanRecorder(enabled=True, capacity=4096)
        n_threads, per_thread = 8, 50

        def work(k):
            for i in range(per_thread):
                with rec.span(f"t{k}", i=i):
                    pass

        threads = [
            threading.Thread(target=work, args=(k,)) for k in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        spans = rec.spans()
        assert len(spans) == n_threads * per_thread
        assert rec.dropped == 0
        # every thread's spans all present, tids distinct per thread name
        by_name = {}
        for name, _t0, _dur, tid, _attrs, _ph in spans:
            by_name.setdefault(name, set()).add(tid)
        assert len(by_name) == n_threads
        assert all(len(tids) == 1 for tids in by_name.values())

    def test_ring_buffer_bounded(self):
        rec = SpanRecorder(enabled=True, capacity=64)
        for i in range(300):
            with rec.span("s", i=i):
                pass
        assert len(rec) == 64
        assert rec.dropped == 236
        spans = rec.spans()
        assert len(spans) == 64
        # the RETAINED spans are the most recent ones, oldest first
        assert [s[4]["i"] for s in spans] == list(range(236, 300))

    def test_clear(self):
        rec = SpanRecorder(enabled=True, capacity=8)
        for _ in range(20):
            rec.instant("x")
        rec.clear()
        assert len(rec) == 0 and rec.dropped == 0 and rec.spans() == []


class TestOffFastPath:
    def test_disabled_records_nothing_and_takes_no_lock(self):
        telemetry.disable()

        class TripLock:
            def __enter__(self):
                raise AssertionError("recorder lock taken on the off path")

            def __exit__(self, *exc):
                return None

        real = telemetry.RECORDER._lock
        telemetry.RECORDER._lock = TripLock()
        try:
            for i in range(100):
                with telemetry.span("decode", shard="x") as sp:
                    sp.set(rows=i)
                telemetry.instant("read.stall")
                telemetry.record_span("batch", 0, 10)
        finally:
            telemetry.RECORDER._lock = real
        assert len(telemetry.RECORDER) == 0

    def test_disabled_span_is_shared_noop(self):
        telemetry.disable()
        a = telemetry.span("x")
        b = telemetry.span("y", k=1)
        assert a is b  # no per-call allocation when off


class TestHistogram:
    def test_quantiles_vs_reference_sort(self):
        rng = np.random.default_rng(7)
        values = rng.lognormal(mean=-7.0, sigma=1.5, size=20_000)
        h = Histogram()
        for v in values:
            h.observe(float(v))
        for q in (0.5, 0.9, 0.99):
            ref = float(np.quantile(values, q))
            est = h.quantile(q)
            # log-bucket growth 2**0.25 bounds the relative error at
            # sqrt(2**0.25)-1 ~ 9.1%; allow a little slack for the
            # rank-vs-interpolation difference at the tail
            assert abs(est - ref) / ref < 0.12, (q, est, ref)

    def test_single_value_clamps_exact(self):
        h = Histogram()
        for _ in range(10):
            h.observe(0.003)
        assert h.quantile(0.5) == pytest.approx(0.003)
        assert h.quantile(0.99) == pytest.approx(0.003)

    def test_empty_and_tiny_values(self):
        h = Histogram()
        assert h.quantile(0.5) is None
        assert h.quantiles() == {}
        h.observe(0.0)  # below the floor: bucket 0, no crash
        assert h.count == 1
        assert h.quantile(0.5) == pytest.approx(0.0)  # clamped to observed max

    def test_quantiles_dict_shape(self):
        h = Histogram()
        h.observe(0.001)
        h.observe(0.002)
        q = h.quantiles()
        assert set(q) == {"p50_s", "p90_s", "p99_s", "count", "mean_s"}
        assert q["count"] == 2
        assert q["mean_s"] == pytest.approx(0.0015)


class TestMetricsIntegration:
    def test_timed_feeds_histogram(self):
        m = Metrics()
        with timed("decode", m):
            time.sleep(0.001)
        snap = m.snapshot("decode")["decode"]
        assert snap["hist_count"] == 1
        assert snap["p50_s"] >= 0.0005
        # the legacy keys are untouched
        for key in ("records_per_sec", "bytes_per_sec", "records", "bytes",
                    "batches", "seconds"):
            assert key in snap

    def test_timed_failure_records_error_counter(self):
        # the PR 5 bugfix: the old __exit__(*exc) swallowed the exception
        # info, so failed stages were indistinguishable from healthy ones
        m = Metrics()
        with pytest.raises(RuntimeError):
            with timed("decode", m):
                time.sleep(0.001)
                raise RuntimeError("boom")
        assert m.counter("decode.errors") == 1
        assert m.stage("decode").seconds >= 0.0005  # elapsed still recorded
        # a healthy block does not bump the error counter
        with timed("decode", m):
            pass
        assert m.counter("decode.errors") == 1

    def test_gauge_first_class(self):
        m = Metrics()
        m.gauge("prefetch.queue_depth", 3)
        m.gauge("prefetch.queue_depth", 1)  # last write wins
        assert m.gauge_value("prefetch.queue_depth") == 1.0
        assert m.gauge_value("missing") is None
        assert m.gauge_value("missing", 0.0) == 0.0
        # distinct snapshot shape; never rides the records field
        assert m.snapshot()["prefetch.queue_depth"] == {"gauge": 1.0}
        assert m.counter("prefetch.queue_depth") == 0

    def test_gauge_concurrency(self):
        m = Metrics()
        n_threads, per_thread = 8, 200

        def work(k):
            for i in range(per_thread):
                m.gauge("g", k * per_thread + i)
                m.count("c")

        threads = [
            threading.Thread(target=work, args=(k,)) for k in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # the counter is exact; the gauge holds SOME written value
        assert m.counter("c") == n_threads * per_thread
        assert 0 <= m.gauge_value("g") < n_threads * per_thread

    def test_reset_clears_gauges_and_hists(self):
        m = Metrics()
        m.gauge("g", 1)
        m.observe("s", 0.01)
        m.reset()
        assert m.gauges() == {} and m.quantiles() == {}

    def test_snapshot_prefix_filters_gauges_too(self):
        m = Metrics()
        m.gauge("write.occupancy", 0.5)
        m.gauge("prefetch.queue_depth", 2)
        m.add("write.io", records=1, seconds=0.1)
        snap = m.snapshot("write")
        assert set(snap) == {"write.occupancy", "write.io"}


class TestVerdict:
    def test_thresholds(self):
        assert boundness_verdict(None) == "unknown"
        assert boundness_verdict(0.9) == "consumer_bound"
        assert boundness_verdict(0.1) == "producer_bound"
        assert boundness_verdict(0.5) == "balanced"

    def test_from_metrics(self):
        m = Metrics()
        assert telemetry.verdict_from_metrics(m) == "unknown"
        m.gauge(telemetry.OCCUPANCY_GAUGE, 0.95)
        assert telemetry.verdict_from_metrics(m) == "consumer_bound"


class TestChromeTrace:
    def test_schema_validity(self, tmp_path):
        rec = SpanRecorder(enabled=True)
        with rec.span("decode", shard="part-0"):
            pass
        rec.instant("read.stall", path="part-1")
        doc = rec.to_chrome_trace()
        # round-trips through JSON (Perfetto loads a file, not a dict)
        doc = json.loads(json.dumps(doc))
        assert doc["displayTimeUnit"] == "ms"
        # metadata records lead: a process_name track label (fleet merges
        # rely on it) and a thread_name for each live recorded thread
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert meta[0]["name"] == "process_name"
        assert any(
            e["name"] == "thread_name" and e["args"]["name"] == "MainThread"
            for e in meta
        )
        events = [e for e in doc["traceEvents"] if e["ph"] != "M"]
        assert len(events) == 2
        for ev in events:
            for key in ("name", "cat", "ph", "ts", "pid", "tid"):
                assert key in ev, ev
            assert ev["ph"] in ("X", "i")
            if ev["ph"] == "X":
                assert "dur" in ev and ev["dur"] >= 0
            else:
                assert ev["s"] == "t"
        x = [e for e in events if e["ph"] == "X"][0]
        assert x["args"] == {"shard": "part-0"}
        path = tmp_path / "trace.json"
        rec.save_chrome_trace(str(path))
        assert json.load(open(path))["traceEvents"]


class TestPulse:
    def test_pulse_line_round_trip(self):
        m = Metrics()
        m.add("decode", records=100, nbytes=5000, seconds=0.5, latency=0.5)
        m.count("read.retries", 2)
        m.gauge(telemetry.OCCUPANCY_GAUGE, 0.9)
        lines = []
        clock = iter([0.0, 2.0]).__next__
        p = Pulse(1.0, metrics=m, emit=lines.append, clock=clock)
        payload = p.tick()
        assert lines == [payload]
        # the pulse line is one machine-parseable JSON object
        rt = json.loads(json.dumps(payload))
        assert rt["event"] == "pulse"
        assert rt["interval_s"] == pytest.approx(2.0)
        assert rt["stages"]["decode"]["records_per_sec"] == pytest.approx(50.0)
        assert rt["stages"]["decode"]["bytes_per_sec"] == pytest.approx(2500.0)
        assert rt["counters"]["read.retries"] == 2
        assert rt["gauges"][telemetry.OCCUPANCY_GAUGE] == pytest.approx(0.9)
        assert rt["quantiles"]["decode"]["count"] == 1
        assert rt["verdict"] == "consumer_bound"

    def test_pulse_reports_interval_deltas(self):
        m = Metrics()
        clock = iter([0.0, 1.0, 2.0]).__next__
        p = Pulse(1.0, metrics=m, emit=lambda _d: None, clock=clock)
        m.add("decode", records=100, seconds=0.1)
        first = p.tick()
        assert first["stages"]["decode"]["records_per_sec"] == pytest.approx(100.0)
        # no new work in the second interval: throughput drops to zero
        # (a stalled pipeline PULSES as stalled, instead of averaging)
        second = p.tick()
        assert second["stages"]["decode"]["records_per_sec"] == 0.0
        assert second["stages"]["decode"]["records"] == 100

    def test_pulse_thread_and_default_log_emit(self, caplog):
        import logging

        m = Metrics()
        m.add("decode", records=10, seconds=0.01)
        p = Pulse(0.02, metrics=m)
        with caplog.at_level(logging.INFO, logger="tpu_tfrecord"):
            p.start()
            time.sleep(0.08)
            p.stop()
        pulse_lines = [
            r.getMessage() for r in caplog.records if "tfrecord.pulse" in r.getMessage()
        ]
        assert pulse_lines
        payload = json.loads(pulse_lines[0].split("tfrecord.pulse ", 1)[1])
        assert payload["event"] == "pulse"

    def test_stop_idempotent(self):
        lines = []
        p = Pulse(10.0, metrics=Metrics(), emit=lines.append).start()
        p.stop()
        n = len(lines)
        p.stop()  # the GC finalizer path: no second final tick
        assert len(lines) == n


class TestOptions:
    def test_defaults(self):
        opts = TFRecordOptions.from_map({})
        assert opts.trace == "off"
        assert opts.pulse_interval_s is None
        assert opts.telemetry_port is None

    def test_parsing(self):
        opts = TFRecordOptions.from_map(
            trace="on", pulse_interval_s="2.5", telemetry_port="9095"
        )
        assert opts.trace == "on"
        assert opts.pulse_interval_s == 2.5
        assert opts.telemetry_port == 9095
        camel = TFRecordOptions.from_map(
            {"pulseIntervalS": 1, "telemetryPort": 0}
        )
        assert camel.pulse_interval_s == 1.0 and camel.telemetry_port == 0

    def test_validation(self):
        with pytest.raises(ValueError, match="trace"):
            TFRecordOptions.from_map(trace="maybe")
        with pytest.raises(ValueError, match="pulse_interval_s"):
            TFRecordOptions.from_map(pulse_interval_s=0)
        with pytest.raises(ValueError, match="telemetry_port"):
            TFRecordOptions.from_map(telemetry_port=70000)


class TestEndToEnd:
    def test_read_with_trace_on_records_pipeline_spans(self, sandbox):
        from tpu_tfrecord.io.dataset import TFRecordDataset

        data = write_dataset(sandbox / "ds")
        ds = TFRecordDataset(
            data, batch_size=16, schema=SCHEMA, drop_remainder=False, trace="on"
        )
        assert telemetry.RECORDER.enabled  # the option enabled the recorder
        with ds.batches() as it:
            rows = sum(b.num_rows for b in it)
        assert rows == 150
        spans = telemetry.RECORDER.spans()
        names = {s[0] for s in spans}
        # open and decode are the feed's own spans (tracing.trace writes
        # the ring and the profiler's timeline in one call since PR 51)
        assert {"tfr:open", "tfr:decode", "batch"} <= names
        decode_shards = {
            (s[4] or {}).get("shard") for s in spans if s[0] == "tfr:decode"
        }
        assert len(decode_shards) == 3  # every shard attributed
        # and the export is valid trace-event JSON containing decode spans
        doc = json.loads(json.dumps(telemetry.RECORDER.to_chrome_trace()))
        assert any(e["name"] == "tfr:decode" for e in doc["traceEvents"])

    def test_trace_off_records_nothing(self, sandbox):
        from tpu_tfrecord.io.dataset import TFRecordDataset

        data = write_dataset(sandbox / "ds")
        ds = TFRecordDataset(
            data, batch_size=16, schema=SCHEMA, drop_remainder=False
        )
        with ds.batches() as it:
            for _ in it:
                pass
        # nothing but the host log, which is always on (tracing.HOST_SPANS)
        from tpu_tfrecord.tracing import HOST_SPANS

        assert {s[0] for s in telemetry.RECORDER.spans()} <= HOST_SPANS
        # but gauges and histograms (always-on, batch-granularity) flowed
        assert METRICS.gauge_value("prefetch.queue_depth") is not None
        assert "decode" in METRICS.quantiles()

    def test_writer_trace_on_records_write_spans(self, sandbox):
        import tpu_tfrecord.io as tfio

        tfio.write(
            [[i, f"s{i}"] for i in range(200)],
            SCHEMA,
            str(sandbox / "out"),
            mode="overwrite",
            options=TFRecordOptions.from_map(
                trace="on", write_workers=2, num_shards=2
            ),
        )
        names = {s[0] for s in telemetry.RECORDER.spans()}
        assert {"write.encode", "write.io", "write.commit"} <= names
        assert METRICS.counter("write.commit.errors") == 0
        assert "write.commit" in METRICS.quantiles()

    def test_cold_cache_epoch_reports_no_errors(self, sandbox):
        # a routine cold miss (absent entry) is NOT an error: a healthy
        # first epoch with cache="auto" must leave every *.errors counter
        # at zero, or dashboards alerting on error rates fire on every
        # fresh cache
        from tpu_tfrecord.io.dataset import TFRecordDataset

        data = write_dataset(sandbox / "ds", n_shards=2)
        for _epoch in range(2):
            ds = TFRecordDataset(
                data,
                batch_size=16,
                schema=SCHEMA,
                drop_remainder=False,
                cache="auto",
                cache_dir=str(sandbox / "cache"),
            )
            with ds.batches() as it:
                for _ in it:
                    pass
        errors = {
            name: totals[0]
            for name, totals in METRICS.raw_totals().items()
            if name.endswith(".errors") and totals[0]
        }
        assert errors == {}, errors
        assert METRICS.counter("cache.hits") > 0  # epoch 2 actually served
        assert "cache.open" in METRICS.quantiles()  # latency still recorded

    def test_pulse_option_emits_during_iteration(self, sandbox, caplog):
        import logging

        from tpu_tfrecord.io.dataset import TFRecordDataset

        data = write_dataset(sandbox / "ds")
        ds = TFRecordDataset(
            data,
            batch_size=16,
            schema=SCHEMA,
            drop_remainder=False,
            pulse_interval_s=0.02,
        )
        with caplog.at_level(logging.INFO, logger="tpu_tfrecord"):
            with ds.batches() as it:
                for _ in it:
                    time.sleep(0.01)
        pulse_lines = [
            r.getMessage() for r in caplog.records if "tfrecord.pulse" in r.getMessage()
        ]
        assert pulse_lines  # at least the final tick
        payload = json.loads(pulse_lines[-1].split("tfrecord.pulse ", 1)[1])
        assert payload["verdict"] in (
            "producer_bound", "consumer_bound", "balanced", "unknown"
        )
        assert "prefetch.queue_depth" in payload["gauges"]


class TestPrometheus:
    def test_text_format(self):
        m = Metrics()
        m.add("decode", records=10, nbytes=100, seconds=0.5, latency=0.5)
        m.gauge("prefetch.queue_depth", 2)
        text = prometheus_text(m)
        assert 'tfrecord_stage_records_total{stage="decode"} 10' in text
        assert 'tfrecord_gauge{name="prefetch.queue_depth"} 2' in text
        assert 'tfrecord_latency_seconds{stage="decode",quantile="0.99"}' in text
        assert 'tfrecord_latency_seconds_count{stage="decode"} 1' in text

    def test_families_are_contiguous_and_parse(self):
        # the exposition format requires one contiguous block per metric
        # family; interleaving per stage makes strict parsers reject the
        # page as duplicate families (pinned with the official parser)
        m = Metrics()
        m.add("decode", records=10, nbytes=100, seconds=0.5, latency=0.5)
        m.add("read.open", records=3, seconds=0.1, latency=0.1)
        m.gauge("prefetch.queue_depth", 2)
        parser = pytest.importorskip("prometheus_client.parser")
        families = list(
            parser.text_string_to_metric_families(prometheus_text(m))
        )
        names = [f.name for f in families]
        assert len(names) == len(set(names)), names  # no duplicate families
        # the parser strips the counter _total suffix into the family name
        recs = {f.name: f for f in families}["tfrecord_stage_records"]
        by_stage = {s.labels["stage"]: s.value for s in recs.samples}
        assert by_stage == {"decode": 10.0, "read.open": 3.0}
        lat = {f.name: f for f in families}["tfrecord_latency_seconds"]
        assert lat.type == "summary"
        assert any(s.name.endswith("_count") for s in lat.samples)

    def test_http_endpoint(self):
        m = Metrics()
        m.add("decode", records=7, seconds=0.1)
        server = telemetry.ensure_exporter(0, metrics=m)
        try:
            # the public way to learn the ephemeral port: keyed by the
            # REQUESTED port (0), not the one the OS picked
            host, port = telemetry.exporter_address(0)
            assert port == server.server_address[1]
            # idempotent per port key
            assert telemetry.ensure_exporter(0, metrics=m) is server
            body = urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=5
            ).read().decode()
            assert 'tfrecord_stage_records_total{stage="decode"} 7' in body
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://{host}:{port}/other", timeout=5
                )
        finally:
            telemetry.shutdown_exporter(0)
        assert telemetry.exporter_address(0) is None

    def test_taken_port_never_raises(self):
        # an observability knob must not take the pipeline down: binding a
        # port another process holds warns and returns None
        import socket

        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        sock.listen(1)
        taken = sock.getsockname()[1]
        try:
            assert telemetry.ensure_exporter(taken, metrics=Metrics()) is None
            assert telemetry.exporter_address(taken) is None
        finally:
            sock.close()


class TestDoctorReport:
    def test_report_subcommand(self, sandbox):
        data = write_dataset(sandbox / "ds")
        trace_out = str(sandbox / "trace.json")
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(
                    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "tools",
                    "tfrecord_doctor.py",
                ),
                "report",
                data,
                "--batches", "6",
                "--batch-size", "16",
                "--trace-out", trace_out,
            ],
            capture_output=True,
            text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, (proc.stdout, proc.stderr)
        lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
        stages = [l for l in lines if l["event"] == "stage"]
        shards = [l for l in lines if l["event"] == "shard"]
        (report,) = [l for l in lines if l["event"] == "report"]
        assert any(l["stage"] == "decode" and "p50_ms" in l for l in stages)
        assert shards and all("seconds" in s for s in shards)
        assert report["verdict"] in (
            "producer_bound", "consumer_bound", "balanced", "unknown"
        )
        assert report["rows"] == 96
        assert report["straggler_p99_p50"] >= 1.0
        assert report["slowest_shard"]
        doc = json.load(open(trace_out))
        assert any(e["name"] == "tfr:decode" for e in doc["traceEvents"])

    def test_report_unreadable_dataset_exits_2(self, sandbox):
        proc = subprocess.run(
            [
                sys.executable,
                os.path.join(
                    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "tools",
                    "tfrecord_doctor.py",
                ),
                "report",
                str(sandbox / "nope"),
            ],
            capture_output=True,
            text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 2, (proc.stdout, proc.stderr)
        lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
        assert lines and lines[0]["event"] == "error"
