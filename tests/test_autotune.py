"""Closed-loop autotuning (ISSUE 6): the flight recorder drives the knobs.

Four layers:

- controller units: hysteresis, cooldown, per-knob clamps, threshold
  derivation from observed p99s, readahead retargeting — all with an
  injected clock and synthetic pulse payloads (no pipeline).
- live pool machinery: the resizable prefetch queue, worker-pool
  accounting, and mid-epoch grow/shrink with byte-identical output and
  checkpoint/resume interchangeability (the guarantees a resize must
  preserve).
- stall-guard integration: controller-updated thresholds are picked up by
  live guarded streams.
- the throttled-decode chaos acceptance test: with every read stalled by
  injected sleeps, ``autotune="on"`` starting from deliberately-wrong
  knobs recovers >= 90% of the hand-tuned fixed-knob throughput, with row
  output byte-identical to the fixed-knob run.
"""

import os
import time

import pytest

import tpu_tfrecord.io as tfio
from tpu_tfrecord import telemetry
from tpu_tfrecord.autotune import (
    AutotuneController,
    AutotunePolicy,
    PipelineControl,
)
from tpu_tfrecord.io.dataset import TFRecordDataset, _ResizableQueue
from tpu_tfrecord.metrics import Metrics
from tpu_tfrecord.options import TFRecordOptions
from tpu_tfrecord.schema import LongType, StringType, StructField, StructType
from tpu_tfrecord.stall import StallGuard


SCHEMA = StructType(
    [
        StructField("id", LongType(), nullable=False),
        StructField("s", StringType()),
    ]
)


def write_dataset(base, n_shards=6, rows_per_shard=40) -> str:
    out = os.path.join(str(base), "ds")
    for s in range(n_shards):
        rows = [
            [i, f"row-{i}"]
            for i in range(s * rows_per_shard, (s + 1) * rows_per_shard)
        ]
        tfio.write(rows, SCHEMA, out, mode="append" if s else "overwrite")
    return out


def read_all(ds) -> list:
    with ds.batches() as it:
        return [r for b in it for r in b["id"].values.tolist()]


# ---------------------------------------------------------------------------
# Resizable prefetch queue
# ---------------------------------------------------------------------------


class TestResizableQueue:
    def test_grow_wakes_blocked_putter(self):
        import threading

        q = _ResizableQueue(maxsize=1)
        q.put(1)
        done = threading.Event()

        def putter():
            q.put(2)  # blocks until resize
            done.set()

        t = threading.Thread(target=putter, daemon=True)
        t.start()
        time.sleep(0.05)
        assert not done.is_set()
        q.resize(2)
        assert done.wait(1.0)
        assert q.get() == 1 and q.get() == 2

    def test_shrink_blocks_new_puts_until_drained(self):
        q = _ResizableQueue(maxsize=4)
        for i in range(3):
            q.put(i)
        q.resize(1)
        with pytest.raises(Exception):
            q.put(99, timeout=0.05)
        # existing items are never dropped
        assert [q.get() for _ in range(3)] == [0, 1, 2]
        q.put(99, timeout=0.5)

    def test_resize_floor_is_one(self):
        q = _ResizableQueue(maxsize=4)
        q.resize(0)
        assert q.maxsize == 1


# ---------------------------------------------------------------------------
# PipelineControl accounting
# ---------------------------------------------------------------------------


class TestPipelineControl:
    def test_set_workers_clamps_and_spawns(self):
        spawned = []
        c = PipelineControl(workers=2, max_workers=4)
        c.bind_spawn(lambda: spawned.append(1))
        assert len(spawned) == 2  # brought up to initial target
        assert c.set_workers(99) == 4  # clamped to the ceiling
        assert len(spawned) == 4
        assert c.set_workers(0) == 1  # clamped to the floor
        # shrink spawns nothing; surplus workers retire via should_exit
        assert len(spawned) == 4

    def test_exit_permits_match_surplus_exactly(self):
        c = PipelineControl(workers=4, max_workers=8)
        c.bind_spawn(lambda: None)
        c.set_workers(2)
        # exactly alive - target workers get an exit permit
        permits = [c.should_exit() for _ in range(4)]
        assert permits.count(True) == 2
        for p in permits:
            if p:
                c.note_exit(permitted=True)
        # books balanced: no further exits allowed at target
        assert not c.should_exit()

    def test_grow_after_shrink_respawns(self):
        spawned = []
        c = PipelineControl(workers=3, max_workers=8)
        c.bind_spawn(lambda: spawned.append(1))
        c.set_workers(1)
        assert c.should_exit() and c.should_exit()
        c.note_exit(permitted=True)
        c.note_exit(permitted=True)
        before = len(spawned)
        c.set_workers(3)
        assert len(spawned) - before == 2

    def test_prefetch_and_readahead_without_queue_or_dataset(self):
        c = PipelineControl(workers=1)
        assert c.prefetch is None
        assert c.set_prefetch(5) == 5 and c.prefetch == 5
        assert c.set_readahead_bytes(8 << 20) == 8 << 20
        assert c.readahead_bytes == 8 << 20


# ---------------------------------------------------------------------------
# Controller units (injected clock, synthetic payloads)
# ---------------------------------------------------------------------------


def payload(verdict="unknown", stages=None, quantiles=None, gauges=None):
    return {
        "event": "pulse",
        "verdict": verdict,
        "stages": stages or {},
        "quantiles": quantiles or {},
        "gauges": gauges or {},
        "counters": {},
    }


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def make_controller(workers=1, policy=None, guard=None, queue=None, **ctrl_kw):
    clock = FakeClock()
    control = PipelineControl(workers=workers, max_workers=8, queue=queue,
                              guard=guard)
    ctl = AutotuneController(
        control,
        interval_s=1.0,
        policy=policy or AutotunePolicy(hysteresis=2, cooldown_s=2.0),
        metrics=Metrics(),
        clock=clock,
        **ctrl_kw,
    )
    return ctl, control, clock


class TestControllerPool:
    def test_hysteresis_requires_consecutive_verdicts(self):
        ctl, control, clock = make_controller()
        ctl.on_pulse(payload("producer_bound"))
        assert control.workers == 1  # one tick is not a trend
        clock.t += 10
        ctl.on_pulse(payload("producer_bound"))
        assert control.workers == 2  # second consecutive tick moves

    def test_balanced_resets_streak(self):
        ctl, control, clock = make_controller()
        ctl.on_pulse(payload("producer_bound"))
        ctl.on_pulse(payload("balanced"))
        clock.t += 10
        ctl.on_pulse(payload("producer_bound"))
        assert control.workers == 1  # streak restarted

    def test_whipsaw_verdicts_never_move_the_pool(self):
        ctl, control, clock = make_controller()
        for i in range(10):
            clock.t += 10  # cooldown is never the limiter here
            ctl.on_pulse(
                payload("producer_bound" if i % 2 else "consumer_bound")
            )
        assert control.workers == 1
        assert ctl.log == []

    def test_cooldown_limits_move_rate(self):
        ctl, control, clock = make_controller(
            policy=AutotunePolicy(hysteresis=1, cooldown_s=5.0)
        )
        ctl.on_pulse(payload("producer_bound"))
        assert control.workers == 2
        clock.t += 1.0  # inside the cooldown window
        ctl.on_pulse(payload("producer_bound"))
        ctl.on_pulse(payload("producer_bound"))
        assert control.workers == 2
        clock.t += 10.0
        ctl.on_pulse(payload("producer_bound"))
        assert control.workers == 3

    def test_consumer_bound_shrinks_to_floor_only(self):
        ctl, control, clock = make_controller(
            workers=2, policy=AutotunePolicy(hysteresis=1, cooldown_s=0.0,
                                             min_workers=1)
        )
        for _ in range(5):
            clock.t += 1
            ctl.on_pulse(payload("consumer_bound"))
        assert control.workers == 1  # clamped at min_workers, never 0

    def test_grow_clamps_at_max_workers(self):
        ctl, control, clock = make_controller(
            policy=AutotunePolicy(hysteresis=1, cooldown_s=0.0, max_workers=3)
        )
        for _ in range(8):
            clock.t += 1
            ctl.on_pulse(payload("producer_bound"))
        assert control.workers == 3

    def test_prefetch_tracks_pool(self):
        q = _ResizableQueue(maxsize=1)
        ctl, control, clock = make_controller(
            policy=AutotunePolicy(hysteresis=1, cooldown_s=0.0), queue=q
        )
        clock.t += 1
        ctl.on_pulse(payload("producer_bound"))
        assert control.workers == 2
        assert q.maxsize == 4  # workers + 2

    def test_decisions_logged_and_counted(self):
        ctl, control, clock = make_controller(
            policy=AutotunePolicy(hysteresis=1, cooldown_s=0.0)
        )
        clock.t += 1
        out = ctl.on_pulse(payload("producer_bound"))
        assert out["autotune"]["workers"] == 2
        assert out["autotune"]["adjusted"][0]["knob"] == "workers"
        assert ctl.log[0]["reason"] == "producer_bound"
        assert ctl.metrics.counter("autotune.adjustments") >= 1
        assert ctl.metrics.gauge_value("autotune.workers") == 2.0


class TestControllerThresholds:
    def q(self, stage, p99_ms, count=100):
        return {stage: {"p50_ms": p99_ms / 2, "p90_ms": p99_ms,
                        "p99_ms": p99_ms, "count": count}}

    def test_hedge_derived_from_read_p99(self):
        guard = StallGuard()
        ctl, control, clock = make_controller(guard=guard)
        ctl.on_pulse(payload(quantiles=self.q("read.io", 50.0)))
        assert guard.hedge_after == pytest.approx(0.2)  # 4 x 50ms

    def test_hedge_floor_clamp(self):
        guard = StallGuard()
        ctl, control, clock = make_controller(guard=guard)
        ctl.on_pulse(payload(quantiles=self.q("read.io", 1.0)))
        assert guard.hedge_after == pytest.approx(0.1)  # min_hedge_ms

    def test_deadlines_adapted_but_never_introduced(self):
        guard = StallGuard()  # user configured NO deadlines
        ctl, control, clock = make_controller(guard=guard)
        ctl.on_pulse(
            payload(quantiles={**self.q("read.io", 500.0),
                               **self.q("read.open", 500.0)})
        )
        assert guard.read_deadline is None
        assert guard.open_deadline is None
        guard2 = StallGuard(read_deadline=1.0, open_deadline=1.0)
        ctl2, _, _ = make_controller(guard=guard2)
        ctl2.on_pulse(
            payload(quantiles={**self.q("read.io", 500.0),
                               **self.q("read.open", 400.0)})
        )
        assert guard2.read_deadline == pytest.approx(10.0)  # 20 x 500ms
        assert guard2.open_deadline == pytest.approx(8.0)

    def test_threshold_band_suppresses_twitch(self):
        guard = StallGuard(hedge_after=0.2)
        ctl, control, clock = make_controller(guard=guard)
        # derived 4 x 55ms = 220ms: within 25% of the current 200ms
        ctl.on_pulse(payload(quantiles=self.q("read.io", 55.0)))
        assert guard.hedge_after == pytest.approx(0.2)
        assert ctl.log == []

    def test_min_latency_samples_gate(self):
        guard = StallGuard()
        ctl, control, clock = make_controller(guard=guard)
        ctl.on_pulse(payload(quantiles=self.q("read.io", 50.0, count=3)))
        assert guard.hedge_after is None  # too few observations to trust

    def test_deadline_ceiling_clamp(self):
        guard = StallGuard(read_deadline=1.0)
        ctl, control, clock = make_controller(guard=guard)
        ctl.on_pulse(payload(quantiles=self.q("read.io", 60_000.0)))
        assert guard.read_deadline == pytest.approx(120.0)  # max_deadline_ms


class TestControllerReadahead:
    def test_retarget_to_bandwidth_horizon(self):
        ctl, control, clock = make_controller()
        control.set_readahead_bytes(64 << 20)
        # 100 MB/s observed -> 0.5s horizon -> 50 MB: within the 50% band
        ctl.on_pulse(payload(stages={"read.io": {"bytes_per_sec": 100e6}}))
        assert control.readahead_bytes == 64 << 20
        # 400 MB/s -> ~191 MiB: beyond the band, retargets
        ctl.on_pulse(payload(stages={"read.io": {"bytes_per_sec": 400e6}}))
        assert control.readahead_bytes == int(round(400e6 * 0.5 / (1 << 20))) << 20

    def test_clamped_to_policy_range(self):
        ctl, control, clock = make_controller()
        control.set_readahead_bytes(64 << 20)
        ctl.on_pulse(payload(stages={"read.io": {"bytes_per_sec": 10e9}}))
        assert control.readahead_bytes == 256 << 20  # max_readahead_mb
        ctl.on_pulse(payload(stages={"read.io": {"bytes_per_sec": 1e6}}))
        assert control.readahead_bytes == 8 << 20  # min_readahead_mb

    def test_disabled_readahead_stays_disabled(self):
        ctl, control, clock = make_controller()
        control.set_readahead_bytes(0)
        ctl.on_pulse(payload(stages={"read.io": {"bytes_per_sec": 400e6}}))
        assert control.readahead_bytes == 0


# ---------------------------------------------------------------------------
# Pulse observer plumbing
# ---------------------------------------------------------------------------


class TestPulseObserver:
    def test_observer_fields_merged_into_emitted_line(self):
        from tpu_tfrecord.telemetry import Pulse

        lines = []
        pulse = Pulse(60.0, metrics=Metrics(), emit=lines.append)
        pulse.add_observer(lambda p: {"autotune": {"workers": 3}})
        pulse.tick()
        assert lines[0]["autotune"] == {"workers": 3}

    def test_observer_exception_never_breaks_the_tick(self):
        from tpu_tfrecord.telemetry import Pulse

        lines = []
        pulse = Pulse(60.0, metrics=Metrics(), emit=lines.append)

        def bad(_p):
            raise RuntimeError("observer bug")

        pulse.add_observer(bad)
        pulse.tick()
        assert lines and lines[0]["event"] == "pulse"


# ---------------------------------------------------------------------------
# Live pool resize: determinism + checkpoint/resume
# ---------------------------------------------------------------------------


class TestLivePoolResize:
    def test_rows_identical_across_mid_epoch_resizes(self, tmp_path):
        out = write_dataset(tmp_path)
        baseline = read_all(
            TFRecordDataset(out, batch_size=7, schema=SCHEMA,
                            drop_remainder=False)
        )
        ds = TFRecordDataset(out, batch_size=7, schema=SCHEMA,
                             drop_remainder=False, autotune="on",
                             autotune_interval_s=300.0)
        got = []
        it = ds.batches()
        with it:
            for i, b in enumerate(it):
                if i == 1:
                    it._control.set_workers(4)
                    it._control.set_prefetch(8)
                if i == 10:
                    it._control.set_workers(1)
                    it._control.set_prefetch(2)
                got.extend(b["id"].values.tolist())
        assert got == baseline

    def test_checkpoint_resume_across_resize(self, tmp_path):
        out = write_dataset(tmp_path)
        baseline = read_all(
            TFRecordDataset(out, batch_size=7, schema=SCHEMA,
                            drop_remainder=False)
        )
        ds = TFRecordDataset(out, batch_size=7, schema=SCHEMA,
                             drop_remainder=False, autotune="on",
                             autotune_interval_s=300.0)
        it = ds.batches()
        head = []
        for i, b in enumerate(it):
            if i == 2:
                it._control.set_workers(3)  # resize BEFORE the checkpoint
            head.extend(b["id"].values.tolist())
            if i == 5:
                break
        state = it.state()
        it.close()
        # resume into a DIFFERENT starting worker count, autotune still on
        ds2 = TFRecordDataset(out, batch_size=7, schema=SCHEMA,
                              drop_remainder=False, num_workers=2,
                              autotune="on", autotune_interval_s=300.0)
        tail = []
        it2 = ds2.batches(state)
        with it2:
            for i, b in enumerate(it2):
                if i == 1:
                    it2._control.set_workers(4)  # and resize mid-resume too
                tail.extend(b["id"].values.tolist())
        assert head + tail == baseline

    def test_single_worker_autotune_path_matches_sequential(self, tmp_path):
        out = write_dataset(tmp_path, n_shards=3)
        baseline = read_all(
            TFRecordDataset(out, batch_size=5, schema=SCHEMA,
                            drop_remainder=False)
        )
        got = read_all(
            TFRecordDataset(out, batch_size=5, schema=SCHEMA,
                            drop_remainder=False, autotune="on",
                            autotune_interval_s=300.0)
        )
        assert got == baseline

    def test_iterator_exposes_controller_only_when_on(self, tmp_path):
        out = write_dataset(tmp_path, n_shards=2)
        ds = TFRecordDataset(out, batch_size=5, schema=SCHEMA)
        with ds.batches() as it:
            assert it.autotune is None and it._control is None
        ds2 = TFRecordDataset(out, batch_size=5, schema=SCHEMA,
                              autotune="on", autotune_interval_s=300.0)
        with ds2.batches() as it2:
            assert it2.autotune is not None
            assert it2._control.guard is ds2._stall_guard
            assert ds2._stall_guard is not None  # created for autotune


# ---------------------------------------------------------------------------
# Stall-guard live thresholds
# ---------------------------------------------------------------------------


class TestLiveThresholds:
    def test_guarded_stream_reads_thresholds_through_guard(self, tmp_path):
        import io

        from tpu_tfrecord.stall import GuardedReadStream

        guard = StallGuard(read_deadline=60.0)
        stream = GuardedReadStream(
            io.BytesIO(b"x" * 1024), "mem", read_deadline=60.0,
            hedge_after=None, reopen=lambda pos: io.BytesIO(b"x" * 1024),
            guard=guard,
        )
        assert stream._deadline == 60.0
        guard.update_thresholds(read_deadline_ms=125.0, hedge_after_ms=250.0)
        assert stream._deadline == pytest.approx(0.125)
        assert stream._hedge_after == pytest.approx(0.25)
        stream.close()

    def test_update_thresholds_units_and_partial(self):
        guard = StallGuard(read_deadline=1.0)
        guard.update_thresholds(hedge_after_ms=500.0)
        assert guard.read_deadline == 1.0  # untouched
        assert guard.hedge_after == pytest.approx(0.5)
        guard.update_thresholds(read_deadline_ms=2000.0,
                                open_deadline_ms=3000.0)
        assert guard.read_deadline == pytest.approx(2.0)
        assert guard.open_deadline == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# Options plumbing
# ---------------------------------------------------------------------------


class TestOptionsPlumbing:
    def test_parse_and_defaults(self):
        opts = TFRecordOptions.from_map()
        assert opts.autotune == "off" and opts.autotune_interval_s is None
        opts = TFRecordOptions.from_map(
            autotune="on", autotune_interval_s="0.5"
        )
        assert opts.autotune == "on"
        assert opts.autotune_interval_s == 0.5

    def test_validation(self):
        with pytest.raises(ValueError, match="autotune must be"):
            TFRecordOptions.from_map(autotune="sometimes")
        with pytest.raises(ValueError, match="autotune_interval_s"):
            TFRecordOptions.from_map(autotune_interval_s=0)

    def test_unknown_key_suggestion(self):
        with pytest.raises(ValueError, match="autotune"):
            TFRecordOptions.from_map(autotunee="on")


# ---------------------------------------------------------------------------
# Doctor `tune` subcommand
# ---------------------------------------------------------------------------


class TestDoctorTune:
    def test_tune_emits_knobs_and_exits_zero(self, tmp_path):
        import json
        import subprocess
        import sys

        out = write_dataset(tmp_path, n_shards=3)
        doctor = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "tfrecord_doctor.py",
        )
        res = subprocess.run(
            [sys.executable, doctor, "tune", out, "--seconds", "0.6",
             "--interval", "0.1", "--batch-size", "16"],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert res.returncode == 0, (res.returncode, res.stdout, res.stderr)
        lines = [json.loads(l) for l in res.stdout.splitlines() if l.strip()]
        final = [l for l in lines if l.get("event") == "tune"]
        assert final and "knobs" in final[0]
        assert final[0]["knobs"]["workers"] >= 1
        assert final[0]["rows"] > 0

    def test_tune_unreadable_dataset_exits_two(self, tmp_path):
        import subprocess
        import sys

        doctor = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "tfrecord_doctor.py",
        )
        res = subprocess.run(
            [sys.executable, doctor, "tune", str(tmp_path / "nope")],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert res.returncode == 2


# ---------------------------------------------------------------------------
# The acceptance test: throttled decode, controller recovers throughput
# ---------------------------------------------------------------------------


class TestThrottledDecodeChaos:
    """Every shard read pays an injected 30ms sleep (GIL released, like a
    real slow store), so throughput scales with decode-pool parallelism.

    Two tiers (ISSUE 13 satellite — the wall-clock throughput ratio was a
    pre-existing flake on the shared 2-vCPU box, where a loaded co-tenant
    can slow EITHER leg arbitrarily and no fixed ratio holds):

    - tier 1 (``test_autotune_grows_and_stays_deterministic``): every
      assertion is counter-based and deterministic — the controller must
      GROW the pool under throttle (its own decision log proves it) and
      rows must be byte-identical to the hand-tuned run. No wall-clock
      bar, so no interference flake.
    - ``slow`` (``test_autotune_recovers_hand_tuned_throughput``): the
      original >= 90%-of-hand-tuned throughput ratio, kept as the
      convergence-quality bar for runs that opt into perf assertions.
    """

    def _run(self, out, epochs, **ds_kw):
        from tpu_tfrecord.faults import FaultPlan, FaultRule, install_chaos

        plan = FaultPlan(
            [FaultRule(op="read", kind="stall", path="part-", times=None,
                       stall_ms=30.0)],
            seed=7,
        )
        ds = TFRecordDataset(
            out, batch_size=20, schema=SCHEMA, drop_remainder=False,
            num_epochs=epochs, use_mmap=False, **ds_kw,
        )
        rows = []
        epoch_times = []
        with install_chaos(plan):
            t0 = time.perf_counter()
            rows_seen = 0
            with ds.batches() as it:
                tuner = it.autotune
                for b in it:
                    rows.extend(b["id"].values.tolist())
                    rows_seen += b.num_rows
                    if rows_seen >= 240:  # one epoch of 6 shards x 40 rows
                        epoch_times.append(time.perf_counter() - t0)
                        t0 = time.perf_counter()
                        rows_seen = 0
        plan.release()
        return rows, epoch_times, tuner

    def test_autotune_grows_and_stays_deterministic(self, tmp_path):
        """Tier-1 half: deterministic counter-based assertions only."""
        out = write_dataset(tmp_path, n_shards=6, rows_per_shard=40)
        fixed_rows, _, _ = self._run(out, 4, num_workers=4, prefetch=4)
        tuned_rows, _, tuner = self._run(
            out, 4, num_workers=1, prefetch=1,
            autotune="on", autotune_interval_s=0.1,
        )
        # determinism across every pool/queue resize the controller made
        assert tuned_rows == fixed_rows
        # the controller actually adjusted knobs (bounded number of pulses)
        grows = [d for d in tuner.log if d["knob"] == "workers"]
        assert grows and grows[0]["to"] > grows[0]["from"], tuner.log
        assert tuner.control.workers > 1

    @pytest.mark.slow
    @pytest.mark.perf
    def test_autotune_recovers_hand_tuned_throughput(self, tmp_path):
        out = write_dataset(tmp_path, n_shards=6, rows_per_shard=40)
        fixed_rows, fixed_times, _ = self._run(
            out, 16, num_workers=4, prefetch=4
        )
        tuned_rows, tuned_times, tuner = self._run(
            out, 16, num_workers=1, prefetch=1,
            autotune="on", autotune_interval_s=0.1,
        )
        assert tuned_rows == fixed_rows
        # converged throughput: compare best epoch over the tail halves
        # (the head pays the deliberate mis-configuration + the climb).
        # Best-of, not mean-of: interference on this shared box is
        # one-sided — other tenants only slow an epoch down — so the min
        # epoch time is the noise-robust estimator (the same argument the
        # perf-floor tests document), and the injected stalls
        # dominate each epoch's floor, which is exactly what the worker
        # pool parallelizes.
        tail = max(2, len(tuned_times) // 2)
        tuned_rate = 1.0 / min(tuned_times[-tail:])
        fixed_rate = 1.0 / min(fixed_times[-tail:])
        assert tuned_rate >= 0.9 * fixed_rate, (
            f"autotuned best-epoch throughput {tuned_rate:.2f} epochs/s is "
            f"below 90% of hand-tuned {fixed_rate:.2f} epochs/s "
            f"(trajectory: {tuner.log})"
        )
