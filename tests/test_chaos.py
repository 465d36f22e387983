"""Chaos-matrix suite: deterministic fault injection x read mode x policy.

The stall-defense tentpole (ISSUE 3): FaultPlan/ChaosFS determinism, the
per-op deadline model (read_deadline_ms / open_deadline_ms), straggler
hedging (hedge_after_ms), the on_stall policy, the pipeline watchdog, the
RetryPolicy deadline-cap satellite, Metrics thread-safety, and the writer
heartbeat lease.

Stall timings: injected stalls are BOUNDED (plan.release() at teardown
frees any thread still blocked) and deadlines are tens of milliseconds, so
the whole suite costs seconds, not stall durations.
"""

import json
import os
import threading
import time

import pytest

import tpu_tfrecord.io as tfio
from tpu_tfrecord import wire
from tpu_tfrecord.faults import (
    ChaosFS,
    FaultPlan,
    FaultRule,
    InjectedFault,
    install_chaos,
)
from tpu_tfrecord.io.dataset import IteratorState, TFRecordDataset
from tpu_tfrecord.metrics import METRICS, Metrics
from tpu_tfrecord.retry import RetryPolicy
from tpu_tfrecord.schema import LongType, StringType, StructField, StructType
from tpu_tfrecord.stall import DeadlineError, GuardedReadStream, StallError

SCHEMA = StructType(
    [StructField("id", LongType(), nullable=False), StructField("s", StringType())]
)
ROWS = [[i, f"val{i}" * (i % 5 + 1)] for i in range(120)]
N_SHARDS = 4
PER_SHARD = len(ROWS) // N_SHARDS

# A permanent stall long enough that any test reaching it without defenses
# would hang past the outer guard; bounded so abandoned daemon threads die
# with the plan's release at teardown.
STALL_MS = 60_000


def _fast_retries(n):
    return RetryPolicy(max_retries=n, sleep=lambda _s: None)


@pytest.fixture(scope="module")
def dataset_dir(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("chaos") / "ds")
    for s in range(N_SHARDS):
        tfio.write(
            ROWS[s * PER_SHARD : (s + 1) * PER_SHARD],
            SCHEMA,
            out,
            mode="append" if s else "overwrite",
        )
    return out


def _shard_names(out):
    return sorted(n for n in os.listdir(out) if n.startswith("part-"))


def _shard_ids(path):
    """The id column of one shard file (ground truth via the wire layer)."""
    from tpu_tfrecord.serde import TFRecordDeserializer, decode_record
    from tpu_tfrecord.options import RecordType

    de = TFRecordDeserializer(SCHEMA)
    return [
        decode_record(de, RecordType.EXAMPLE, rec)[0]
        for rec in wire.read_records(path)
    ]


def _read_ids(out, state=None, max_batches=None, **kw):
    kw.setdefault("batch_size", 7)
    kw.setdefault("schema", SCHEMA)
    kw.setdefault("drop_remainder", False)
    ds = TFRecordDataset(out, **kw)
    got = []
    with ds.batches(state) as it:
        n = 0
        for cb in it:
            got.extend(cb["id"].values.tolist())
            n += 1
            if max_batches is not None and n >= max_batches:
                return got, it.state()
    return got, None


# Read-mode configurations: kwargs forcing each decode path, plus whether
# the native decoder must be detached (the pure-Python strict path).
MODES = {
    "strict": {"use_mmap": False, "_python": True},
    "fused": {"use_mmap": False},
    "mmap": {"use_mmap": True},
    "salvage": {"use_mmap": False, "on_corrupt": "skip_record"},
}


def _make_ds(out, mode, **kw):
    cfg = dict(MODES[mode])
    python_only = cfg.pop("_python", False)
    cfg.update(kw)
    cfg.setdefault("batch_size", 7)
    cfg.setdefault("schema", SCHEMA)
    cfg.setdefault("drop_remainder", False)
    ds = TFRecordDataset(out, **cfg)
    if python_only:
        ds._native_decoder = None  # force the two-pass Python strict path
    return ds


def _drain(ds, timeout=30):
    """Consume a dataset on a side thread under an outer deadlock guard:
    a stall bug here must FAIL the test, never hang the suite."""
    result = {}

    def run():
        try:
            got = []
            with ds.batches() as it:
                for cb in it:
                    got.extend(cb["id"].values.tolist())
            result["rows"] = got
        except BaseException as e:
            result["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(timeout)
    assert not t.is_alive(), "epoch hung: stall defense failed (outer guard)"
    return result


class TestFaultPlan:
    def test_json_round_trip(self):
        plan = FaultPlan(
            [
                FaultRule(op="read", kind="stall", path="p0", stall_ms=5.0),
                FaultRule(
                    op="open", kind="transient_error", ordinal=2, times=3,
                    probability=0.5,
                ),
            ],
            seed=7,
        )
        clone = FaultPlan.from_json(json.dumps(plan.to_json()))
        assert clone.to_json() == plan.to_json()
        assert clone.seed == 7

    def test_rule_validation(self):
        with pytest.raises(ValueError):
            FaultRule(op="nope", kind="stall", stall_ms=1.0)
        with pytest.raises(ValueError):
            FaultRule(op="read", kind="nope")
        with pytest.raises(ValueError):
            FaultRule(op="read", kind="stall", stall_ms=1.0, times=0)
        with pytest.raises(ValueError):
            FaultRule(op="read", kind="stall", stall_ms=1.0, probability=0.0)
        # cap 0 would be silent truncation (read(0) == b"" == EOF), and a
        # 0ms "stall" is a no-op: both are config mistakes, not scenarios
        with pytest.raises(ValueError):
            FaultRule(op="read", kind="short_read")
        with pytest.raises(ValueError):
            FaultRule(op="read", kind="stall")

    def test_ordinal_and_times(self):
        plan = FaultPlan(
            [FaultRule(op="read", kind="transient_error", ordinal=1, times=2)]
        )
        fired = [bool(plan.decide("read", "x")) for _ in range(5)]
        assert fired == [False, True, True, False, False]
        assert [e["ordinal"] for e in plan.ledger] == [1, 2]

    def test_probability_is_seed_deterministic(self):
        def ledger(seed):
            plan = FaultPlan(
                [
                    FaultRule(
                        op="read", kind="transient_error", times=None,
                        probability=0.5,
                    )
                ],
                seed=seed,
            )
            for _ in range(40):
                plan.decide("read", "x")
            return plan.ledger_json()

        assert ledger(3) == ledger(3)
        assert ledger(3) != ledger(4)  # 2^-40 flake odds: both draws equal

    def test_stall_uses_injectable_sleep(self):
        slept = []
        plan = FaultPlan(
            [FaultRule(op="read", kind="stall", stall_ms=2500.0)],
            sleep=slept.append,
        )
        plan.apply("read", "x", 100)
        assert slept == [2.5]  # no wall time: the seam took the stall


class TestChaosMatrix:
    """Fault kind x read mode x policy: the epoch either completes with
    the correct rows or raises, exactly per policy."""

    @pytest.mark.parametrize("mode", list(MODES))
    def test_no_faults_baseline(self, dataset_dir, mode):
        ds = _make_ds(dataset_dir, mode)
        result = _drain(ds)
        assert sorted(result["rows"]) == sorted(r[0] for r in ROWS)

    @pytest.mark.parametrize("mode", list(MODES))
    @pytest.mark.parametrize("policy", ["raise", "skip_shard"])
    def test_transient_error_retried_to_success(self, dataset_dir, mode, policy):
        """One injected transient error per shard + retries: every row
        arrives under every mode and policy (the fault heals)."""
        rules = [
            FaultRule(op="read", kind="transient_error", path=name, times=1)
            for name in _shard_names(dataset_dir)
        ]
        # mmap never read()s through the chaos file: fault its opens instead
        if mode == "mmap":
            rules = [
                FaultRule(
                    op="open", kind="transient_error", path=name, times=1
                )
                for name in _shard_names(dataset_dir)
            ]
        plan = FaultPlan(rules)
        ds = _make_ds(
            dataset_dir, mode, retry_policy=_fast_retries(3), on_stall=policy
        )
        with install_chaos(plan):
            result = _drain(ds)
        assert sorted(result["rows"]) == sorted(r[0] for r in ROWS)
        assert len(plan.ledger) == N_SHARDS  # every rule fired exactly once

    @pytest.mark.parametrize("mode", list(MODES))
    @pytest.mark.parametrize("policy", ["raise", "skip_shard"])
    def test_permanent_error_raises(self, dataset_dir, mode, policy):
        """A permanently erroring shard exhausts retries and raises under
        BOTH stall policies: on_stall covers stalls, not hard IO errors."""
        victim = _shard_names(dataset_dir)[1]
        op = "open" if mode == "mmap" else "read"
        plan = FaultPlan(
            [FaultRule(op=op, kind="permanent_error", path=victim, times=None)]
        )
        ds = _make_ds(
            dataset_dir, mode, retry_policy=_fast_retries(2), on_stall=policy
        )
        with install_chaos(plan):
            result = _drain(ds)
        assert isinstance(result["error"], InjectedFault)

    @pytest.mark.parametrize("mode", list(MODES))
    def test_short_reads_stream_correctly(self, dataset_dir, mode):
        """A 13-byte read cap must stream through every mode's refill
        logic, never misread as EOF/truncation."""
        if mode == "mmap":
            pytest.skip("mmap decodes from memory, not read() calls")
        plan = FaultPlan(
            [
                FaultRule(
                    op="read", kind="short_read", times=None, cap_bytes=13
                )
            ]
        )
        ds = _make_ds(dataset_dir, mode)
        with install_chaos(plan):
            result = _drain(ds, timeout=60)
        assert sorted(result["rows"]) == sorted(r[0] for r in ROWS)

    @pytest.mark.parametrize("mode", list(MODES))
    @pytest.mark.parametrize("policy", ["raise", "skip_shard"])
    def test_stall_per_policy(self, dataset_dir, mode, policy):
        """THE acceptance scenario: a shard whose read (open, for mmap)
        stalls 'forever' no longer hangs the epoch. Default policy raises
        within the configured deadline; skip_shard completes the epoch
        minus the stalled shard, counted in read.skipped_shards."""
        names = _shard_names(dataset_dir)
        victim = names[1]
        op = "open" if mode == "mmap" else "read"
        plan = FaultPlan(
            [
                FaultRule(
                    op=op, kind="stall", path=victim, times=None,
                    stall_ms=STALL_MS,
                )
            ]
        )
        METRICS.reset()
        ds = _make_ds(
            dataset_dir,
            mode,
            read_deadline_ms=150,
            open_deadline_ms=150,
            on_stall=policy,
        )
        try:
            with install_chaos(plan):
                result = _drain(ds)
            if policy == "raise":
                assert isinstance(result["error"], DeadlineError)
            else:
                victim_ids = set(_shard_ids(os.path.join(dataset_dir, victim)))
                expect = sorted(r[0] for r in ROWS if r[0] not in victim_ids)
                assert sorted(result["rows"]) == expect
                assert METRICS.counter("read.skipped_shards") == 1
            assert METRICS.counter("read.stalls") >= 1
            assert METRICS.counter("read.deadline_misses") >= 1
        finally:
            plan.release()


class TestChaosDeterminism:
    def _run(self, out, plan, checkpoint_at=None):
        """One tolerant epoch under ``plan``; optionally checkpoint after
        N batches and resume with a FRESH dataset + the same plan spec."""
        kw = dict(
            read_deadline_ms=150,
            on_stall="skip_shard",
            on_corrupt="skip_record",
            use_mmap=False,
            retry_policy=_fast_retries(1),
            num_workers=1,  # a ledger replays only if shards are read in one order
        )
        if checkpoint_at is None:
            rows, _ = _read_ids(out, **kw)
            return rows
        head, state = _read_ids(out, max_batches=checkpoint_at, **kw)
        resumed = FaultPlan.from_json(plan.to_json())
        with install_chaos(resumed):
            tail, _ = _read_ids(out, state=state, **kw)
        resumed.release()
        return head + tail

    def test_same_seed_same_ledger_and_rows(self, dataset_dir):
        """Same FaultPlan spec => byte-identical ledger and identical
        surviving row set across two full runs."""
        names = _shard_names(dataset_dir)
        spec = {
            "seed": 11,
            "rules": [
                {"op": "read", "kind": "stall", "path": names[2],
                 "ordinal": 0, "times": None, "stall_ms": STALL_MS},
                {"op": "read", "kind": "transient_error", "path": names[0],
                 "ordinal": 1, "times": 1},
            ],
        }
        runs = []
        for _ in range(2):
            plan = FaultPlan.from_json(spec)
            with install_chaos(plan):
                rows = self._run(dataset_dir, plan)
            plan.release()
            runs.append((rows, plan.ledger_json()))
        assert runs[0][0] == runs[1][0]
        assert runs[0][1] == runs[1][1]
        assert runs[0][1]  # the plan actually fired

    def test_determinism_across_checkpoint_resume(self, dataset_dir):
        """A checkpoint/resume boundary mid-epoch yields the same surviving
        row sequence as the uninterrupted run under the same plan spec."""
        names = _shard_names(dataset_dir)
        spec = {
            "seed": 5,
            "rules": [
                {"op": "read", "kind": "stall", "path": names[3],
                 "ordinal": 0, "times": None, "stall_ms": STALL_MS},
            ],
        }
        plan_a = FaultPlan.from_json(spec)
        with install_chaos(plan_a):
            full = self._run(dataset_dir, plan_a)
        plan_a.release()
        plan_b = FaultPlan.from_json(spec)
        with install_chaos(plan_b):
            resumed = self._run(dataset_dir, plan_b, checkpoint_at=5)
        plan_b.release()
        assert resumed == full


class TestHedgedReads:
    def test_hedge_win_is_byte_identical(self, dataset_dir):
        """Primary stalls once mid-shard; the hedge's backup read wins and
        the epoch's rows equal the fault-free run exactly."""
        baseline, _ = _read_ids(dataset_dir, use_mmap=False)
        victim = _shard_names(dataset_dir)[1]
        plan = FaultPlan(
            [
                FaultRule(
                    op="read", kind="stall", path=victim, ordinal=0, times=1,
                    stall_ms=STALL_MS,
                )
            ]
        )
        METRICS.reset()
        try:
            with install_chaos(plan):
                rows, _ = _read_ids(
                    dataset_dir, hedge_after_ms=50, use_mmap=False
                )
        finally:
            plan.release()
        assert rows == baseline
        assert METRICS.counter("read.hedges") >= 1
        assert METRICS.counter("read.hedge_wins") >= 1
        assert METRICS.counter("read.stalls") == 0  # hedge beat the stall

    def test_primary_win_is_byte_identical(self, dataset_dir):
        """No stall: the primary always wins, the hedge never launches,
        output matches the unguarded run."""
        baseline, _ = _read_ids(dataset_dir, use_mmap=False)
        METRICS.reset()
        rows, _ = _read_ids(dataset_dir, hedge_after_ms=10_000, use_mmap=False)
        assert rows == baseline
        assert METRICS.counter("read.hedges") == 0

    def test_guarded_stream_hedge_unit(self, tmp_path):
        """Unit-level: the backup side reads the same byte range, the
        stream's output is identical to the file, and the loser's handle
        is abandoned without corrupting the stream position."""
        path = str(tmp_path / "blob.bin")
        payload = bytes(range(256)) * 5000  # ~1.25 MB
        with open(path, "wb") as fh:
            fh.write(payload)
        release = threading.Event()
        state = {"opens": 0}

        class SlowFirstRead:
            """First read() of the FIRST handle blocks until released."""

            def __init__(self, fh, first):
                self._fh = fh
                self._first = first
                self._reads = 0

            def read(self, n=-1):
                self._reads += 1
                if self._first and self._reads == 1:
                    release.wait(30)
                return self._fh.read(n)

            def seek(self, pos):
                self._fh.seek(pos)

            def close(self):
                self._fh.close()

        def reopen(pos):
            state["opens"] += 1
            fh = SlowFirstRead(open(path, "rb"), first=False)
            fh.seek(pos)
            return fh

        m = Metrics()
        gs = GuardedReadStream(
            SlowFirstRead(open(path, "rb"), first=True),
            path,
            read_deadline=None,
            hedge_after=0.05,
            reopen=reopen,
            metrics=m,
            io_chunk=64 << 10,
        )
        try:
            out = gs.read(-1)
        finally:
            release.set()
            gs.close()
        assert out == payload
        assert state["opens"] == 1
        assert m.counter("read.hedges") == 1
        assert m.counter("read.hedge_wins") == 1


class TestHedgeBackupFailure:
    def test_failed_backup_does_not_shorten_primary_deadline(self, tmp_path):
        """A hedge whose BACKUP side errors must fall back to waiting on
        the merely-slow primary for the remaining read budget — not declare
        the primary stalled at hedge time."""
        path = str(tmp_path / "blob.bin")
        payload = os.urandom(128 << 10)
        with open(path, "wb") as fh:
            fh.write(payload)

        class SlowRead:
            """Every read takes 0.2s — slow, NOT stalled."""

            def __init__(self, fh):
                self._fh = fh

            def read(self, n=-1):
                time.sleep(0.2)
                return self._fh.read(n)

            def close(self):
                self._fh.close()

        def reopen(_pos):
            raise OSError("backup open refused")

        m = Metrics()
        gs = GuardedReadStream(
            SlowRead(open(path, "rb")),
            path,
            read_deadline=5.0,
            hedge_after=0.05,
            reopen=reopen,
            metrics=m,
            io_chunk=1 << 20,
        )
        try:
            out = gs.read(-1)
        finally:
            gs.close()
        assert out == payload  # the primary's bytes arrived intact
        assert m.counter("read.hedges") >= 1
        assert m.counter("read.hedge_wins") == 0
        assert m.counter("read.stalls") == 0  # no false stall declared


class TestWatchdog:
    def test_wedged_worker_skip_shard_completes(self, dataset_dir):
        """No deadline configured — only the watchdog stands between a
        wedged worker and an epoch that blocks forever. This test
        deadlocks without the watchdog (outer _drain guard enforces)."""
        victim = _shard_names(dataset_dir)[0]
        plan = FaultPlan(
            [
                FaultRule(
                    op="read", kind="stall", path=victim, times=None,
                    stall_ms=STALL_MS,
                )
            ]
        )
        METRICS.reset()
        ds = _make_ds(
            dataset_dir,
            "fused",
            num_workers=2,
            watchdog_timeout_ms=300,
            on_stall="skip_shard",
        )
        try:
            with install_chaos(plan):
                result = _drain(ds)
        finally:
            plan.release()
        victim_ids = set(_shard_ids(os.path.join(dataset_dir, victim)))
        expect = sorted(r[0] for r in ROWS if r[0] not in victim_ids)
        assert sorted(result["rows"]) == expect
        assert METRICS.counter("read.watchdog_restarts") >= 1
        assert METRICS.counter("read.stalls") >= 1
        assert METRICS.counter("read.skipped_shards") >= 1

    def test_wedged_worker_default_raises(self, dataset_dir):
        victim = _shard_names(dataset_dir)[0]
        plan = FaultPlan(
            [
                FaultRule(
                    op="read", kind="stall", path=victim, times=None,
                    stall_ms=STALL_MS,
                )
            ]
        )
        ds = _make_ds(
            dataset_dir, "fused", num_workers=2, watchdog_timeout_ms=300
        )
        try:
            with install_chaos(plan):
                result = _drain(ds)
        finally:
            plan.release()
        assert isinstance(result["error"], StallError)

    def test_no_watchdog_config_means_no_watchdog_thread(self, dataset_dir):
        """The default path spawns no watchdog and reads normally."""
        ds = _make_ds(dataset_dir, "fused", num_workers=2)
        result = _drain(ds)
        assert sorted(result["rows"]) == sorted(r[0] for r in ROWS)

    def test_backpressure_is_not_a_stall(self, tmp_path):
        """A SLOW CONSUMER must never trip the watchdog: workers blocked
        handing over chunks AND end sentinels (full job queues while the
        emitter waits on the prefetch queue) keep their heartbeat fresh —
        a done shard backpressured behind the emitter is healthy, never
        wedged. Shards here are >2 decode chunks, so the END put really
        blocks on the depth-2 job queue while the consumer dawdles."""
        long_schema = StructType([StructField("id", LongType(), nullable=False)])
        out = str(tmp_path / "bp")
        n = 4500  # > 2 * 2048-record chunks per shard => end-put blocks
        tfio.write([[i] for i in range(n)], long_schema, out, mode="overwrite")
        METRICS.reset()
        ds = TFRecordDataset(
            out, batch_size=512, schema=long_schema, drop_remainder=False,
            num_workers=2, prefetch=1, num_epochs=2,
            watchdog_timeout_ms=150, use_mmap=False,
        )
        got = []
        with ds.batches() as it:
            for cb in it:
                got.extend(cb["id"].values.tolist())
                time.sleep(0.08)  # consumer far slower than the decoders
        assert sorted(got) == sorted(list(range(n)) * 2)
        assert METRICS.counter("read.watchdog_restarts") == 0
        assert METRICS.counter("read.skipped_shards") == 0


class TestChaosFSWriteSide:
    def test_rename_race_is_absorbed_by_commit(self, tmp_path):
        """An injected landed-but-errored rename: PR 2's landed-rename
        detection plus write_retries absorbs it; output is complete."""
        out = str(tmp_path / "out")
        plan = FaultPlan(
            [FaultRule(op="rename", kind="rename_race", path="part-", times=1)]
        )
        with install_chaos(plan):
            tfio.write(
                ROWS[:10], SCHEMA, out, mode="overwrite", write_retries=2
            )
        assert len(plan.ledger) == 1
        table = tfio.read(out, schema=SCHEMA)
        assert sorted(table.column("id")) == list(range(10))

    def test_flaky_listing_raises(self, tmp_path, dataset_dir):
        plan = FaultPlan(
            [FaultRule(op="listdir", kind="flaky_listing", times=None)]
        )
        fs_obj = ChaosFS(__import__("tpu_tfrecord.fs", fromlist=["fs"]).LocalFS(), plan)
        with pytest.raises(InjectedFault):
            fs_obj.listdir(dataset_dir)
        with pytest.raises(InjectedFault):
            list(fs_obj.walk_files(dataset_dir, lambda n: True))


class TestRetryDeadlineCap:
    def test_backoff_capped_to_remaining_budget(self):
        """The deadline caps the next backoff sleep instead of refusing
        the retry: the policy never sleeps past its deadline but spends
        ALL of the budget it has (injectable clock proves it)."""
        t = [0.0]
        sleeps = []

        def clock():
            return t[0]

        def sleep(s):
            sleeps.append(s)
            t[0] += s

        pol = RetryPolicy(
            max_retries=10, base_delay=4.0, max_delay=4.0, jitter=False,
            deadline=10.0, sleep=sleep, clock=clock,
        )
        start = pol.clock()
        assert pol.pause(1, start)  # sleeps 4.0 (remaining 10)
        assert pol.pause(2, start)  # sleeps 4.0 (remaining 6)
        assert pol.pause(3, start)  # capped: sleeps the remaining 2.0
        assert not pol.pause(4, start)  # budget exhausted: no retry
        assert sleeps == [4.0, 4.0, 2.0]
        assert t[0] == 10.0  # never slept past the deadline

    def test_no_deadline_unchanged(self):
        sleeps = []
        pol = RetryPolicy(
            max_retries=2, base_delay=1.0, max_delay=8.0, jitter=False,
            sleep=sleeps.append, clock=lambda: 0.0,
        )
        assert pol.pause(1, 0.0) and pol.pause(2, 0.0)
        assert not pol.pause(3, 0.0)
        assert sleeps == [1.0, 2.0]


class TestMetricsThreadSafety:
    def test_concurrent_increments_lose_nothing(self):
        m = Metrics()
        n_threads, per_thread = 8, 2000
        start = threading.Barrier(n_threads)

        def bump():
            start.wait()
            for _ in range(per_thread):
                m.count("read.stalls")
                m.add("decode", records=1, nbytes=2, seconds=0.0)

        threads = [threading.Thread(target=bump) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert m.counter("read.stalls") == n_threads * per_thread
        st = m.stage("decode")
        assert st.records == n_threads * per_thread
        assert st.bytes == 2 * n_threads * per_thread
        assert st.batches == n_threads * per_thread


class TestWriterHeartbeatLease:
    def test_job_meta_carries_heartbeat(self, tmp_path):
        from tpu_tfrecord.io.writer import DatasetWriter, _JOB_MARKER, _WriteJob

        out = str(tmp_path / "hb")
        w = DatasetWriter(out, SCHEMA, mode="overwrite")
        assert w._prepare_output()
        job = _WriteJob(w, task_id=0)
        with open(os.path.join(job.temp_root, _JOB_MARKER)) as fh:
            meta = json.load(fh)
        assert meta["heartbeat"] >= meta["created"]
        # a forced re-stamp advances the heartbeat
        job._last_beat = 0.0
        time.sleep(0.01)
        job.heartbeat()
        with open(os.path.join(job.temp_root, _JOB_MARKER)) as fh:
            meta2 = json.load(fh)
        assert meta2["heartbeat"] > meta["heartbeat"]
        job.abort()

    def test_sweep_reclaims_stale_lease_cross_host(self, tmp_path):
        """A staging dir stamped by ANOTHER host whose heartbeat lease
        expired is swept (remote-FS orphan recovery); a fresh-lease foreign
        dir is left alone (may be a live writer)."""
        from tpu_tfrecord import fs as tfs
        from tpu_tfrecord.io import paths as p
        from tpu_tfrecord.io.writer import _JOB_MARKER, sweep_orphan_jobs

        out = str(tmp_path / "sweep")
        root = os.path.join(out, p.TEMP_PREFIX)
        stale = os.path.join(root, "deadjob")
        fresh = os.path.join(root, "livejob")
        os.makedirs(stale)
        os.makedirs(fresh)
        now = time.time()
        for d, beat in ((stale, now - 7200), (fresh, now)):
            with open(os.path.join(d, _JOB_MARKER), "w") as fh:
                json.dump(
                    {"pid": 999999, "host": "some-other-host",
                     "created": beat, "heartbeat": beat},
                    fh,
                )
        removed = sweep_orphan_jobs(tfs.LocalFS(), out, lease_ttl=3600)
        assert removed == [stale]
        assert not os.path.isdir(stale)
        assert os.path.isdir(fresh)

    def test_sweep_still_uses_local_dead_pid(self, tmp_path):
        """The PR 2 same-host dead-pid check still works even with a fresh
        heartbeat (a crashed job's last stamp can be recent)."""
        import socket

        from tpu_tfrecord import fs as tfs
        from tpu_tfrecord.io import paths as p
        from tpu_tfrecord.io.writer import _JOB_MARKER, sweep_orphan_jobs

        out = str(tmp_path / "sweep2")
        dead = os.path.join(out, p.TEMP_PREFIX, "crashed")
        os.makedirs(dead)
        now = time.time()
        with open(os.path.join(dead, _JOB_MARKER), "w") as fh:
            json.dump(
                {"pid": 999999999, "host": socket.gethostname(),
                 "created": now, "heartbeat": now},
                fh,
            )
        removed = sweep_orphan_jobs(tfs.LocalFS(), out)
        assert removed == [dead]


class TestGuardHygiene:
    def test_real_open_error_does_not_leak_worker_threads(self, tmp_path):
        """A genuine open failure (not a stall) under open_deadline_ms
        returns the pooled worker: repeated failures (a flaky store under
        retries) must not grow the thread count."""
        from tpu_tfrecord.stall import StallGuard

        guard = StallGuard(open_deadline=2.0)
        missing = str(tmp_path / "nope" / "missing.tfrecord")

        def boom():
            return open(missing, "rb")

        with pytest.raises(FileNotFoundError):
            guard.call_open(boom, missing)
        before = threading.active_count()
        for _ in range(25):
            with pytest.raises(FileNotFoundError):
                guard.call_open(boom, missing)
        assert threading.active_count() <= before + 1

    def test_row_api_shard_guards_share_the_process_pool(self, dataset_dir):
        """The row API builds one guard per ShardReader; guards share the
        process-wide worker pool, so reading many shards/epochs with stall
        options set keeps the thread count bounded instead of stranding
        idle workers per discarded guard."""
        before = threading.active_count()
        for _ in range(6):
            table = tfio.read(
                dataset_dir, schema=SCHEMA,
                read_deadline_ms=5000, open_deadline_ms=5000,
            )
            assert len(table.column("id")) == len(ROWS)
        from tpu_tfrecord.stall import _WorkerPool

        assert threading.active_count() <= before + _WorkerPool._MAX_IDLE

    def test_live_local_pid_vetoes_stale_lease_sweep(self, tmp_path):
        """A same-host writer whose pid is provably ALIVE is never swept,
        even when its heartbeat lease looks stale (marker re-stamps are
        best-effort and can silently fail while the job keeps writing)."""
        import socket

        from tpu_tfrecord import fs as tfs
        from tpu_tfrecord.io import paths as p
        from tpu_tfrecord.io.writer import _JOB_MARKER, sweep_orphan_jobs

        out = str(tmp_path / "live")
        live = os.path.join(out, p.TEMP_PREFIX, "livejob")
        os.makedirs(live)
        with open(os.path.join(live, _JOB_MARKER), "w") as fh:
            json.dump(
                {"pid": os.getpid(), "host": socket.gethostname(),
                 "created": 0.0, "heartbeat": 0.0},  # ancient lease
                fh,
            )
        removed = sweep_orphan_jobs(tfs.LocalFS(), out, lease_ttl=1.0)
        assert removed == []
        assert os.path.isdir(live)


class TestOptionsPlumbing:
    def test_stall_options_parse_and_validate(self):
        from tpu_tfrecord.options import TFRecordOptions

        o = TFRecordOptions.from_map(
            read_deadline_ms=250, openDeadlineMs=100, hedge_after_ms=50,
            on_stall="skip_shard", watchdogTimeoutMs=1000,
        )
        assert o.read_deadline_ms == 250
        assert o.open_deadline_ms == 100
        assert o.hedge_after_ms == 50
        assert o.on_stall == "skip_shard"
        assert o.watchdog_timeout_ms == 1000
        with pytest.raises(ValueError):
            TFRecordOptions.from_map(on_stall="retry")
        with pytest.raises(ValueError):
            TFRecordOptions.from_map(read_deadline_ms=0)

    def test_guard_from_options_none_by_default(self):
        from tpu_tfrecord.options import TFRecordOptions
        from tpu_tfrecord.stall import guard_from_options

        assert guard_from_options(TFRecordOptions()) is None
        g = guard_from_options(TFRecordOptions.from_map(read_deadline_ms=500))
        assert g is not None and g.read_deadline == 0.5


class TestDoctorSimulate:
    def test_simulate_replays_plan_and_reports_ledger(self, dataset_dir, tmp_path):
        import subprocess
        import sys

        victim = _shard_names(dataset_dir)[0]
        plan_path = str(tmp_path / "plan.json")
        with open(plan_path, "w") as fh:
            json.dump(
                {
                    "seed": 1,
                    "rules": [
                        {"op": "read", "kind": "transient_error",
                         "path": victim, "times": 1}
                    ],
                },
                fh,
            )
        out = subprocess.run(
            [
                sys.executable,
                os.path.join(
                    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "tools",
                    "tfrecord_doctor.py",
                ),
                "--simulate",
                plan_path,
                os.path.join(dataset_dir, victim),
            ],
            capture_output=True,
            text=True,
        )
        lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
        ledger = [l for l in lines if l.get("event") == "fault"]
        errors = [l for l in lines if l.get("event") == "error"]
        assert ledger and ledger[0]["kind"] == "transient_error"
        assert errors  # the injected fault surfaced in the scan report
        assert out.returncode == 2

    def test_simulate_emits_ledger_even_when_expansion_fails(
        self, dataset_dir, tmp_path
    ):
        """A plan whose own listdir fault kills shard discovery still gets
        its ledger into the report — the ledger IS the repro artifact."""
        import subprocess
        import sys

        plan_path = str(tmp_path / "plan2.json")
        with open(plan_path, "w") as fh:
            json.dump(
                {"seed": 2,
                 "rules": [{"op": "listdir", "kind": "flaky_listing",
                            "times": None}]},
                fh,
            )
        out = subprocess.run(
            [
                sys.executable,
                os.path.join(
                    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "tools",
                    "tfrecord_doctor.py",
                ),
                "--simulate", plan_path, dataset_dir,
            ],
            capture_output=True,
            text=True,
        )
        lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
        assert out.returncode == 2
        assert any(l.get("event") == "fault" for l in lines)

    def test_unreadable_plan_is_a_clean_error(self, dataset_dir, tmp_path):
        """A missing/bad --simulate plan keeps the CLI's line-JSON + exit-2
        contract instead of a raw traceback."""
        import subprocess
        import sys

        doctor = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "tfrecord_doctor.py",
        )
        for bad in ["/nonexistent/plan.json"]:
            out = subprocess.run(
                [sys.executable, doctor, "--simulate", bad, dataset_dir],
                capture_output=True, text=True,
            )
            lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
            assert out.returncode == 2
            assert any(l.get("event") == "error" for l in lines)
        bad_json = str(tmp_path / "bad.json")
        with open(bad_json, "w") as fh:
            fh.write("{not json")
        out = subprocess.run(
            [sys.executable, doctor, "--simulate", bad_json, dataset_dir],
            capture_output=True, text=True,
        )
        assert out.returncode == 2
        assert not out.stderr.strip()  # no traceback leaked
