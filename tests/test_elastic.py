"""Elastic, multi-tenant data service suite (ISSUE 12): the shared
BoundedClimber guard rails, FleetScaler decisions (grow on
producer_bound, drain on consumer_bound/idle, refill below the floor,
pending-spawn accounting, whipsaw immunity under an injected clock),
dispatcher drain semantics (lease hand-back, route exclusion, clean
goodbye, journal replay of draining/tenant state), tenant-keyed
multi-tenant leasing (fingerprint sharing, isolation, the two-job
zero-ground-truth-reads pin — local via cache counters and remote via
the Range server's file-GET counter), the serve-status doctor's tenant +
scaler lines, and the chaos acceptance run: a subprocess fleet that
grows, gracefully drains, and loses a victim to SIGKILL mid-drain, all
mid-epoch, with byte-identical consumer output."""

import dataclasses
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from tpu_tfrecord import elastic, fleet, service, telemetry
from tpu_tfrecord.autotune import BoundedClimber
from tpu_tfrecord.columnar import batch_to_rows
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.io.writer import DatasetWriter
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.options import TFRecordOptions
from tpu_tfrecord.schema import (
    ArrayType,
    LongType,
    StringType,
    StructField,
    StructType,
)

DOCTOR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools",
    "tfrecord_doctor.py",
)

SCHEMA = StructType(
    [
        StructField("id", LongType(), nullable=False),
        StructField("s", StringType()),
        StructField("arr", ArrayType(LongType())),
    ]
)
ROWS = [
    [i, None if i % 7 == 0 else f"v{i}" * (i % 3 + 1), list(range(i % 5))]
    for i in range(180)
]
PER_SHARD = 30  # 6 shards


@pytest.fixture(autouse=True)
def _reset_metrics():
    METRICS.reset()
    yield


@pytest.fixture
def data_dir(sandbox):
    out = str(sandbox / "ds")
    DatasetWriter(
        out, SCHEMA, mode="overwrite", max_records_per_file=PER_SHARD
    ).write_rows(ROWS)
    return out


def make_ds(data_dir, batch_size=8, **kw):
    return TFRecordDataset(
        data_dir, batch_size=batch_size, schema=SCHEMA,
        drop_remainder=False, num_epochs=1, **kw,
    )


def collect(data_dir, batch_size=8, hook=None, **kw):
    ds = make_ds(data_dir, batch_size=batch_size, **kw)
    got = []
    with ds.batches() as it:
        for b in it:
            got.extend(batch_to_rows(b, ds.schema))
            if hook is not None:
                hook(got)
    return got


@pytest.fixture
def local_rows(data_dir):
    return collect(data_dir)


def start_worker(dispatcher, **kw):
    w = service.DecodeWorker(dispatcher.addr, **kw).start()
    assert w.wait_registered(10), "worker failed to register"
    return w


def stage_records(name):
    return METRICS.raw_totals().get(name, (0, 0, 0, 0.0))[0]


def wait_for(cond, timeout=20.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


class FakeAggregator:
    """The scaler's test seam: a FleetSnapshot-shaped verdict source whose
    verdict and consumer-liveness are script-controlled."""

    def __init__(self, verdict="balanced", running=True):
        self.verdict = verdict
        self.running = running

    def aggregate(self, roles=None):
        procs = []
        if self.running:
            procs = [fleet.ProcessSnapshot(
                path="fake", host="h", pid=1, role="trainer", trace_id=None,
                heartbeat=time.time(), interval_s=1.0, seq=1,
                gauges={telemetry.OCCUPANCY_GAUGE: 0.1},
            )]
        return fleet.FleetSnapshot(
            processes=procs, alive=procs, dead=[], counters={}, stages={},
            hists={}, verdict=self.verdict, occupancy=None,
        )


# ---------------------------------------------------------------------------
# BoundedClimber — the shared whipsaw guard
# ---------------------------------------------------------------------------


class TestBoundedClimber:
    def test_hysteresis_requires_consecutive_same_verdict(self):
        c = BoundedClimber(hysteresis=3, cooldown_s=0.0, clock=lambda: 0.0)
        assert c.observe("producer_bound") is None
        assert c.observe("producer_bound") is None
        assert c.observe("producer_bound") == "producer_bound"

    def test_non_actionable_resets_streak(self):
        c = BoundedClimber(hysteresis=2, cooldown_s=0.0, clock=lambda: 0.0)
        assert c.observe("producer_bound") is None
        assert c.observe("balanced") is None
        assert c.observe("producer_bound") is None  # streak restarted
        assert c.observe("producer_bound") == "producer_bound"

    def test_verdict_flip_restarts_streak(self):
        c = BoundedClimber(hysteresis=2, cooldown_s=0.0, clock=lambda: 0.0)
        assert c.observe("producer_bound") is None
        assert c.observe("consumer_bound") is None
        assert c.observe("consumer_bound") == "consumer_bound"

    def test_cooldown_blocks_until_elapsed(self):
        now = [0.0]
        c = BoundedClimber(hysteresis=1, cooldown_s=10.0, clock=lambda: now[0])
        assert c.observe("producer_bound") == "producer_bound"
        c.acted()
        now[0] = 5.0
        assert c.observe("producer_bound") is None
        assert c.cooldown_remaining() == pytest.approx(5.0)
        now[0] = 10.0
        assert c.observe("producer_bound") == "producer_bound"

    def test_custom_actionable_set(self):
        c = BoundedClimber(
            hysteresis=1, cooldown_s=0.0, clock=lambda: 0.0,
            actionable=("producer_bound", "consumer_bound", "idle"),
        )
        assert c.observe("idle") == "idle"


# ---------------------------------------------------------------------------
# FleetScaler decisions
# ---------------------------------------------------------------------------


@pytest.fixture
def dispatcher():
    d = service.ServiceDispatcher(lease_ttl_s=1.0).start()
    yield d
    d.stop()


class TestScalerDecisions:
    def _scaler(self, d, spawn, agg, **pol):
        defaults = dict(hysteresis=1, cooldown_s=0.0, min_workers=1,
                        max_workers=4)
        defaults.update(pol)
        return elastic.FleetScaler(
            d, spawn, aggregator=agg,
            policy=elastic.ScalerPolicy(**defaults),
        )

    def test_below_min_refills_immediately(self, dispatcher):
        spawned = []

        def spawn():
            spawned.append(start_worker(dispatcher, drain_grace_s=0.1))

        s = self._scaler(dispatcher, spawn, FakeAggregator("balanced"))
        decision = s.step()
        assert decision == {
            "tick": 1, "action": "scale_up", "reason": "below_min",
            "workers": 0, "target": 1,
        }
        assert len(spawned) == 1
        assert METRICS.counter("elastic.scale_ups") == 1
        # the registered spawn retires the pending slot; at the floor no
        # further refill happens
        assert s.step() is None
        for w in spawned:
            w.stop()

    def test_producer_bound_grows_consumer_bound_needs_headroom(
        self, dispatcher
    ):
        workers = [start_worker(dispatcher, drain_grace_s=0.1)]

        def spawn():
            workers.append(start_worker(dispatcher, drain_grace_s=0.1))

        agg = FakeAggregator("producer_bound")
        s = self._scaler(dispatcher, spawn, agg, hysteresis=2)
        assert s.step() is None  # streak 1 < hysteresis
        d2 = s.step()
        assert d2 and d2["action"] == "scale_up" and d2["reason"] == "producer_bound"
        wait_for(lambda: len(dispatcher.status()["workers"]) == 2,
                 msg="second worker registration")
        for w in workers:
            w.stop()

    def test_whipsaw_alternating_verdicts_never_move(self, dispatcher):
        workers = [start_worker(dispatcher, drain_grace_s=0.1)]
        agg = FakeAggregator()
        s = self._scaler(dispatcher, lambda: None, agg, hysteresis=2)
        for i in range(10):
            agg.verdict = ("producer_bound", "consumer_bound")[i % 2]
            assert s.step() is None, "a flapping verdict moved the fleet"
        assert METRICS.counter("elastic.scale_ups") == 0
        assert METRICS.counter("elastic.scale_downs") == 0
        workers[0].stop()

    def test_cooldown_blocks_consecutive_moves_injected_clock(
        self, dispatcher
    ):
        now = [0.0]
        spawned = []
        workers = [start_worker(dispatcher, drain_grace_s=0.1)]
        agg = FakeAggregator("producer_bound")
        s = elastic.FleetScaler(
            dispatcher, lambda: spawned.append(now[0]),
            aggregator=agg, clock=lambda: now[0],
            policy=elastic.ScalerPolicy(
                hysteresis=1, cooldown_s=100.0, min_workers=1, max_workers=8
            ),
        )
        assert s.step()["action"] == "scale_up"
        now[0] = 50.0
        assert s.step() is None, "cooldown did not hold"
        now[0] = 100.0
        assert s.step()["action"] == "scale_up"
        assert len(spawned) == 2
        workers[0].stop()

    def test_pending_spawns_count_against_ceiling(self, dispatcher):
        now = [0.0]
        spawns = []
        workers = [start_worker(dispatcher, drain_grace_s=0.1)]
        agg = FakeAggregator("producer_bound")
        s = elastic.FleetScaler(
            dispatcher, lambda: spawns.append(now[0]),  # never registers
            aggregator=agg, clock=lambda: now[0],
            policy=elastic.ScalerPolicy(
                hysteresis=1, cooldown_s=0.0, min_workers=1, max_workers=3,
                pending_timeout_s=30.0,
            ),
        )
        assert s.step()["action"] == "scale_up"   # effective 1 -> 2
        assert s.step()["action"] == "scale_up"   # effective 2 -> 3
        assert s.step() is None, "pending spawns did not count against max"
        assert len(spawns) == 2
        # timed-out pendings stop counting (the exec died): retry allowed
        now[0] = 31.0
        assert s.step()["action"] == "scale_up"
        workers[0].stop()

    def test_idle_drains_to_min_and_status_surfaces(self, dispatcher):
        w1 = start_worker(dispatcher, worker_id="w-a", drain_grace_s=0.05)
        w2 = start_worker(dispatcher, worker_id="w-b", drain_grace_s=0.05)
        agg = FakeAggregator(running=False)  # no running consumer: idle
        s = self._scaler(dispatcher, lambda: None, agg, min_workers=1)
        decision = s.step()
        assert decision and decision["action"] == "scale_down"
        assert decision["reason"] == "idle"
        assert decision["victim"] == "w-b"  # deterministic: sorted()[-1]
        assert METRICS.counter("elastic.scale_downs") == 1
        # the victim finishes (nothing in flight), says goodbye, exits
        assert w2.drained.wait(10), "victim never drained"
        wait_for(
            lambda: [x["worker_id"] for x in dispatcher.status()["workers"]]
            == ["w-a"],
            msg="goodbye to remove the victim",
        )
        assert METRICS.counter("elastic.drains") == 1
        # at the floor: no further drain
        assert s.step() is None
        st = dispatcher.status()
        assert st["scaler"]["workers"] == 1
        assert st["scaler"]["last_decision"]["victim"] == "w-b"
        assert st["scaler"]["scale_downs"] == 1
        w1.stop()

    def test_spawn_failure_is_counted_not_fatal(self, dispatcher):
        workers = [start_worker(dispatcher, drain_grace_s=0.1)]

        def spawn():
            raise RuntimeError("exec failed")

        s = self._scaler(dispatcher, spawn, FakeAggregator("producer_bound"))
        assert s.step() is None
        assert METRICS.counter("elastic.spawn_errors") == 1
        assert METRICS.counter("elastic.scale_ups") == 0
        workers[0].stop()

    def test_unreadable_spool_never_drains_a_loaded_fleet(self, dispatcher):
        # an aggregator that RAISES (EACCES, EIO — not merely absent)
        # must be non-actionable: blindness is not idleness
        workers = [start_worker(dispatcher, worker_id=f"w-{i}",
                                drain_grace_s=0.1) for i in range(2)]

        class Broken:
            def aggregate(self, roles=None):
                raise PermissionError("spool dir unreadable")

        s = self._scaler(dispatcher, lambda: None, Broken())
        for _ in range(5):
            assert s.step() is None, "unreadable spool moved the fleet"
        assert METRICS.counter("elastic.scale_downs") == 0
        assert METRICS.counter("elastic.verdict_errors") == 5
        # a MISSING spool dir (no consumer ever spooled) IS idle: drain
        s2 = elastic.FleetScaler(
            dispatcher, lambda: None, spool_dir=str(dispatcher.addr) + "-none",
            policy=elastic.ScalerPolicy(hysteresis=1, cooldown_s=0.0,
                                        min_workers=1, max_workers=4),
        )
        s2.aggregator.spool_dir = "/nonexistent/tfr-spool"
        decision = s2.step()
        assert decision and decision["reason"] == "idle"
        for w in workers:
            w.stop()

    def test_scaler_thread_refills_from_zero(self, dispatcher):
        spawned = []

        def spawn():
            spawned.append(start_worker(dispatcher, drain_grace_s=0.1))

        s = elastic.FleetScaler(
            dispatcher, spawn, aggregator=FakeAggregator("balanced"),
            interval_s=0.05,
            policy=elastic.ScalerPolicy(min_workers=1, max_workers=2),
        ).start()
        try:
            wait_for(lambda: len(spawned) == 1, msg="thread refill")
        finally:
            s.stop()
            for w in spawned:
                w.stop()

    def test_roles_scope_reaches_the_aggregator(self, dispatcher):
        workers = [start_worker(dispatcher, drain_grace_s=0.1)]
        seen = []
        inner = FakeAggregator("balanced")

        class Agg:
            def aggregate(self, roles=None):
                seen.append(roles)
                return inner.aggregate()

        s = elastic.FleetScaler(
            dispatcher, lambda: None, aggregator=Agg(), roles=["trainer"],
            policy=elastic.ScalerPolicy(min_workers=1, max_workers=4),
        )
        s.step()
        assert seen == [["trainer"]]
        workers[0].stop()

    def test_ctor_needs_exactly_one_verdict_source(self, dispatcher):
        with pytest.raises(ValueError):
            elastic.FleetScaler(dispatcher, lambda: None)
        with pytest.raises(ValueError):
            elastic.FleetScaler(
                dispatcher, lambda: None, spool_dir="/tmp/x",
                aggregator=FakeAggregator(),
            )

    def test_policy_validation(self):
        with pytest.raises(ValueError):
            elastic.ScalerPolicy(min_workers=0)
        with pytest.raises(ValueError):
            elastic.ScalerPolicy(min_workers=3, max_workers=2)
        with pytest.raises(ValueError):
            elastic.ScalerPolicy(hysteresis=0)


# ---------------------------------------------------------------------------
# Dispatcher drain semantics
# ---------------------------------------------------------------------------


def _route(d, shard_index, path, tenant="t0", exclude=()):
    return d._handle({
        "op": "route", "proto": service.PROTO_VERSION, "job": "j0",
        "tenant": tenant, "consumer": "c0", "path": path,
        "shard_index": shard_index, "exclude": list(exclude),
    })


class TestDrain:
    def test_drain_releases_leases_and_routes_around(self, dispatcher):
        w1 = start_worker(dispatcher, worker_id="w-a", drain_grace_s=5.0)
        w2 = start_worker(dispatcher, worker_id="w-b", drain_grace_s=5.0)
        # lease shard 0 onto whoever owns it
        first = _route(dispatcher, 0, "s0")
        owner = first["worker_id"]
        assert dispatcher.drain(owner) is True
        assert dispatcher.drain(owner) is False  # already draining
        assert dispatcher.drain("nope") is False
        assert METRICS.counter("elastic.drained_leases") == 1
        # the lease was handed back; re-route goes to the survivor and is
        # planned drift, never a lease_reassignment
        second = _route(dispatcher, 0, "s0")
        assert second["worker_id"] != owner
        assert dispatcher.status()["lease_reassignments"] == 0
        assert dispatcher.status()["draining"] == [owner]
        w1.stop()
        w2.stop()

    def test_all_draining_still_routes(self, dispatcher):
        w = start_worker(dispatcher, worker_id="w-a", drain_grace_s=30.0)
        assert dispatcher.drain("w-a")
        # availability beats drain purity when nothing else is alive
        reply = _route(dispatcher, 0, "s0")
        assert reply.get("ok") and reply["worker_id"] == "w-a"
        w.stop()

    def test_goodbye_unknown_worker_is_benign(self, dispatcher):
        reply = dispatcher._handle({
            "op": "goodbye", "proto": service.PROTO_VERSION,
            "worker_id": "ghost",
        })
        assert reply == {"ok": True, "known": False}
        assert METRICS.counter("elastic.drains") == 0

    def test_reregister_clears_drain_mark(self, dispatcher):
        w = start_worker(dispatcher, worker_id="w-a", drain_grace_s=30.0)
        assert dispatcher.drain("w-a")
        dispatcher._handle({
            "op": "register_worker", "proto": service.PROTO_VERSION,
            "worker_id": "w-a", "addr": w.addr, "pid": 1,
        })
        assert dispatcher.status()["draining"] == []
        w.stop()

    def test_journal_replay_restores_draining_and_tenants(self, tmp_path):
        journal = str(tmp_path / "journal.json")
        d = service.ServiceDispatcher(journal=journal, lease_ttl_s=5.0)
        try:
            for wid in ("w-a", "w-b"):
                d._handle({
                    "op": "register_worker", "proto": service.PROTO_VERSION,
                    "worker_id": wid, "addr": "127.0.0.1:1", "pid": 1,
                })
            _route(d, 0, "s0", tenant="t-shared")
            d._handle({
                "op": "shard_done", "proto": service.PROTO_VERSION,
                "job": "j0", "tenant": "t-shared", "consumer": "c0",
                "path": "s0", "worker_id": "w-a", "cached": True,
            })
            assert d.drain("w-b")
        finally:
            d.stop()
        d2 = service.ServiceDispatcher(journal=journal, lease_ttl_s=5.0)
        try:
            st = d2.status()
            assert st["draining"] == ["w-b"]
            t = st["tenants"]["t-shared"]
            assert t["consumers"] == 1 and t["jobs"] == 1
            assert t["shards_done"] == 1
            assert t["shared_cache_hits"] == 1 and t["completions"] == 1
        finally:
            d2.stop()


# ---------------------------------------------------------------------------
# Multi-tenant leasing + the shared warm cache
# ---------------------------------------------------------------------------


class TestMultiTenant:
    def test_tenant_digest_ignores_consumption_shape(self, data_dir):
        a = service.tenant_digest(make_ds(data_dir, batch_size=8))
        b = service.tenant_digest(make_ds(data_dir, batch_size=16, prefetch=7))
        c = service.tenant_digest(make_ds(data_dir, columns=["id"]))
        assert a == b
        assert a != c

    def test_same_fingerprint_shares_one_lease_table(
        self, dispatcher, data_dir, local_rows
    ):
        workers = [start_worker(dispatcher) for _ in range(2)]
        try:
            got8 = collect(data_dir, batch_size=8, service=dispatcher.addr,
                           service_deadline_ms=15000)
            got16 = collect(data_dir, batch_size=16, service=dispatcher.addr,
                            service_deadline_ms=15000)
            assert got8 == local_rows and got16 == local_rows
            st = dispatcher.status()
            assert len(st["tenants"]) == 1, st["tenants"]
            (tenant_info,) = st["tenants"].values()
            assert tenant_info["consumers"] == 2
            assert tenant_info["jobs"] == 2
            # the done-set is shared: 6 shards paid once FLEET-WIDE even
            # though two jobs each completed them
            assert tenant_info["shards_done"] == 6
            assert tenant_info["completions"] == 12
            assert st["shards_done"] == 6
            assert METRICS.counter("service.tenants") == 1
        finally:
            for w in workers:
                w.stop()

    def test_different_fingerprints_isolated(
        self, dispatcher, data_dir
    ):
        workers = [start_worker(dispatcher)]
        try:
            collect(data_dir, batch_size=8, service=dispatcher.addr,
                    service_deadline_ms=15000)
            collect(data_dir, batch_size=8, columns=["id"],
                    service=dispatcher.addr, service_deadline_ms=15000)
            st = dispatcher.status()
            assert len(st["tenants"]) == 2, st["tenants"]
            assert st["shards_done"] == 12  # nothing shared across tenants
            assert METRICS.counter("service.tenants") == 2
        finally:
            for w in workers:
                w.stop()

    def test_job2_zero_ground_truth_reads_local(
        self, dispatcher, data_dir, local_rows, tmp_path
    ):
        opts = TFRecordOptions.from_map(
            cache="auto", cache_dir=str(tmp_path / "cache")
        )
        w = service.DecodeWorker(dispatcher.addr, options=opts).start()
        assert w.wait_registered(10)
        try:
            got1 = collect(data_dir, batch_size=8, service=dispatcher.addr,
                           service_deadline_ms=15000)
            assert got1 == local_rows
            misses_before = METRICS.counter("cache.misses")
            hits_before = METRICS.counter("cache.hits")
            decode_before = stage_records("decode")
            got2 = collect(data_dir, batch_size=16, service=dispatcher.addr,
                           service_deadline_ms=15000)
            assert got2 == local_rows
            # job 2 is served ENTIRELY from the warm columnar cache: zero
            # ground-truth reads, pinned three ways
            assert METRICS.counter("cache.misses") == misses_before
            assert METRICS.counter("cache.hits") - hits_before == 6
            assert stage_records("decode") == decode_before
            assert METRICS.counter("service.cache_served") == 6
            assert METRICS.counter("service.shared_cache_hits") == 6
            (tenant_info,) = dispatcher.status()["tenants"].values()
            assert tenant_info["shared_cache_hits"] == 6
        finally:
            w.stop()

    def test_job2_zero_file_gets_remote(
        self, dispatcher, data_dir, local_rows, tmp_path, sandbox
    ):
        from tpu_tfrecord import httpfs

        opts = TFRecordOptions.from_map(
            cache="auto", cache_dir=str(tmp_path / "cache")
        )
        w = service.DecodeWorker(dispatcher.addr, options=opts).start()
        assert w.wait_registered(10)
        try:
            with httpfs.serve_directory(str(sandbox)) as srv:
                url = srv.url_for("ds")
                got1 = collect(url, batch_size=8, service=dispatcher.addr,
                               service_deadline_ms=15000)
                assert got1 == local_rows
                gets_after_job1 = srv.file_get_count
                assert gets_after_job1 > 0  # job 1 paid the link once
                got2 = collect(url, batch_size=16, service=dispatcher.addr,
                               service_deadline_ms=15000)
                assert got2 == local_rows
                # the PR 9 pin, now FLEET-wide: job 2 issues ZERO
                # ground-truth file GETs — the warm cache absorbed the
                # whole second job
                assert srv.file_get_count == gets_after_job1
                assert METRICS.counter("service.shared_cache_hits") == 6
        finally:
            w.stop()


# ---------------------------------------------------------------------------
# Aggregator role scoping (the scaler's verdict filter)
# ---------------------------------------------------------------------------


class TestAggregatorRoles:
    def test_roles_filter(self, tmp_path):
        spool = str(tmp_path / "spool")
        for pid, role in ((111, "trainer"), (222, "decode_worker")):
            ctx = dataclasses.replace(
                telemetry.TraceContext.new(role=role), pid=pid
            )
            sp = fleet.TelemetrySpool(spool, context=ctx)
            sp.tick()
        agg = fleet.TelemetryAggregator(spool, stale_after_s=3600.0)
        assert {p.role for p in agg.processes()} == {"trainer", "decode_worker"}
        only = agg.processes(roles=["trainer"])
        assert [p.role for p in only] == ["trainer"]
        snap = agg.aggregate(roles=["trainer"])
        assert [p.role for p in snap.processes] == ["trainer"]


# ---------------------------------------------------------------------------
# Options plumbing
# ---------------------------------------------------------------------------


class TestOptionsElastic:
    def test_round_trip_both_spellings(self):
        o = TFRecordOptions.from_map(
            elastic_min_workers=2, elastic_max_workers=6,
            elastic_interval_s=0.5,
        )
        assert (o.elastic_min_workers, o.elastic_max_workers,
                o.elastic_interval_s) == (2, 6, 0.5)
        o = TFRecordOptions.from_map(
            elasticMinWorkers="2", elasticMaxWorkers="6",
            elasticIntervalS="0.5",
        )
        assert (o.elastic_min_workers, o.elastic_max_workers,
                o.elastic_interval_s) == (2, 6, 0.5)

    def test_defaults(self):
        o = TFRecordOptions()
        assert o.elastic_min_workers == 1
        assert o.elastic_max_workers is None
        assert o.elastic_interval_s is None

    def test_validation_loud(self):
        with pytest.raises(ValueError):
            TFRecordOptions.from_map(elastic_min_workers=0)
        with pytest.raises(ValueError):
            TFRecordOptions.from_map(
                elastic_min_workers=4, elastic_max_workers=2
            )
        with pytest.raises(ValueError):
            TFRecordOptions.from_map(elastic_interval_s=0)


# ---------------------------------------------------------------------------
# serve-status doctor: tenant + scaler lines
# ---------------------------------------------------------------------------


class TestServeStatusElastic:
    def test_tenant_and_scaler_lines(self, dispatcher, data_dir, local_rows):
        w = start_worker(dispatcher, worker_id="w-a")
        s = elastic.FleetScaler(
            dispatcher, lambda: None, aggregator=FakeAggregator(),
            policy=elastic.ScalerPolicy(min_workers=1, max_workers=4),
        )
        s.step()
        try:
            got = collect(data_dir, service=dispatcher.addr,
                          service_deadline_ms=15000)
            assert got == local_rows
            doc = subprocess.run(
                [sys.executable, DOCTOR, "serve-status", dispatcher.addr],
                capture_output=True, text=True,
            )
            assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
            lines = [json.loads(l) for l in doc.stdout.splitlines() if l.strip()]
            tenants = [l for l in lines if l.get("event") == "tenant"]
            assert len(tenants) == 1
            assert tenants[0]["consumers"] == 1
            assert tenants[0]["shards_done"] == 6
            assert tenants[0]["cache_hit_ratio"] == 0.0  # no cache configured
            (scaler_line,) = [l for l in lines if l.get("event") == "scaler"]
            assert scaler_line["workers"] == 1
            assert scaler_line["min_workers"] == 1
            (summary,) = [l for l in lines if l.get("event") == "service"]
            assert summary["tenants"] == 1
            assert summary["draining"] == []
        finally:
            w.stop()


# ---------------------------------------------------------------------------
# Worker CLI: --fault-plan + --drain-grace on a real subprocess
# ---------------------------------------------------------------------------


class TestWorkerCli:
    def test_subprocess_worker_with_fault_plan_serves_and_drains(
        self, dispatcher, data_dir, local_rows, tmp_path
    ):
        plan_path = str(tmp_path / "plan.json")
        with open(plan_path, "w") as fh:
            json.dump({
                "seed": 3,
                "rules": [{"op": "read", "kind": "stall", "path": "part-",
                           "times": 2, "stall_ms": 5}],
            }, fh)
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        p = subprocess.Popen(
            [sys.executable, "-m", "tpu_tfrecord.service", "worker",
             "--dispatcher", dispatcher.addr, "--worker-id", "w-cli",
             "--drain-grace", "0.1", "--fault-plan", plan_path],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            env=env,
        )
        try:
            ready = json.loads(p.stdout.readline())
            assert ready["worker_id"] == "w-cli"
            wait_for(
                lambda: any(w["alive"]
                            for w in dispatcher.status()["workers"]),
                msg="subprocess worker registration",
            )
            got = collect(data_dir, service=dispatcher.addr,
                          service_deadline_ms=15000)
            assert got == local_rows
            # drain it: the process must exit cleanly on its own
            assert dispatcher.drain("w-cli")
            assert p.wait(timeout=20) == 0
            wait_for(lambda: dispatcher.status()["workers"] == [],
                     msg="goodbye from the CLI worker")
            assert METRICS.counter("elastic.drains") == 1
        finally:
            if p.poll() is None:
                p.kill()
                p.wait()


# ---------------------------------------------------------------------------
# Chaos acceptance: grow + graceful drain + SIGKILL mid-drain, mid-epoch
# ---------------------------------------------------------------------------


class TestResizeChaosAcceptance:
    def test_fleet_resize_mid_epoch_byte_identical(
        self, data_dir, local_rows
    ):
        d = service.ServiceDispatcher(lease_ttl_s=3.0).start()
        spawner = elastic.SubprocessSpawner(
            d.addr, ("--drain-grace", "0.2"),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        agg = FakeAggregator("balanced")
        scaler = elastic.FleetScaler(
            d, spawner, aggregator=agg,
            policy=elastic.ScalerPolicy(
                hysteresis=1, cooldown_s=0.0, min_workers=1, max_workers=3
            ),
        )
        try:
            spawner()
            spawner()
            wait_for(lambda: d.status()["alive"] >= 2, timeout=60,
                     msg="initial fleet registration")
            phases = {"grown": False, "drained": False, "killed": None}

            def hook(rows):
                if len(rows) >= 16 and not phases["grown"]:
                    # GROW mid-epoch: the scaler spawns worker 3
                    agg.verdict = "producer_bound"
                    assert scaler.step()["action"] == "scale_up"
                    wait_for(lambda: d.status()["alive"] >= 3, timeout=60,
                             msg="scaled-up worker registration")
                    phases["grown"] = True
                elif len(rows) >= 80 and not phases["drained"]:
                    # graceful DRAIN mid-epoch (no waiting here: the
                    # victim may be serving us right now, and its drain
                    # completes only once this very epoch stops needing
                    # it — asserted after the epoch)
                    agg.verdict = "consumer_bound"
                    decision = scaler.step()
                    assert decision["action"] == "scale_down"
                    phases["drained"] = decision["victim"]
                elif len(rows) >= 120 and phases["killed"] is None:
                    # second drain decision, victim SIGKILLed MID-DRAIN:
                    # it never gets to say goodbye
                    agg.verdict = "consumer_bound"
                    decision = scaler.step()
                    assert decision["action"] == "scale_down"
                    victim = decision["victim"]
                    pid = next(
                        w["pid"] for w in d.status()["workers"]
                        if w["worker_id"] == victim
                    )
                    os.kill(pid, signal.SIGKILL)
                    phases["killed"] = victim

            got = collect(data_dir, service=d.addr,
                          service_deadline_ms=15000, hook=hook)
            assert got == local_rows, "resize broke byte-identity"
            assert phases["grown"] and phases["drained"] and phases["killed"]
            assert phases["drained"] != phases["killed"]
            # exactly the expected elastic counters
            assert METRICS.counter("elastic.scale_ups") == 1
            assert METRICS.counter("elastic.scale_downs") == 2
            assert METRICS.counter("service.fallbacks") == 0
            # the graceful victim says goodbye once its streams finish...
            wait_for(
                lambda: phases["drained"] not in
                [w["worker_id"] for w in d.status()["workers"]],
                timeout=30, msg="graceful victim goodbye",
            )
            assert METRICS.counter("elastic.drains") == 1
            # ...the SIGKILLed one never does: it goes stale by heartbeat
            wait_for(
                lambda: any(
                    w["worker_id"] == phases["killed"] and not w["alive"]
                    for w in d.status()["workers"]
                ),
                timeout=30, msg="killed victim heartbeat expiry",
            )
            st = d.status()
            assert phases["killed"] in st["draining"]
        finally:
            spawner.reap()
            d.stop()
