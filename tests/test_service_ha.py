"""HA data service suite (ISSUE 17): the static partition map
(rendezvous ownership, spec grammar, minimal remap on growth), the v2
line-oriented dispatcher journal (durable appends over a snapshot line,
replay-to-newest-consistent-prefix under every truncation shape, pinned
with the ``torn_write`` fault kind), zombie fencing via the journal
inode (``FencedWriteError`` before any stale byte lands) and
self-demotion after consecutive journal failures, warm-standby tailing
+ promotion (generation bump, address adoption), partitioned routing
end to end, the federated FleetScaler census (dedupe across partitions,
whipsaw guard on an unreadable partition, ``DispatcherHandle`` RPCs),
the federated serve-status doctor, and THE acceptance scenario: the
primary dispatcher SIGKILLed mid-epoch, the standby taking over, and
the consumers' epochs finishing byte-identical with zero fallbacks."""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from tpu_tfrecord import checkpoint, elastic, fleet, service, telemetry
from tpu_tfrecord.columnar import batch_to_rows
from tpu_tfrecord.faults import FaultPlan, FaultRule, install_chaos
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.io.writer import DatasetWriter
from tpu_tfrecord.metrics import METRICS
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


def make_ds(data_dir, **kw):
    return TFRecordDataset(
        data_dir, batch_size=8, schema=SCHEMA, drop_remainder=False,
        num_epochs=1, **kw,
    )


def collect(data_dir, **kw):
    ds = make_ds(data_dir, **kw)
    got = []
    with ds.batches() as it:
        for b in it:
            got.extend(batch_to_rows(b, ds.schema))
    return got


@pytest.fixture
def local_rows(data_dir):
    return collect(data_dir)


def wait_for(cond, timeout=20.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


def _register(d, wid):
    r = d._handle({"op": "register_worker", "worker_id": wid,
                   "addr": f"h:{wid}", "pid": 0})
    return r


def _route(d, shard_index, exclude=()):
    return d._handle(
        {
            "op": "route",
            "job": "j",
            "path": f"/data/shard-{shard_index}",
            "shard_index": shard_index,
            "exclude": list(exclude),
        }
    )


def _journal_records(path):
    with open(path, "rb") as fh:
        data = fh.read()
    return [json.loads(ln) for ln in data.split(b"\n") if ln.strip()]


class FakeAggregator:
    """Script-controlled verdict source (the scaler's test seam)."""

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
# PartitionMap: spec grammar + rendezvous ownership
# ---------------------------------------------------------------------------


class TestPartitionMap:
    def test_spec_forms_and_roundtrip(self):
        pm = service.PartitionMap.parse("127.0.0.1:70")
        assert pm.k == 1 and pm.addrs(0) == ["127.0.0.1:70"]
        pm = service.PartitionMap.parse("h:1|h:2, h:3|h:4")
        assert pm.k == 2
        # primary first, then the standby — the client's rotation order
        assert pm.addrs(0) == ["h:1", "h:2"]
        assert pm.addrs(1) == ["h:3", "h:4"]
        assert pm.to_spec() == "h:1|h:2,h:3|h:4"
        assert service.PartitionMap.parse(pm.to_spec()).partitions == pm.partitions

    def test_file_spec(self, tmp_path):
        p = tmp_path / "map.json"
        p.write_text(json.dumps(
            {"partitions": [["h:1", "h:2"], ["h:3"]]}
        ))
        pm = service.PartitionMap.parse(f"@{p}")
        assert pm.k == 2 and pm.addrs(0) == ["h:1", "h:2"]

    def test_garbage_specs_are_loud(self, tmp_path):
        for spec in ("nonsense", "", "h:1,|", f"@{tmp_path}/absent.json"):
            with pytest.raises((OSError, ValueError)):
                service.PartitionMap.parse(spec)

    def test_rendezvous_is_deterministic_and_covers_every_partition(self):
        pm = service.PartitionMap.parse("h:1,h:2,h:3")
        tenants = [f"tenant-{i:04x}" for i in range(300)]
        owners = [pm.partition_for(t) for t in tenants]
        assert owners == [pm.partition_for(t) for t in tenants]
        assert set(owners) == {0, 1, 2}

    def test_growing_k_remaps_only_a_minority(self):
        """The rendezvous property the map exists for: adding partition
        N+1 steals ~1/(N+1) of the tenants and moves NOTHING else."""
        pm2 = service.PartitionMap([["h:1"], ["h:2"]])
        pm3 = service.PartitionMap([["h:1"], ["h:2"], ["h:3"]])
        tenants = [f"tenant-{i:04x}" for i in range(300)]
        moved = 0
        for t in tenants:
            before, after = pm2.partition_for(t), pm3.partition_for(t)
            if before != after:
                moved += 1
                # a moved tenant moved TO the new partition, never
                # between survivors
                assert after == 2
        assert 0 < moved < 150  # ~100 expected; never a majority


# ---------------------------------------------------------------------------
# Journal v2: snapshot + durable delta lines
# ---------------------------------------------------------------------------


class TestJournalV2:
    def test_snapshot_plus_deltas_roundtrip(self, tmp_path):
        j = str(tmp_path / "j.json")
        d = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0)
        try:
            _register(d, "w0")
            _register(d, "w1")
            assert _route(d, 0)["ok"]
            d._handle({"op": "shard_done", "job": "j",
                       "path": "/data/shard-0", "worker_id": "w0"})
        finally:
            d.stop()
        recs = _journal_records(j)
        assert recs[0]["kind"] == "snapshot"
        assert recs[0]["version"] == service.JOURNAL_VERSION
        assert recs[0]["generation"] == 0
        assert [r["kind"] for r in recs[1:]] == [
            "register", "register", "lease", "done",
        ]
        d2 = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0)
        try:
            st = d2.status()
            assert {w["worker_id"] for w in st["workers"]} == {"w0", "w1"}
            assert st["shards_done"] == 1 and st["active_leases"] == 0
        finally:
            d2.stop()

    def test_v1_journal_upgraded_in_place(self, tmp_path):
        j = str(tmp_path / "j.json")
        with open(j, "wb") as fh:
            fh.write(json.dumps({
                "workers": {"w0": {"addr": "h:w0", "pid": 7}},
                "leases": {"t/data-0": "w0"},
                "done": {},
                "reassignments": 3,
            }).encode())
        d = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0)
        try:
            st = d.status()
            assert [w["worker_id"] for w in st["workers"]] == ["w0"]
            assert st["active_leases"] == 1
            assert st["lease_reassignments"] == 3
        finally:
            d.stop()
        # birth compaction rewrote the legacy object as a v2 snapshot line
        recs = _journal_records(j)
        assert len(recs) == 1
        assert recs[0]["kind"] == "snapshot"
        assert recs[0]["version"] == service.JOURNAL_VERSION

    def test_compaction_bounds_the_delta_tail(self, tmp_path, monkeypatch):
        monkeypatch.setattr(service, "JOURNAL_COMPACT_EVERY", 4)
        j = str(tmp_path / "j.json")
        d = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0)
        try:
            for i in range(10):
                _register(d, f"w{i}")
            recs = _journal_records(j)
            # 10 appends with compaction every 4: the file is snapshot +
            # at most 3 trailing deltas, never the raw mutation history
            assert recs[0]["kind"] == "snapshot"
            assert len(recs) <= 4
            assert len(recs[0]["workers"]) >= 7
        finally:
            d.stop()


# ---------------------------------------------------------------------------
# Truncation replay: newest consistent prefix (satellite 3)
# ---------------------------------------------------------------------------


class TestJournalTruncation:
    def test_empty_journal_is_a_fresh_start(self, tmp_path):
        j = str(tmp_path / "j.json")
        open(j, "wb").close()
        d = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0)
        try:
            assert d.status()["workers"] == []
            assert d.accepting
        finally:
            d.stop()

    def test_torn_final_line_drops_only_the_tail(self, tmp_path):
        j = str(tmp_path / "j.json")
        d = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0)
        try:
            _register(d, "w0")
            _register(d, "w1")
        finally:
            d.stop()
        with open(j, "ab") as fh:
            fh.write(b'{"kind": "register", "worker_id": "w')  # no newline
        d2 = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0)
        try:
            st = d2.status()
            assert {w["worker_id"] for w in st["workers"]} == {"w0", "w1"}
            assert d2.accepting
        finally:
            d2.stop()

    def test_mid_record_tear_keeps_the_prefix_before_it(self, tmp_path):
        """A tear in the MIDDLE of the file (a record that is complete as
        a line but not as JSON): everything before it replays, everything
        after it is ignored — records past a tear were written by a
        writer that already knew its append failed."""
        j = str(tmp_path / "j.json")
        snap = {"kind": "snapshot", "version": 2, "generation": 0,
                "workers": {}, "leases": {}, "done": {}}
        with open(j, "wb") as fh:
            fh.write(json.dumps(snap).encode() + b"\n")
            fh.write(b'{"kind": "register", "worker_id": "w0", '
                     b'"addr": "h:0", "pid": 0}\n')
            fh.write(b'{"kind": "regis\n')  # torn, newline landed
            fh.write(b'{"kind": "register", "worker_id": "w1", '
                     b'"addr": "h:1", "pid": 0}\n')
        d = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0)
        try:
            st = d.status()
            assert [w["worker_id"] for w in st["workers"]] == ["w0"]
        finally:
            d.stop()

    def test_parse_journal_units(self):
        parse = service.ServiceDispatcher._parse_journal
        assert parse(b"") == []
        assert parse(b"   \n") == []
        snap = json.dumps({"kind": "snapshot", "generation": 1}).encode()
        assert parse(snap + b"\n")[0]["generation"] == 1
        # torn tail after the last newline is dropped by construction
        assert len(parse(snap + b"\n" + b'{"kind": "reg')) == 1
        # a complete line WITHOUT a "kind" ends the consistent prefix
        assert len(parse(snap + b"\n" + b'{"nope": 1}\n' + snap + b"\n")) == 1

    def test_torn_write_fault_kind_pins_crash_mid_append(self, tmp_path):
        """The ISSUE-17 pin: tear a journal append at a byte cap with the
        ``torn_write`` fault kind (the exact bytes a host crash
        mid-append leaves behind), then replay — the torn record is
        absorbed, the prefix survives, and the failure was counted."""
        j = str(tmp_path / "j.json")
        plan = FaultPlan(
            [FaultRule(op="journal", kind="torn_write", cap_bytes=12,
                       ordinal=1)]  # ordinal 0 is the birth compaction
        )
        with install_chaos(plan):
            d = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0)
            try:
                _register(d, "w0")  # this append tears
            finally:
                d.stop()
        fired = [e for e in plan.ledger if e["kind"] == "torn_write"]
        assert len(fired) == 1 and fired[0]["cap_bytes"] == 12
        assert METRICS.counter("service.journal_errors") == 1
        with open(j, "rb") as fh:
            data = fh.read()
        # 12 record bytes landed after the snapshot's newline — a torn
        # tail, not a parseable record
        tail = data.split(b"\n")[-1]
        assert len(tail) == 12
        d2 = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0)
        try:
            assert d2.status()["workers"] == []  # torn register absorbed
            assert d2.accepting
        finally:
            d2.stop()


# ---------------------------------------------------------------------------
# Fencing + self-demotion (satellite 2)
# ---------------------------------------------------------------------------


class TestFencingAndDemotion:
    def test_durable_append_fences_before_any_byte_lands(self, tmp_path):
        p = str(tmp_path / "log")
        checkpoint.durable_write(p, b"a\n")
        ino = os.stat(p).st_ino
        assert checkpoint.durable_append(p, b"b\n", expect_ino=ino) == ino
        checkpoint.durable_write(p, b"replaced\n")  # new inode
        with pytest.raises(checkpoint.FencedWriteError):
            checkpoint.durable_append(p, b"stale\n", expect_ino=ino)
        with open(p, "rb") as fh:
            assert fh.read() == b"replaced\n"

    def test_resurrected_zombie_is_fenced_and_demoted(self, tmp_path):
        """The zero-duplicate-grants pin: after a standby promotes, the
        old primary's very next journaled mutation hits the inode fence,
        lands zero bytes, and demotes it — every later lease op is
        rejected with ``not_primary``."""
        j = str(tmp_path / "j.json")
        a = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0)
        b = None
        try:
            _register(a, "w0")
            assert _route(a, 0)["worker_id"] == "w0"
            b = service.ServiceDispatcher(
                journal=j, standby_of=a.addr, lease_ttl_s=5.0,
                takeover_addr=False,
            )
            b.promote()
            assert b.accepting and b.generation == 1 and b.failed_over
            # the zombie still believes it is primary; one mutation is
            # all it gets
            assert a.accepting
            _register(a, "w9")
            assert METRICS.counter("service.fenced_writes") == 1
            assert METRICS.counter("service.demotions") == 1
            assert not a.accepting
            r = _route(a, 1)
            assert r["error"] == "not_primary" and r["demoted"] is True
            # not a single stale byte interleaved into the successor's
            # journal: it is exactly the generation-1 snapshot
            recs = _journal_records(j)
            assert recs[0]["generation"] == 1
            assert all("w9" not in json.dumps(r) for r in recs)
            assert METRICS.counter("service.not_primary_rejects") >= 1
        finally:
            a.stop()
            if b is not None:
                b.stop()

    def test_demotes_after_n_consecutive_journal_failures(self, tmp_path):
        j = str(tmp_path / "j.json")
        plan = FaultPlan(
            [FaultRule(op="journal", kind="permanent_error", ordinal=1,
                       times=None)]
        )
        with install_chaos(plan):
            d = service.ServiceDispatcher(
                journal=j, lease_ttl_s=5.0, demote_after=3
            )
            try:
                _register(d, "w0")
                _register(d, "w1")
                assert d.accepting  # 2 failures < demote_after
                _register(d, "w2")
                assert not d.accepting
                assert METRICS.counter("service.demotions") == 1
                assert METRICS.counter("service.journal_errors") == 3
                r = _route(d, 0)
                assert r["error"] == "not_primary" and r["demoted"] is True
                # and it tells pingers honestly — takeover bait for a
                # standby that would recover journaled (consistent) state
                ping = d._handle({"op": "ping"})
                assert ping["ok"] and ping["accepting"] is False
            finally:
                d.stop()

    def test_dirty_journal_heals_by_compaction_on_next_write(self, tmp_path):
        """One failed append leaves an undefined tail; the next mutation
        must rewrite the whole journal as a fresh snapshot (covering both
        mutations), clearing the failure streak."""
        j = str(tmp_path / "j.json")
        plan = FaultPlan(
            [FaultRule(op="journal", kind="permanent_error", ordinal=1,
                       times=1)]
        )
        with install_chaos(plan):
            d = service.ServiceDispatcher(
                journal=j, lease_ttl_s=5.0, demote_after=3
            )
            try:
                _register(d, "w0")  # append fails -> dirty
                _register(d, "w1")  # heals: full snapshot compaction
                recs = _journal_records(j)
                assert len(recs) == 1 and recs[0]["kind"] == "snapshot"
                assert set(recs[0]["workers"]) == {"w0", "w1"}
                assert d.accepting
                assert d._journal_fail_streak == 0
            finally:
                d.stop()


# ---------------------------------------------------------------------------
# Warm standby: tailing, promotion, address adoption
# ---------------------------------------------------------------------------


class TestStandbyFailover:
    def test_standby_rejects_lease_ops_and_names_its_primary(self, tmp_path):
        j = str(tmp_path / "j.json")
        b = service.ServiceDispatcher(
            journal=j, standby_of="127.0.0.1:9", lease_ttl_s=5.0,
            ping_interval_s=30.0, takeover_addr=False,
        )
        try:
            r = _route(b, 0)
            assert r["error"] == "not_primary"
            assert r["role"] == "standby" and r["primary"] == "127.0.0.1:9"
            st = b.status()
            assert st["role"] == "standby" and st["accepting"] is False
            assert st["standby_of"] == "127.0.0.1:9"
            # register/heartbeat still land: the standby keeps fleet
            # freshness warm for takeover
            assert _register(b, "w0")["ok"]
            assert b._handle({"op": "heartbeat", "worker_id": "w0"})["known"]
        finally:
            b.stop()

    def test_standby_tails_journal_and_promotes_on_primary_death(
        self, tmp_path
    ):
        j = str(tmp_path / "j.json")
        a = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0).start()
        b = None
        try:
            _register(a, "w0")
            _register(a, "w1")
            assert _route(a, 0)["ok"]
            b = service.ServiceDispatcher(
                journal=j, standby_of=a.addr, lease_ttl_s=5.0,
                ping_interval_s=0.1, takeover_misses=2, takeover_addr=False,
            ).start()
            wait_for(
                lambda: len(b.status()["workers"]) == 2,
                msg="standby journal tail",
            )
            assert not b.accepting
            a.stop()
            # the counter lands AFTER the promotion compaction — waiting
            # on it (not on ``accepting``, which flips first) means the
            # journal read below sees the promoted snapshot
            wait_for(
                lambda: METRICS.counter("service.failovers") == 1,
                msg="standby promotion",
            )
            st = b.status()
            assert b.accepting
            assert st["role"] == "dispatcher" and st["failed_over"] is True
            assert b.generation == 1
            # the promotion compaction IS the fence: a fresh snapshot
            # carrying the bumped generation and the tailed lease state
            recs = _journal_records(j)
            assert recs[0]["kind"] == "snapshot"
            assert recs[0]["generation"] == 1
            assert set(recs[0]["workers"]) == {"w0", "w1"}
            assert st["active_leases"] == 1
        finally:
            if b is not None:
                b.stop()
            a.stop()

    def test_promoted_standby_adopts_the_primarys_address(self, tmp_path):
        j = str(tmp_path / "j.json")
        a = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0).start()
        primary_addr = a.addr
        b = service.ServiceDispatcher(
            journal=j, standby_of=primary_addr, lease_ttl_s=5.0,
            ping_interval_s=0.1, takeover_misses=2,
        ).start()
        try:
            a.stop()
            wait_for(
                lambda: METRICS.counter("service.failovers") == 1,
                msg="standby promotion",
            )

            def answered():
                try:
                    return service.fetch_status(primary_addr, timeout=1.0)
                except OSError:
                    return None

            wait_for(lambda: answered() is not None, msg="address adoption")
            st = answered()
            # a client that only ever knew the dead primary's host:port
            # reconnects and finds the promoted standby answering there
            assert st["role"] == "dispatcher"
            assert st["failed_over"] is True and st["generation"] == 1
            assert st["addr"] == b.addr
        finally:
            b.stop()
            a.stop()


# ---------------------------------------------------------------------------
# Partitioned routing: the consumer/worker side of K > 1
# ---------------------------------------------------------------------------


class TestPartitionedRouting:
    def test_client_routes_to_the_owning_partition(self, data_dir):
        d0 = service.ServiceDispatcher(lease_ttl_s=5.0).start()
        d1 = service.ServiceDispatcher(lease_ttl_s=5.0).start()
        try:
            spec = f"{d0.addr},{d1.addr}"
            ds = make_ds(data_dir, service=spec)
            client = service.ServiceClient(ds)
            try:
                pm = service.PartitionMap.parse(spec)
                owner = pm.partition_for(client._tenant)
                assert client.partition == owner
                assert client.addr == pm.addrs(owner)[0]
                assert METRICS.gauge_value("service.partition") == float(owner)
            finally:
                client.close()
        finally:
            d0.stop()
            d1.stop()

    def test_worker_registers_everywhere_and_the_epoch_reads_clean(
        self, data_dir, local_rows
    ):
        d0 = service.ServiceDispatcher(lease_ttl_s=5.0).start()
        d1 = service.ServiceDispatcher(lease_ttl_s=5.0).start()
        w = None
        try:
            spec = f"{d0.addr},{d1.addr}"
            w = service.DecodeWorker(spec).start()
            assert w.wait_registered(10)
            # one worker, K partitions: every partition can route to it
            wait_for(
                lambda: len(d0.status()["workers"]) == 1
                and len(d1.status()["workers"]) == 1,
                msg="registration with every partition",
            )
            got = collect(data_dir, service=spec, service_deadline_ms=3000)
            assert got == local_rows
            assert METRICS.counter("service.fallbacks") == 0
            # the tenant's lease table lives on exactly ONE partition
            owner_leased = [
                d for d in (d0, d1) if d.status()["shards_done"] > 0
            ]
            assert len(owner_leased) == 1
        finally:
            if w is not None:
                w.stop()
            d0.stop()
            d1.stop()


# ---------------------------------------------------------------------------
# Federated FleetScaler: merged census, whipsaw guard, remote handles
# ---------------------------------------------------------------------------


class _DeadPartition:
    """A partition whose primary AND standby are unreachable."""

    scaler_status = None

    def status(self):
        raise OSError("unreachable")

    def drain(self, worker_id):
        raise OSError("unreachable")


class TestFederatedScaler:
    def test_census_merges_partitions_and_dedupes_workers(self):
        d0 = service.ServiceDispatcher(lease_ttl_s=5.0)
        d1 = service.ServiceDispatcher(lease_ttl_s=5.0)
        try:
            _register(d0, "w0")
            _register(d1, "w0")  # same worker, every partition
            _register(d0, "w1")
            s = elastic.FleetScaler(
                [d0, d1], lambda: None, aggregator=FakeAggregator(),
                policy=elastic.ScalerPolicy(min_workers=1, max_workers=4),
            )
            c = s._census()
            assert sorted(c["active"]) == ["w0", "w1"]
            # draining on ANY partition means draining in the merged view
            assert d0.drain("w1")
            c = s._census()
            assert c["active"] == ["w0"] and c["draining"] == ["w1"]
            # the ctor published its status block to every partition
            assert d0.scaler_status is not None
            assert d1.scaler_status is not None
        finally:
            d0.stop()
            d1.stop()

    def test_unreadable_partition_skips_the_tick_no_whipsaw(self):
        d0 = service.ServiceDispatcher(lease_ttl_s=5.0)
        try:
            _register(d0, "w0")
            spawned = []
            s = elastic.FleetScaler(
                [d0, _DeadPartition()], lambda: spawned.append(1),
                aggregator=FakeAggregator("producer_bound"),
                policy=elastic.ScalerPolicy(
                    hysteresis=1, cooldown_s=0.0, min_workers=1,
                    max_workers=4,
                ),
            )
            for _ in range(3):
                assert s.step() is None, (
                    "scaler acted on a partial fleet view"
                )
            assert spawned == []
            assert METRICS.counter("elastic.census_errors") >= 3
            assert METRICS.counter("elastic.scale_ups") == 0
            assert METRICS.counter("elastic.scale_downs") == 0
        finally:
            d0.stop()

    def test_drain_fans_out_to_every_partition(self):
        d0 = service.ServiceDispatcher(lease_ttl_s=5.0)
        d1 = service.ServiceDispatcher(lease_ttl_s=5.0)
        try:
            _register(d0, "w0")
            _register(d1, "w0")
            assert _route(d1, 0)["worker_id"] == "w0"
            s = elastic.FleetScaler(
                [d0, d1], lambda: None, aggregator=FakeAggregator(),
                policy=elastic.ScalerPolicy(min_workers=1, max_workers=4),
            )
            assert s._drain_one(["w0"], "idle") is not None
            # the victim's leases were handed back on the partition that
            # actually routed to it, and both partitions mark it draining
            assert d0.status()["draining"] == ["w0"]
            assert d1.status()["draining"] == ["w0"]
            assert d1.status()["active_leases"] == 0
        finally:
            d0.stop()
            d1.stop()

    def test_dispatcher_handle_walks_members_and_proxies_rpcs(self):
        d = service.ServiceDispatcher(lease_ttl_s=5.0).start()
        try:
            _register(d, "w0")
            # dead member first: the handle walks to the live one
            h = elastic.DispatcherHandle(f"127.0.0.1:9|{d.addr}", timeout=2.0)
            st = h.status()
            assert [w["worker_id"] for w in st["workers"]] == ["w0"]
            h.scaler_status = {"workers": 1, "verdict": "balanced"}
            assert d.scaler_status == {"workers": 1, "verdict": "balanced"}
            assert h.drain("w0") is True
            assert d.status()["draining"] == ["w0"]
        finally:
            d.stop()

    def test_dispatcher_handle_skips_standbys_for_primary_only_ops(
        self, tmp_path
    ):
        j = str(tmp_path / "j.json")
        a = service.ServiceDispatcher(journal=j, lease_ttl_s=5.0).start()
        b = service.ServiceDispatcher(
            journal=j, standby_of=a.addr, lease_ttl_s=5.0,
            ping_interval_s=30.0, takeover_addr=False,
        ).start()
        try:
            _register(a, "w0")
            # standby listed FIRST: a drain routed there would be
            # rejected; the handle must skip to the acting primary
            h = elastic.DispatcherHandle([b.addr, a.addr], timeout=2.0)
            assert h.drain("w0") is True
            assert a.status()["draining"] == ["w0"]
        finally:
            b.stop()
            a.stop()


# ---------------------------------------------------------------------------
# Federated serve-status doctor
# ---------------------------------------------------------------------------


def _doctor(*argv):
    proc = subprocess.run(
        [sys.executable, DOCTOR, "serve-status", *argv],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    events = [json.loads(ln) for ln in proc.stdout.splitlines() if ln]
    return proc.returncode, events


class TestDoctorFederated:
    def test_two_partitions_exit_0_with_ha_summary(self):
        d0 = service.ServiceDispatcher(lease_ttl_s=5.0).start()
        d1 = service.ServiceDispatcher(lease_ttl_s=5.0).start()
        try:
            _register(d0, "w0")
            _register(d1, "w0")  # registered with every partition
            rc, events = _doctor(f"{d0.addr},{d1.addr}", "--timeout", "2")
            assert rc == 0
            services = [e for e in events if e["event"] == "service"]
            assert [e["partition"] for e in services] == [0, 1]
            assert all(
                e["role"] == "dispatcher" and e["generation"] == 0
                and e["accepting"] for e in services
            )
            workers = [e for e in events if e["event"] == "worker"]
            assert {e["partition"] for e in workers} == {0, 1}
            (ha,) = [e for e in events if e["event"] == "ha"]
            assert ha["partitions"] == 2 and ha["answered"] == 2
            assert ha["acting_primaries"] == 2 and ha["failed_over"] == 0
            assert ha["workers"] == 1  # deduped across partitions
        finally:
            d0.stop()
            d1.stop()

    def test_unreachable_partition_exits_2(self):
        d0 = service.ServiceDispatcher(lease_ttl_s=5.0).start()
        try:
            rc, events = _doctor(f"{d0.addr},127.0.0.1:9", "--timeout", "1")
            assert rc == 2
            (err,) = [e for e in events if e["event"] == "error"]
            assert err["partition"] == 1
            (ha,) = [e for e in events if e["event"] == "ha"]
            assert ha["answered"] == 1 and ha["partitions"] == 2
        finally:
            d0.stop()

    def test_standby_answer_counts_the_partition_alive(self, tmp_path):
        j = str(tmp_path / "j.json")
        b = service.ServiceDispatcher(
            journal=j, standby_of="127.0.0.1:9", lease_ttl_s=5.0,
            ping_interval_s=30.0, takeover_addr=False,
        ).start()
        try:
            rc, events = _doctor(f"127.0.0.1:9|{b.addr}", "--timeout", "1")
            assert rc == 0  # the partition is alive, if not accepting
            (svc,) = [e for e in events if e["event"] == "service"]
            assert svc["role"] == "standby" and svc["accepting"] is False
        finally:
            b.stop()


# ---------------------------------------------------------------------------
# THE chaos acceptance: SIGKILL the primary mid-epoch, ride the standby
# ---------------------------------------------------------------------------


def _spawn_worker_proc(dispatcher_spec):
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))
        ),
    }
    proc = subprocess.Popen(
        [sys.executable, "-m", "tpu_tfrecord.service", "worker",
         "--dispatcher", dispatcher_spec],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
    )
    ready = json.loads(proc.stdout.readline())
    assert ready["event"] == "ready"
    return proc, ready


class TestHAChaosAcceptance:
    def test_sigkill_primary_mid_epoch_standby_takeover_byte_identical(
        self, data_dir, local_rows, tmp_path
    ):
        """THE acceptance scenario (ISSUE 17): the primary dispatcher —
        a real subprocess — is SIGKILLed mid-epoch while 2 consumers
        stream from 2 decode-worker subprocesses. The warm standby tails
        the journal, detects the death by heartbeat loss, promotes
        (generation bump), and both consumers finish the epoch
        byte-identical to a local read — zero fallbacks, zero duplicated
        or missing rows, every shard served exactly once — then the
        serve-status doctor reports the completed failover with exit 0."""
        journal = str(tmp_path / "journal.json")
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))
            ),
        }
        prim = subprocess.Popen(
            [sys.executable, "-m", "tpu_tfrecord.service", "dispatcher",
             "--journal", journal, "--lease-ttl-s", "10"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        procs = []
        standby = None
        try:
            ready = json.loads(prim.stdout.readline())
            assert ready["event"] == "ready"
            primary_addr = ready["addr"]
            standby = service.ServiceDispatcher(
                journal=journal, standby_of=primary_addr, lease_ttl_s=10.0,
                ping_interval_s=0.2, takeover_misses=3, takeover_addr=False,
            ).start()
            spec = f"{primary_addr}|{standby.addr}"
            for _ in range(2):
                procs.append(_spawn_worker_proc(spec))
            # the standby learns the fleet from the journal tail alone
            wait_for(
                lambda: len(standby.status()["workers"]) == 2,
                timeout=30, msg="standby tailed worker registrations",
            )

            chaos_done = threading.Event()
            gate = threading.Barrier(3, timeout=120)  # 2 consumers + chaos

            def consume(out):
                ds = make_ds(data_dir, service=spec, service_deadline_ms=3000)
                rows = []
                paused = False
                with ds.batches() as it:
                    for b in it:
                        rows.extend(batch_to_rows(b, ds.schema))
                        if len(rows) >= 40 and not paused:
                            paused = True
                            gate.wait()
                            chaos_done.wait()
                out.extend(rows)

            def chaos():
                gate.wait()
                os.kill(prim.pid, signal.SIGKILL)  # no atexit, no goodbye
                prim.wait()
                # hold the consumers until the standby has detected the
                # death (heartbeat loss x takeover_misses) and promoted —
                # the same shape as the dispatcher-restart acceptance,
                # where the replacement is up before consumers resume.
                # Consumers still exercise the client half of failover:
                # their persistent dispatcher conns are dead, and the
                # next RPC reconnects through the partition-map rotation.
                wait_for(lambda: standby.accepting, timeout=30,
                         msg="standby promotion")
                chaos_done.set()

            outs = [[], []]
            threads = [
                threading.Thread(target=consume, args=(outs[k],))
                for k in range(2)
            ]
            threads.append(threading.Thread(target=chaos))
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
                assert not t.is_alive(), "acceptance run wedged"

            assert outs[0] == local_rows
            assert outs[1] == local_rows
            assert METRICS.counter("service.fallbacks") == 0
            wait_for(
                lambda: METRICS.counter("service.failovers") == 1,
                msg="failover counted",
            )
            assert standby.accepting and standby.failed_over
            assert standby.generation == 1
            # exactly-once at the books too: 6 shards, 6 completions,
            # across the generation boundary. Five where BOTH consumers'
            # first call after the kill was a shard_done: the client sends
            # it once on its dead connection and drops the error
            # ("accounting only", service.py `_shard_done`), where a route
            # reconnects and is sent again (ROADMAP D16: the module's)
            wait_for(lambda: standby.status()["shards_done"] >= 6,
                     timeout=5, msg="six completions booked")
            assert standby.status()["shards_done"] == 6
            # and the doctor sees the completed failover as a finding,
            # not a failure
            rc, events = _doctor(spec, "--timeout", "2")
            assert rc == 0
            (svc,) = [e for e in events if e["event"] == "service"]
            assert svc["failed_over"] is True and svc["generation"] == 1
            assert svc["role"] == "dispatcher"
        finally:
            for proc, _ in procs:
                if proc.poll() is None:
                    proc.terminate()
            for proc, _ in procs:
                if proc.poll() is None:
                    try:
                        proc.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        proc.kill()
            if standby is not None:
                standby.stop()
            if prim.poll() is None:
                prim.kill()
