#!/usr/bin/env bash
# One-entry-point verification: the fast syntax gate, the smokes, then the
# tier-1 tests in the shape the driver runs them (xdist, 6 workers by file,
# 1470 s: the `commands` of the driver's last-run record; ROADMAP.md's
# "Tier-1 verify" line is older and is not what is run).
# Usage: tools/verify.sh  (from the repo root or anywhere)
set -u
cd "$(dirname "$0")/.."

echo "== syntax gate (compileall) =="
python -m compileall -q tpu_tfrecord || exit 1

echo "== graftlint gate (AST invariants vs the committed baseline) =="
# Zero non-baselined findings over tpu_tfrecord/ tools/ examples/: clock
# discipline in policy modules, atomic persisted writes, the Metrics lock
# contract + lock-order graph, exception-swallow audit, and the metric
# vocabulary (call sites AND the README block). The HLO collective
# contracts (tools/graftlint/hlo_contracts.py) are compiled by the
# migrated pins inside the tier-1 run below.
python -m tools.graftlint || exit 1

echo "== tfrecord_doctor self-check =="
# Write a shard, flip one byte, assert the doctor reports exactly one bad
# frame and that --repair round-trips every other record — so the salvage
# CLI can't rot.
env JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import json, os, subprocess, sys, tempfile

from tpu_tfrecord import wire

tmp = tempfile.mkdtemp(prefix="tfr_doctor_check_")
shard = os.path.join(tmp, "self.tfrecord")
recs = [f"record-{i:03d}-".encode() * 3 for i in range(20)]
wire.write_records(shard, recs)
raw = bytearray(open(shard, "rb").read())
raw[len(raw) // 2] ^= 0xFF  # one flipped byte mid-file
open(shard, "wb").write(bytes(raw))

out = subprocess.run(
    [sys.executable, "tools/tfrecord_doctor.py", "--repair", shard],
    capture_output=True, text=True,
)
assert out.returncode == 1, (out.returncode, out.stdout, out.stderr)
lines = [json.loads(l) for l in out.stdout.splitlines() if l.strip()]
summary = [l for l in lines if l.get("event") == "summary"][0]
assert summary["corrupt_events"] == 1, lines
got = list(wire.read_records(summary["repaired_path"]))
assert len(got) == 19 and all(r in recs for r in got), len(got)
print("doctor self-check OK:", json.dumps(summary))
PY

echo "== chaos smoke (seeded stall -> deadline -> skip_shard) =="
# One seeded stall scenario end-to-end: a shard whose read() hangs is
# converted by the read deadline into a skip_shard, the epoch COMPLETES,
# and the fault fires exactly as planned (ledger-checked) — so the
# stall-defense layer can't rot. The injected stall is bounded and the
# deadline is 100ms: the whole step costs well under a second.
env JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import json, os, tempfile

import tpu_tfrecord.io as tfio
from tpu_tfrecord.faults import FaultPlan, FaultRule, install_chaos
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.schema import LongType, StructField, StructType

schema = StructType([StructField("id", LongType(), nullable=False)])
out = os.path.join(tempfile.mkdtemp(prefix="tfr_chaos_smoke_"), "ds")
for s in range(3):
    tfio.write([[i] for i in range(s * 20, (s + 1) * 20)], schema, out,
               mode="append" if s else "overwrite")
victim = sorted(n for n in os.listdir(out) if n.startswith("part-"))[0]
plan = FaultPlan([FaultRule(op="read", kind="stall", path=victim,
                            times=None, stall_ms=60_000)], seed=1)
ds = TFRecordDataset(out, batch_size=5, schema=schema, drop_remainder=False,
                     read_deadline_ms=100, on_stall="skip_shard",
                     use_mmap=False)
METRICS.reset()
got = []
with install_chaos(plan):
    with ds.batches() as it:
        for cb in it:
            got.extend(cb["id"].values.tolist())
plan.release()
assert METRICS.counter("read.stalls") >= 1, "no stall detected"
assert METRICS.counter("read.skipped_shards") == 1, "stalled shard not skipped"
assert len(got) == 40 and len(set(got)) == 40, (len(got), "epoch incomplete")
assert plan.ledger and plan.ledger[0]["kind"] == "stall", plan.ledger
print("chaos smoke OK:", json.dumps({
    "rows": len(got),
    "stalls": METRICS.counter("read.stalls"),
    "skipped_shards": METRICS.counter("read.skipped_shards"),
    "ledger_events": len(plan.ledger),
}))
PY

echo "== cache smoke (populate -> mmap-served epoch -> corrupt fallback) =="
# Write a dataset, run two epochs with cache="auto", assert the second
# (cache-served) epoch's rows are byte-identical with cache.hits > 0, then
# flip one byte inside a cache section and assert exactly one
# cache.corrupt_fallbacks with ground-truth rows — so the epoch cache
# can't rot.
env JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import json, os, tempfile

import tpu_tfrecord.io as tfio
from tpu_tfrecord import cache as cache_mod
from tpu_tfrecord.columnar import batch_to_rows
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.schema import LongType, StringType, StructField, StructType

schema = StructType([StructField("id", LongType(), nullable=False),
                     StructField("s", StringType())])
base = tempfile.mkdtemp(prefix="tfr_cache_smoke_")
out = os.path.join(base, "ds"); cdir = os.path.join(base, "cache")
tfio.write([[i, f"s{i}"] for i in range(60)], schema, out, mode="overwrite")

def epoch_rows():
    ds = TFRecordDataset(out, batch_size=7, schema=schema, drop_remainder=False,
                         cache="auto", cache_dir=cdir)
    with ds.batches() as it:
        return [r for b in it for r in batch_to_rows(b, ds.schema)]

METRICS.reset()
ep1 = epoch_rows()          # populate
ep2 = epoch_rows()          # mmap-served
assert ep1 == ep2 and len(ep1) == 60, "epoch-2 rows differ from epoch-1"
assert METRICS.counter("cache.hits") > 0, "no cache hit on epoch 2"
entry = [os.path.join(cdir, n) for n in os.listdir(cdir)
         if n.endswith(cache_mod.ENTRY_SUFFIX)][0]
off = cache_mod.load_footer(entry)["chunks"][0]["columns"][0]["sections"][0][1]["off"]
raw = bytearray(open(entry, "rb").read()); raw[off] ^= 0xFF
open(entry, "wb").write(bytes(raw))
METRICS.reset()
ep3 = epoch_rows()          # corrupt entry -> ground-truth decode + rewrite
assert ep3 == ep1, "corrupt-cache fallback rows differ from ground truth"
assert METRICS.counter("cache.corrupt_fallbacks") == 1, \
    METRICS.counter("cache.corrupt_fallbacks")
print("cache smoke OK:", json.dumps({
    "rows": len(ep3),
    "hits": METRICS.counter("cache.hits"),
    "corrupt_fallbacks": METRICS.counter("cache.corrupt_fallbacks"),
}))
PY

echo "== telemetry smoke (trace -> Chrome trace + pulse + doctor report) =="
# One traced read end-to-end: the exported trace parses and contains decode
# spans, one pulse line parses, and the bottleneck doctor exits 0 with a
# verdict — so the flight recorder can't rot. All device-free, < 2s.
env JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import json, os, subprocess, sys, tempfile

import tpu_tfrecord.io as tfio
from tpu_tfrecord import telemetry
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.schema import LongType, StructField, StructType
from tpu_tfrecord.telemetry import Pulse

schema = StructType([StructField("id", LongType(), nullable=False)])
out = os.path.join(tempfile.mkdtemp(prefix="tfr_tele_smoke_"), "ds")
tfio.write([[i] for i in range(120)], schema, out, mode="overwrite")

METRICS.reset(); telemetry.RECORDER.clear()
pulses = []
pulse = Pulse(0.05, emit=pulses.append).start()
ds = TFRecordDataset(out, batch_size=16, schema=schema, drop_remainder=False,
                     trace="on")
with ds.batches() as it:
    rows = sum(b.num_rows for b in it)
pulse.stop()  # final tick guarantees at least one line
telemetry.disable()
assert rows == 120, rows
trace = json.loads(json.dumps(telemetry.RECORDER.to_chrome_trace()))
decode = [e for e in trace["traceEvents"] if e["name"] == "tfr:decode"]
assert decode, "no decode spans in exported trace"
assert all("ts" in e and "dur" in e for e in decode), decode[0]
line = json.loads(json.dumps(pulses[-1]))
assert line["event"] == "pulse" and "verdict" in line, line

doc = subprocess.run([sys.executable, "tools/tfrecord_doctor.py", "report",
                      out, "--batches", "4", "--batch-size", "16"],
                     capture_output=True, text=True)
assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
lines = [json.loads(l) for l in doc.stdout.splitlines() if l.strip()]
report = [l for l in lines if l.get("event") == "report"][0]
assert report.get("verdict"), report
print("telemetry smoke OK:", json.dumps({
    "decode_spans": len(decode),
    "pulse_lines": len(pulses),
    "doctor_verdict": report["verdict"],
}))
PY

echo "== autotune smoke (seeded throttle -> pool grows -> identical rows) =="
# One closed-loop scenario end-to-end: every shard read pays a seeded
# 25ms injected stall, autotune starts from deliberately-wrong knobs
# (1 worker, depth-1 prefetch), the controller must GROW the decode pool
# at pulse boundaries (autotune.adjustments counters prove it), and the
# rows must be byte-identical to a fixed-knob run — so the autotuner
# can't rot. Bounded stalls + fast pulses: a few seconds total.
env JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import json, os, tempfile

import tpu_tfrecord.io as tfio
from tpu_tfrecord.faults import FaultPlan, FaultRule, install_chaos
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.schema import LongType, StructField, StructType

schema = StructType([StructField("id", LongType(), nullable=False)])
out = os.path.join(tempfile.mkdtemp(prefix="tfr_autotune_smoke_"), "ds")
for s in range(6):
    tfio.write([[i] for i in range(s * 30, (s + 1) * 30)], schema, out,
               mode="append" if s else "overwrite")

def run(**kw):
    # fresh registry per leg: the controller reads process-global
    # quantiles/gauges, which must describe ITS run, not the previous leg
    METRICS.reset()
    plan = FaultPlan([FaultRule(op="read", kind="stall", path="part-",
                                times=None, stall_ms=25)], seed=3)
    ds = TFRecordDataset(out, batch_size=10, schema=schema,
                         drop_remainder=False, num_epochs=8,
                         use_mmap=False, **kw)
    rows = []
    with install_chaos(plan):
        with ds.batches() as it:
            tuner = it.autotune
            for cb in it:
                rows.extend(cb["id"].values.tolist())
    plan.release()
    return rows, tuner

fixed_rows, _ = run(num_workers=4, prefetch=4)
tuned_rows, tuner = run(num_workers=1, prefetch=1,
                        autotune="on", autotune_interval_s=0.1)
assert tuned_rows == fixed_rows, "autotuned rows differ from fixed-knob run"
grows = [d for d in tuner.log if d["knob"] == "workers" and d["to"] > d["from"]]
assert grows, f"controller never grew the pool: {tuner.log}"
assert METRICS.counter("autotune.adjustments") >= len(tuner.log) > 0
assert METRICS.gauge_value("autotune.workers", 0) > 1
print("autotune smoke OK:", json.dumps({
    "rows": len(tuned_rows),
    "adjustments": METRICS.counter("autotune.adjustments"),
    "final_workers": tuner.control.workers,
    "trajectory": [(d["knob"], d["from"], d["to"]) for d in tuner.log],
}))
PY

echo "== fleet smoke (3 spooling readers -> exact aggregation + fleet doctor + merged trace) =="
# Three short-lived reader subprocesses spool into one directory while a
# shared trace context propagates via TFR_TRACE_CONTEXT: the aggregator's
# merged read decode count must equal the SUM of the per-process counts
# exactly, `tfrecord_doctor fleet` must exit 0 with a verdict, and the
# merged Chrome trace must parse with >= 3 distinct pid tracks — so the
# cluster flight recorder can't rot.
env JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import json, os, subprocess, sys, tempfile

import tpu_tfrecord.io as tfio
from tpu_tfrecord import fleet, telemetry
from tpu_tfrecord.schema import LongType, StringType, StructField, StructType

schema = StructType([StructField("id", LongType(), nullable=False),
                     StructField("s", StringType())])
root = tempfile.mkdtemp(prefix="tfr_fleet_smoke_")
out = os.path.join(root, "ds")
for s in range(3):
    tfio.write([[i, f"s{i}"] for i in range(s * 40, (s + 1) * 40)],
               schema, out, mode="append" if s else "overwrite")

spool = os.path.join(root, "spool")
ctx = telemetry.TraceContext.new(role="verify")
env = {**os.environ, "JAX_PLATFORMS": "cpu", **ctx.to_env()}
traces = [os.path.join(root, f"trace-{i}.json") for i in range(3)]
procs = [subprocess.Popen(
    [sys.executable, "tests/fleet_worker.py", out, spool,
     "--role", f"reader{i}", "--epochs", "2", "--interval", "0.1",
     "--trace-out", traces[i]],
    stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
) for i in range(3)]
outs = []
for p in procs:
    o, e = p.communicate(timeout=240)
    assert p.returncode == 0, (p.returncode, o, e)
    outs.append(json.loads(o.splitlines()[-1]))
assert {o["trace_id"] for o in outs} == {ctx.trace_id}, outs

snap = fleet.TelemetryAggregator(spool).aggregate()
per_proc = sum(o["decode_records"] for o in outs)
assert len(snap.processes) == 3, [p.path for p in snap.processes]
assert snap.stages["decode"][0] == per_proc, \
    (snap.stages["decode"], per_proc)

doc = subprocess.run([sys.executable, "tools/tfrecord_doctor.py", "fleet",
                      spool, "--stale-after", "3600"],
                     capture_output=True, text=True)
assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
lines = [json.loads(l) for l in doc.stdout.splitlines() if l.strip()]
fleet_line = [l for l in lines if l.get("event") == "fleet"][0]
assert fleet_line.get("verdict"), fleet_line

merged_path = os.path.join(root, "merged.json")
mt = subprocess.run([sys.executable, "tools/tfrecord_doctor.py",
                     "merge-trace", merged_path] + traces,
                    capture_output=True, text=True)
assert mt.returncode == 0, (mt.returncode, mt.stdout, mt.stderr)
doc = json.load(open(merged_path))
pids = {e["pid"] for e in doc["traceEvents"]}
assert len(pids) >= 3, pids
named = {e["pid"] for e in doc["traceEvents"]
         if e.get("ph") == "M" and e["name"] == "process_name"}
assert pids <= named, (pids, named)
print("fleet smoke OK:", json.dumps({
    "decode_sum": per_proc,
    "doctor_verdict": fleet_line["verdict"],
    "merged_pid_tracks": len(pids),
}))
PY

echo "== service smoke (3 workers + 1 consumer + worker SIGKILL -> exactly-once) =="
# Three decode-worker subprocesses leased by an in-process dispatcher feed
# one consumer; mid-epoch the worker HOLDING the active lease is SIGKILLed.
# The epoch must complete with rows byte-identical to a direct local read
# (exactly-once: nothing duplicated, nothing missing), the dispatcher must
# count exactly one lease reassignment, no shard may fall back to local
# reads, and `tfrecord_doctor serve-status` must exit 0 — so the
# disaggregated data service can't rot.
env JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import json, os, signal, subprocess, sys, tempfile, time

import tpu_tfrecord.io as tfio
from tpu_tfrecord import service
from tpu_tfrecord.columnar import batch_to_rows
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.schema import LongType, StringType, StructField, StructType

schema = StructType([StructField("id", LongType(), nullable=False),
                     StructField("s", StringType())])
out = os.path.join(tempfile.mkdtemp(prefix="tfr_service_smoke_"), "ds")
for s in range(6):
    tfio.write([[i, f"s{i}"] for i in range(s * 30, (s + 1) * 30)],
               schema, out, mode="append" if s else "overwrite")

def epoch_rows(**kw):
    ds = TFRecordDataset(out, batch_size=8, schema=schema,
                         drop_remainder=False, **kw)
    rows = []
    with ds.batches() as it:
        for b in it:
            rows.extend(batch_to_rows(b, ds.schema))
            yield_hook(rows, ds)
    return rows

yield_hook = lambda rows, ds: None
local = epoch_rows()

d = service.ServiceDispatcher(lease_ttl_s=10.0).start()
env = {**os.environ, "JAX_PLATFORMS": "cpu"}
procs = {}

# a failed assert anywhere below must not leak worker subprocesses (their
# heartbeat loops retry the dead dispatcher forever); the clean
# terminate/wait path at the bottom still runs first on success
import atexit
def _reap():
    for p in procs.values():
        if p.poll() is None:
            p.kill()
atexit.register(_reap)
for _ in range(3):
    p = subprocess.Popen(
        [sys.executable, "-m", "tpu_tfrecord.service", "worker",
         "--dispatcher", d.addr],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True, env=env,
    )
    ready = json.loads(p.stdout.readline())
    procs[ready["worker_id"]] = p
deadline = time.monotonic() + 60
while time.monotonic() < deadline and len(d.status()["workers"]) < 3:
    time.sleep(0.05)
assert len(d.status()["workers"]) == 3, d.status()

# Warm epoch: each worker's FIRST fetch pays dataset construction
# (seconds on a loaded box), which must not be mistaken for a dead
# worker by the kill epoch below.
warm = epoch_rows(service=d.addr, service_deadline_ms=10000)
assert warm == local, "warm service epoch rows differ from direct local read"
assert d.status()["lease_reassignments"] == 0, d.status()

killed = []
def yield_hook(rows, ds):
    if killed or len(rows) < 40:
        return
    holders = [w["worker_id"] for w in d.status()["workers"] if w["leases"]]
    if holders:  # SIGKILL whoever is serving the consumer RIGHT NOW
        victim = procs[holders[0]]
        os.kill(victim.pid, signal.SIGKILL)
        victim.wait()
        killed.append(holders[0])

METRICS.reset()
got = epoch_rows(service=d.addr, service_deadline_ms=10000)
assert killed, "no active lease ever observed — nothing was killed"
assert got == local, "service epoch rows differ from direct local read"
st = d.status()
assert st["lease_reassignments"] == 1, st
assert METRICS.counter("service.fallbacks") == 0, "degraded to local reads"

doc = subprocess.run([sys.executable, "tools/tfrecord_doctor.py",
                      "serve-status", d.addr],
                     capture_output=True, text=True)
assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
lines = [json.loads(l) for l in doc.stdout.splitlines() if l.strip()]
summary = [l for l in lines if l.get("event") == "service"][0]
assert summary["lease_reassignments"] == 1, summary

for p in procs.values():
    if p.poll() is None:
        p.terminate()
for p in procs.values():
    if p.poll() is None:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
d.stop()
print("service smoke OK:", json.dumps({
    "rows": len(got),
    "killed_worker": killed[0],
    "lease_reassignments": st["lease_reassignments"],
    "reconnects": METRICS.counter("service.reconnects"),
}))
PY

echo "== elastic smoke (throttled fleet grows -> drains on idle -> identical rows) =="
# The elastic service layer end-to-end, production-shaped: the FleetScaler
# brings up ONE decode-worker subprocess (below-min refill), every worker
# read pays a seeded 25ms injected stall (--fault-plan), so the consumer's
# spool says producer_bound and the scaler must GROW the fleet mid-run;
# when the consumer closes (load removed) the verdict goes idle and the
# scaler must DRAIN back to 1 worker via clean goodbyes. Rows must be
# byte-identical throughout, and serve-status (with its new tenant +
# scaler lines) must exit 0 — so the elastic layer can't rot.
env JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import json, os, subprocess, sys, tempfile, time

import tpu_tfrecord.io as tfio
from tpu_tfrecord import elastic, service
from tpu_tfrecord.columnar import batch_to_rows
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.schema import LongType, StructField, StructType

schema = StructType([StructField("id", LongType(), nullable=False)])
root = tempfile.mkdtemp(prefix="tfr_elastic_smoke_")
out = os.path.join(root, "ds")
for s in range(6):
    tfio.write([[i] for i in range(s * 30, (s + 1) * 30)], schema, out,
               mode="append" if s else "overwrite")

def epoch_rows(**kw):
    ds = TFRecordDataset(out, batch_size=10, schema=schema,
                         drop_remainder=False, **kw)
    with ds.batches() as it:
        return [r for b in it for r in batch_to_rows(b, ds.schema)]

local = epoch_rows(num_epochs=1)

plan_path = os.path.join(root, "plan.json")
with open(plan_path, "w") as fh:
    json.dump({"seed": 3, "rules": [{"op": "read", "kind": "stall",
                                     "path": "part-", "times": None,
                                     "stall_ms": 25}]}, fh)
spool = os.path.join(root, "spool")
d = service.ServiceDispatcher(lease_ttl_s=2.0).start()
spawner = elastic.SubprocessSpawner(
    d.addr, ("--fault-plan", plan_path, "--drain-grace", "0.2"),
    env={**os.environ, "JAX_PLATFORMS": "cpu"})
scaler = elastic.FleetScaler(
    d, spawner, spool_dir=spool,
    policy=elastic.ScalerPolicy(hysteresis=2, cooldown_s=0.4,
                                min_workers=1, max_workers=3),
    interval_s=0.2).start()
try:
    # the scaler itself brings up worker 1 (below-min refill)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and d.status()["alive"] < 1:
        time.sleep(0.05)
    assert d.status()["alive"] >= 1, d.status()

    # OFFERED LOAD: 8 epochs through the service, every worker-side read
    # under the seeded 25ms stall -> producer_bound -> the fleet GROWS
    rows = epoch_rows(num_epochs=8, service=d.addr,
                      service_deadline_ms=15000,
                      telemetry_spool_dir=spool, spool_interval_s=0.1)
    assert rows == local * 8, "elastic service rows differ from local"
    ups = METRICS.counter("elastic.scale_ups")  # scaler is in-process
    grows = [x for x in scaler.log if x["action"] == "scale_up"
             and x["reason"] == "producer_bound"]
    assert grows, f"scaler never grew the fleet: {scaler.log}"
    peak = max(x["target"] for x in grows)
    assert peak >= 2, scaler.log

    # LOAD REMOVED: consumer closed (its spool says final) -> idle ->
    # the scaler drains the fleet back to the 1-worker floor
    active = []
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        active = [w for w in d.status()["workers"]
                  if w["alive"] and not w["draining"]]
        if len(active) == 1:
            break
        time.sleep(0.2)
    assert len(active) == 1, d.status()
    drains = [x for x in scaler.log if x["action"] == "scale_down"
              and x["reason"] == "idle"]
    assert drains, scaler.log

    doc = subprocess.run([sys.executable, "tools/tfrecord_doctor.py",
                          "serve-status", d.addr],
                         capture_output=True, text=True)
    assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
    lines = [json.loads(l) for l in doc.stdout.splitlines() if l.strip()]
    assert [l for l in lines if l.get("event") == "scaler"], lines
    assert [l for l in lines if l.get("event") == "tenant"], lines
finally:
    scaler.stop()
    spawner.reap()
    d.stop()
print("elastic smoke OK:", json.dumps({
    "rows": len(rows),
    "peak_workers": peak,
    "scale_ups": ups,
    "scale_downs": METRICS.counter("elastic.scale_downs"),
}))
PY

echo "== remote smoke (real HTTP backend + seeded resets/stalls/truncation -> byte-identical epoch) =="
# Serve a local dataset through the threaded Range server, fire a seeded
# plan mixing connection resets, a server-side stall, a truncated body,
# and a 503 — all at the real socket — and assert one epoch with retries
# is byte-identical to the local read with zero corrupt rows and the
# fault ledger populated. Then tfrecord_doctor scans an http:// source.
env JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import json, os, subprocess, sys, tempfile

import tpu_tfrecord.io as tfio
from tpu_tfrecord import httpfs
from tpu_tfrecord.faults import FaultPlan, FaultRule
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.retry import RetryPolicy
from tpu_tfrecord.schema import LongType, StringType, StructField, StructType

schema = StructType([StructField("id", LongType(), nullable=False),
                     StructField("s", StringType())])
root = tempfile.mkdtemp(prefix="tfr_remote_smoke_")
out = os.path.join(root, "ds")
for s in range(3):
    tfio.write([[i, f"s{i}"] for i in range(s * 60, (s + 1) * 60)],
               schema, out, mode="append" if s else "overwrite")
names = sorted(n for n in os.listdir(out) if n.startswith("part-"))

def read_ids(src, **kw):
    ds = TFRecordDataset(src, batch_size=16, schema=schema,
                         drop_remainder=False, **kw)
    with ds.batches() as it:
        return [i for cb in it for i in cb["id"].values.tolist()]

local = read_ids(out)
plan = FaultPlan([
    FaultRule(op="http", kind="reset", path=names[0], cap_bytes=128, times=1),
    FaultRule(op="http", kind="stall", path=names[1], stall_ms=50, times=1),
    FaultRule(op="http", kind="truncated_body", path=names[1], cap_bytes=90,
              times=1),
    FaultRule(op="http", kind="http_error", path=names[2], status=503,
              retry_after_s=0.01, times=1),
], seed=9)
with httpfs.serve_directory(root, plan=plan) as srv:
    METRICS.reset()
    got = read_ids(srv.url_for("ds"),
                   retry_policy=RetryPolicy(max_retries=3,
                                            sleep=lambda _s: None))
    assert got == local, "remote epoch differs from local read"
    assert METRICS.counter("read.retries") > 0, "no retry ever fired"
    assert METRICS.counter("read.corrupt_records") == 0, "corrupt rows leaked"
    kinds = sorted(e["kind"] for e in plan.ledger)
    assert kinds == ["http_error", "reset", "stall", "truncated_body"], kinds

    doc = subprocess.run(
        [sys.executable, "tools/tfrecord_doctor.py",
         srv.url_for("ds/" + names[0])],
        capture_output=True, text=True)
    assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
    lines = [json.loads(l) for l in doc.stdout.splitlines() if l.strip()]
    summary = [l for l in lines if l.get("event") == "summary"][0]
    assert summary["records"] == 60 and summary["corrupt_events"] == 0, summary
print("remote smoke OK:", json.dumps({
    "rows": len(got),
    "retries": METRICS.counter("read.retries"),
    "ledger_kinds": kinds,
    "doctor_records": summary["records"],
}))
PY

echo "== LM smoke (8-device mesh, kill -9 mid-run, resume -> byte-identical data order + continued loss) =="
# Train the causal LM (zigzag ring attention, dp x sp on the 8-device CPU
# mesh) twice over the same generated dataset: once uninterrupted, once
# SIGKILLed the moment step 10 is logged and then resumed from its last
# atomic checkpoint (step 8). The resumed leg's packed-batch digests must
# equal the uninterrupted run's for every overlapping step (byte-identical
# data order) and its losses must continue the same curve exactly — so the
# model-parallel consumer path can't rot.
env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'PY' || exit 1
import json, os, signal, subprocess, sys, tempfile

root = tempfile.mkdtemp(prefix="tfr_lm_smoke_")
data = os.path.join(root, "data")
def run(ck, digests, extra=(), kill_at=None):
    cmd = [sys.executable, "examples/train_lm.py", "--steps", "16",
           "--save-every", "4", "--data-dir", data, "--ckpt-dir", ck,
           "--digest-out", digests, *extra]
    p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    out = []
    for line in p.stdout:
        out.append(line)
        if kill_at is not None and line.startswith("lm_step"):
            if json.loads(line.split(" ", 1)[1])["step"] >= kill_at:
                os.kill(p.pid, signal.SIGKILL)
                break
    p.wait()
    if kill_at is None:
        assert p.returncode == 0, (p.returncode, "".join(out)[-2000:])
    return "".join(out)

def load(path):
    return {json.loads(l)["step"]: json.loads(l) for l in open(path)}

a_digests = os.path.join(root, "a.jsonl")
run(os.path.join(root, "ck_a"), a_digests)                       # reference
b_digests = os.path.join(root, "b.jsonl")
run(os.path.join(root, "ck_b"), b_digests, kill_at=10)           # killed
resumed = run(os.path.join(root, "ck_b"), b_digests)             # resumed
# the SIGKILL fires after the step-10 line, so the surviving checkpoint is
# step 8 — or step 12 if the child squeezed past the next save boundary
# before the signal landed; derive the actual resume point, require a real
# mid-run resume either way
import re
m = re.search(r"resumed at step (\d+)", resumed)
assert m, resumed[-1500:]
rstep = int(m.group(1))
assert rstep in (8, 12), rstep
A, B = load(a_digests), load(b_digests)
overlap = sorted(s for s in A if s > rstep and s in B)
assert len(overlap) == 16 - rstep, (rstep, sorted(A), sorted(B))
for s in overlap:
    assert A[s]["digest"] == B[s]["digest"], (s, A[s], B[s])
    assert abs(float(A[s]["loss"]) - float(B[s]["loss"])) < 1e-6, (s, A[s], B[s])
losses = [float(A[s]["loss"]) for s in sorted(A)]
assert losses[-1] < losses[0], losses  # training signal, not noise
print("lm smoke OK:", json.dumps({
    "steps_compared": len(overlap),
    "first_loss": losses[0],
    "final_loss": losses[-1],
}))
PY

echo "== LM fsdp smoke (dp x fsdp weight sharding: same data, same loss as pure dp + HLO contract rows) =="
# The full-GSPMD-mesh leg (PR 19): train 8 steps under --mesh dp and
# --mesh dp_fsdp over the SAME generated dataset. Weight sharding is a
# layout choice, not a numerics choice: the packed-batch digests must be
# byte-identical and the per-step losses equal to float tolerance, the
# trainer must report its sharded per-device param bytes, and the two
# fsdp HLO contract rows (gather-on-use dp×fsdp, and dp×fsdp×pp composed
# under the pipeline's boundary reshard) must pass against live compiles.
env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'PY' || exit 1
import json, os, re, subprocess, sys, tempfile

root = tempfile.mkdtemp(prefix="tfr_lm_fsdp_smoke_")
data = os.path.join(root, "data")

def run(mesh, tag):
    digests = os.path.join(root, tag + ".jsonl")
    res = subprocess.run(
        [sys.executable, "examples/train_lm.py", "--mesh", mesh,
         "--steps", "8", "--save-every", "4", "--data-dir", data,
         "--ckpt-dir", os.path.join(root, "ck_" + tag),
         "--digest-out", digests],
        capture_output=True, text=True, timeout=600,
    )
    assert res.returncode == 0, (res.returncode, res.stdout[-2000:],
                                 res.stderr[-1000:])
    lines = {json.loads(l)["step"]: json.loads(l) for l in open(digests)}
    return res.stdout, lines

_, dp = run("dp", "dp")
out_f, fsdp = run("dp_fsdp", "fsdp")
m = re.search(r"fsdp param bytes/device: (\d+)", out_f)
assert m, out_f[-1500:]
per_dev = int(m.group(1))
assert "'fsdp': 4" in out_f, out_f[-1500:]
assert sorted(dp) == sorted(fsdp) == list(range(1, 9)), (sorted(dp), sorted(fsdp))
for s in dp:
    assert dp[s]["digest"] == fsdp[s]["digest"], (s, dp[s], fsdp[s])
    d = abs(float(dp[s]["loss"]) - float(fsdp[s]["loss"]))
    assert d < 5e-4, (s, dp[s], fsdp[s])

from tools.graftlint import hlo_contracts
for row in ("lm_train_step_fsdp", "lm_train_step_fsdp_pp"):
    hlo_contracts.verify(row)
print("lm fsdp smoke OK:", json.dumps({
    "steps_compared": len(dp),
    "fsdp_param_bytes_per_device": per_dev,
    "contract_rows": ["lm_train_step_fsdp", "lm_train_step_fsdp_pp"],
}))
PY

echo "== serving smoke (train_lm dp_pp interleaved -> serve_lm streams the checkpoint byte-identically) =="
# The inference path end-to-end (ISSUE 15): train the LM on the dp×pp
# interleaved mesh (2 stages × 2 virtual chunks), leave its atomic
# checkpoint behind, then serve N streamed microbatches through LMStream.
# serve_lm itself asserts the streamed logits equal the batch path
# (batch-mode pipeline_apply on the same slices) BITWISE; here we pin
# that it exits 0, reports that byte-identity, and lands a requests/s
# number — so the serving surface can't rot.
env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'PY' || exit 1
import json, os, subprocess, sys, tempfile

root = tempfile.mkdtemp(prefix="tfr_serve_smoke_")
data, ck = os.path.join(root, "data"), os.path.join(root, "ckpt")
res = subprocess.run(
    [sys.executable, "examples/train_lm.py", "--mesh", "dp_pp",
     "--virtual", "2", "--steps", "8", "--save-every", "4",
     "--data-dir", data, "--ckpt-dir", ck],
    capture_output=True, text=True, timeout=600,
)
assert res.returncode == 0, (res.returncode, res.stdout[-2000:], res.stderr[-1000:])
# the async generation layout: newest COMPLETE generation carries the
# manifest committed last
assert os.path.exists(os.path.join(ck, "gen-00000008", "MANIFEST.json")), \
    os.listdir(ck)

srv = subprocess.run(
    [sys.executable, "examples/serve_lm.py", "--ckpt-dir", ck,
     "--pipe", "2", "--virtual", "2", "--requests", "12"],
    capture_output=True, text=True, timeout=600,
)
assert srv.returncode == 0, (srv.returncode, srv.stdout[-2000:], srv.stderr[-1000:])
line = [l for l in srv.stdout.splitlines() if l.startswith("serve_lm OK:")]
assert line, srv.stdout[-2000:]
rep = json.loads(line[0].split("serve_lm OK:", 1)[1])
assert rep["byte_identical_to_batch"] is True, rep
assert rep["requests"] == 12 and rep["requests_per_s"] > 0, rep
assert rep["ckpt_step"] == 8, rep
print("serving smoke OK:", json.dumps({
    "requests_per_s": rep["requests_per_s"],
    "latency_ms_p50": rep["latency_ms_p50"],
    "byte_identical": rep["byte_identical_to_batch"],
}))
PY

echo "== serving-tier smoke (subprocess replica + injected disconnect -> 4 concurrent clients byte-identical to sequential; doctor serve verdict) =="
# ISSUE 18 end-to-end: one synthetic-model replica in its own process
# with a seeded op='serve' client_disconnect fault armed on the reply
# seam. 4 concurrent ServeClients multiplex onto the continuous-batching
# engine; the victim's connection is dropped mid-exchange and its client
# reconnects and resends (deterministic model => same bytes). Every
# client's output must be byte-identical to a one-at-a-time
# sequential_reference run, SIGTERM must drain gracefully (exit 0, final
# spool snapshot), and `tfrecord_doctor serve` on the spool must exit 0
# with the disconnect counted and a verdict.
env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'PY' || exit 1
import json, os, signal, subprocess, sys, tempfile, threading, time

import numpy as np

root = tempfile.mkdtemp(prefix="tfr_serve_tier_smoke_")
spool = os.path.join(root, "spool")
plan_path = os.path.join(root, "plan.json")
from tpu_tfrecord import faults
plan = faults.FaultPlan([
    faults.FaultRule(op="serve", kind="client_disconnect",
                     path="reply:", times=1),
])
with open(plan_path, "w") as fh:
    json.dump(plan.to_json(), fh)

srv = subprocess.Popen(
    [sys.executable, "-m", "tpu_tfrecord.serving", "--seed", "0",
     "--spool-dir", spool, "--fault-plan", plan_path],
    stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
)
try:
    ready = json.loads(srv.stdout.readline())
    addr = ready["addr"]

    rng = np.random.default_rng(7)
    windows = [
        rng.integers(1, 96, size=16).astype(np.int32) for _ in range(5)
    ]

    from tpu_tfrecord import service_protocol as sp
    from tpu_tfrecord.serving import ServeClient

    # phase 1 — the 4 concurrent clients, injected chaos armed: the
    # FIRST reply written on any connection is killed (times=1), so
    # exactly one client loses a completed reply and its retry policy
    # resends (the +1 in the doctor's request count below)
    results, errors = {}, []

    def client(i):
        c = ServeClient([addr])
        try:
            results[i] = c.generate(windows[i], n_new=3)
        except Exception as e:  # noqa: BLE001
            errors.append((i, repr(e)))
        finally:
            c.close()

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert sorted(results) == [0, 1, 2, 3], sorted(results)

    # phase 2 — a doomed raw-socket client (the injected fault is spent,
    # so status replies are safe now): long request, hang up the moment
    # the engine has it in flight — the dropped slot must free (counted
    # serve.disconnects) and the replica must still drain cleanly
    doomed = sp.connect(addr, timeout=30.0)
    sp.send_msg(doomed, {
        "v": sp.PROTO_VERSION, "op": "generate", "req": 1,
        "tokens": windows[4].tolist(), "n_new": 500, "deadline_s": None,
    })
    probe = sp.connect(addr, timeout=30.0)
    deadline = time.monotonic() + 60
    while True:
        st = sp.request(probe, addr, {
            "v": sp.PROTO_VERSION, "op": "status", "req": 1,
        })
        if st["in_flight"] >= 1:
            break
        assert time.monotonic() < deadline, st
        time.sleep(0.02)
    doomed.close()
    # the freed slot: in_flight drains back to 0 before the goodbye
    deadline = time.monotonic() + 60
    while True:
        st = sp.request(probe, addr, {
            "v": sp.PROTO_VERSION, "op": "status", "req": 2,
        })
        if st["in_flight"] == 0 and st["queue_depth"] == 0:
            break
        assert time.monotonic() < deadline, st
        time.sleep(0.05)
    assert st["counters"].get("serve.disconnects", 0) >= 1, st
    probe.close()

    # the local reference: same seed 0 => same params => exact bytes
    import jax
    from tpu_tfrecord.models import lm
    from tpu_tfrecord.serving import sequential_reference
    from tpu_tfrecord.tpu import create_mesh
    cfg = lm.LMConfig(vocab_size=96, d_model=32, n_heads=2, n_layers=4,
                      max_len=16, n_micro=4, n_virtual=1)
    params = lm.init_params(jax.random.key(0), cfg)
    mesh = create_mesh({"pipe": 2}, jax.devices()[:2])
    ref = sequential_reference(
        params, cfg, mesh, [(w, 3) for w in windows], 4
    )
    for i in range(4):
        assert results[i] == ref[i], (i, results[i], ref[i])

    srv.send_signal(signal.SIGTERM)  # graceful drain
    out, err = srv.communicate(timeout=60)
    assert srv.returncode == 0, (srv.returncode, out[-2000:], err[-2000:])
finally:
    if srv.poll() is None:
        srv.kill()
        srv.wait()

doc = subprocess.run(
    [sys.executable, "tools/tfrecord_doctor.py", "serve", spool, "--json"],
    capture_output=True, text=True, timeout=120,
)
assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
events = json.loads(doc.stdout)["events"]
summary = [e for e in events if e["event"] == "serve"][-1]
# 5 completed requests: 4 clients + ONE resend — the injected reply-seam
# disconnect killed exactly one completed reply and that client's retry
# policy resent it (deterministic model => same bytes). The doomed raw
# client's mid-generation hangup is the counted disconnect; the injected
# one dropped a COMPLETED request's reply, which is a resend, not lost
# work.
assert summary["requests"] == 5, summary
assert summary["sheds"]["disconnects"] >= 1, summary
assert summary["verdict"] in (
    "meeting_slo", "compute_bound", "queue_bound", "unknown"
), summary
print("serving-tier smoke OK:", json.dumps({
    "byte_identical": True,
    "disconnects": summary["sheds"]["disconnects"],
    "verdict": summary["verdict"],
    "latency_p99_ms": summary.get("latency_p99_ms"),
}))
PY

echo "== SLO + request-tracing smoke (traced replica, 4 clients + injected deadline expiry -> doctor slo burn verdict; merged trace has one serve.request per admitted request) =="
# ISSUE 20 end-to-end: a --trace-out replica under 4 concurrent clients
# plus ONE request submitted with an already-expired deadline. The spool's
# history must drive `tfrecord_doctor slo` to exit 0 with a burn-rate
# verdict on the availability objective (1 expiry against 4 completions
# burns far past the 14.4x fast threshold), and `merge-trace` pointed at
# the TRACE DIRECTORY must produce a timeline holding exactly one
# serve.request root span per admitted request, each with a
# serve.queue_wait child and >= 1 serve.tick slice under the same
# client-minted span id.
env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'PY' || exit 1
import json, os, signal, subprocess, sys, tempfile, threading

import numpy as np

root = tempfile.mkdtemp(prefix="tfr_slo_smoke_")
spool = os.path.join(root, "spool")
traces = os.path.join(root, "traces")
os.makedirs(traces)

srv = subprocess.Popen(
    [sys.executable, "-m", "tpu_tfrecord.serving", "--seed", "0",
     "--spool-dir", spool,
     "--trace-out", os.path.join(traces, "replica.json")],
    stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
)
try:
    ready = json.loads(srv.stdout.readline())
    addr = ready["addr"]

    from tpu_tfrecord import telemetry
    from tpu_tfrecord.serving import DeadlineExpired, ServeClient

    telemetry.enable()  # the client half of the merged timeline
    rng = np.random.default_rng(7)
    windows = [
        rng.integers(1, 96, size=16).astype(np.int32) for _ in range(4)
    ]
    results, errors = {}, []

    def client(i):
        c = ServeClient([addr])
        try:
            results[i] = c.generate(windows[i], n_new=3)
        except Exception as e:  # noqa: BLE001
            errors.append((i, repr(e)))
        finally:
            c.close()

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert sorted(results) == [0, 1, 2, 3], sorted(results)

    # the injected deadline expiry: already unmeetable at admission, so
    # it is REFUSED (never admitted -> no serve.request span) but counted
    # into serve.deadline_expired — the availability objective's burn
    expired = ServeClient([addr])
    try:
        expired.generate(windows[0], n_new=3, deadline_s=0.0)
        raise AssertionError("deadline_s=0 request was served")
    except DeadlineExpired:
        pass
    finally:
        expired.close()

    telemetry.RECORDER.save_chrome_trace(os.path.join(traces, "clients.json"))
    telemetry.disable()

    srv.send_signal(signal.SIGTERM)  # graceful drain -> final spool line
    out, err = srv.communicate(timeout=60)
    assert srv.returncode == 0, (srv.returncode, out[-2000:], err[-2000:])
finally:
    if srv.poll() is None:
        srv.kill()
        srv.wait()

# doctor slo: exit 0, the availability objective named, burning fast
doc = subprocess.run(
    [sys.executable, "tools/tfrecord_doctor.py", "slo", spool, "--json"],
    capture_output=True, text=True, timeout=120,
)
assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
events = json.loads(doc.stdout)["events"]
avail = [
    e for e in events
    if e["event"] == "objective" and e["kind"] == "availability"
]
assert len(avail) == 1, events
assert avail[0]["objective"] == "availability:0.999", avail
assert avail[0]["bad"] >= 1 and avail[0]["total"] >= 5, avail
assert avail[0]["verdict"] == "fast_burn", avail
summary = [e for e in events if e["event"] == "slo"][-1]
assert summary["verdict"] == "fast_burn", summary

# merge-trace on the DIRECTORY: one serve.request per admitted request,
# each with its queue_wait child and >= 1 tick slice
merged_path = os.path.join(root, "merged.json")
mt = subprocess.run(
    [sys.executable, "tools/tfrecord_doctor.py", "merge-trace",
     merged_path, traces],
    capture_output=True, text=True, timeout=120,
)
assert mt.returncode == 0, (mt.returncode, mt.stdout, mt.stderr)
with open(merged_path) as fh:
    merged = json.load(fh)
evs = merged["traceEvents"]
reqs = [e for e in evs if e.get("name") == "serve.request"]
assert len(reqs) == 4, [e.get("name") for e in evs][:40]
span_ids = {e["args"]["span_id"] for e in reqs}
assert len(span_ids) == 4, reqs
for sid in span_ids:
    kids = [
        e for e in evs
        if e.get("args", {}).get("parent_span_id") == sid
    ]
    names = [e["name"] for e in kids]
    assert "serve.queue_wait" in names, (sid, names)
    assert names.count("serve.tick") >= 1, (sid, names)
expiries = [e for e in evs if e.get("name") == "serve.deadline_expired"]
assert len(expiries) >= 1, "injected expiry left no instant"
print("slo smoke OK:", json.dumps({
    "availability_verdict": avail[0]["verdict"],
    "budget_remaining": avail[0]["budget_remaining"],
    "request_spans": len(reqs),
    "merged_events": len(evs),
}))
PY

echo "== async-ckpt smoke (seeded slow disk, SIGKILL mid-commit -> resume from complete generation, non-ckpt_bound) =="
# ISSUE 16 end-to-end: train_lm under a seeded commit throttle (the
# slow-disk fault). The kill leg SIGKILLs right after step 9 — the step-8
# generation's background commit is mid-throttle, so only the step-4
# generation is complete on disk. The resume leg must restore from a
# COMPLETE generation (4, or 8 if the commit squeaked through), run to
# the full step budget, and — because the commit runs off the step path —
# its verdict line must NOT read ckpt_bound even with the throttle still
# armed. `doctor train` on the resumed run's spool exits 0. The LM smoke
# above already pins byte-identical digests across kill/resume at the
# default (async) mode.
env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'PY' || exit 1
import json, os, re, signal, subprocess, sys, tempfile

root = tempfile.mkdtemp(prefix="tfr_ackpt_smoke_")
data, ck = os.path.join(root, "data"), os.path.join(root, "ckpt")
spool = os.path.join(root, "spool")
env = {**os.environ, "TFR_CKPT_COMMIT_THROTTLE_S": "0.5"}

# kill leg: SIGKILL lands while generation 8's commit sleeps in the
# throttle (the step lines keep flowing — the loop is not waiting on it)
cmd = [sys.executable, "examples/train_lm.py", "--mesh", "dp",
       "--steps", "16", "--save-every", "4", "--data-dir", data,
       "--ckpt-dir", ck, "--digest-out", os.path.join(root, "k.jsonl")]
p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                     stderr=subprocess.STDOUT, text=True, env=env)
for line in p.stdout:
    if line.startswith("lm_step") and \
            json.loads(line.split(" ", 1)[1])["step"] >= 9:
        os.kill(p.pid, signal.SIGKILL)
        break
p.wait()
gens = sorted(n for n in os.listdir(ck) if n.startswith("gen-"))
complete = [g for g in gens
            if os.path.exists(os.path.join(ck, g, "MANIFEST.json"))]
assert complete, (gens, "no complete generation survived the kill")

# resume leg: lighter throttle (commit hides under 4 steps of compute),
# must resume from a complete generation and finish all 16 steps with a
# non-ckpt_bound verdict
env["TFR_CKPT_COMMIT_THROTTLE_S"] = "0.05"
res = subprocess.run(cmd + ["--spool", spool, "--spool-interval", "0.2"],
                     capture_output=True, text=True, env=env, timeout=600)
assert res.returncode == 0, (res.returncode, res.stdout[-2000:], res.stderr[-1000:])
m = re.search(r"resumed at step (\d+)", res.stdout)
assert m and int(m.group(1)) in (4, 8), res.stdout[-1500:]
assert re.search(r"done: 16 steps", res.stdout), res.stdout[-1500:]
v = re.search(r"verdict: (\w+)", res.stdout)
assert v and v.group(1) != "ckpt_bound", res.stdout[-1500:]

# doctor train on the resumed run's spool: exit 0 with a verdict
doc = subprocess.run([sys.executable, "tools/tfrecord_doctor.py", "train",
                      spool, "--stale-after", "3600"],
                     capture_output=True, text=True)
assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
summary = [json.loads(l) for l in doc.stdout.splitlines()
           if l.strip() and json.loads(l).get("event") == "train"][0]
assert summary["verdict"] != "ckpt_bound", summary
print("async-ckpt smoke OK:", json.dumps({
    "resumed_at": int(m.group(1)),
    "complete_generations_after_kill": complete,
    "resume_verdict": v.group(1),
    "doctor_verdict": summary["verdict"],
}))
PY

echo "== trainer-telemetry smoke (train_lm --spool -> doctor train + step-marked trace + MoE counts) =="
# The training flight recorder end-to-end: a short MoE train_lm run spools
# under the trainer role with the flight recorder on. `doctor train` must
# exit 0 with a phase-share verdict, the exported Chrome trace must parse
# with train.step markers, and the in-jit MoE diagnostics must count
# exactly tokens*top_k routed assignments (pinned in-process against the
# same batch) — so the trainer-side observability can't rot.
env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python - <<'PY' || exit 1
import json, os, subprocess, sys, tempfile

root = tempfile.mkdtemp(prefix="tfr_train_smoke_")
spool = os.path.join(root, "spool")
trace_path = os.path.join(root, "trace.json")
env = {**os.environ}
res = subprocess.run(
    [sys.executable, "examples/train_lm.py", "--mesh", "dp", "--moe", "4",
     "--diagnostics", "--steps", "8", "--epochs", "1", "--save-every", "4",
     "--data-dir", os.path.join(root, "data"),
     "--ckpt-dir", os.path.join(root, "ckpt"),
     "--spool", spool, "--spool-interval", "0.2",
     "--trace-out", trace_path],
    capture_output=True, text=True, env=env, timeout=600,
)
assert res.returncode == 0, (res.returncode, res.stdout[-2000:], res.stderr[-1000:])

# the clean exit landed a final trainer snapshot with the train phases
from tpu_tfrecord import fleet
files = [n for n in os.listdir(spool) if n.endswith(fleet.SPOOL_SUFFIX)]
snap = fleet.read_spool(os.path.join(spool, files[0]))
assert snap.final and snap.role == "trainer", (snap.final, snap.role)
assert snap.counters.get("train.steps") == 8, snap.counters
assert "moe.dropped_fraction" in snap.gauges, sorted(snap.gauges)

# doctor train: exit 0, a verdict, phase shares
doc = subprocess.run([sys.executable, "tools/tfrecord_doctor.py", "train",
                      spool, "--stale-after", "3600"],
                     capture_output=True, text=True)
assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
lines = [json.loads(l) for l in doc.stdout.splitlines() if l.strip()]
summary = [l for l in lines if l.get("event") == "train"][0]
assert summary["verdict"] in ("input_bound", "compute_bound", "ckpt_bound")
assert summary["phase_shares"], summary

# the Chrome trace parses and carries one train.step span per step
trace = json.load(open(trace_path))
steps = [e for e in trace["traceEvents"]
         if e.get("name") == "train.step" and e.get("ph") == "X"]
assert len(steps) == 8, len(steps)

# MoE expert counts sum to tokens routed (counts are oracle-pinned in
# tests; here the invariant on a live batch)
import numpy as np, jax, jax.numpy as jnp
from tpu_tfrecord.models import moe
cfg = moe.MoEConfig(d_model=8, d_ff=16, n_experts=4, top_k=2)
params = moe.init_params(jax.random.key(0), cfg)
x = jnp.asarray(np.random.default_rng(0).normal(size=(24, 8)), jnp.float32)
_, _, diag = moe.moe_apply(params, x, cfg, diagnostics=True)
routed = float(np.asarray(diag["expert_tokens"]).sum())
assert routed == 24 * cfg.top_k, routed
print("trainer-telemetry smoke OK:", json.dumps({
    "steps": summary["steps"],
    "verdict": summary["verdict"],
    "step_spans": len(steps),
    "moe_routed": routed,
}))
PY

echo "== HA smoke (2 partitions + warm standby, primary SIGKILL mid-read -> standby serves, byte-identical) =="
# The HA control plane end-to-end, production-shaped: two dispatcher
# PARTITION primaries plus one warm standby, all real subprocesses sharing
# a journal file, two decode workers registered with every partition. The
# primary of the partition that OWNS the dataset's tenant is SIGKILLed
# mid-read; the standby must detect death by ping loss, promote with a
# bumped generation, take over the dead primary's address, and finish the
# epoch byte-identical to a direct local read with ZERO local-read
# fallbacks. `serve-status` over the partition map must exit 0 and report
# the failover — so the failover path can't rot.
env JAX_PLATFORMS=cpu python - <<'PY' || exit 1
import json, os, signal, subprocess, sys, tempfile, time

import tpu_tfrecord.io as tfio
from tpu_tfrecord import service
from tpu_tfrecord.columnar import batch_to_rows
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.schema import LongType, StringType, StructField, StructType

schema = StructType([StructField("id", LongType(), nullable=False),
                     StructField("s", StringType())])
base = tempfile.mkdtemp(prefix="tfr_ha_smoke_")
out = os.path.join(base, "ds")
for s in range(6):
    tfio.write([[i, f"s{i}"] for i in range(s * 30, (s + 1) * 30)],
               schema, out, mode="append" if s else "overwrite")

def epoch_rows(**kw):
    ds = TFRecordDataset(out, batch_size=8, schema=schema,
                         drop_remainder=False, **kw)
    rows = []
    with ds.batches() as it:
        for b in it:
            rows.extend(batch_to_rows(b, ds.schema))
            yield_hook(rows, ds)
    return rows

yield_hook = lambda rows, ds: None
local = epoch_rows()

# which of the two partitions will own this dataset's tenant? (rendezvous
# hashing is over partition INDICES, so the answer predates the addresses)
tenant = service.tenant_digest(
    TFRecordDataset(out, batch_size=8, schema=schema))
owner = service.PartitionMap.parse("h:1,h:2").partition_for(tenant)

env = {**os.environ, "JAX_PLATFORMS": "cpu"}
procs = []
import atexit
def _reap():
    for p in procs:
        if p.poll() is None:
            p.kill()
atexit.register(_reap)

def spawn(*argv):
    p = subprocess.Popen([sys.executable, "-m", "tpu_tfrecord.service", *argv],
                         stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                         text=True, env=env)
    procs.append(p)
    return p, json.loads(p.stdout.readline())

journals = [os.path.join(base, f"journal-{i}.jsonl") for i in range(2)]
prim, addrs = [], []
for i in range(2):
    p, ready = spawn("dispatcher", "--journal", journals[i],
                     "--partition", str(i), "--lease-ttl-s", "10")
    prim.append(p)
    addrs.append(ready["addr"])
standby_p, standby_ready = spawn(
    "dispatcher", "--journal", journals[owner],
    "--standby-of", addrs[owner], "--partition", str(owner),
    "--lease-ttl-s", "10", "--ping-interval", "0.2",
    "--takeover-misses", "3")
groups = list(addrs)
groups[owner] = f"{addrs[owner]}|{standby_ready['addr']}"
spec = ",".join(groups)

for _ in range(2):
    spawn("worker", "--dispatcher", spec)
deadline = time.monotonic() + 60
while time.monotonic() < deadline:
    counts = [len(service.fetch_status(a).get("workers", [])) for a in addrs]
    if counts == [2, 2]:
        break
    time.sleep(0.05)
assert counts == [2, 2], f"workers never registered everywhere: {counts}"

killed = []
def yield_hook(rows, ds):
    if killed or len(rows) < 40:
        return
    os.kill(prim[owner].pid, signal.SIGKILL)  # mid-read, no warning
    prim[owner].wait()
    killed.append(owner)

METRICS.reset()
got = epoch_rows(service=spec, service_deadline_ms=10000)
assert killed, "epoch ended before the kill hook fired"
assert got == local, "post-failover epoch rows differ from direct local read"
assert METRICS.counter("service.fallbacks") == 0, "degraded to local reads"

doc = subprocess.run([sys.executable, "tools/tfrecord_doctor.py",
                      "serve-status", spec],
                     capture_output=True, text=True)
assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
lines = [json.loads(l) for l in doc.stdout.splitlines() if l.strip()]
svc = [l for l in lines if l.get("event") == "service"
       and l.get("partition") == owner][0]
assert svc.get("failed_over") and svc.get("generation", 0) >= 1, svc
ha = [l for l in lines if l.get("event") == "ha"][0]
assert ha["answered"] == 2 and ha["failed_over"] >= 1, ha

for p in procs:
    if p.poll() is None:
        p.terminate()
for p in procs:
    if p.poll() is None:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
print("HA smoke OK:", json.dumps({
    "rows": len(got),
    "owner_partition": owner,
    "failed_over_generation": svc.get("generation"),
    "reconnects": METRICS.counter("service.reconnects"),
}))
PY

echo "== tier-1 tests =="
set -o pipefail
rm -rf /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 1470 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors \
    -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml \
    -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
