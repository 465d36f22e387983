#!/usr/bin/env python
"""Write-path benchmark: Criteo-shaped columnar batches -> TFRecord shards.

The materialization half of the BASELINE.md north-star: examples/sec
serialized + framed (CRC32C) + codec-compressed + committed to disk through
DatasetWriter.write_batches, for the Criteo write schema of
examples/criteo.py (int64 label, 13 int64 dense, 26 categorical byte strings).

Measures the sequential legacy path (write_workers=1) and the parallel slab
pipeline (write_workers=N, num_shards=S) for both uncompressed and zlib
output, and prints ONE JSON line: {"metric", "value", "unit",
"vs_baseline"} where value is the parallel rate for the default codec and
vs_baseline is value / 1e6. The writer has no cell in BENCHMARK.json yet:
these are host-clock readings of whatever machine runs the script.

Methodology (this is a SHARED box):
- sequential and parallel reps are INTERLEAVED and each side reports its
  best-of (one-sided noise: other tenants only slow a rep down);
- ``parallel_scaling_probe`` is measured first: the wall-clock scaling of
  two plain threads running zlib.compress concurrently (GIL released, no
  pipeline) — the box's attainable parallel ceiling. On a host with P real
  cores this approaches min(P, workers); on SMT-shared or host-contended
  vCPUs it can be well under 2, and then NO writer can reach 2x. The
  disclosed ``speedup_vs_attainable`` (speedup / probe) is the pipeline's
  efficiency against that ceiling.

Env knobs: TFR_BENCH_WRITE_WORKERS (4), TFR_BENCH_WRITE_SHARDS (4),
TFR_BENCH_WRITE_CODEC (zlib; 'none' for uncompressed headline),
TFR_BENCH_WRITE_BATCH (16384), TFR_BENCH_WRITE_BATCHES (6),
TFR_BENCH_WRITE_REPS (3 interleaved pairs), TFR_BENCH_WRITE_DIR.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "examples"))

import numpy as np

from criteo import criteo_schema, random_batch

BATCH = int(os.environ.get("TFR_BENCH_WRITE_BATCH", 16384))
N_BATCHES = int(os.environ.get("TFR_BENCH_WRITE_BATCHES", 6))
WORKERS = int(os.environ.get("TFR_BENCH_WRITE_WORKERS", 4))
SHARDS = int(os.environ.get("TFR_BENCH_WRITE_SHARDS", 4))
REPS = int(os.environ.get("TFR_BENCH_WRITE_REPS", 3))
CODEC = os.environ.get("TFR_BENCH_WRITE_CODEC", "zlib")


def make_batches(schema):
    """Criteo-shaped ColumnarBatches built directly from numpy buffers (no
    per-row Python) so the benchmark measures the writer, not the setup."""
    rng = np.random.default_rng(0)
    return [random_batch(rng, schema, BATCH) for _ in range(N_BATCHES)]


def parallel_scaling_probe() -> float:
    """Attainable 2-thread scaling for GIL-free compression on this box:
    wall(1 thread doing 2N units) / wall(2 threads doing N units each).
    2.0 = two real unshared cores; ~1.0 = no parallelism to win."""
    import zlib

    data = os.urandom(4 << 20)
    n = 3

    def spin(count):
        for _ in range(count):
            zlib.compress(data)

    spin(1)  # warm
    t0 = time.perf_counter()
    spin(2 * n)
    serial = time.perf_counter() - t0
    threads = [threading.Thread(target=spin, args=(n,)) for _ in range(2)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dual = time.perf_counter() - t0
    return serial / dual


def run_once(schema, batches, out_dir, codec, workers, num_shards, trace="off"):
    """One full write_batches job (encode + frame + compress + commit);
    returns (examples/sec, METRICS 'write' family snapshot, occupancy).
    ``occupancy`` is the slab pipeline's in-flight fill EMA (None for the
    sequential path) — telemetry.boundness_verdict reads it as
    committer-bound (high) vs encode-bound (low)."""
    from tpu_tfrecord.io.writer import DatasetWriter
    from tpu_tfrecord.metrics import METRICS
    from tpu_tfrecord.options import TFRecordOptions

    opts = TFRecordOptions.from_map(
        codec=None if codec in (None, "none") else codec,
        write_workers=workers,
        num_shards=num_shards,
        trace=trace,
    )
    n_examples = sum(b.num_rows for b in batches)
    METRICS.reset()
    writer = DatasetWriter(out_dir, schema, opts, mode="overwrite")
    t0 = time.perf_counter()
    writer.write_batches(batches)
    rate = n_examples / (time.perf_counter() - t0)
    stages = METRICS.snapshot("write")
    occupancy = METRICS.gauge_value("write.occupancy")
    shutil.rmtree(out_dir, ignore_errors=True)
    return rate, stages, occupancy


def measure_pair(schema, batches, out_dir, codec):
    """Interleaved best-of-REPS for sequential vs parallel under the same
    ambient load; returns (seq_best, par_best, par_best_stages, par_occ)."""
    run_once(schema, batches, out_dir, codec, 1, None)  # warm both paths
    run_once(schema, batches, out_dir, codec, WORKERS, SHARDS)
    seq_best, par_best, par_stages, par_occ = 0.0, 0.0, {}, None
    for _ in range(REPS):
        seq, _, _ = run_once(schema, batches, out_dir, codec, 1, None)
        par, stages, occ = run_once(
            schema, batches, out_dir, codec, WORKERS, SHARDS
        )
        seq_best = max(seq_best, seq)
        if par > par_best:
            par_best, par_stages, par_occ = par, stages, occ
    return seq_best, par_best, par_stages, par_occ


def tracing_overhead(schema, batches, out_dir, codec):
    """Flight-recorder overhead on the parallel write path: interleaved
    trace-off/trace-on reps, best-of-each (one-sided noise: other tenants
    only slow a rep down). Returns the overhead pct (negative = in the
    noise)."""
    from tpu_tfrecord import telemetry as tm

    off_best, on_best = 0.0, 0.0
    for r in range(REPS):
        order = (("off",), ("on",)) if r % 2 == 0 else (("on",), ("off",))
        for (mode,) in order:
            if mode == "on":
                tm.RECORDER.clear()
            rate, _, _ = run_once(
                schema, batches, out_dir, codec, WORKERS, SHARDS, trace=mode
            )
            tm.disable()
            if mode == "on":
                on_best = max(on_best, rate)
            else:
                off_best = max(off_best, rate)
    tm.RECORDER.clear()
    return round((1.0 - on_best / off_best) * 100.0, 2) if off_best else None


def main() -> None:
    from tpu_tfrecord.telemetry import boundness_verdict, quantiles_ms

    schema = criteo_schema()
    batches = make_batches(schema)
    work_dir = os.environ.get("TFR_BENCH_WRITE_DIR") or tempfile.mkdtemp(
        prefix="tpu_tfrecord_bench_write_"
    )
    out_dir = os.path.join(work_dir, "out")
    probe = parallel_scaling_probe()
    results, breakdowns, quantiles, occupancies = {}, {}, {}, {}
    for codec in ("none", "zlib"):
        seq, par, stages, occ = measure_pair(schema, batches, out_dir, codec)
        results[codec] = (seq, par)
        # gauges share the snapshot namespace with distinct shapes — only
        # stage entries carry "seconds"
        breakdowns[codec] = {
            name: round(st["seconds"], 3)
            for name, st in sorted(stages.items())
            if "seconds" in st
        }
        quantiles[codec] = quantiles_ms(stages)
        occupancies[codec] = occ
    trace_pct = tracing_overhead(schema, batches, out_dir, "zlib")
    shutil.rmtree(work_dir, ignore_errors=True)

    headline = {"": "none", "none": "none", "zlib": "zlib", "deflate": "zlib"}.get(
        CODEC
    )
    if headline is None:
        raise SystemExit(
            f"TFR_BENCH_WRITE_CODEC={CODEC!r} is not measured by this bench "
            "(supported: none, zlib/deflate)"
        )
    seq, par = results[headline]
    speedup = par / seq if seq else None
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        cores = os.cpu_count() or 1
    out = {
        "metric": "criteo_tf_example_write_to_disk",
        "value": round(par, 1),
        "unit": "examples/sec/host",
        # BASELINE.md's >=1M examples/s, as a ratio
        "vs_baseline": round(par / 1_000_000, 4),
        "codec": None if headline == "none" else "deflate",
        "write_workers": WORKERS,
        "num_shards": SHARDS,
        "examples": BATCH * N_BATCHES,
        "seq_value": round(seq, 1),
        "speedup": round(speedup, 2) if speedup else None,
        # the box's measured parallel ceiling and our efficiency against it:
        # 2 unshared cores -> probe ~2.0 and speedup reads directly against
        # the >=2x target; SMT/host-contended vCPUs cap the probe (and any
        # writer) below that
        "cores": cores,
        "parallel_scaling_probe": round(probe, 2),
        "speedup_vs_attainable": round(speedup / probe, 2) if speedup else None,
        "uncompressed_value": round(results["none"][1], 1),
        "uncompressed_seq_value": round(results["none"][0], 1),
        "uncompressed_speedup": round(
            results["none"][1] / results["none"][0], 2
        ) if results["none"][0] else None,
        "zlib_value": round(results["zlib"][1], 1),
        "zlib_seq_value": round(results["zlib"][0], 1),
        "zlib_speedup": round(
            results["zlib"][1] / results["zlib"][0], 2
        ) if results["zlib"][0] else None,
        # per-stage wall seconds of the best parallel rep (worker stages sum
        # across threads, so encode+compress can exceed the job wall time —
        # that overlap is the point)
        "breakdown_seconds": breakdowns[headline],
        # flight-recorder A/B on the parallel path (ISSUE 5 acceptance:
        # <= 2%; negative = in the noise)
        "tracing_overhead_pct": trace_pct,
        # per-stage latency quantiles (always-on histograms) + the write
        # pipeline's bound-ness: "consumer_bound" = the committer (IO) is
        # the bottleneck, "producer_bound" = encode/planner is
        "telemetry": {
            "quantiles": quantiles[headline],
            "write_occupancy": (
                round(occupancies[headline], 4)
                if occupancies[headline] is not None
                else None
            ),
            "verdict": boundness_verdict(occupancies[headline]),
        },
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
