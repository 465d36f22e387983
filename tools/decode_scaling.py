"""Measured multi-worker decode scaling, recorded as a JSON artifact.

The scaling test that runs on multi-core CI is pass/fail only. This tool
produces the NUMBER (a host-clock reading of the runner, not a device
metric): it writes a Criteo-shaped dataset (examples/criteo.py), measures
sustained decode throughput at
num_workers = 1 and N (default: min(4, cores)), and prints one JSON line

    {"metric": "decode_scaling", "workers": N, "t1_ex_s": ..., "tn_ex_s":
     ..., "ratio": ..., "cores": ...}

CI uploads this as the decode-scaling artifact.
Exit code is 0 even for poor ratios on busy runners — the artifact records,
the perf-tier test (tests/test_pipeline_features.py) enforces.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "examples"))

from criteo import criteo_schema, write_dataset

from tpu_tfrecord import _native
from tpu_tfrecord.io.dataset import TFRecordDataset

SHARDS = int(os.environ.get("TFR_SCALING_SHARDS", 8))
ROWS_PER_SHARD = int(os.environ.get("TFR_SCALING_ROWS", 20_000))
WORKERS = int(os.environ.get("TFR_SCALING_WORKERS", 0)) or min(
    4, os.cpu_count() or 1
)
BATCH = 8192
if SHARDS * ROWS_PER_SHARD < 2 * BATCH:
    raise SystemExit(
        f"TFR_SCALING_SHARDS*TFR_SCALING_ROWS = {SHARDS * ROWS_PER_SHARD} "
        f"rows yields < 2 batches of {BATCH} (warmup consumes one; the "
        f"measurement needs at least one more) — raise the knobs"
    )

SCHEMA = criteo_schema()


def run(out: str, workers: int, **ds_kw) -> float:
    """Sustained decode throughput (ex/s), first batch excluded (warmup)."""
    ds = TFRecordDataset(
        out, batch_size=BATCH, schema=SCHEMA, num_workers=workers, **ds_kw
    )
    with ds.batches() as it:
        next(it)
        t0 = time.perf_counter()
        n = 0
        for b in it:
            n += b.num_rows
        dt = time.perf_counter() - t0
    return n / dt


def main() -> None:
    if not _native.available():
        print(json.dumps({"metric": "decode_scaling", "skipped": "no native"}))
        return
    with tempfile.TemporaryDirectory(prefix="tfr_scaling_") as d:
        out = os.path.join(d, "ds")
        write_dataset(out, seed=7, shards=SHARDS, rows_per_shard=ROWS_PER_SHARD)
        t1 = max(run(out, 1), run(out, 1))
        tn = max(run(out, WORKERS), run(out, WORKERS))
        # Cached-read series (ISSUE 4): the mmap-served columnar epoch
        # cache replaces decode entirely, so its single-worker rate is the
        # ceiling decode-worker scaling chases — tn approaching tc means
        # more workers only re-derive what one cache pass serves for free.
        cache_kw = dict(cache="auto", cache_dir=os.path.join(d, "cache"))
        run(out, 1, **cache_kw)  # populate pass (decode + cache append)
        tc = max(run(out, 1, **cache_kw), run(out, 1, **cache_kw))
    print(
        json.dumps(
            {
                "metric": "decode_scaling",
                "workers": WORKERS,
                "t1_ex_s": round(t1),
                "tn_ex_s": round(tn),
                "ratio": round(tn / t1, 3),
                "cached_ex_s": round(tc),
                "cached_vs_t1": round(tc / t1, 3),
                "cores": os.cpu_count(),
            }
        )
    )


if __name__ == "__main__":
    main()
