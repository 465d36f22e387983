#!/usr/bin/env python
"""Prepare Criteo click-log TSV data as TFRecord shards.

The dataset-prep half of the pipeline: raw Criteo TSV (label \\t 13 integer
features \\t 26 hex categorical features, empty field = missing) becomes
TFRecord shards written through the native columnar encoder, under the
schema of examples/criteo.py — the files examples/train_dlrm.py then streams
into the TPU.

Usage:
    python examples/criteo_prepare.py [input.tsv] [output_dir]

With no arguments it generates a small synthetic TSV first (demo mode).
ColumnarBatches are built straight from parsed numpy columns (values +
validity masks) — no per-row Example objects anywhere.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from criteo import NUM_CAT, NUM_DENSE, criteo_schema

from tpu_tfrecord.columnar import Column, ColumnarBatch
from tpu_tfrecord.io.writer import DatasetWriter
from tpu_tfrecord.options import TFRecordOptions
from tpu_tfrecord.schema import LongType, StringType

CHUNK_ROWS = 50_000


def rows_to_batch(lines) -> ColumnarBatch:
    """Parse TSV lines into a ColumnarBatch (values + masks, no rows)."""
    import itertools

    split = [ln.rstrip("\n").split("\t") for ln in lines]
    n = len(split)
    # one transpose instead of 40 per-column passes with bounds checks
    columns = list(itertools.zip_longest(*split, fillvalue=""))
    columns += [("",) * n] * (1 + NUM_DENSE + NUM_CAT - len(columns))
    labels_raw = columns[0]
    bad = next((i for i, v in enumerate(labels_raw) if not v.lstrip("-").isdigit()), None)
    if bad is not None:
        raise ValueError(
            f"bad label {labels_raw[bad]!r} in line: {lines[bad].rstrip()[:80]!r}"
        )
    cols = {}
    cols["label"] = Column(
        "label",
        LongType(),
        values=np.array([int(v) for v in labels_raw], dtype=np.int64),
        mask=np.ones(n, dtype=bool),
    )
    for i in range(NUM_DENSE):
        raw = columns[1 + i]
        mask = np.array([v != "" for v in raw], dtype=bool)
        vals = np.array([int(v) if v != "" else 0 for v in raw], dtype=np.int64)
        cols[f"I{i+1}"] = Column(f"I{i+1}", LongType(), values=vals, mask=mask)
    for i in range(NUM_CAT):
        raw = columns[1 + NUM_DENSE + i]
        mask = np.array([v != "" for v in raw], dtype=bool)
        col = Column(f"C{i+1}", StringType(), mask=mask)
        col.set_blobs([v.encode() for v in raw])
        cols[f"C{i+1}"] = col
    return ColumnarBatch(cols, n)


def generate_demo_tsv(path: str, rows: int = 20_000) -> None:
    rng = np.random.default_rng(0)
    with open(path, "w") as fh:  # graftlint: allow(atomic-write: demo input generator; a torn file is re-generated, never served)
        for _ in range(rows):
            parts = [str(int(rng.integers(0, 2)))]
            for _ in range(NUM_DENSE):
                parts.append(
                    "" if rng.random() < 0.1 else str(int(rng.integers(0, 10_000)))
                )
            for _ in range(NUM_CAT):
                parts.append(
                    "" if rng.random() < 0.05 else f"{int(rng.integers(0, 1 << 32)):08x}"
                )
            fh.write("\t".join(parts) + "\n")


def prepare(tsv_path: str, out_dir: str) -> None:
    schema = criteo_schema()
    writer = DatasetWriter(
        out_dir,
        schema,
        TFRecordOptions(),
        mode="overwrite",
        max_records_per_file=500_000,
    )

    def batches():
        with open(tsv_path) as fh:
            chunk = []
            for line in fh:
                if not line.strip():
                    continue  # tolerate stray blank lines
                chunk.append(line)
                if len(chunk) >= CHUNK_ROWS:
                    yield rows_to_batch(chunk)
                    chunk = []
            if chunk:
                yield rows_to_batch(chunk)

    files = writer.write_batches(batches())
    print(f"wrote {len(files)} shard(s) to {out_dir}")


def main() -> None:
    if len(sys.argv) >= 3:
        tsv, out = sys.argv[1], sys.argv[2]
    elif len(sys.argv) == 2:
        tsv = sys.argv[1]
        out = tsv + ".tfrecords"
        print(f"no output dir given; writing to {out}")
    else:
        base = "/tmp/tpu_tfrecord_criteo"
        os.makedirs(base, exist_ok=True)
        tsv = os.path.join(base, "demo.tsv")
        out = os.path.join(base, "tfrecords")
        if not os.path.exists(tsv):
            print("demo mode: generating synthetic Criteo TSV ...")
            generate_demo_tsv(tsv)
    prepare(tsv, out)

    # sanity: stream it back the way training would
    schema = criteo_schema()
    from tpu_tfrecord.io.dataset import TFRecordDataset

    ds = TFRecordDataset(out, batch_size=4096, schema=schema, drop_remainder=False)
    total = 0
    missing_I1 = 0
    with ds.batches() as it:
        for cb in it:
            total += cb.num_rows
            missing_I1 += int((~cb["I1"].mask).sum())
    print(f"read back {total} records; I1 missing in {missing_I1} ({missing_I1/total:.1%})")


if __name__ == "__main__":
    main()
