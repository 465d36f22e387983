"""The data shape ``criteo``: Criteo-shaped TFRecord shards from ``--seed``,
and what the reader must make of them. ``run.py`` finds this module by a
configuration's ``"data"`` and asks it for :func:`write` and
:func:`describe`; the rest is the generator.

A copy of ``chip_smoke.write_dataset`` (commit 0c9422d) with two changes. A
categorical column is not uniform over 16^8 values but draws a rank from
that column's published cardinality ``n`` under a power law,
``rank = floor(n ** u)`` with ``u`` uniform in [0, 1), and writes the rank
through a seeded 32-bit bijection as 8 lower-case hex characters. Real
Criteo columns with 3 to 100 values repeat thousands of times in a batch,
and the step's dedup and scatter exist for exactly that. And a label is 1
at the data set's published click rate, not at one half: under balanced
labels on seeded weights the gradient is the noise of a batch around zero,
and two sound programs' second steps then differ by percents of its norm
(measured, PERF.md).

Besides writing, it returns the ``[rows, 40]`` int32 matrix the reader has
to produce, in the order the reader walks the files (sorted names), made by
nothing under test: label, the 13 ints as int32, ``crc32c(value) % 2^20``.
"""

from __future__ import annotations

import os
import shutil

import numpy as np

from benchmark.harness import criteo_io
from benchmark.harness.crc32c import crc32c_rows

_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)
_M32 = np.uint64(0xFFFFFFFF)


def mix32(x: np.ndarray) -> np.ndarray:
    """A bijection on 32-bit words (murmur3's finalizer), uint32 in and out."""
    x = np.asarray(x, dtype=np.uint64) & _M32
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x85EBCA6B)) & _M32
    x ^= x >> np.uint64(13)
    x = (x * np.uint64(0xC2B2AE35)) & _M32
    x ^= x >> np.uint64(16)
    return x.astype(np.uint32)


def hex8(words: np.ndarray) -> np.ndarray:
    """uint32 ``[n]`` -> ``[n, 8]`` uint8 of lower-case hex digits."""
    shifts = np.arange(28, -4, -4, dtype=np.uint32)
    return _HEX[(words[:, None] >> shifts[None, :]) & np.uint32(0xF)]


def column_values(rng, n_rows: int, cardinality: int, exponent: float,
                  col_seed: int) -> np.ndarray:
    """One categorical column as ``[n_rows, 8]`` hex bytes."""
    u = rng.random(n_rows)
    rank = np.floor(float(cardinality) ** (u ** exponent)).astype(np.uint32)
    return hex8(mix32(rank ^ np.uint32(col_seed)))


def shard_columns(seed: int, shard: int, n_rows: int, cardinalities, exponent,
                  positive_rate: float):
    """(label [n] i64, dense [n, 13] i64, cats: list of [n, 8] u8) of one shard."""
    rng = np.random.default_rng([int(seed), int(shard)])
    label = (rng.random(n_rows) < positive_rate).astype(np.int64)
    dense = rng.integers(0, 1 << 31, size=(n_rows, criteo_io.NUM_DENSE), dtype=np.int64)
    col_seeds = mix32(np.uint64(int(seed) & 0xFFFFFFFF)
                      + np.arange(1, len(cardinalities) + 1, dtype=np.uint64)
                      * np.uint64(0x9E3779B1))
    cats = [
        column_values(rng, n_rows, int(card), exponent, int(cs))
        for card, cs in zip(cardinalities, col_seeds)
    ]
    return label, dense, cats


def expected_rows(label, dense, cats) -> np.ndarray:
    """What the reader owes for these columns: ``[n, 40]`` int32."""
    out = np.empty((label.shape[0], 1 + criteo_io.NUM_DENSE + len(cats)), np.int32)
    out[:, 0] = label
    out[:, 1:criteo_io.KEEP] = dense.astype(np.int32)
    for i, c in enumerate(cats):
        out[:, criteo_io.KEEP + i] = crc32c_rows(c) % np.uint32(criteo_io.HASH_BUCKETS)
    return out


def write_dataset(data_dir: str, seed: int, shards: int, rows_per_shard: int,
                  cardinalities, exponent: float, positive_rate: float) -> np.ndarray:
    """Write the shards with the framework's columnar writer (one append
    job a shard, the shard's number as its task: the reader walks sorted
    names, so the seed fixes the order too, where the job's random id alone
    would decide it); returns the expected matrix in reading order."""
    from tpu_tfrecord.columnar import Column, ColumnarBatch
    from tpu_tfrecord.io.writer import DatasetWriter
    from tpu_tfrecord.options import TFRecordOptions

    if len(cardinalities) != criteo_io.NUM_CAT:
        raise ValueError(f"need {criteo_io.NUM_CAT} cardinalities, got {len(cardinalities)}")
    schema = criteo_io.criteo_schema()
    shutil.rmtree(data_dir, ignore_errors=True)
    n = rows_per_shard
    offsets = np.arange(n + 1, dtype=np.int64) * 8
    by_file = {}
    for shard in range(shards):
        label, dense, cats = shard_columns(seed, shard, n, cardinalities, exponent,
                                           positive_rate)
        cols = {"label": Column("label", schema["label"].data_type, values=label)}
        for i in range(criteo_io.NUM_DENSE):
            name = f"I{i + 1}"
            cols[name] = Column(name, schema[name].data_type,
                                values=np.ascontiguousarray(dense[:, i]))
        for i, c in enumerate(cats):
            name = f"C{i + 1}"
            cols[name] = Column(name, schema[name].data_type,
                                blob=c.tobytes(), blob_offsets=offsets)
        before = set(os.listdir(data_dir)) if os.path.isdir(data_dir) else set()
        DatasetWriter(
            data_dir, schema, TFRecordOptions.from_map(), mode="append"
        ).write_batches([ColumnarBatch(cols, n)], task_id=shard)
        new = [f for f in set(os.listdir(data_dir)) - before if f.endswith(".tfrecord")]
        if len(new) != 1:
            raise RuntimeError(f"shard {shard}: the writer left {len(new)} new files")
        by_file[new[0]] = expected_rows(label, dense, cats)
    return np.concatenate([by_file[f] for f in sorted(by_file)], axis=0)


def write(data_dir: str, seed: int, cfg: dict, mix: dict) -> np.ndarray:
    """The seed's shards for a configuration and a mix; returns the expected
    matrix (``env.expected``), which the Criteo loops hold the reader to."""
    return write_dataset(data_dir, seed, mix["shards"], mix["rows_per_shard"],
                         cfg["cardinalities"], cfg["key_law_exponent"],
                         cfg["label_positive_rate"])


def distinct_key_share(expected: np.ndarray, cfg: dict, batch: int) -> float:
    """Distinct (table, row) keys of the first batch over all of its keys:
    the step's sort, dedup and scatter take as long as the key law says."""
    keep, n_v = 1 + cfg["num_dense"], cfg["rows_per_table"]
    cat = expected[:batch, keep:].astype(np.int64) % n_v
    keys = cat + np.arange(cat.shape[1], dtype=np.int64)[None, :] * n_v
    return float(np.unique(keys).size / keys.size)


def describe(expected: np.ndarray, cfg: dict, mix: dict) -> dict:
    """This data shape's fields of the ``[data]`` line."""
    return {"distinct_key_share": distinct_key_share(expected, cfg, mix["batch"])}
