"""The Criteo workload as the program's entry scripts know it.

One definition for ``chip_smoke.py``, ``bench_write.py``,
``examples/criteo_prepare.py`` and ``examples/train_dlrm.py``: the column
names (``label``, ``I1..I13``, ``C1..C26``), 13 dense and 26 categorical
features, 2^20 hash buckets, 20 bits an index on the wire, the ``[B, 40]``
int32 pack, log1p on the dense ints. The package itself knows no Criteo.
``benchmark/harness/criteo_io.py`` is the yardstick's own copy, kept apart on
purpose; ``tests/test_criteo_definition.py`` holds the two equal.

Scripts reach this module with its directory on ``sys.path`` (as they reach
``_harness``). It imports no jax: ``chip_smoke.py``'s parent holds no chip.
"""

from __future__ import annotations

import os
import shutil

import numpy as np

NUM_DENSE, NUM_CAT = 13, 26
DENSE_COLS = [f"I{i}" for i in range(1, NUM_DENSE + 1)]
CAT_COLS = [f"C{i}" for i in range(1, NUM_CAT + 1)]
HASH_BUCKETS = 1 << 20
CAT_BITS = 20  # hash_buckets = 2**20 -> bucket indices carry 20 bits


def criteo_schema():
    """Write-side schema (inference parity: ints are LongType)."""
    from tpu_tfrecord.schema import LongType, StringType, StructField, StructType

    fields = [StructField("label", LongType(), nullable=False)]
    fields += [StructField(c, LongType()) for c in DENSE_COLS]
    fields += [StructField(c, StringType()) for c in CAT_COLS]
    return StructType(fields)


def criteo_read_schema():
    """Read-side schema: IntegerType for the int features — the reference's
    IntegerType read path (Long.toInt truncation, TFRecordDeserializer
    IntegerType case) — so every device-bound column is int32 and the whole
    batch packs into ONE [B, 40] i32 matrix (one transfer dispatch)."""
    from tpu_tfrecord.schema import IntegerType, StringType, StructField, StructType

    fields = [StructField("label", IntegerType(), nullable=False)]
    fields += [StructField(c, IntegerType()) for c in DENSE_COLS]
    fields += [StructField(c, StringType()) for c in CAT_COLS]
    return StructType(fields)


def criteo_reader_spec():
    """(hash_buckets, pack) of the Criteo reader: categorical hashing to
    2^20 buckets fused into the native decode, and ONE column group = one
    [B, 40] i32 host matrix = ONE device transfer (the consumer jit splits
    label/dense/cat on device, free under XLA fusion)."""
    hash_buckets = {c: HASH_BUCKETS for c in CAT_COLS}
    return hash_buckets, {"packed": ["label"] + DENSE_COLS + CAT_COLS}


def criteo_dlrm_config(vocab: int, top_mlp=(64, 1), **kw):
    """The smoke's DLRM: 26 tables x ``vocab`` x 32, bottom 64-32, dot
    interaction (bf16 activations unless ``dtype`` is given)."""
    from tpu_tfrecord.models import DLRMConfig

    return DLRMConfig(
        num_dense=NUM_DENSE, num_categorical=NUM_CAT, vocab_size=vocab,
        embed_dim=32, bottom_mlp=(64, 32), top_mlp=top_mlp,
        interaction="dot", **kw,
    )


def split_wire(gb, vocab: int):
    """The consumer-side split of the bit-packed wire batch: label / 13
    dense / 26 categorical indices, the 20-bit unpack fused into the
    caller's jit (the train step is a separate program — its donated
    params preclude merging here). Dense ints get the standard Criteo
    log1p so SGD steps stay finite; indices fold only when the table is
    smaller than the hashed space (CPU smoke runs shrink it)."""
    import jax.numpy as jnp

    from tpu_tfrecord.tpu import unpack_bits

    m = gb["wire"]
    cat = unpack_bits(m[:, 1 + NUM_DENSE:], NUM_CAT, CAT_BITS)
    return {
        "label": m[:, 0].astype(jnp.float32),
        "dense": jnp.log1p(m[:, 1:1 + NUM_DENSE].astype(jnp.float32)),
        "cat": cat % vocab if vocab < HASH_BUCKETS else cat,
    }


def random_batch(rng, schema, n: int):
    """One ColumnarBatch of ``n`` Criteo-shaped rows straight from numpy
    buffers (no per-row Python): labels 0/1, dense ints under 2^31,
    categoricals of 8 bytes ``a``-``p``."""
    from tpu_tfrecord.columnar import Column, ColumnarBatch

    offsets = np.arange(n + 1, dtype=np.int64) * 8
    cols = {
        "label": Column(
            "label", schema["label"].data_type,
            values=rng.integers(0, 2, size=n, dtype=np.int64),
        )
    }
    for c in DENSE_COLS:
        cols[c] = Column(
            c, schema[c].data_type,
            values=rng.integers(0, 1 << 31, size=n, dtype=np.int64),
        )
    for c in CAT_COLS:
        blob = (rng.integers(0, 16, size=n * 8, dtype=np.uint8) + 97).tobytes()
        cols[c] = Column(c, schema[c].data_type, blob=blob, blob_offsets=offsets)
    return ColumnarBatch(cols, n)


def write_dataset(data_dir: str, seed: int, shards: int, rows_per_shard: int) -> int:
    """Write ``shards`` TFRecord files of ``rows_per_shard`` Criteo-shaped
    rows with the framework's columnar writer (one append job per shard, so
    the layout holds at any size). Returns rows written."""
    from tpu_tfrecord.io.writer import DatasetWriter
    from tpu_tfrecord.options import TFRecordOptions

    schema = criteo_schema()
    shutil.rmtree(data_dir, ignore_errors=True)
    rng = np.random.default_rng(seed)
    for _ in range(shards):
        DatasetWriter(
            data_dir, schema, TFRecordOptions.from_map(), mode="append"
        ).write_batches([random_batch(rng, schema, rows_per_shard)])
    files = [f for f in os.listdir(data_dir) if f.endswith(".tfrecord")]
    if len(files) != shards:
        raise RuntimeError(f"wrote {len(files)} shard files, wanted {shards}")
    return shards * rows_per_shard
