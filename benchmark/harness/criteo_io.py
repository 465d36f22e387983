"""The Criteo reader's schema, pack spec and consumer-side split.

Copied from ``bench.py`` (``criteo_schema``, ``criteo_read_schema``,
``criteo_reader_spec``, ``split_wire``) at commit 0c9422d, so that the
yardstick does not move when bench.py does. They describe how the system is
called, not how it is measured; the originals are listed in PERF.md's Open
questions for the PR that replaces bench.py.
"""

from __future__ import annotations

NUM_DENSE, NUM_CAT = 13, 26
HASH_BUCKETS = 1 << 20
CAT_BITS = 20  # 2**20 buckets -> 20 bits per index on the wire
KEEP = 1 + NUM_DENSE  # label + dense lanes pass through the wire verbatim


def criteo_schema():
    """Write-side schema: int64 label, 13 int64 dense, 26 byte strings."""
    from tpu_tfrecord.schema import LongType, StringType, StructField, StructType

    fields = [StructField("label", LongType(), nullable=False)]
    fields += [StructField(f"I{i}", LongType()) for i in range(1, NUM_DENSE + 1)]
    fields += [StructField(f"C{i}", StringType()) for i in range(1, NUM_CAT + 1)]
    return StructType(fields)


def criteo_read_schema():
    """Read-side schema: IntegerType ints (Long.toInt truncation), so every
    device-bound column is int32 and a batch packs into one [B, 40] matrix."""
    from tpu_tfrecord.schema import IntegerType, StringType, StructField, StructType

    fields = [StructField("label", IntegerType(), nullable=False)]
    fields += [StructField(f"I{i}", IntegerType()) for i in range(1, NUM_DENSE + 1)]
    fields += [StructField(f"C{i}", StringType()) for i in range(1, NUM_CAT + 1)]
    return StructType(fields)


def criteo_reader_spec():
    """(hash_buckets, pack): CRC32C hashing to 2^20 buckets fused into the
    native decode, and one column group = one [B, 40] int32 host matrix."""
    hash_buckets = {f"C{i}": HASH_BUCKETS for i in range(1, NUM_CAT + 1)}
    pack = {
        "packed": ["label"]
        + [f"I{i}" for i in range(1, NUM_DENSE + 1)]
        + [f"C{i}" for i in range(1, NUM_CAT + 1)],
    }
    return hash_buckets, pack


def split_wire(gb, vocab: int):
    """Wire batch -> label / log1p(dense) / indices, the 20-bit unpack fused
    into the caller's jit; indices fold ``% vocab`` only where the table is
    smaller than the hashed space."""
    import jax.numpy as jnp

    from tpu_tfrecord.tpu import unpack_bits

    m = gb["wire"]
    cat = unpack_bits(m[:, KEEP:], NUM_CAT, CAT_BITS)
    return {
        "label": m[:, 0].astype(jnp.float32),
        "dense": jnp.log1p(m[:, 1:KEEP].astype(jnp.float32)),
        "cat": cat % vocab if vocab < HASH_BUCKETS else cat,
    }
