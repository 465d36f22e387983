"""The data shape ``token_docs``: TFRecord shards of tokenised documents
from ``--seed``, and what the reader owes for them.

Every ``tf.Example`` holds one variable-length int64 list ``tokens``. A
document's length is lognormal (the configuration's ``doc_length``: mu,
sigma, clipped to [min, max]): most documents are a few hundred tokens and
most TOKENS sit in documents of thousands, as in a tokenised web corpus. A
token is a rank drawn under a Zipf law (``rank = floor(n ** u)``, ``u``
uniform: p(rank) ~ 1 / rank) over the ``vocab_size - 1`` ids the
configuration's slice of the vocabulary holds besides 0, written through a
seeded bijection; 0 is the packer's end-of-document id and no document
holds it. A shard's number is its writer task, so sorted names are the
order written and the same seed gives the same epoch.

``write`` returns the documents in the order the reader walks them, made by
nothing under test: a list of int32 arrays (``env.expected``).
"""

from __future__ import annotations

import os
import shutil

import numpy as np


def schema():
    from tpu_tfrecord.schema import ArrayType, LongType, StructField, StructType

    return StructType([StructField("tokens", ArrayType(LongType()))])


def doc_lengths(rng, count: int, law: dict) -> np.ndarray:
    raw = np.exp(rng.normal(law["mu"], law["sigma"], size=count))
    return np.clip(np.rint(raw), law["min"], law["max"]).astype(np.int64)


def shard_docs(seed: int, shard: int, count: int, cfg: dict):
    """(flat int64 tokens, offsets [count + 1]) of one shard."""
    rng = np.random.default_rng([int(seed), 0x444F43, int(shard)])
    lengths = doc_lengths(rng, count, cfg["doc_length"])
    offsets = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int64)
    ids = cfg["vocab_size"] - 1                       # 1 .. vocab_size - 1; 0 ends a document
    rank = np.minimum(np.floor(float(ids) ** rng.random(int(offsets[-1]))), ids - 1)
    bijection = np.random.default_rng([int(seed), 0x564F43]).permutation(ids)
    return bijection[rank.astype(np.int64)].astype(np.int64) + 1, offsets


def write(data_dir: str, seed: int, cfg: dict, mix: dict) -> list:
    from tpu_tfrecord.columnar import Column, ColumnarBatch
    from tpu_tfrecord.io.writer import DatasetWriter
    from tpu_tfrecord.options import TFRecordOptions

    shutil.rmtree(data_dir, ignore_errors=True)
    sch = schema()
    by_file = {}
    for shard in range(mix["shards"]):
        flat, offsets = shard_docs(seed, shard, mix["docs_per_shard"], cfg)
        col = Column("tokens", sch["tokens"].data_type, values=flat, offsets=offsets)
        before = set(os.listdir(data_dir)) if os.path.isdir(data_dir) else set()
        DatasetWriter(data_dir, sch, TFRecordOptions.from_map(), mode="append").write_batches(
            [ColumnarBatch({"tokens": col}, mix["docs_per_shard"])], task_id=shard)
        new = [f for f in set(os.listdir(data_dir)) - before if f.endswith(".tfrecord")]
        if len(new) != 1:
            raise RuntimeError(f"shard {shard}: the writer left {len(new)} new files")
        small = flat.astype(np.int32)
        by_file[new[0]] = [small[a:b] for a, b in zip(offsets[:-1], offsets[1:])]
    return [doc for name in sorted(by_file) for doc in by_file[name]]


def describe(expected: list, cfg: dict, mix: dict) -> dict:
    lengths = np.array([len(d) for d in expected])
    return {"tokens": int(lengths.sum()), "median_len": float(np.median(lengths)),
            "mean_len": float(lengths.mean()), "longest": int(lengths.max()),
            "at_max_share": float((lengths == cfg["doc_length"]["max"]).mean())}
