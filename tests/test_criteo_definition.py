"""The program's one Criteo definition (``examples/criteo.py``) and the
yardstick's own copy (``benchmark/harness/criteo_io.py``) say the same thing.

The copy is deliberate: the benchmark must not move when the program's entry
scripts do. What keeps the two from drifting apart is this file, which reads
the yardstick and edits nothing there.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "examples"))

import criteo  # noqa: E402  (examples/criteo.py)
from benchmark.harness import criteo_io  # noqa: E402


@pytest.mark.parametrize(
    "name", ["NUM_DENSE", "NUM_CAT", "HASH_BUCKETS", "CAT_BITS"]
)
def test_constants_agree(name):
    assert getattr(criteo, name) == getattr(criteo_io, name)


@pytest.mark.parametrize("schema_fn", ["criteo_schema", "criteo_read_schema"])
def test_schemas_agree_field_for_field(schema_fn):
    ours = getattr(criteo, schema_fn)()
    theirs = getattr(criteo_io, schema_fn)()
    assert len(ours.fields) == len(theirs.fields) == 1 + 13 + 26
    for a, b in zip(ours.fields, theirs.fields):
        assert (a.name, a.data_type, a.nullable) == (b.name, b.data_type, b.nullable)


def test_reader_specs_agree():
    assert criteo.criteo_reader_spec() == criteo_io.criteo_reader_spec()


@pytest.mark.parametrize("vocab", [1 << 20, 1 << 14], ids=["as_hashed", "folded"])
def test_split_wire_agrees_bitwise(vocab):
    from tpu_tfrecord.tpu import pack_mixed

    rng = np.random.default_rng(29)
    packed = np.concatenate(
        [
            rng.integers(0, 2, size=(64, 1)),
            rng.integers(0, 1 << 31, size=(64, criteo.NUM_DENSE)),
            rng.integers(0, criteo.HASH_BUCKETS, size=(64, criteo.NUM_CAT)),
        ],
        axis=1,
    ).astype(np.int32)
    gb = {"wire": pack_mixed(packed, 1 + criteo.NUM_DENSE, criteo.CAT_BITS)}
    ours = criteo.split_wire(gb, vocab)
    theirs = criteo_io.split_wire(gb, vocab)
    assert set(ours) == set(theirs) == {"label", "dense", "cat"}
    for k in ours:
        a, b = np.asarray(ours[k]), np.asarray(theirs[k])
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), k
    # and the split undoes the pack: the indices that went in come out
    assert np.array_equal(np.asarray(ours["cat"]), packed[:, 1 + criteo.NUM_DENSE:] % vocab)


def test_write_dataset_is_read_back_by_the_read_schema_and_spec(tmp_path):
    from tpu_tfrecord.io.dataset import TFRecordDataset
    from tpu_tfrecord.tpu import host_batch_from_columnar

    data = str(tmp_path / "criteo")
    assert criteo.write_dataset(data, seed=3, shards=3, rows_per_shard=128) == 384
    assert len([f for f in os.listdir(data) if f.endswith(".tfrecord")]) == 3
    hash_buckets, pack = criteo.criteo_reader_spec()
    ds = TFRecordDataset(
        data, batch_size=64, schema=criteo.criteo_read_schema(), num_epochs=1,
        hash_buckets=hash_buckets, pack=pack,
    )
    rows = 0
    with ds.batches() as it:
        for cb in it:
            m = host_batch_from_columnar(
                cb, ds.schema, hash_buckets=hash_buckets, pack=pack
            )["packed"]
            assert m.shape == (64, 40) and m.dtype == np.int32
            cat = m[:, 1 + criteo.NUM_DENSE:]
            assert cat.min() >= 0 and cat.max() < criteo.HASH_BUCKETS
            rows += m.shape[0]
    assert rows == 384
