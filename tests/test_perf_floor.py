"""Red/green floors for the device-free decode paths, as RATIOS taken
inside each test (VERDICT r3 item 3).

A decode regression must be caught by CI as a failing test, not discovered
rounds later as a mysteriously slower loader. Each test times the
fast path and its own plain oracle on the same rows, windows interleaved so
both see the same load, checks that they yield the same bytes, and holds
the fast path to a conservative multiple of the oracle:

- Criteo shape: native frame scan + CRC + Example decode + fused hashing +
  column-group packing vs the pure-Python decoder (measures ~100x; floor
  ``NATIVE_OVER_ORACLE``);
- SequenceExample shape: fused native pad + bf16 cast vs the numpy fallback
  (measures 7-12x; floor ``FUSED_PAD_OVER_FALLBACK``).

These floors catch a fallback to the slow path or a lost fused stage. They
do not catch the 30% decode regression the old absolute floors aimed at:
no ratio against a 100x-slower oracle can, on a shared box. That guard
comes back as a bound on a benchmark cell (ROADMAP S1).

They used to be calibrated against a reference box's decode-per-microbench
ratio; that failed under the driver's six-worker load with the code
unchanged. A ratio of two timings from one test holds on any box. Absolute
rates belong to the benchmark, on the chip machine.

TFR_PERF_FLOOR_SELFTEST_PCT=30 degrades the fast path's measurement by 30%
before the assert (run by hand when touching the factors).
"""

import os
import time

import numpy as np
import pytest

from tpu_tfrecord import _native, wire
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.options import RecordType
from tpu_tfrecord.schema import (
    IntegerType,
    LongType,
    StringType,
    StructField,
    StructType,
)
from tpu_tfrecord.serde import TFRecordSerializer, encode_row

# red-path self-test: degrade the fast path's measurement by this percent
# before the assert (a hand tool for whoever retunes the factors)
_SELFTEST_SCALE = 1.0 - float(os.environ.get("TFR_PERF_FLOOR_SELFTEST_PCT", 0)) / 100.0
N_RECORDS = 16384
BATCH = 4096


def _write_criteo_shard(path: str, n: int) -> None:
    fields = [StructField("label", LongType(), nullable=False)]
    fields += [StructField(f"I{i}", LongType()) for i in range(1, 14)]
    fields += [StructField(f"C{i}", StringType()) for i in range(1, 27)]
    ser = TFRecordSerializer(StructType(fields))
    rng = np.random.default_rng(0)
    ints = rng.integers(0, 1 << 31, size=(n, 13))
    cats = rng.integers(0, 16, size=(n, 26, 8), dtype=np.uint8) + 97

    def rows():
        for r in range(n):
            row = [r & 1]
            row += [int(v) for v in ints[r]]
            row += [cats[r, c].tobytes().decode() for c in range(26)]
            yield encode_row(ser, RecordType.EXAMPLE, row)

    wire.write_records(path, rows())


#: the native decode+hash+pack path must beat the pure-Python oracle by at
#: least this factor on the same rows in the same test. It measures 96-111x
#: on an idle box and 124-228x with 8-16 busy processes beside it (the
#: oracle suffers more), so 30x tells "native" from "fell back" or "lost a
#: fused stage" with 3x to spare — NOT a 30% decode regression: that guard
#: is owed by the benchmark (ROADMAP S1)
NATIVE_OVER_ORACLE = 30.0


@pytest.mark.perf
@pytest.mark.skipif(not _native.available(), reason="native decoder unavailable")
def test_criteo_decode_hash_pack_floor(tmp_path):
    """The floor that holds on any box: the NATIVE path is taken, yields
    the oracle's bytes, and beats the pure-Python oracle — timed here, on
    the same rows, windows interleaved — by NATIVE_OVER_ORACLE. The old
    floor (a reference box's decode-per-microbench ratio) failed under the
    driver's six-worker load with the code unchanged; absolute rates belong
    to the benchmark, on the chip machine."""
    from tpu_tfrecord.tpu import host_batch_from_columnar

    _write_criteo_shard(str(tmp_path / "part-00000.tfrecord"), N_RECORDS)
    read_fields = [StructField("label", IntegerType(), nullable=False)]
    read_fields += [StructField(f"I{i}", IntegerType()) for i in range(1, 14)]
    read_fields += [StructField(f"C{i}", StringType()) for i in range(1, 27)]
    schema = StructType(read_fields)
    hash_buckets = {f"C{i}": 1 << 20 for i in range(1, 27)}
    pack = {
        "packed": ["label"]
        + [f"I{i}" for i in range(1, 14)]
        + [f"C{i}" for i in range(1, 27)],
    }

    def make(native: bool, batch: int):
        ds = TFRecordDataset(
            str(tmp_path), batch_size=batch, schema=schema, prefetch=4,
            num_epochs=None, hash_buckets=hash_buckets, pack=pack,
        )
        assert ds._native_decoder is not None  # the native path is taken
        if not native:
            ds._native_decoder = None  # the pure-Python oracle
        return ds

    def packed(ds, cb):
        return host_batch_from_columnar(
            cb, ds.schema, hash_buckets=hash_buckets, pack=pack
        )["packed"]

    fast, slow = make(True, BATCH), make(False, 512)

    def oracle_window():
        """One fresh oracle iterator, timed from its start over BATCH rows
        (its prefetch thread can hide no decode that way)."""
        t0 = time.perf_counter()
        with slow.batches() as it:
            got = np.concatenate(
                [packed(slow, next(it)) for _ in range(BATCH // 512)]
            )
        return got, BATCH / (time.perf_counter() - t0)

    best_fast = best_slow = 0.0
    with fast.batches() as it_fast:
        first = packed(fast, next(it_fast))  # also warms the decode thread
        assert first.shape == (BATCH, 40)
        for _ in range(3):  # interleaved windows: both see the same load
            oracle, rate = oracle_window()
            best_slow = max(best_slow, rate)
            t0 = time.perf_counter()
            n = 0
            while time.perf_counter() - t0 < 0.3:
                n += packed(fast, next(it_fast)).shape[0]
            best_fast = max(best_fast, n / (time.perf_counter() - t0))
    np.testing.assert_array_equal(first, oracle)  # rows equal, bit for bit
    best_fast *= _SELFTEST_SCALE
    assert best_fast >= NATIVE_OVER_ORACLE * best_slow, (
        f"native decode+hash+pack {best_fast:,.0f} ex/s is not "
        f"{NATIVE_OVER_ORACLE:.0f}x the pure-Python oracle "
        f"{best_slow:,.0f} ex/s — decode-path regression (native "
        "disabled? turbo cache broken? per-batch copies?)"
    )


SEQ_MAX_LEN = 64
SEQ_DIM = 16
SEQ_BATCH = 1024


def _write_seq_shard(path: str, n: int) -> None:
    from tpu_tfrecord.schema import ArrayType, FloatType

    fields = [
        StructField("label", LongType(), nullable=False),
        StructField("frames", ArrayType(ArrayType(FloatType()))),
    ]
    ser = TFRecordSerializer(StructType(fields))
    rng = np.random.default_rng(1)

    def rows():
        for r in range(n):
            ln = int(rng.integers(8, SEQ_MAX_LEN + 1))
            frames = rng.normal(size=(ln, SEQ_DIM)).astype(np.float32)
            yield encode_row(
                ser,
                RecordType.SEQUENCE_EXAMPLE,
                [r & 1, [row.tolist() for row in frames]],
            )

    wire.write_records(path, rows())


#: the fused native pad+cast must beat the numpy fallback by at least this
#: factor on the same batches in the same test (it measures 9-12x idle and
#: 7-11x with 8-16 busy processes beside it)
FUSED_PAD_OVER_FALLBACK = 4.0


@pytest.mark.perf
@pytest.mark.skipif(not _native.available(), reason="native decoder unavailable")
def test_sequence_pad_bf16_floor(tmp_path, monkeypatch):
    """Floor for the SequenceExample host path (VERDICT r4 item 1): ragged^2
    decode + fused native pad+bf16 ([B, 64, 16] frames). Holds on any box:
    the fused pad yields the numpy fallback's bytes and beats it — timed
    here, on the same decoded batches, windows interleaved — by
    FUSED_PAD_OVER_FALLBACK (a lost fused pad or per-row padding
    reintroduced lands at 1x)."""
    import ml_dtypes

    from tpu_tfrecord.schema import ArrayType, FloatType
    from tpu_tfrecord.tpu import host_batch_from_columnar

    _write_seq_shard(str(tmp_path / "part-00000.tfrecord"), 4096)
    schema = StructType([
        StructField("label", LongType(), nullable=False),
        StructField("frames", ArrayType(ArrayType(FloatType()))),
    ])
    pad_to = {"frames": (SEQ_MAX_LEN, SEQ_DIM)}
    cast = {"frames": ml_dtypes.bfloat16}
    ds = TFRecordDataset(
        str(tmp_path), batch_size=SEQ_BATCH, schema=schema, prefetch=4,
        num_epochs=1, recordType="SequenceExample",
    )
    assert ds._native_decoder is not None  # the native decode path is taken
    with ds.batches() as it:
        batches = list(it)
    assert sum(cb.num_rows for cb in batches) == 4096

    def window():
        t0 = time.perf_counter()
        out = [
            host_batch_from_columnar(cb, ds.schema, pad_to=pad_to, cast=cast)
            for cb in batches
        ]
        return out, time.perf_counter() - t0

    window()  # warm entry-shape caches
    t_fused = t_fallback = float("inf")
    for _ in range(3):  # interleaved: both see the same load
        fused, dt = window()
        t_fused = min(t_fused, dt)
        with monkeypatch.context() as m:
            m.setattr(_native, "available", lambda: False)
            fallback, dt = window()
        t_fallback = min(t_fallback, dt)
    for a, b in zip(fused, fallback):
        assert a["frames"].dtype == ml_dtypes.bfloat16
        assert a["frames"].shape == (SEQ_BATCH, SEQ_MAX_LEN, SEQ_DIM)
        np.testing.assert_array_equal(
            a["frames"].view(np.uint16), b["frames"].view(np.uint16)
        )
    t_fused /= _SELFTEST_SCALE
    assert t_fallback >= FUSED_PAD_OVER_FALLBACK * t_fused, (
        f"fused native pad+bf16 took {t_fused * 1e3:.2f} ms for 4096 rows, "
        f"not {FUSED_PAD_OVER_FALLBACK}x faster than the numpy fallback "
        f"({t_fallback * 1e3:.2f} ms) — ragged^2 path regression "
        "(fused native pad lost? per-row padding reintroduced?)"
    )
