"""Tests for pipeline features: multi-worker decode, shard shuffle, retries."""

import os
import threading
import time

import numpy as np
import pytest

import tpu_tfrecord.io as tfio
from tpu_tfrecord.io.dataset import IteratorState, TFRecordDataset
from tpu_tfrecord.retry import RetryPolicy
from tpu_tfrecord.schema import FloatType, LongType, StructField, StructType


def _fast_retries(n, sleep=None):
    """Retry policy for tests: real retry semantics, no wall-clock sleeping
    (``sleep`` hooks let fault tests repair the file 'during' the backoff)."""
    return RetryPolicy(max_retries=n, sleep=sleep or (lambda _s: None))

SCHEMA = StructType([StructField("uid", LongType()), StructField("v", FloatType())])


def write_shards(sandbox, num_shards=6, rows_per_shard=7):
    out = str(sandbox / "pf")
    uid = 0
    for s in range(num_shards):
        tfio.write(
            [[uid + i, float(uid + i)] for i in range(rows_per_shard)],
            SCHEMA,
            out,
            mode="append",
        )
        uid += rows_per_shard
    return out


def collect_uids(ds, state=None):
    uids = []
    with ds.batches(state) as it:
        for b in it:
            uids.extend(b["uid"].values.tolist())
    return uids


class TestMultiWorker:
    @pytest.mark.parametrize("workers", [None, 2, 4])
    def test_identical_to_sequential(self, sandbox, workers):
        out = write_shards(sandbox)
        seq = collect_uids(
            TFRecordDataset(out, batch_size=5, schema=SCHEMA, num_workers=1)
        )
        par = collect_uids(
            TFRecordDataset(out, batch_size=5, schema=SCHEMA, num_workers=workers)
        )
        assert par == seq  # exact order, not just same multiset

    @pytest.mark.parametrize(
        "cores, workers", [(1, 1), (2, 1), (4, 2), (8, 4), (13, 4), (30, 4)]
    )
    def test_default_is_half_the_cores_at_most_four(self, monkeypatch, cores, workers):
        from tpu_tfrecord.io import dataset

        monkeypatch.setattr(os, "sched_getaffinity", lambda _pid: set(range(cores)))
        assert dataset.default_num_workers() == workers

    def test_who_gets_the_default(self, sandbox, monkeypatch):
        """Not given: the default pool, and its shards decode on several
        threads. Given: as given, at least 1. With the epoch cache on: one
        shard at a time, because the cache commits an entry at its shard's end."""
        from tpu_tfrecord.io import dataset

        out = write_shards(sandbox)
        monkeypatch.setattr(dataset, "default_num_workers", lambda: 3)
        kw = dict(batch_size=5, schema=SCHEMA)
        assert TFRecordDataset(out, **kw).num_workers == 3
        assert TFRecordDataset(out, num_workers=2, **kw).num_workers == 2
        assert TFRecordDataset(out, num_workers=0, **kw).num_workers == 1
        cached = TFRecordDataset(
            out, cache="auto", cache_dir=str(sandbox / "cache"), **kw
        )
        assert cached.num_workers == 1
        assert TFRecordDataset(
            out, num_workers=2, cache="auto", cache_dir=str(sandbox / "cache"), **kw
        ).num_workers == 2
        sequential = collect_uids(TFRecordDataset(out, num_workers=1, **kw))
        decoders = set()
        inner = TFRecordDataset._decode_shard

        def seen(self, *task):
            decoders.add(threading.current_thread().name)
            time.sleep(0.05)  # long enough for the other workers to take a shard each
            yield from inner(self, *task)

        monkeypatch.setattr(TFRecordDataset, "_decode_shard", seen)
        assert collect_uids(TFRecordDataset(out, **kw)) == sequential
        assert len(decoders) == 3

    def test_parallel_resume(self, sandbox):
        out = write_shards(sandbox)
        ds = TFRecordDataset(out, batch_size=5, schema=SCHEMA, num_workers=3)
        with ds.batches() as it:
            first = next(it)["uid"].values.tolist()
            st = it.state()
        rest = collect_uids(
            TFRecordDataset(out, batch_size=5, schema=SCHEMA, num_workers=3), st
        )
        seq_all = collect_uids(TFRecordDataset(out, batch_size=5, schema=SCHEMA))
        assert first + rest == seq_all

    def test_num_workers_scales_wall_clock(self, tmp_path):
        """N workers must overlap N shards. Each shard open is made to
        stall 100 ms (seeded chaos, a sleep — it needs no spare core), so
        one worker pays 8 stalls in a row and four pay two: a ratio of two
        timings taken here, which holds on any box however loaded, where
        the old absolute CPU-scaling bar did not. Rows and the number of
        shard opens must be the same either way."""
        import time

        from tpu_tfrecord.faults import FaultPlan, FaultRule, install_chaos

        schema = StructType(
            [StructField("uid", LongType())]
            + [StructField(f"I{i}", LongType()) for i in range(12)]
        )
        out = str(tmp_path / "scale")
        rng = np.random.default_rng(0)
        for s in range(8):
            rows = [
                [int(v) for v in rng.integers(0, 1 << 30, size=13)]
                for _ in range(500)
            ]
            tfio.write(rows, schema, out, mode="append")

        def run(workers: int):
            plan = FaultPlan(
                [FaultRule(op="open", kind="stall", times=None, stall_ms=100)]
            )
            try:
                with install_chaos(plan):
                    ds = TFRecordDataset(
                        out, batch_size=500, schema=schema, num_workers=workers
                    )
                    t0 = time.perf_counter()
                    with ds.batches() as it:
                        uids = [u for b in it for u in b["uid"].values.tolist()]
                    dt = time.perf_counter() - t0
                return dt, uids, len(plan.ledger)
            finally:
                plan.release()

        t1, rows1, opens1 = run(1)
        t4, rows4, opens4 = run(4)
        assert rows4 == rows1 and len(rows1) == 8 * 500
        assert opens1 == opens4 == 8  # every shard opened once, stalled once
        assert t1 >= 8 * 0.1  # the stalls were really paid in a row
        assert t4 < t1 / 1.4, (
            f"4-worker decode ({t4:.3f}s) did not overlap the per-shard "
            f"stalls a single worker pays in a row ({t1:.3f}s)"
        )

    def test_parallel_error_propagates(self, sandbox):
        out = write_shards(sandbox, num_shards=2)
        f = sorted(
            os.path.join(out, x) for x in os.listdir(out) if x.endswith(".tfrecord")
        )[1]
        raw = bytearray(open(f, "rb").read())
        raw[20] ^= 0xFF
        open(f, "wb").write(bytes(raw))
        ds = TFRecordDataset(out, batch_size=4, schema=SCHEMA, num_workers=2)
        with pytest.raises(Exception):
            collect_uids(ds)


from tpu_tfrecord import _native as _native_mod


@pytest.mark.skipif(
    not _native_mod.available(),
    reason="mmap fast path requires the native fused decoder",
)
class TestMmapPath:
    def test_mmap_and_buffered_paths_agree(self, sandbox):
        """Local uncompressed shards default to the mmap fast path; it must
        be indistinguishable from the buffered path (order, values, resume
        positions)."""
        out = write_shards(sandbox, num_shards=3, rows_per_shard=11)
        mm = TFRecordDataset(out, batch_size=7, schema=SCHEMA, drop_remainder=False)
        buf = TFRecordDataset(
            out, batch_size=7, schema=SCHEMA, drop_remainder=False, use_mmap=False
        )
        assert collect_uids(mm) == collect_uids(buf)
        # mid-stream state from one path resumes identically on the other
        it = mm.batches()
        next(it)
        st = it.state()
        it.close()
        assert collect_uids(
            TFRecordDataset(
                out, batch_size=7, schema=SCHEMA, drop_remainder=False, use_mmap=False
            ),
            st,
        ) == collect_uids(
            TFRecordDataset(out, batch_size=7, schema=SCHEMA, drop_remainder=False),
            st,
        )

    def test_mmap_transient_open_error_retried(self, sandbox, monkeypatch):
        """The mmap path opens files via its own seam (_open_local);
        a transient OSError there must be retried like the buffered path."""
        out = write_shards(sandbox, num_shards=1)
        calls = {"n": 0}
        import tpu_tfrecord.io.dataset as dsmod

        real_open = dsmod._open_local

        def flaky(path, mode):
            calls["n"] += 1
            if calls["n"] == 1:
                raise OSError("transient blip")
            return real_open(path, mode)

        monkeypatch.setattr(dsmod, "_open_local", flaky)
        ds = TFRecordDataset(out, batch_size=7, schema=SCHEMA,
                             retry_policy=_fast_retries(2))
        assert len(collect_uids(ds)) == 7
        assert calls["n"] == 2

    def test_mmap_mid_shard_retry_no_duplicates(self, sandbox, monkeypatch):
        """Corruption past the first chunk: the retry must resume after the
        records already emitted — no duplicates, no holes (mmap path)."""
        out = write_shards(sandbox, num_shards=1, rows_per_shard=3000)
        f = [os.path.join(out, x) for x in os.listdir(out) if x.endswith(".tfrecord")][0]
        good = open(f, "rb").read()
        bad = bytearray(good)
        bad[-10] ^= 0x55  # corrupt the LAST record (second decode chunk)
        open(f, "wb").write(bytes(bad))

        def repair(_seconds):
            open(f, "wb").write(good)

        ds = TFRecordDataset(
            out, batch_size=2048, schema=SCHEMA, drop_remainder=False,
            retry_policy=_fast_retries(2, sleep=repair),
        )
        uids = collect_uids(ds)
        assert uids == list(range(3000))  # exactly once each, in order

    def test_mmap_bogus_length_within_file_raises(self, sandbox):
        """verify_crc=False + a corrupt length field whose bogus value still
        FITS in the remaining file: must raise max_record_bytes corruption,
        never swallow the remaining records as one giant 'record'."""
        import struct

        from tpu_tfrecord import wire

        out = write_shards(sandbox, num_shards=1, rows_per_shard=200)
        f = [os.path.join(out, x) for x in os.listdir(out) if x.endswith(".tfrecord")][0]
        raw = bytearray(open(f, "rb").read())
        struct.pack_into("<Q", raw, 0, len(raw) // 2)  # bogus but in-bounds
        open(f, "wb").write(bytes(raw))
        ds = TFRecordDataset(
            out, batch_size=10, schema=SCHEMA, verify_crc=False,
            max_record_bytes=1024,
        )
        with pytest.raises(wire.TFRecordCorruptionError, match="max_record_bytes"):
            collect_uids(ds)

    def test_mmap_truncated_shard_raises(self, sandbox):
        out = write_shards(sandbox, num_shards=1, rows_per_shard=20)
        f = [os.path.join(out, x) for x in os.listdir(out) if x.endswith(".tfrecord")][0]
        blob = open(f, "rb").read()
        open(f, "wb").write(blob[: len(blob) - 7])
        from tpu_tfrecord import wire

        ds = TFRecordDataset(out, batch_size=4, schema=SCHEMA)
        with pytest.raises(wire.TFRecordCorruptionError, match="truncated"):
            collect_uids(ds)


class TestShuffle:
    def test_shuffle_is_permutation_and_seeded(self, sandbox):
        out = write_shards(sandbox)
        base = collect_uids(
            TFRecordDataset(out, batch_size=7, schema=SCHEMA, drop_remainder=False)
        )
        s1 = collect_uids(
            TFRecordDataset(out, batch_size=7, schema=SCHEMA, shuffle=True, seed=1,
                            drop_remainder=False)
        )
        s1b = collect_uids(
            TFRecordDataset(out, batch_size=7, schema=SCHEMA, shuffle=True, seed=1,
                            drop_remainder=False)
        )
        s2 = collect_uids(
            TFRecordDataset(out, batch_size=7, schema=SCHEMA, shuffle=True, seed=2,
                            drop_remainder=False)
        )
        assert sorted(s1) == sorted(base)
        assert s1 == s1b           # deterministic for a seed
        assert s1 != base or s2 != base  # actually shuffles

    def test_epochs_reshuffle(self, sandbox):
        out = write_shards(sandbox)
        ds = TFRecordDataset(out, batch_size=42, schema=SCHEMA, shuffle=True, seed=3,
                             num_epochs=2, drop_remainder=False)
        uids = collect_uids(ds)
        e1, e2 = uids[:42], uids[42:]
        assert sorted(e1) == sorted(e2)
        assert e1 != e2  # different epoch permutation

    def test_shuffled_resume_matches_uninterrupted(self, sandbox):
        out = write_shards(sandbox)
        full = collect_uids(
            TFRecordDataset(out, batch_size=5, schema=SCHEMA, shuffle=True, seed=7)
        )
        ds = TFRecordDataset(out, batch_size=5, schema=SCHEMA, shuffle=True, seed=7)
        with ds.batches() as it:
            first = next(it)["uid"].values.tolist()
            st = it.state()
        rest = collect_uids(
            TFRecordDataset(out, batch_size=5, schema=SCHEMA, shuffle=True, seed=7), st
        )
        assert first + rest == full

    def test_shuffle_with_workers(self, sandbox):
        out = write_shards(sandbox)
        a = collect_uids(
            TFRecordDataset(out, batch_size=5, schema=SCHEMA, shuffle=True, seed=5)
        )
        b = collect_uids(
            TFRecordDataset(out, batch_size=5, schema=SCHEMA, shuffle=True, seed=5,
                            num_workers=3)
        )
        assert a == b


class TestRetries:
    def test_transient_io_error_retried(self, sandbox, monkeypatch):
        out = write_shards(sandbox, num_shards=1)
        # use_mmap=False: stream-level fault injection targets the buffered path
        ds = TFRecordDataset(out, batch_size=7, schema=SCHEMA,
                             retry_policy=_fast_retries(2),
                             drop_remainder=False, use_mmap=False)
        real_open = __import__("tpu_tfrecord.wire", fromlist=["wire"]).open_compressed
        calls = {"n": 0}

        def flaky(path, mode, codec):
            calls["n"] += 1
            if calls["n"] == 1:
                raise OSError("transient network blip")
            return real_open(path, mode, codec)

        monkeypatch.setattr("tpu_tfrecord.wire.open_compressed", flaky)
        uids = collect_uids(ds)
        assert len(uids) == 7
        assert calls["n"] == 2

    def test_exhausted_retries_raise(self, sandbox, monkeypatch):
        out = write_shards(sandbox, num_shards=1)
        ds = TFRecordDataset(out, batch_size=7, schema=SCHEMA,
                             retry_policy=_fast_retries(1), use_mmap=False)

        def always_fail(path, mode, codec):
            raise OSError("gone")

        monkeypatch.setattr("tpu_tfrecord.wire.open_compressed", always_fail)
        with pytest.raises(OSError):
            collect_uids(ds)


class TestAbandonedIterator:
    def test_threads_exit_after_gc_without_close(self, sandbox):
        """Review regression: dropping an iterator without close() must not
        leak pipeline threads or pin shard buffers forever."""
        import gc
        import threading
        import time as _time

        out = write_shards(sandbox, num_shards=6, rows_per_shard=20)
        before = threading.active_count()
        ds = TFRecordDataset(out, batch_size=5, schema=SCHEMA, num_workers=3,
                             num_epochs=None)
        it = ds.batches()
        next(it)  # pipeline running
        assert threading.active_count() > before
        del it
        gc.collect()
        deadline = _time.time() + 5
        while threading.active_count() > before and _time.time() < deadline:
            _time.sleep(0.1)
        assert threading.active_count() <= before + 1  # poll-loop grace


class TestTracing:
    def test_trace_and_duty_cycle(self):
        from tpu_tfrecord.tracing import DutyCycle, trace
        import time as _t

        with trace("host-region"):
            pass
        d = DutyCycle()
        with d.wait():
            _t.sleep(0.01)
        with d.step():
            _t.sleep(0.03)
        # assert the arithmetic, not OS scheduler timing
        assert d.busy_seconds > 0 and d.wait_seconds > 0
        assert d.value() == pytest.approx(
            d.busy_seconds / (d.busy_seconds + d.wait_seconds)
        )
        assert DutyCycle().value() is None


class TestHashBucketsValidation:
    def test_bad_hash_buckets_raise(self, sandbox):
        from tpu_tfrecord.schema import StringType

        schema = StructType([StructField("c", StringType()), StructField("x", LongType())])
        out = str(sandbox / "hv")
        tfio.write([["a", 1]], schema, out, mode="overwrite")
        with pytest.raises(ValueError, match="no such data column"):
            TFRecordDataset(out, batch_size=1, schema=schema, hash_buckets={"nope": 8})
        with pytest.raises(ValueError, match="string/binary"):
            TFRecordDataset(out, batch_size=1, schema=schema, hash_buckets={"x": 8})
        with pytest.raises(ValueError, match="positive"):
            TFRecordDataset(out, batch_size=1, schema=schema, hash_buckets={"c": 0})


class TestSlabStreaming:
    def test_tiny_slabs_identical_to_whole_shard(self, sandbox):
        """Force many slabs per shard (slab smaller than one record frame
        included): stream must be identical to the default path."""
        out = write_shards(sandbox, num_shards=3, rows_per_shard=25)
        ref = collect_uids(TFRecordDataset(out, batch_size=10, schema=SCHEMA))
        for slab in (17, 64, 300):
            got = collect_uids(
                TFRecordDataset(out, batch_size=10, schema=SCHEMA, slab_bytes=slab)
            )
            assert got == ref, f"slab_bytes={slab}"

    def test_tiny_slabs_resume(self, sandbox):
        out = write_shards(sandbox, num_shards=2, rows_per_shard=30)
        ds = TFRecordDataset(out, batch_size=8, schema=SCHEMA, slab_bytes=50)
        with ds.batches() as it:
            first = next(it)["uid"].values.tolist()
            st = it.state()
        rest = collect_uids(
            TFRecordDataset(out, batch_size=8, schema=SCHEMA, slab_bytes=50), st
        )
        ref = collect_uids(TFRecordDataset(out, batch_size=8, schema=SCHEMA))
        assert first + rest == ref

    def test_truncated_tail_detected(self, sandbox):
        from tpu_tfrecord.wire import TFRecordCorruptionError

        out = write_shards(sandbox, num_shards=1, rows_per_shard=5)
        f = [os.path.join(out, x) for x in os.listdir(out) if x.endswith(".tfrecord")][0]
        raw = open(f, "rb").read()
        open(f, "wb").write(raw[:-3])
        ds = TFRecordDataset(out, batch_size=1, schema=SCHEMA, slab_bytes=64,
                             drop_remainder=False)
        with pytest.raises(TFRecordCorruptionError):
            collect_uids(ds)

    def test_bogus_length_bounded_not_buffered(self, sandbox):
        """A corrupt length field with verify_crc=False must raise promptly
        via max_record_bytes, not buffer the rest of the shard."""
        import struct

        from tpu_tfrecord.wire import TFRecordCorruptionError

        out = write_shards(sandbox, num_shards=1, rows_per_shard=50)
        f = [os.path.join(out, x) for x in os.listdir(out) if x.endswith(".tfrecord")][0]
        raw = bytearray(open(f, "rb").read())
        # overwrite the FIRST record's length with a huge value
        struct.pack_into("<Q", raw, 0, 1 << 60)
        open(f, "wb").write(bytes(raw))
        ds = TFRecordDataset(out, batch_size=10, schema=SCHEMA, slab_bytes=64,
                             verify_crc=False, max_record_bytes=1 << 20)
        with pytest.raises(TFRecordCorruptionError, match="max_record_bytes"):
            collect_uids(ds)

    def test_gzip_slab_streaming(self, sandbox):
        out = str(sandbox / "gz")
        rows = [[i, float(i)] for i in range(40)]
        tfio.write(rows, SCHEMA, out, mode="overwrite", codec="gzip")
        got = collect_uids(
            TFRecordDataset(out, batch_size=10, schema=SCHEMA, slab_bytes=100)
        )
        ref = collect_uids(TFRecordDataset(out, batch_size=10, schema=SCHEMA))
        assert got == ref

    def test_mid_shard_retry_no_duplicates(self, sandbox, monkeypatch):
        """IO error mid-shard: retry must resume after the already-emitted
        records, not duplicate them."""
        out = write_shards(sandbox, num_shards=1, rows_per_shard=60)
        real_open = __import__("tpu_tfrecord.wire", fromlist=["wire"]).open_compressed
        state = {"opens": 0}

        class FlakyFile:
            def __init__(self, fh):
                self._fh = fh
                self._reads = 0

            def read(self, n=-1):
                self._reads += 1
                if state["opens"] == 1 and self._reads == 3:
                    raise OSError("mid-shard blip")
                return self._fh.read(n)

            def close(self):
                self._fh.close()

            def __enter__(self):
                return self

            def __exit__(self, *a):
                self.close()

        def flaky(path, mode, codec):
            state["opens"] += 1
            return FlakyFile(real_open(path, mode, codec))

        monkeypatch.setattr("tpu_tfrecord.wire.open_compressed", flaky)
        # use_mmap=False: stream-level fault injection targets the buffered
        # path (the mmap fast path opens files directly; see use_mmap doc)
        ds = TFRecordDataset(out, batch_size=10, schema=SCHEMA, slab_bytes=200,
                             retry_policy=_fast_retries(2),
                             drop_remainder=False, use_mmap=False)
        uids = collect_uids(ds)
        assert uids == list(range(60))
        assert state["opens"] >= 2  # retried
