"""Pluggable-filesystem tests against fsspec's memory:// backend.

The reference reads/writes any Hadoop FileSystem (GCS/S3/HDFS) for free
(TFRecordOutputWriter.scala:19 CodecStreams, TFRecordFileReader.scala:24-32);
these pin the same pluggability through tpu_tfrecord.fs: full round trips,
save modes, partitionBy layout, codec streams, and the streaming dataset
reader, all on a non-local filesystem.
"""

import os
import uuid

import numpy as np
import pytest

import tpu_tfrecord.io as tfio
from tpu_tfrecord import fs as tfs
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.schema import (
    FloatType,
    LongType,
    StringType,
    StructField,
    StructType,
)

fsspec = pytest.importorskip("fsspec")

SCHEMA = StructType(
    [
        StructField("id", LongType(), nullable=False),
        StructField("x", FloatType()),
        StructField("name", StringType()),
    ]
)
ROWS = [[i, i / 2.0, f"n{i}"] for i in range(20)]


@pytest.fixture
def mem_url():
    url = f"memory://fs-{uuid.uuid4().hex[:8]}"
    yield url
    mem = fsspec.filesystem("memory")
    try:
        mem.rm(url.split("://", 1)[1], recursive=True)
    except FileNotFoundError:
        pass


def test_filesystem_for_dispatch(tmp_path):
    assert isinstance(tfs.filesystem_for(str(tmp_path)), tfs.LocalFS)
    assert isinstance(tfs.filesystem_for("memory://x"), tfs.FsspecFS)
    assert not tfs.has_scheme("/plain/path")
    assert tfs.has_scheme("gs://bucket/key")


def test_round_trip_memory(mem_url):
    out = mem_url + "/ds"
    tfio.write(ROWS, SCHEMA, out, mode="overwrite")
    assert tfio.has_success_marker(out)
    table = tfio.read(out, schema=SCHEMA)
    assert sorted(table.column("id")) == list(range(20))
    assert sorted(table.column("name"))[0] == "n0"


def test_schema_inference_memory(mem_url):
    out = mem_url + "/infer"
    tfio.write(ROWS, SCHEMA, out, mode="overwrite")
    table = tfio.read(out)  # infers from the remote file bytes
    assert set(table.schema.names) == {"id", "x", "name"}


def test_save_modes_memory(mem_url):
    out = mem_url + "/modes"
    tfio.write(ROWS[:5], SCHEMA, out)
    with pytest.raises(FileExistsError):
        tfio.write(ROWS, SCHEMA, out, mode="error")
    # ignore: no-op
    tfio.write(ROWS, SCHEMA, out, mode="ignore")
    assert len(tfio.read(out, schema=SCHEMA).rows) == 5
    # append adds
    tfio.write(ROWS[5:8], SCHEMA, out, mode="append")
    assert len(tfio.read(out, schema=SCHEMA).rows) == 8
    # overwrite replaces
    tfio.write(ROWS[:3], SCHEMA, out, mode="overwrite")
    assert len(tfio.read(out, schema=SCHEMA).rows) == 3


def test_partition_by_memory(mem_url):
    out = mem_url + "/pt"
    rows = [[i, float(i), f"g{i % 3}"] for i in range(9)]
    tfio.write(rows, SCHEMA, out, mode="overwrite", partition_by=["name"])
    fs = tfs.filesystem_for(out)
    entries = fs.listdir(out)
    assert sorted(e for e in entries if e.startswith("name=")) == [
        "name=g0",
        "name=g1",
        "name=g2",
    ]
    table = tfio.read(out)
    assert table.schema.names[-1] == "name"  # partition col appended
    assert sorted(table.column("id")) == list(range(9))


def test_gzip_codec_memory(mem_url):
    out = mem_url + "/gz"
    tfio.write(ROWS, SCHEMA, out, mode="overwrite", codec="gzip")
    fs = tfs.filesystem_for(out)
    names = [n for n in fs.listdir(out) if n.endswith(".tfrecord.gz")]
    assert names, "gzip shard extension expected"
    table = tfio.read(out, schema=SCHEMA)
    assert sorted(table.column("id")) == list(range(20))


def test_streaming_dataset_memory(mem_url):
    out = mem_url + "/stream"
    tfio.write(ROWS, SCHEMA, out, mode="overwrite")
    ds = TFRecordDataset(out, batch_size=8, schema=SCHEMA, drop_remainder=False)
    got = []
    with ds.batches() as it:
        for cb in it:
            got.extend(np.asarray(cb["id"].values).tolist())
    assert sorted(got) == list(range(20))


def test_glob_memory(mem_url):
    for sub in ("a", "b"):
        tfio.write(ROWS[:4], SCHEMA, mem_url + f"/glob/{sub}", mode="overwrite")
    table = tfio.read(mem_url + "/glob/*", schema=SCHEMA)
    assert len(table.rows) == 8


def test_walk_order_deterministic_memory(mem_url):
    """Directory recursion must be sorted (fsspec's own walk follows ls/dict
    order): every host must derive the SAME global shard order."""
    for sub in ["b", "a", "c"]:  # insertion order != sorted order
        tfio.write(ROWS[:2], SCHEMA, mem_url + f"/walk/{sub}", mode="overwrite")
    fs = tfs.filesystem_for(mem_url)
    seen = [p for p, _ in fs.walk_files(mem_url + "/walk", lambda n: not n.startswith("_"))]
    assert seen == sorted(seen)
    shards = tfio.discover_shards(mem_url + "/walk")
    assert [s.path for s in shards] == sorted(s.path for s in shards)


def test_local_walk_ignores_dir_symlink_cycles(tmp_path):
    """A symlink cycle inside the dataset must not hang discovery, and a
    symlink into the tree must not double-count shards (os.walk default)."""
    out = str(tmp_path / "ds")
    tfio.write([[1, 1.0, "a"]], SCHEMA, out, mode="overwrite")
    os.symlink(out, os.path.join(out, "loop"))
    shards = tfio.discover_shards(out)
    assert len(shards) == 1


def test_failed_write_leaves_no_partial_output_memory(mem_url):
    """A job that dies mid-write must leave NOTHING visible on the remote
    store: no data files, no _SUCCESS (the temp-dir commit protocol must
    hold on fsspec backends, not just local rename)."""
    out = mem_url + "/aborted"

    def exploding_rows():
        yield [1, 1.0, "a"]
        yield [2, 2.0, "b"]
        raise RuntimeError("upstream died")

    with pytest.raises(RuntimeError, match="upstream died"):
        tfio.write(exploding_rows(), SCHEMA, out, mode="error")
    fs = tfs.filesystem_for(out)
    if fs.exists(out):
        leftovers = [n for n in fs.listdir(out) if not n.startswith("_temporary")]
        assert leftovers == [], leftovers
    assert not tfio.has_success_marker(out)
    # and a retry with the same mode succeeds cleanly afterwards
    tfio.write(ROWS[:4], SCHEMA, out, mode="error")
    assert len(tfio.read(out, schema=SCHEMA).rows) == 4


def test_scheme_errors_cleanly(monkeypatch):
    # unknown protocol should raise a clear error, not silently read nothing
    with pytest.raises(Exception):
        tfio.read("noproto42://bucket/x", schema=SCHEMA)


class TestIndependentReadHandles:
    """The explicit handle-capability flag (ISSUE 7 satellite, ROADMAP #3 /
    ADVICE #1): PrefetchReader may only run concurrent range fetches on a
    backend KNOWN to hand out one independent file object per open().
    Unknown backends default to the safe serialized path — slower, never
    silently corrupt — where the old protocol sniff defaulted them to the
    corrupting parallel path."""

    def _proto(self, proto):
        class _FS:
            protocol = proto

        return _FS()

    def test_known_object_stores_are_independent(self):
        for proto in ("s3", "gs", "gcs", "abfs", "http", "hdfs", "file"):
            assert tfs.independent_read_handles(self._proto(proto)), proto

    def test_memory_and_unknown_schemes_serialize(self):
        assert not tfs.independent_read_handles(self._proto("memory"))
        assert not tfs.independent_read_handles(self._proto("someproto42"))
        assert not tfs.independent_read_handles(object())  # no declaration
        assert not tfs.independent_read_handles(None)

    def test_multi_protocol_requires_all_known(self):
        assert tfs.independent_read_handles(self._proto(("gs", "gcs")))
        assert not tfs.independent_read_handles(self._proto(("gs", "weird")))

    def test_capability_flag_beats_protocol(self):
        # a wrapper/backend that KNOWS its handle semantics declares them,
        # overriding whatever the protocol classification would say
        class _IndependentUnknown:
            protocol = "someproto42"
            independent_read_handles = True

        class _SharedS3:
            protocol = "s3"
            independent_read_handles = False

        assert tfs.independent_read_handles(_IndependentUnknown())
        assert not tfs.independent_read_handles(_SharedS3())

    def test_walks_wrapper_chain(self):
        # FsspecFS/ChaosFS-style wrappers: the first declaration found
        # walking ._fs wins
        class _Inner:
            protocol = "s3"

        class _Wrapper:
            def __init__(self, inner):
                self._fs = inner

        assert tfs.independent_read_handles(_Wrapper(_Inner()))
        assert not tfs.independent_read_handles(_Wrapper(_Wrapper(object())))

        class _OptOutWrapper:
            # e.g. a caching wrapper that funnels every handle through one
            # shared buffer: declares, so the inner s3 is never consulted
            independent_read_handles = False

            def __init__(self, inner):
                self._fs = inner

        assert not tfs.independent_read_handles(_OptOutWrapper(_Inner()))

    def test_fsspec_memory_serializes_end_to_end(self, mem_url):
        # the real memory:// filesystem classifies as shared-handle
        mfs = tfs.filesystem_for(mem_url)
        assert not tfs.independent_read_handles(mfs)


class TestRemotePrefetch:
    """Block-pipelined remote readahead (VERDICT r4 item 3): N concurrent
    range fetches hide per-block link latency; a serial read pays it."""

    @staticmethod
    def _latency_fs(base_fs, per_read_s):
        """Wrap an FsspecFS so every read on every handle sleeps per_read_s
        first — a simulated high-RTT link whose handles, like a real object
        store's (and unlike fsspec memory://'s shared cursor), are
        INDEPENDENT and safe to use from concurrent fetch threads: each
        _SlowFile keeps its own position and serializes only the brief
        seek+read on the shared inner file, with the latency sleep outside
        the lock so concurrent range requests overlap like real GETs."""
        import threading
        import time as _time

        io_lock = threading.Lock()

        class _SlowFile:
            def __init__(self, inner):
                self._inner = inner
                self._pos = 0
                self._closed = False

            def seek(self, pos, whence=0):
                assert whence == 0
                self._pos = pos
                return pos

            def tell(self):
                return self._pos

            def read(self, size=-1):
                _time.sleep(per_read_s)  # the link RTT: outside the lock
                with io_lock:
                    self._inner.seek(self._pos)
                    data = self._inner.read(size)
                self._pos += len(data)
                return data

            def readinto(self, b):
                data = self.read(len(b))
                b[: len(data)] = data
                return len(data)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                self.close()

            def close(self):
                self._closed = True

            @property
            def closed(self):
                return self._closed

        class _SlowFS:
            # each open() returns its own _SlowFile (own cursor): declare
            # the capability explicitly — "slowlink" is an unknown scheme,
            # which fs.independent_read_handles would otherwise serialize
            protocol = "slowlink"
            independent_read_handles = True

            def __init__(self, fs):
                self._fs = fs

            def open(self, path, mode):
                return _SlowFile(self._fs.open(path, mode))

            def __getattr__(self, name):
                return getattr(self._fs, name)

        return _SlowFS(base_fs)

    @pytest.mark.perf
    def test_prefetch_saturates_simulated_link(self, mem_url, monkeypatch):
        """With per-block latency L and depth D, a serial loop takes
        ~nblocks*L while the pipeline takes ~nblocks*L/D — assert a real
        win, and byte-exact equality with the serial read."""
        import time as _time

        # small blocks, long RTT: the simulated latency (a sleep, which
        # overlaps on any box) must dominate the byte copies (which do not
        # under the suite's six-worker load), or the ratio measures the box
        block = 256 << 10
        nbytes = 12 * block
        payload = bytes(np.random.default_rng(0).integers(0, 256, nbytes, np.uint8))
        path = mem_url + "/big.bin"
        fs = tfs.filesystem_for(path)
        with fs.open(path, "wb") as fh:
            fh.write(payload)
        monkeypatch.setenv("TFR_REMOTE_BLOCK_BYTES", str(block))
        monkeypatch.setenv("TFR_REMOTE_PREFETCH_DEPTH", "4")
        slow = self._latency_fs(fs, per_read_s=0.1)

        def drain(fh):
            # drain at the SAME granularity the link charges latency per
            # (one RTT per read call): 12 RTTs serial vs ceil(12/4) waves
            # pipelined — a 4x gap with real margin for per-block overhead
            out = []
            while True:
                chunk = fh.read(block)
                if not chunk:
                    return b"".join(out)
                out.append(chunk)

        t0 = _time.perf_counter()
        with slow.open(path, "rb") as fh:
            serial = drain(fh)
        t_serial = _time.perf_counter() - t0
        t0 = _time.perf_counter()
        with tfs.open_for_read(slow, path) as fh:
            assert isinstance(fh, tfs.PrefetchReader)
            pipelined = drain(fh)
        t_pipe = _time.perf_counter() - t0
        assert pipelined == serial == payload
        # depth 4 should give ~4x; 1.8x is the regression bar (pool silently
        # degrading to serial)
        assert t_pipe < t_serial / 1.8, (t_serial, t_pipe)

    def test_dataset_read_uses_prefetch_and_matches(self, mem_url, monkeypatch):
        """End-to-end: a remote dataset big enough to engage the prefetcher
        decodes identically with pipelining on and off — and the pipelined
        leg PROVABLY routes through PrefetchReader (a block size above
        size/2 would silently fall back to the plain handle and compare two
        identical code paths)."""
        out = mem_url + "/ds"
        schema = StructType([StructField("x", LongType()), StructField("s", StringType())])
        rows = [[i, "v" * 64] for i in range(5000)]
        tfio.write(rows, schema, out, mode="overwrite")
        # ~0.6 MB shard: 128 KiB blocks satisfy open_for_read's
        # size >= 2*block engagement bar with blocks to spare
        monkeypatch.setenv("TFR_REMOTE_BLOCK_BYTES", str(128 << 10))
        built = []
        real_init = tfs.PrefetchReader.__init__
        monkeypatch.setattr(
            tfs.PrefetchReader,
            "__init__",
            lambda self, *a, **k: (built.append(1), real_init(self, *a, **k))[1],
        )

        def read_ids():
            ds = TFRecordDataset(out, batch_size=512, schema=schema,
                                 drop_remainder=False, use_mmap=False)
            got = []
            with ds.batches() as it:
                for cb in it:
                    got.extend(cb["x"].values.tolist())
            return got

        monkeypatch.setenv("TFR_REMOTE_PREFETCH_DEPTH", "4")
        with_prefetch = read_ids()
        assert built, "prefetcher never engaged — block bar not met?"
        monkeypatch.setenv("TFR_REMOTE_PREFETCH_DEPTH", "0")
        n_engaged = len(built)
        without = read_ids()
        assert len(built) == n_engaged, "depth=0 must disable the prefetcher"
        assert with_prefetch == without == list(range(5000))


def test_remote_gzip_streams_through_prefetcher(mem_url, monkeypatch):
    """A big compressed remote object: the codec wrapper must stream off
    PrefetchReader (raw block pipeline UNDER the gzip layer) and decode
    byte-identically to the plain handle."""
    import gzip

    path = mem_url + "/big.tfrecord.gz"
    fs = tfs.filesystem_for(path)
    rows = [[i, "pad" * 40] for i in range(40000)]
    schema = StructType([StructField("x", LongType()), StructField("s", StringType())])
    out = mem_url + "/gzds"
    tfio.write(rows, schema, out, mode="overwrite", codec="gzip")
    part = sorted(n for n in fs.listdir(out) if n.startswith("part-"))[0]
    size = fs.size(out + "/" + part)
    # block small enough that the object engages the prefetcher
    monkeypatch.setenv("TFR_REMOTE_BLOCK_BYTES", str(max(64 << 10, size // 8)))
    built = []
    real_init = tfs.PrefetchReader.__init__
    monkeypatch.setattr(
        tfs.PrefetchReader,
        "__init__",
        lambda self, *a, **k: (built.append(1), real_init(self, *a, **k))[1],
    )
    got = tfio.read(out, schema=schema)
    assert built, "gzip read did not engage the prefetcher"
    assert [r[0] for r in got.rows] == [r[0] for r in rows]
    assert got.rows[-1][1] == "pad" * 40
