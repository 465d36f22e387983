"""Streaming dataset pipeline: shards -> columnar batches, with prefetch and
checkpoint/resume.

The reference is a batch connector with no resumability beyond the _SUCCESS
marker (SURVEY.md §5). The TPU-native pipeline adds what a training loop
needs (the Grain-style plan from SURVEY.md §5):

- deterministic global shard order + per-host assignment (the DP axis)
- batches that span shard boundaries (records/batch stays constant so the
  device-side step shape is static)
- a background prefetch thread with a bounded queue (decode overlaps the
  consumer's compute; with the C++ decoder the GIL is released during parse)
- O(1)-size iterator state: (epoch, shard cursor, record offset) — resuming
  re-opens one shard and skips ``record offset`` records, not the dataset.
"""

from __future__ import annotations

import hashlib
import json
import os
import queue
import threading
import time
import weakref
from dataclasses import dataclass, field, fields, replace
from typing import Any, Callable, Dict, Iterator, List, Optional

import numpy as np

from tpu_tfrecord import _native, wire
from tpu_tfrecord.columnar import (
    Column,
    ColumnarBatch,
    ColumnarDecoder,
    concat_batches,
    slice_batch,
    take_rows,
)
from tpu_tfrecord.io import paths as p
from tpu_tfrecord.io.reader import (
    CorruptQuotaError,
    DatasetReader,
    SalvageTracker,
    _timed_open,
    salvage_spans_stream,
)
from tpu_tfrecord import telemetry
from tpu_tfrecord.metrics import METRICS, log_salvage_event, timed
from tpu_tfrecord.options import TFRecordOptions
from tpu_tfrecord.retry import RetryPolicy
from tpu_tfrecord.schema import StructType
from tpu_tfrecord.tracing import STOPPED, get_or_wait, put_or_wait, trace
from tpu_tfrecord.stall import (
    StallError,
    StallGuard,
    WatchdogError,
    guard_from_options,
)


# Injectable opener for the mmap fast path (it bypasses wire.open_compressed,
# so fault-injection tests patch THIS seam).
_open_local = open


class _ResizableQueue(queue.Queue):
    """queue.Queue whose maxsize can change while producers/consumers are
    live — the prefetch queue under autotune. Growing wakes blocked
    putters immediately; shrinking below the current fill simply blocks
    new puts until the consumer drains (items are never dropped)."""

    def resize(self, maxsize: int) -> None:
        with self.mutex:
            self.maxsize = max(1, int(maxsize))
            self.not_full.notify_all()


def default_num_workers() -> int:
    """Decode threads a dataset starts when ``num_workers`` is not given:
    half the cores this process may run on, at most 4. One thread decodes
    16,384 Criteo rows in 11 ms and a v5e scores them in 5.3, so a single
    thread starves the chip; four keep it busy with room for a slow core
    (PERF.md §6, PR 25). A host that feeds several chips from one dataset
    asks for more."""
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:  # not Linux
        cores = os.cpu_count() or 1
    return max(1, min(4, cores // 2))


def _noop_hint(_pos: int) -> None:
    return


def _make_readahead(fh, size: int, window: int):
    """Sliding posix_fadvise(WILLNEED) hinter for a local file object.

    ``hint(pos)`` keeps [pos, pos + window) in flight: WILLNEED is
    asynchronous, so the kernel streams the window from the store while the
    decoder works the current chunk — cold reads run at streaming bandwidth
    instead of fault-per-page latency (see readahead_bytes in
    TFRecordDataset). Degrades to a no-op for objects without a real fd
    (fault-injection fakes, remote wrappers) or platforms without fadvise."""
    if not window or size <= 0:
        return _noop_hint
    try:
        fd = fh.fileno()
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_SEQUENTIAL)
    except (AttributeError, OSError, ValueError):
        return _noop_hint
    cursor = [0]

    def hint(pos: int) -> None:
        want_end = min(size, pos + window)
        if want_end > cursor[0]:
            try:
                os.posix_fadvise(
                    fd, cursor[0], want_end - cursor[0], os.POSIX_FADV_WILLNEED
                )
            except OSError:
                cursor[0] = size  # fd went away mid-shard: stop hinting
                return
            cursor[0] = want_end

    hint(0)
    return hint


@dataclass(frozen=True)
class IteratorState:
    """Grain-style resumable position. ``shard_cursor`` is the POSITION in
    the epoch's iteration order over this host's shard list (identity order,
    or the (seed, epoch)-derived permutation when shuffling);
    ``record_offset`` counts records already consumed from that shard.

    ``fingerprint`` identifies the dataset the position is valid FOR (global
    shard list + process slot + shuffle seed + record type): resuming
    against a changed dataset raises loudly instead of silently reading
    wrong or duplicate data. None (e.g. states from older checkpoints) skips
    the check. Excluded from equality — two states at the same position are
    the same position.

    With windowed row shuffling (``shuffle_window``), a position inside a
    window points at the WINDOW START and ``window_emitted`` counts batches
    already yielded from it: resume re-decodes the window from the stored
    position, re-derives the same permutation (seeded by the start
    position), and skips the emitted batches — state stays O(1)."""

    epoch: int = 0
    shard_cursor: int = 0
    record_offset: int = 0
    fingerprint: Optional[str] = field(default=None, compare=False)
    window_emitted: int = 0

    def to_json(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "epoch": self.epoch,
            "shard_cursor": self.shard_cursor,
            "record_offset": self.record_offset,
        }
        if self.fingerprint is not None:
            out["fingerprint"] = self.fingerprint
        if self.window_emitted:
            out["window_emitted"] = self.window_emitted
        return out

    @staticmethod
    def from_json(obj: Dict[str, Any]) -> "IteratorState":
        # Tolerate unknown keys: state files are forward-compatible within a
        # format version (e.g. 'fingerprint' was added without a version
        # bump), so a newer writer's extra fields must not crash an older
        # reader with a TypeError from the constructor.
        known = {f.name for f in fields(IteratorState)}
        return IteratorState(**{k: v for k, v in obj.items() if k in known})


class TFRecordDataset:
    """Plan a per-host streaming read of a TFRecord dataset.

    ``process_index/process_count`` select this host's shards from the
    deterministic global order (tpu.mesh.assign_shards semantics inline so
    this module stays importable without jax).

    ``num_workers`` shards decode at once (default:
    :func:`default_num_workers`; 1 with the epoch cache on) and their chunks
    are emitted in stream order, so batches and resume states are the same
    for every worker count. Only ``num_workers=1`` opens and reads shards
    strictly one after the other.
    """

    def __init__(
        self,
        paths,
        batch_size: int,
        options: Optional[TFRecordOptions] = None,
        columns: Optional[List[str]] = None,
        drop_remainder: bool = True,
        num_epochs: Optional[int] = 1,
        process_index: int = 0,
        process_count: int = 1,
        prefetch: int = 2,
        num_workers: Optional[int] = None,
        shuffle: bool = False,
        shuffle_window: int = 0,
        seed: int = 0,
        read_retries: int = 0,
        retry_policy: Optional[RetryPolicy] = None,
        hash_buckets: Optional[Dict[str, int]] = None,
        pack: Optional[Dict[str, List[str]]] = None,
        slab_bytes: int = 256 << 20,
        max_record_bytes: int = 1 << 30,
        use_mmap: bool = True,
        readahead_bytes: int = 64 << 20,
        **option_kwargs: Any,
    ):
        self._reader = (
            DatasetReader(paths, options=options)
            if options is not None
            else DatasetReader(paths, **option_kwargs)
        )
        self.options = self._reader.options
        # The ORIGINAL source spec (pre-discovery), kept for the data
        # service's job spec: decode workers re-discover the same shard
        # list from it (and prove agreement via the shard-list digest).
        self.source_paths = [
            os.fspath(p)
            for p in (paths if isinstance(paths, (list, tuple)) else [paths])
        ]
        # Flight recorder opt-in (tpu_tfrecord.telemetry): the recorder is
        # process-global (spans come from prefetch workers, the stall
        # guard, and writer threads on one shared timeline), so any
        # dataset built with trace="on" switches it on; trace="off"
        # deliberately does NOT switch it off — another live dataset may
        # be tracing.
        if self.options.trace == "on":
            telemetry.enable()
        if self.options.telemetry_port is not None:
            telemetry.ensure_exporter(self.options.telemetry_port)
        if self.options.telemetry_role is not None:
            # process identity for pulse lines, spool snapshots, and
            # merged-trace track labels (tpu_tfrecord.fleet); like the
            # recorder, the context is process-global
            telemetry.adopt(
                telemetry.current_context().with_role(
                    self.options.telemetry_role
                )
            )
        self.batch_size = batch_size
        self.drop_remainder = drop_remainder
        self.num_epochs = num_epochs
        self.prefetch = prefetch
        full = self._reader.schema()
        part_cols = set(self._reader.partition_schema.names)
        wanted = full if columns is None else full.select(columns)
        # Columnar decode covers the physical record columns; requested
        # partition columns are materialized per row from shard metadata
        # (batches span shards, so this happens during batch assembly).
        self.schema: StructType = StructType(list(wanted.fields))
        self._data_schema = StructType([f for f in wanted if f.name not in part_cols])
        self._partition_fields = [f for f in wanted if f.name in part_cols]
        all_shards = self._reader.shards
        self.process_index = process_index
        self.process_count = process_count
        self._fingerprint: Optional[str] = None
        self.shards = p.interleave(all_shards, process_index, process_count)
        self._decoder = ColumnarDecoder(self._data_schema, self.options.record_type)
        # hash_buckets fuses categorical hashing into the native decode;
        # pack pushes column-group assembly down too ([B, K] matrices).
        # Validation is shared with NativeDecoder and runs eagerly here even
        # when the native library is unavailable — a config typo must fail
        # loudly, never silently disable the fast path.
        self.hash_buckets = _native.validate_hash_buckets(
            self._data_schema, hash_buckets
        )
        self.pack = _native.validate_pack(
            self._data_schema, pack, self.hash_buckets
        )
        self._native_decoder = _native.make_decoder(
            self._data_schema, self.options.record_type, self.hash_buckets, self.pack
        )
        if num_workers is None:
            # Shards decode in parallel unless told otherwise. The epoch
            # cache is the exception: it fills and commits one shard at a
            # time, and a pool that runs ahead into the next epoch would
            # decode shards whose entries are about to land.
            num_workers = 1 if self.options.cache == "auto" else default_num_workers()
        self.num_workers = max(1, num_workers)
        self._scratch_local = threading.local()
        self.shuffle = shuffle
        # Row-level shuffling: permute rows across windows of
        # ``shuffle_window`` batches (0 = off). Deterministic (seeded by the
        # window's start position) and resumable in O(1) state — see
        # IteratorState.window_emitted. Composes with shard-order
        # ``shuffle`` for cross-shard mixing at two scales; TFRecord has no
        # index (reference: isSplitable=false, DefaultSource.scala:26-29),
        # so a GLOBAL row permutation is impossible without a sidecar —
        # windowed shuffle is the streaming-format-native equivalent of
        # tf.data's shuffle buffer, made deterministic.
        if shuffle_window < 0:
            raise ValueError(f"shuffle_window must be >= 0, got {shuffle_window}")
        self.shuffle_window = shuffle_window
        self.seed = seed
        self.read_retries = read_retries
        # One policy object owns retry budget + backoff for every transient
        # read fault (replacing three copy-pasted sleep loops). read_retries
        # stays as the simple spelling; an explicit RetryPolicy wins and
        # brings injectable sleep/clock for tests and deadline support.
        self.retry_policy = (
            retry_policy
            if retry_policy is not None
            else RetryPolicy(max_retries=read_retries)
        )
        self.slab_bytes = max(1, slab_bytes)
        self.max_record_bytes = max_record_bytes
        # mmap fast path for LOCAL uncompressed shards: decode reads the
        # page cache directly (no read() copy pass). Tradeoff: an async
        # disk/NFS error surfaces as SIGBUS instead of a retryable OSError —
        # set use_mmap=False on unreliable mounts to keep stream semantics.
        self.use_mmap = use_mmap
        # Stall defense (tpu_tfrecord.stall): None unless one of the
        # read_deadline_ms / open_deadline_ms / hedge_after_ms options is
        # set, so the default hot path pays nothing. The watchdog
        # (watchdog_timeout_ms) is wired separately in _parallel_chunks.
        self._stall_guard = guard_from_options(self.options)
        if self._stall_guard is None and self.options.autotune == "on":
            # autotune derives hedge/deadline thresholds from observed
            # p99s; an empty guard (no thresholds yet — opens run direct,
            # reads unwrapped) gives the controller a place to install
            # them; streams opened after an install are guarded
            self._stall_guard = StallGuard()
        if self._stall_guard is not None:
            # remote block fetches (PrefetchReader) self-heal under the
            # SAME budget as the shard-level retries
            self._stall_guard.retry_policy = self.retry_policy
        # Sliding posix_fadvise(WILLNEED) window for local shards (0 = off):
        # the kernel fetches ahead ASYNCHRONOUSLY while the C++ decoder
        # chews the current chunk, so cold (non-page-cache-resident) reads
        # run at the store's streaming bandwidth instead of
        # fault-per-page latency. Measured on a development box: 152 MB/s
        # serial-faulting vs 1068 MB/s with WILLNEED issued ahead — the
        # difference between IO-bound and decode-bound cold ingest
        # (BASELINE.md configs[4], "read at line rate").
        self.readahead_bytes = max(0, readahead_bytes)
        # Columnar epoch cache (tpu_tfrecord.cache): the first pass over a
        # shard appends its decoded chunks to a per-shard entry; later
        # epochs — and later runs with the same decode fingerprint — serve
        # zero-copy mmap views instead of re-decoding, turning warm epochs
        # from CPU-bound into page-cache-bound. Engaged only under the
        # strict corruption policy: tolerant policies can legally emit
        # fewer rows than the shard holds, and caching a salvaged subset
        # would freeze one corruption outcome into later epochs.
        self._cache = None
        if self.options.cache == "auto":
            if self.options.on_corrupt != "raise":
                from tpu_tfrecord.metrics import logger as _logger

                _logger.warning(
                    "tfrecord.cache disabled: cache='auto' requires "
                    "on_corrupt='raise' (got %r)", self.options.on_corrupt,
                )
            else:
                from tpu_tfrecord import cache as _cache_mod

                # the exact column set a decoded chunk carries: data
                # columns (minus pack members when the native fused decode
                # folds them into group matrices) + group names +
                # requested partition fields
                fused = self._native_decoder is not None
                members = (
                    {m for ms in self.pack.values() for m in ms} if fused else set()
                )
                expect = (
                    {f.name for f in self._data_schema if f.name not in members}
                    | (set(self.pack) if fused else set())
                    | {f.name for f in self._partition_fields}
                )
                self._cache = _cache_mod.ShardCache(
                    self.options.cache_dir or _cache_mod.default_cache_dir(),
                    ident=self._cache_ident(),
                    max_bytes=self.options.cache_max_bytes,
                    expect_columns=expect,
                )
                self._cache_dtypes = self.chunk_dtypes()

    # -- chunked decode stream with positional accounting --------------------
    #
    # Each shard streams as slabs of complete frames (bounded memory, tail
    # carried between reads), each slab is decoded in large chunks (one C++
    # call per chunk, GIL released). Chunks carry (epoch, cursor,
    # start_offset) so any row boundary maps back to an exact resume
    # position.

    def _decode_chunk(self, buf, offsets, lengths) -> ColumnarBatch:
        if self._native_decoder is not None:
            return self._native_decoder.decode_spans(buf, offsets, lengths)
        records = [
            bytes(buf[o : o + l]) for o, l in zip(offsets.tolist(), lengths.tolist())
        ]
        return self._decoder.decode_batch(records)

    def _truncated_error(self, path: str) -> "wire.TFRecordCorruptionError":
        return wire.TFRecordCorruptionError(f"truncated TFRecord at end of {path}")

    def _check_declared_length(self, declared: int, path: str) -> None:
        """One owner for the corrupt-length contract (possible with
        verify_crc=False): an absurd declared length must raise promptly,
        never buffer or swallow the rest of a shard."""
        if declared > self.max_record_bytes:
            raise wire.TFRecordCorruptionError(
                f"record length {declared} exceeds max_record_bytes "
                f"({self.max_record_bytes}) in {path} — corrupt length field?"
            )

    def _shard_slabs(self, shard) -> Iterator[tuple]:
        """Stream one shard as (buf, offsets, lengths) slabs of complete
        frames — shards larger than memory never materialize whole (the tail
        of each read carries into the next slab). Compressed shards stream
        through the codec the same way. The framing loop itself (bounded
        tail-carry, declared-length guard) has ONE owner:
        io.reader.scan_spans_stream; this wires in the dataset's slab size,
        record-size cap, and sliding readahead window."""
        from tpu_tfrecord import fs as _fs
        from tpu_tfrecord.io.reader import scan_spans_stream

        def make_hint(fh):
            if _fs.has_scheme(shard.path):
                return None
            try:
                return _make_readahead(
                    fh, os.path.getsize(shard.path), self.readahead_bytes
                )
            except OSError:
                return None

        yield from scan_spans_stream(
            shard.path,
            self.options.verify_crc,
            slab_bytes=self.slab_bytes,
            max_record_bytes=self.max_record_bytes,
            make_hint=make_hint,
            open_fn=self._guarded_open_fn(),
        )

    def _guarded_open_fn(self):
        """The (path, codec) opener the span streams use: the stall guard's
        deadline/hedge open when configured, otherwise a plain
        wire.open_compressed that carries this dataset's retry policy to
        the remote block prefetcher (so PrefetchReader fetches self-heal
        from the exact byte offset under the same budget the shard-level
        retries use)."""
        if self._stall_guard is not None:
            return self._stall_guard.open_compressed
        pol = self.retry_policy

        def open_fn(path, codec):
            from tpu_tfrecord import fs as _fs

            # local paths keep the exact legacy call shape (tests stub
            # wire.open_compressed with 3-arg fakes; the policy only
            # matters for the remote block prefetcher anyway)
            if _fs.has_scheme(path):
                return wire.open_compressed(path, "rb", codec,
                                            retry_policy=pol)
            return wire.open_compressed(path, "rb", codec)

        return open_fn

    def epoch_order(self, epoch: int) -> List[int]:
        """Iteration order over this host's shard list for one epoch.

        With ``shuffle`` the order is a permutation derived purely from
        (seed, epoch): every host and every resume reconstructs it without
        coordination or stored state.
        """
        if not self.shuffle:
            return list(range(len(self.shards)))
        rng = np.random.default_rng((self.seed, epoch))
        return rng.permutation(len(self.shards)).tolist()

    def _shard_tasks(self, state: IteratorState) -> Iterator[tuple]:
        """Enumerate (epoch, position, shard_index, skip) from the resume
        point, in the deterministic per-epoch iteration order."""
        epoch = state.epoch
        while self.num_epochs is None or epoch < self.num_epochs:
            order = self.epoch_order(epoch)
            start_pos = state.shard_cursor if epoch == state.epoch else 0
            for pos in range(start_pos, len(order)):
                skip = (
                    state.record_offset
                    if (epoch == state.epoch and pos == state.shard_cursor)
                    else 0
                )
                yield epoch, pos, order[pos], skip
            epoch += 1

    def _retrying(self, make_attempt: Callable[[], Iterator[tuple]]) -> Iterator[tuple]:
        """Shard-level transient-fault retry (SURVEY.md §5 failure-handling
        plan; the reference leans on Spark task retry), shared by every
        decode path: on an IO/corruption error the attempt restarts under
        ``self.retry_policy`` — each attempt body keeps its own
        emitted-record accounting, so re-entry skips what was already
        yielded (no duplicates, no holes)."""
        pol = self.retry_policy
        attempt = 0
        start = pol.clock()
        while True:
            try:
                yield from make_attempt()
                return
            except (OSError, wire.TFRecordCorruptionError):
                attempt += 1
                if not pol.pause(attempt, start):
                    raise
                METRICS.count("read.retries")
                telemetry.instant("read.retry", attempt=attempt)

    def _decode_shard(self, epoch: int, pos: int, shard_idx: int, skip: int) -> Iterator[tuple]:
        """Decode one shard into chunk tuples, applying the epoch cache
        (serve-on-hit / populate-on-miss), ``on_corrupt`` (via
        ``_decode_shard_inner``) and then ``on_stall``: a stall that
        escaped the transient retries (a DeadlineError from the stall
        guard) either propagates (``"raise"``, the default) or drops the
        rest of this shard with the same deterministic skipped-shard
        accounting corruption uses (``"skip_shard"``)."""
        try:
            if self._cache is not None:
                yield from self._decode_shard_caching(epoch, pos, shard_idx, skip)
            else:
                yield from self._decode_shard_inner(epoch, pos, shard_idx, skip)
        except StallError as e:
            if self.options.on_stall != "skip_shard":
                raise
            self._note_skipped_shard(shard_idx, str(e), kind="shard_stalled")

    def _decode_shard_caching(
        self, epoch: int, pos: int, shard_idx: int, skip: int
    ) -> Iterator[tuple]:
        """The cache layer around one shard's decode: a validated entry
        serves mmap-backed chunks; a miss decodes from the TFRecord source
        and (on a fresh, full pass) appends each chunk to a staging entry
        committed atomically at shard end. Any mid-decode exception —
        including GeneratorExit from an abandoned iterator — aborts the
        staging entry, so only complete shards are ever cached."""
        shard = self.shards[shard_idx]
        entry = self._cache.open_entry(shard)
        if entry is not None:
            yield from self._serve_cached(entry, epoch, pos, shard_idx, skip)
            return
        # resume mid-shard (skip > 0) decodes a suffix only: populating
        # would cache a partial entry, so it stays a plain decode
        pop = self._cache.populator(shard) if skip == 0 else None
        if pop is None:
            yield from self._decode_shard_inner(epoch, pos, shard_idx, skip)
            return
        try:
            for item in self._decode_shard_inner(epoch, pos, shard_idx, 0):
                pop.append(item[0], item[3])
                yield item
        except BaseException:
            pop.abort()
            raise
        pop.commit()

    def _serve_cached(
        self, entry, epoch: int, pos: int, shard_idx: int, skip: int
    ) -> Iterator[tuple]:
        """Yield a cached shard's chunk tuples from the resume point. Chunk
        boundaries are the ones recorded at populate time (the fresh-pass
        decode boundaries), and record indices are absolute within the
        shard — so IteratorState checkpoints resume interchangeably between
        cached and uncached reads; a mid-chunk resume slices the straddling
        chunk exactly like the decode paths start mid-slab."""
        dtype_of = self._cache_dtypes.__getitem__
        shard_path = self.shards[shard_idx].path
        for i in range(entry.num_chunks):
            start, n = entry.chunk_span(i)
            if n == 0 or start + n <= skip:
                continue
            with timed("cache.serve", METRICS) as t, \
                    trace("tfr:cache", shard=shard_path) as tr:
                chunk = entry.chunk_batch(i, dtype_of)
                if skip > start:
                    chunk = slice_batch(chunk, skip - start, chunk.num_rows)
                    start = skip
                t.records += chunk.num_rows
                tr.set_metadata(rows=chunk.num_rows)
            yield chunk, epoch, pos, start

    def _decode_shard_inner(
        self, epoch: int, pos: int, shard_idx: int, skip: int
    ) -> Iterator[tuple]:
        """Decode one shard into chunk tuples (chunk, epoch, pos, start),
        applying the configured ``on_corrupt`` policy:

        - ``raise`` (default): the strict paths, byte-exact legacy behavior.
        - ``skip_record``: the salvage scanner resyncs past corrupt frames;
          quota exhaustion escalates to ``corrupt_fallback``.
        - ``skip_shard``: first corruption (after transient retries) drops
          the rest of the shard and the epoch continues.

        Record indices in emitted tuples always count EMITTED records, so a
        checkpoint/resume over a corrupt shard skips the same frames the
        original pass skipped (the salvage scan is deterministic)."""
        mode = self.options.on_corrupt
        if mode == "skip_record":
            try:
                yield from self._decode_shard_salvage(epoch, pos, shard_idx, skip)
            except CorruptQuotaError as e:
                if self.options.corrupt_fallback == "skip_shard":
                    self._note_skipped_shard(shard_idx, str(e))
                    return
                raise wire.TFRecordCorruptionError(str(e)) from e
            return
        if mode == "skip_shard":
            try:
                yield from self._decode_shard_strict(epoch, pos, shard_idx, skip)
            except wire.TFRecordCorruptionError as e:
                METRICS.count("read.corrupt_records")
                self._note_skipped_shard(shard_idx, str(e))
            return
        yield from self._decode_shard_strict(epoch, pos, shard_idx, skip)

    def _note_skipped_shard(
        self, shard_idx: int, reason: str, kind: str = "shard_skipped"
    ) -> None:
        path = self.shards[shard_idx].path
        log_salvage_event(path=path, kind=kind, error=reason)
        METRICS.count("read.skipped_shards")

    def _emit_chunks(
        self, slabs: Iterator[tuple], epoch: int, pos: int, shard_idx: int,
        next_index: List[int],
    ) -> Iterator[tuple]:
        """Chunk-decode a (buf, offsets, lengths) slab stream from the
        resume point: skip the ``next_index[0]`` records already emitted,
        yield (chunk, epoch, pos, start) tuples, and advance the shared
        emitted-record cell — ONE owner for the skip/chunk/index accounting
        used by both the strict two-pass path and the salvage path."""
        chunk_records = max(self.batch_size, 2048)
        shard_path = self.shards[shard_idx].path
        base = 0
        for buf, offsets, lengths in slabs:
            n = len(offsets)
            if base + n <= next_index[0]:
                base += n
                continue
            for start in range(max(0, next_index[0] - base), n, chunk_records):
                stop = min(start + chunk_records, n)
                with timed("decode", METRICS) as t, \
                        trace("tfr:decode", shard=shard_path) as tr:
                    chunk = self._decode_chunk(
                        buf, offsets[start:stop], lengths[start:stop]
                    )
                    t.records += chunk.num_rows
                    t.bytes += int(lengths[start:stop].sum())
                    tr.set_metadata(rows=chunk.num_rows, bytes=t.bytes)
                if self._partition_fields:
                    self._attach_partition_chunk(chunk, shard_idx)
                yield chunk, epoch, pos, base + start
                next_index[0] = base + stop
            base += n

    def _decode_shard_salvage(
        self, epoch: int, pos: int, shard_idx: int, skip: int
    ) -> Iterator[tuple]:
        """skip_record decode: frames stream through the salvage scanner
        (valid spans only; corrupt regions resync'd past and reported), and
        chunks decode exactly like the buffered strict path. Indices count
        emitted (valid) records — deterministic across resumes."""
        shard = self.shards[shard_idx]
        tracker = SalvageTracker(shard.path, self.options)
        next_index = [skip]  # record index within the shard to emit next

        def attempt() -> Iterator[tuple]:
            tracker.reset()  # a transient-IO retry re-scans the same regions
            return self._emit_chunks(
                salvage_spans_stream(
                    shard.path,
                    on_event=tracker,
                    slab_bytes=self.slab_bytes,
                    max_record_bytes=self.max_record_bytes,
                    open_fn=self._guarded_open_fn(),
                ),
                epoch, pos, shard_idx, next_index,
            )

        yield from self._retrying(attempt)

    def _decode_shard_strict(
        self, epoch: int, pos: int, shard_idx: int, skip: int
    ) -> Iterator[tuple]:
        """Strict decode (on_corrupt='raise' semantics): dispatches to the
        fused/mmap native paths when available, the two-pass Python path
        otherwise."""
        if self._native_decoder is not None:
            yield from self._decode_shard_fused(epoch, pos, shard_idx, skip)
            return
        next_index = [skip]  # record index within the shard to emit next

        def attempt() -> Iterator[tuple]:
            return self._emit_chunks(
                self._shard_slabs(self.shards[shard_idx]),
                epoch, pos, shard_idx, next_index,
            )

        yield from self._retrying(attempt)

    # IO scratch sizing for the fused path: big enough that a typical shard
    # (or a full decode chunk) fits in one readinto, small enough to keep
    # resident memory modest; grows geometrically for huge records.
    _SCRATCH_INIT = 32 << 20

    def _io_scratch(self) -> Dict[str, Any]:
        """Per-thread reusable read buffer — readinto a persistent buffer
        instead of fh.read()'s fresh allocation halves raw-IO cost (no
        per-slab page faults)."""
        loc = self._scratch_local
        if not hasattr(loc, "scratch"):
            loc.scratch = {
                "buf": np.empty(min(self.slab_bytes, self._SCRATCH_INIT), np.uint8)
            }
        return loc.scratch

    def _refill_scratch(self, fh, scratch, tail_len: int, path: str) -> int:
        """Fill scratch['buf'] after the carried tail; same bounded-carry
        contract as ``scan_spans_stream``. Returns the new valid length, or -1 at
        clean EOF; raises on truncation / absurd declared length."""
        buf = scratch["buf"]
        if tail_len >= 8:
            declared = int(buf[:8].view(np.uint64)[0])
            self._check_declared_length(declared, path)
            needed = 16 + declared
            if needed > buf.nbytes:
                grown = np.empty(int(needed), np.uint8)
                grown[:tail_len] = buf[:tail_len]
                scratch["buf"] = buf = grown
        reader = getattr(fh, "readinto", None)
        t0 = time.perf_counter()
        with telemetry.span("read", shard=path) as sp:
            if reader is not None:
                n = reader(memoryview(buf)[tail_len:])
            else:
                # file-like without readinto (wrappers, remote FS objects):
                # one extra copy, same contract
                data = fh.read(buf.nbytes - tail_len)
                n = len(data)
                buf[tail_len : tail_len + n] = np.frombuffer(data, np.uint8)
            sp.set(bytes=int(n or 0))
        dt = time.perf_counter() - t0
        METRICS.add("read.io", nbytes=int(n or 0), seconds=dt, latency=dt)
        if not n:
            if tail_len:
                raise self._truncated_error(path)
            return -1
        return tail_len + n

    def _decode_shard_mmap(
        self, epoch: int, pos: int, shard_idx: int, skip: int
    ) -> Iterator[tuple]:
        """Local uncompressed shards: mmap the file and scan+decode straight
        out of the page cache — no read() copy pass at all. Slab bounds are
        irrelevant (nothing is materialized; the kernel evicts clean pages
        freely); chunk positions and retry semantics match the buffered
        path."""
        import mmap

        chunk_records = max(self.batch_size, 2048)
        next_index = [skip]
        dec = self._native_decoder
        verify = self.options.verify_crc
        shard = self.shards[shard_idx]

        def raw_open(path: str, _codec) -> Any:
            # the open runs under the open deadline when configured (mmap
            # READS are page-cache memory — the open is the only stallable
            # filesystem op on this path); _open_local resolves at call
            # time so the chaos injector's patch is honored
            if self._stall_guard is not None:
                return self._stall_guard.call_open(
                    lambda: _open_local(path, "rb"), path
                )
            return _open_local(path, "rb")

        def attempt() -> Iterator[tuple]:
            opened = _timed_open(raw_open, shard.path, None)
            with opened as fh:
                size = os.fstat(fh.fileno()).st_size
                if size == 0:
                    return
                hint = _make_readahead(fh, size, self.readahead_bytes)
                mm = mmap.mmap(fh.fileno(), 0, prot=mmap.PROT_READ)
                try:
                    buf = np.frombuffer(mm, np.uint8)
                    to_skip = next_index[0]
                    abs_idx = 0
                    bpos = 0
                    while True:
                        hint(bpos)
                        with timed("decode", METRICS) as t, \
                                trace("tfr:decode", shard=shard.path) as tr:
                            cb, n_sk, n_done, consumed = dec.scan_decode(
                                buf, bpos, verify, to_skip, chunk_records,
                                length=size,
                                max_record_bytes=self.max_record_bytes,
                            )
                            t.records += n_done
                            t.bytes += consumed - bpos
                            tr.set_metadata(rows=n_done, bytes=consumed - bpos)
                        to_skip -= n_sk
                        abs_idx += n_sk
                        bpos = consumed
                        if n_done == 0:
                            if bpos != size:
                                # an oversized declared length raised
                                # inside scan_decode; what remains here
                                # is a genuine partial tail frame
                                raise self._truncated_error(shard.path)
                            return
                        if self._partition_fields:
                            self._attach_partition_chunk(cb, shard_idx)
                        yield cb, epoch, pos, abs_idx
                        abs_idx += n_done
                        next_index[0] = abs_idx
                finally:
                    # the numpy view exports mm's buffer: drop it before
                    # closing, else BufferError; if anything else still
                    # holds the view, GC closes the map later
                    try:
                        del buf
                        mm.close()
                    except (BufferError, UnboundLocalError):
                        pass

        yield from self._retrying(attempt)

    def _decode_shard_fused(
        self, epoch: int, pos: int, shard_idx: int, skip: int
    ) -> Iterator[tuple]:
        """Fused scan+decode shard stream: ONE native pass per chunk — each
        record is parsed immediately after its CRC while its bytes are still
        cache-hot, and no offsets/lengths arrays materialize. IO goes through
        a reused per-thread buffer (readinto, no per-slab allocations). Same
        chunk positions, retry semantics, and bounded tail-carry contract as
        the two-pass path."""
        from tpu_tfrecord import fs as _fs
        shard = self.shards[shard_idx]
        codec = wire.codec_from_path(shard.path)
        if self.use_mmap and codec is None and not _fs.has_scheme(shard.path):
            yield from self._decode_shard_mmap(epoch, pos, shard_idx, skip)
            return
        chunk_records = max(self.batch_size, 2048)
        next_index = [skip]  # record index within the shard to emit next
        dec = self._native_decoder
        verify = self.options.verify_crc
        scratch = self._io_scratch()

        open_fn = self._guarded_open_fn()

        def attempt() -> Iterator[tuple]:
            with _timed_open(open_fn, shard.path, codec) as fh:
                # Readahead for local shards: hint by the wrapper's
                # tell() each refill. For codecs tell() is the DECODED
                # offset, which overshoots the raw offset — that only
                # makes the window more eager (clamped at file size).
                hint = _noop_hint
                if not _fs.has_scheme(shard.path):
                    try:
                        hint = _make_readahead(
                            fh, os.path.getsize(shard.path), self.readahead_bytes
                        )
                    except OSError:
                        pass
                to_skip = next_index[0]
                abs_idx = 0  # shard record index at buffer position bpos
                data_len = 0
                bpos = 0
                while True:
                    buf = scratch["buf"]
                    tail_len = data_len - bpos
                    if tail_len and bpos:
                        # compact the (sub-frame) tail to the front
                        buf[:tail_len] = buf[bpos:data_len].copy()
                    try:
                        hint(fh.tell())
                    except (AttributeError, OSError, ValueError):
                        hint = _noop_hint
                    data_len = self._refill_scratch(fh, scratch, tail_len, shard.path)
                    if data_len < 0:
                        return
                    buf = scratch["buf"]
                    bpos = 0
                    while True:
                        with timed("decode", METRICS) as t, \
                                trace("tfr:decode", shard=shard.path) as tr:
                            cb, n_sk, n_done, consumed = dec.scan_decode(
                                buf, bpos, verify, to_skip, chunk_records,
                                length=data_len,
                                max_record_bytes=self.max_record_bytes,
                            )
                            t.records += n_done
                            t.bytes += consumed - bpos
                            tr.set_metadata(rows=n_done, bytes=consumed - bpos)
                        to_skip -= n_sk
                        abs_idx += n_sk
                        bpos = consumed
                        if n_done == 0:
                            break  # only a tail remains: refill
                        if self._partition_fields:
                            self._attach_partition_chunk(cb, shard_idx)
                        yield cb, epoch, pos, abs_idx
                        abs_idx += n_done
                        next_index[0] = abs_idx

        yield from self._retrying(attempt)

    def _chunk_stream(
        self, state: IteratorState, stop_event=None, control=None
    ) -> Iterator[tuple]:
        """Yield (chunk, epoch, position, start_offset) from the resume point
        onward. With ``num_workers > 1`` shards decode in a thread pool (the
        native decoder releases the GIL) and chunks are re-emitted in exact
        stream order; memory is bounded by num_workers in-flight shards.
        With a ``control`` (autotune.PipelineControl) the pool path is
        taken even at num_workers=1 so the pool can grow mid-epoch.
        With ``options.service`` set, chunks are FETCHED from the
        disaggregated data service instead of decoded here (same tuples,
        same positions — decode parallelism lives in the worker fleet, so
        ``num_workers`` and the pool control do not apply)."""
        if self.options.service is not None:
            yield from self._service_chunks(
                state, stop_event or threading.Event()
            )
            return
        if self.num_workers <= 1 and control is None:
            for epoch, pos, shard_idx, skip in self._shard_tasks(state):
                yield from self._decode_shard(epoch, pos, shard_idx, skip)
            return
        yield from _parallel_chunks(
            self, state, stop_event or threading.Event(), control
        )

    def _service_chunks(self, state: IteratorState, stop) -> Iterator[tuple]:
        """Service-backed chunk source (tpu_tfrecord.service): each shard's
        chunks stream from a leased decode worker, with exactly-once
        dedupe, reconnect-with-backoff across worker/dispatcher death, and
        graceful degradation to ``_decode_shard`` when the service stays
        unreachable — so resume states are interchangeable between
        service-backed and local iterators by construction."""
        from tpu_tfrecord import service as _service

        client = _service.ServiceClient(self)
        try:
            for epoch, pos, shard_idx, skip in self._shard_tasks(state):
                if stop.is_set():
                    return
                yield from client.shard_chunks(epoch, pos, shard_idx, skip, stop)
        finally:
            client.close()

    def chunk_dtypes(self) -> Dict[str, Any]:
        """name -> schema DataType for every column a decoded chunk can
        carry (requested fields + pack group matrices): the reconstruction
        map shared by the epoch cache (``CachedShard.chunk_batch``) and
        the data service's chunk deserializer."""
        dtypes: Dict[str, Any] = {f.name: f.data_type for f in self.schema}
        for gname, members in self.pack.items():
            dtypes[gname] = self._data_schema[members[0]].data_type
        return dtypes

    def _attach_partition_chunk(self, chunk: ColumnarBatch, cursor: int) -> None:
        """Partition values are constant within a shard: materialize them as
        constant columns over the chunk."""
        from tpu_tfrecord.io.paths import cast_partition_value
        from tpu_tfrecord.schema import numpy_dtype

        n = chunk.num_rows
        for f in self._partition_fields:
            raw = self.shards[cursor].partitions.get(f.name)
            val = cast_partition_value(raw, f.data_type)
            col = Column(
                f.name,
                f.data_type,
                mask=np.full(n, val is not None, dtype=bool),
            )
            np_dt = numpy_dtype(f.data_type)
            if np_dt is None:
                item = val.encode("utf-8") if val is not None else b""
                col.blob = item * n
                col.blob_offsets = np.arange(n + 1, dtype=np.int64) * len(item)
            else:
                col.values = np.full(n, val if val is not None else 0, dtype=np_dt)
            chunk.columns[f.name] = col

    # -- identity ------------------------------------------------------------

    def _cache_ident(self) -> Dict[str, Any]:
        """Everything that changes decoded chunk CONTENT, for the epoch
        cache's decode fingerprint (tpu_tfrecord.cache.decode_fingerprint):
        the physical data schema, requested partition fields, record type,
        the hash/pack decode fusions, CRC verification, and the
        record-size cap. Options that only change how chunks are produced
        (batch_size, workers, prefetch, mmap, readahead, retries,
        deadlines) are excluded so changing them still hits."""
        ident: Dict[str, Any] = {
            "schema": self._data_schema.to_json(),
            "partition_fields": [f.name for f in self._partition_fields],
            "record_type": self.options.record_type.value,
            "hash_buckets": self.hash_buckets,
            "pack": self.pack,
            "verify_crc": self.options.verify_crc,
            "max_record_bytes": self.max_record_bytes,
        }
        if self.hash_buckets or self.pack:
            # hash/pack fusion only happens in the native decoder: chunks
            # produced with vs without it carry different columns, so the
            # environments must not share entries
            ident["fused"] = self._native_decoder is not None
        return ident

    def fingerprint(self) -> str:
        """Digest of everything a resume position depends on: the GLOBAL
        shard list (paths + sizes), this host's process slot, the shuffle
        configuration, and the record type. A saved IteratorState carries
        this; resuming against a dataset with a different fingerprint raises
        instead of silently skewing."""
        if self._fingerprint is None:
            ident = {
                "shards": [(sh.path, sh.size) for sh in self._reader.shards],
                "process_index": self.process_index,
                "process_count": self.process_count,
                "shuffle": self.shuffle,
                "seed": self.seed,
                "record_type": self.options.record_type.value,
            }
            if self.shuffle_window:
                # only stamped when in use: states from row-shuffled
                # iterators must not resume under a different window size
                # (or none), and vice versa; absent for shuffle_window=0 so
                # existing unshuffled states stay valid. batch_size joins
                # because window_emitted counts BATCHES — a different batch
                # size makes the same count a different number of rows.
                ident["shuffle_window"] = self.shuffle_window
                ident["batch_size"] = self.batch_size
            blob = json.dumps(ident, sort_keys=True).encode()
            self._fingerprint = hashlib.sha256(blob).hexdigest()[:32]
        return self._fingerprint

    # -- batched iteration ---------------------------------------------------

    def batches(
        self, state: Optional[IteratorState] = None
    ) -> "CheckpointableIterator":
        if state is not None and state.fingerprint is not None:
            mine = self.fingerprint()
            if state.fingerprint != mine:
                raise ValueError(
                    "iterator state does not match this dataset (fingerprint "
                    f"{state.fingerprint} != {mine}): the shard list, "
                    "process slot, shuffle seed, or record type changed "
                    "since the state was saved — resuming would read wrong "
                    "or duplicate data"
                )
        return CheckpointableIterator(self, state or IteratorState())


def _put_batch(out_queue: queue.Queue, item, stop: threading.Event) -> bool:
    """Hand a batch to the consumer; False once ``stop`` is set. A full
    queue means the consumer is behind: one ``read.backpressure_waits``
    count and one ``tfr:blocked.batch`` span per blocked put, not per poll."""
    if out_queue.full():
        METRICS.count("read.backpressure_waits")
    return put_or_wait(out_queue, item, stop, "tfr:blocked.batch")


def _producer_loop(
    ds: TFRecordDataset,
    start: IteratorState,
    out_queue: queue.Queue,
    stop: threading.Event,
    control=None,
) -> None:
    """Background batch producer (module-level so the thread never pins the
    consumer-side iterator object)."""
    B = ds.batch_size

    def emit_from(pending: List[list], n: int) -> bool:
        """Assemble a batch of n rows from the front of the pending chunks;
        the resume state is the position after the batch's last row."""
        entry = pending[0]
        chunk, consumed, epoch, cursor, chunk_start = entry
        if consumed == 0 and chunk.num_rows == n:
            # Aligned fast path: one decode chunk IS the batch (the common
            # case — _decode_shard chunks at batch_size granularity), so the
            # chunk's columnar buffers pass through without the
            # slice_batch/concat_batches memcpy.
            pending.pop(0)
            batch = chunk
            end_pos = IteratorState(epoch, cursor, chunk_start + n)
        else:
            slices = []
            need = n
            end_pos = start
            while need:
                entry = pending[0]
                chunk, consumed, epoch, cursor, chunk_start = entry
                take = min(need, chunk.num_rows - consumed)
                slices.append(slice_batch(chunk, consumed, consumed + take))
                entry[1] = consumed + take
                need -= take
                end_pos = IteratorState(epoch, cursor, chunk_start + entry[1])
                if entry[1] >= chunk.num_rows:
                    pending.pop(0)
            batch = concat_batches(slices)
        return _put_batch(out_queue, (batch, end_pos), stop)

    if ds.shuffle_window:
        _shuffled_producer_loop(ds, start, out_queue, stop, control)
        return
    try:
        # pending: [chunk, consumed_rows, epoch, cursor, chunk_start]
        pending: List[list] = []
        avail = 0
        for chunk, epoch, cursor, chunk_start in ds._chunk_stream(start, stop, control):
            if stop.is_set():
                return
            if chunk.num_rows == 0:
                continue
            pending.append([chunk, 0, epoch, cursor, chunk_start])
            avail += chunk.num_rows
            while avail >= B:
                if not emit_from(pending, B):
                    return
                avail -= B
        if avail and not ds.drop_remainder:
            emit_from(pending, avail)
        _put_until_stopped(out_queue, None, stop)
    except BaseException as e:  # propagate to consumer  # graftlint: swallow(exception forwarded to the consumer queue and re-raised there)
        _put_until_stopped(out_queue, e, stop)


def _window_permutation(seed: int, pos: IteratorState, n: int) -> np.ndarray:
    """The deterministic row permutation for the window starting at ``pos``:
    derived purely from (seed, start position), so a resume re-creates it
    without any stored buffer state."""
    ss = np.random.SeedSequence(
        [seed & 0xFFFFFFFF, pos.epoch, pos.shard_cursor, pos.record_offset]
    )
    return np.random.default_rng(ss).permutation(n)


def _shuffled_producer_loop(
    ds: TFRecordDataset,
    start: IteratorState,
    out_queue: queue.Queue,
    stop: threading.Event,
    control=None,
) -> None:
    """Windowed row shuffle: accumulate ``shuffle_window`` batches worth of
    rows, permute them (seeded by the window's start position), emit
    batch-size slices. Windows may span shards and epochs, exactly like
    batches do in the unshuffled path.

    Positions: every batch except a window's last carries the WINDOW START
    plus ``window_emitted``; the last batch carries the position after the
    window's end (so a checkpoint between windows needs no window replay).
    """
    B = ds.batch_size
    target = ds.shuffle_window * B

    try:
        # Resume mid-window: rebuild from the stored window START; skip the
        # batches the consumer already saw.
        emit_skip = start.window_emitted
        win_start = IteratorState(start.epoch, start.shard_cursor, start.record_offset)
        win: List[ColumnarBatch] = []
        rows = 0

        def flush(end_pos: IteratorState, tail: bool) -> bool:
            """Permute + emit the accumulated window; True to continue."""
            nonlocal emit_skip, win, rows, win_start
            if rows:
                window = concat_batches(win) if len(win) > 1 else win[0]
                perm = _window_permutation(ds.seed, win_start, rows)
                n_batches = rows // B
                if tail and rows % B and not ds.drop_remainder:
                    n_batches += 1
                for k in range(n_batches):
                    if k < emit_skip:
                        continue  # resume: skipped batches are never gathered
                    # gather each emitted slice of the permutation directly:
                    # one copy per batch instead of a whole-window gather
                    # followed by per-batch slices
                    piece = take_rows(window, perm[k * B : min((k + 1) * B, rows)])
                    last = k == n_batches - 1
                    pos = (
                        end_pos
                        if last
                        else IteratorState(
                            win_start.epoch,
                            win_start.shard_cursor,
                            win_start.record_offset,
                            window_emitted=k + 1,
                        )
                    )
                    if not _put_batch(out_queue, (piece, pos), stop):
                        return False
            emit_skip = 0
            win = []
            rows = 0
            win_start = end_pos
            return True

        stream_end = win_start  # position after the last consumed row
        for chunk, epoch, cursor, chunk_start in ds._chunk_stream(
            win_start, stop, control
        ):
            if stop.is_set():
                return
            consumed = 0
            while consumed < chunk.num_rows:
                take = min(target - rows, chunk.num_rows - consumed)
                if consumed == 0 and take == chunk.num_rows:
                    win.append(chunk)  # aligned: no slice copy
                else:
                    win.append(slice_batch(chunk, consumed, consumed + take))
                rows += take
                consumed += take
                stream_end = IteratorState(epoch, cursor, chunk_start + consumed)
                if rows >= target:
                    if not flush(stream_end, tail=False):
                        return
        # stream end: the final (short) window
        if rows and not flush(stream_end, tail=True):
            return
        _put_until_stopped(out_queue, None, stop)
    except BaseException as e:  # propagate to consumer  # graftlint: swallow(exception forwarded to the consumer queue and re-raised there)
        _put_until_stopped(out_queue, e, stop)


def _put_until_stopped(q: queue.Queue, item, stop: threading.Event) -> None:
    """Enqueue without blocking forever on an abandoned consumer."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.1)
            return
        except queue.Full:
            continue


class _ShardJob:
    """One shard's decode job in the parallel pipeline: a bounded output
    queue written by a worker, drained in stream order by the emitter.

    ``beat`` is the worker's progress heartbeat (monotonic seconds) — it is
    stamped on every chunk handed over AND on every blocked-put poll
    iteration, so backpressure (a full queue while the emitter drains
    earlier shards) never looks like a stall. The watchdog declares the job
    wedged (``wedged``/``failed``) only when the heartbeat goes silent,
    which on a daemon worker means it is blocked inside a read that will
    never return."""

    __slots__ = ("task", "out", "beat", "failed", "wedged")

    def __init__(self, task: tuple, depth: int, now: float = 0.0):
        self.task = task
        self.out: queue.Queue = queue.Queue(maxsize=depth)
        self.beat = now
        self.failed: Optional[BaseException] = None
        self.wedged = False


def _parallel_chunks(
    ds: TFRecordDataset, state: IteratorState, stop: threading.Event,
    control=None,
) -> Iterator[tuple]:
    """Ordered parallel shard decode, with an optional watchdog and an
    optionally LIVE-RESIZABLE pool.

    A dispatcher enumerates shard tasks lazily (epochs may be infinite) and
    hands each to the worker pool; every task owns a small bounded queue, so
    backpressure is per shard and total buffering is bounded by the
    in-flight shard cap. The emitter drains task queues in the exact task
    order, so output is identical to the sequential stream — checkpoint
    state and batch contents do not depend on the worker count, which is
    exactly what makes the pool safely resizable mid-epoch: with a
    ``control`` (autotune.PipelineControl), growth spawns extra worker
    threads that pull from the same task queue, and shrink lets surplus
    workers retire between shards (``should_exit``) — ordering, chunk
    boundaries, and resume positions never change.

    With ``watchdog_timeout_ms`` set, a watchdog thread scans the in-flight
    jobs' progress heartbeats: a worker that goes silent past the timeout
    (wedged in a read that raises nothing — the failure mode deadlines
    cannot see when unconfigured) has its job failed with a WatchdogError
    and a REPLACEMENT worker spawned, so the remaining shards keep decoding
    instead of the consumer blocking on the dead worker's queue forever.
    The emitter applies ``on_stall`` to the failed job after draining the
    chunks it produced before wedging."""
    n_workers = ds.num_workers if control is None else control.workers
    # queue capacities are fixed at construction: under a control they are
    # sized to the pool CEILING so later growth is not strangled by a
    # queue sized for the starting worker count
    cap = n_workers if control is None else max(control.max_workers, n_workers)
    task_q: queue.Queue = queue.Queue(maxsize=cap)
    order_q: queue.Queue = queue.Queue(maxsize=cap + 1)
    END = object()
    clock = time.monotonic
    wd_ms = ds.options.watchdog_timeout_ms
    wd_timeout = wd_ms / 1000.0 if wd_ms else None
    inflight: Dict[int, _ShardJob] = {}
    inflight_lock = threading.Lock()

    def put_checked(q: queue.Queue, item, job: Optional[_ShardJob] = None) -> bool:
        while not stop.is_set():
            if job is not None:
                job.beat = clock()  # blocked-on-full-queue is not a stall
                if job.wedged:
                    return False
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def dispatcher() -> None:
        try:
            for task in ds._shard_tasks(state):
                job = _ShardJob(task, depth=2, now=clock())
                if not put_checked(order_q, job):
                    return
                if not put_checked(task_q, job):
                    return
            put_checked(order_q, END)
        finally:
            if control is not None:
                # dynamic pool: ONE sentinel, re-put by each worker that
                # sees it — terminates any number of workers
                put_checked(task_q, END)
            else:
                for _ in range(n_workers):
                    if not put_checked(task_q, END):
                        break

    def worker() -> None:
        permitted = False
        replaced = False  # declared wedged: the watchdog's replacement
        # already took over this slot, so this thread's (possibly very
        # late) exit must NOT debit the pool books a second time
        try:
            while not stop.is_set():
                if control is not None and control.should_exit():
                    permitted = True  # pool over target: retire between shards
                    return
                try:
                    job = task_q.get(timeout=0.1)
                except queue.Empty:
                    continue
                if job is END:
                    if control is not None:
                        put_checked(task_q, END)  # pass the sentinel on
                    return
                job.beat = clock()
                with inflight_lock:
                    inflight[id(job)] = job
                    METRICS.gauge("read.inflight_workers", len(inflight))
                try:
                    try:
                        for item in ds._decode_shard(*job.task):
                            if not put_checked(job.out, ("chunk", item), job=job):
                                replaced = job.wedged
                                return
                            job.beat = clock()
                        if job.wedged:
                            replaced = True
                            return  # declared dead: a replacement already runs
                        # job= keeps the heartbeat fresh while blocked on a
                        # full queue — a DONE shard backpressured behind the
                        # emitter must never look wedged
                        put_checked(job.out, ("end", None), job=job)
                    except BaseException as e:  # graftlint: swallow(failure encoded into the job result for the emitter)
                        if job.wedged:
                            replaced = True
                            return
                        put_checked(job.out, ("error", e), job=job)
                        return
                finally:
                    with inflight_lock:
                        inflight.pop(id(job), None)
                        METRICS.gauge("read.inflight_workers", len(inflight))
                        if job.wedged:
                            # the watchdog declared THIS job wedged (under
                            # this lock) before we removed it: a
                            # replacement is (being) spawned for our slot,
                            # so this thread must retire even though it
                            # may have just finished the job normally —
                            # two unbooked threads working one slot would
                            # skew the pool books
                            replaced = True
                if replaced:
                    return
        finally:
            if control is not None and not replaced:
                control.note_exit(permitted)

    def watchdog() -> None:
        interval = max(0.01, wd_timeout / 4.0)
        while not stop.is_set():
            stop.wait(interval)
            if stop.is_set():
                return
            now = clock()
            with inflight_lock:
                # wedged is DECIDED under the lock, against jobs still in
                # flight: a worker finishing a job pops it (and observes
                # wedged) in its own locked finally, so exactly one side
                # wins — a job can complete normally or be declared
                # wedged+replaced, never both (racing the mark after the
                # pop let a just-finished worker keep running unaware it
                # had been replaced, skewing the autotune pool books)
                stale = [
                    j
                    for j in inflight.values()
                    if not j.wedged and now - j.beat > wd_timeout
                ]
                for j in stale:
                    j.wedged = True
                    inflight.pop(id(j), None)
            for job in stale:
                path = ds.shards[job.task[2]].path
                job.failed = WatchdogError(
                    f"shard worker made no progress for "
                    f"{wd_timeout * 1000:.0f} ms on {path}"
                )
                METRICS.count("read.stalls")
                METRICS.count("read.watchdog_restarts")
                telemetry.instant("watchdog_restart", path=path)
                log_salvage_event(
                    path=path, kind="watchdog_restart", error=str(job.failed)
                )
                # the wedged thread can never be cancelled (blocked in a
                # C-level read); a fresh worker takes over the task queue
                # so the epoch keeps decoding. Pool books under a control:
                # the replacement inherits the wedged thread's slot — it
                # is NOT booked as a spawn, and the wedged thread's own
                # eventual exit is suppressed (`replaced` in worker()) —
                # so the accounted pool always equals the PRODUCTIVE
                # worker count and should_exit never retires a healthy
                # worker to pay for a zombie
                threading.Thread(target=worker, daemon=True).start()

    threads = [threading.Thread(target=dispatcher, daemon=True)]
    if control is None:
        threads += [
            threading.Thread(target=worker, daemon=True) for _ in range(n_workers)
        ]
    if wd_timeout is not None:
        threads.append(threading.Thread(target=watchdog, daemon=True))
    for t in threads:
        t.start()
    if control is not None:
        # the control owns worker lifecycle: this brings the pool up to
        # its current target and lets set_workers() grow it later
        control.bind_spawn(
            lambda: threading.Thread(target=worker, daemon=True).start()
        )

    while not stop.is_set():
        try:
            job = order_q.get(timeout=0.1)
        except queue.Empty:
            continue
        if job is END:
            return
        while not stop.is_set():
            try:
                kind, payload = job.out.get(timeout=0.1)
            except queue.Empty:
                if job.failed is not None:
                    # drained everything the worker produced before it
                    # wedged; now apply the stall policy
                    if ds.options.on_stall == "skip_shard":
                        ds._note_skipped_shard(
                            job.task[2], str(job.failed), kind="shard_stalled"
                        )
                        break
                    raise job.failed
                continue
            if kind == "end":
                break
            if kind == "error":
                raise payload
            yield payload


class CheckpointableIterator:
    """Iterator of ColumnarBatch with a live, resumable ``state``.

    ``state()`` reflects the last batch YIELDED (not prefetched): restoring
    from it replays nothing and skips nothing, even though a background
    thread runs ahead of the consumer.
    """

    def __init__(self, dataset: TFRecordDataset, state: IteratorState):
        self._ds = dataset
        self._start = state
        self._consumed_state = state
        self._finished = None  # None=running, True=exhausted, Exception=failed
        self._queue: queue.Queue = _ResizableQueue(maxsize=max(1, dataset.prefetch))
        self._stop = threading.Event()
        # Bound-ness telemetry: EMA of the prefetch queue's fill fraction,
        # sampled by the consumer at each batch get (telemetry.Pulse reads
        # the gauge; boundness_verdict interprets it).
        self._occupancy = telemetry.OccupancyEma(telemetry.OCCUPANCY_GAUGE)
        # Closed-loop autotuning (tpu_tfrecord.autotune): a PipelineControl
        # exposes THIS iterator's live knobs (decode pool, prefetch queue,
        # readahead window, stall-guard thresholds); the controller runs
        # as a pulse observer, so autotune="on" implies a pulse (at
        # pulse_interval_s if configured, else autotune_interval_s).
        self._control = None
        self.autotune = None
        pulse_interval = dataset.options.pulse_interval_s
        if dataset.options.autotune == "on" and dataset.options.service is not None:
            from tpu_tfrecord.metrics import logger as _logger

            _logger.warning(
                "autotune disabled: this iterator is service-backed "
                "(options.service=%r) — decode parallelism lives in the "
                "worker fleet, not in a local pool the controller could "
                "resize", dataset.options.service,
            )
        elif dataset.options.autotune == "on":
            from tpu_tfrecord import autotune as _autotune

            self._control = _autotune.PipelineControl(
                workers=dataset.num_workers,
                queue=self._queue,
                dataset=dataset,
                guard=dataset._stall_guard,
            )
            if pulse_interval is None:
                pulse_interval = (
                    dataset.options.autotune_interval_s
                    or _autotune.DEFAULT_INTERVAL_S
                )
            self.autotune = _autotune.AutotuneController(
                self._control, interval_s=pulse_interval
            )
        self._pulse = None
        if pulse_interval is not None:
            from tpu_tfrecord.telemetry import Pulse

            self._pulse = Pulse(pulse_interval)
            if self.autotune is not None:
                self._pulse.add_observer(self.autotune.on_pulse)
            self._pulse.start()
            # like the stop-event finalizer below: an abandoned iterator
            # must not leave its pulse thread ticking forever (the
            # finalizer holds the Pulse, never this object)
            self._pulse_finalizer = weakref.finalize(
                self, Pulse.stop, self._pulse, False
            )
        # Cluster telemetry spool (tpu_tfrecord.fleet): periodic atomic
        # snapshots of this process's registry + heartbeat into one file
        # per process under spool_dir, for the fleet aggregator/doctor.
        # Refcounted process singleton (snapshots are process-global);
        # spool_dir unset = this branch is the only new work.
        # abspath ONCE: acquire and the (possibly much later) release must
        # agree on the registry key even if the process chdirs in between.
        # Scheme'd dirs ("gs://...") pass through untouched — abspath would
        # mangle them into a local path BEFORE TelemetrySpool's loud
        # rejection could see the scheme, silently spooling into a private
        # local dir on every host.
        spool_dir = dataset.options.telemetry_spool_dir
        if spool_dir is not None:
            from tpu_tfrecord import fs as _fs

            if not _fs.has_scheme(spool_dir):
                spool_dir = os.path.abspath(spool_dir)
        self._spool_dir = spool_dir
        if self._spool_dir is not None:
            from tpu_tfrecord import fleet

            fleet.acquire_spool(
                self._spool_dir,
                # None keeps the process's adopted trace-context role (the
                # documented telemetry_role default) instead of clobbering
                # it back to a fixed label
                role=dataset.options.telemetry_role,
                interval_s=dataset.options.spool_interval_s,
            )
            # the finalizer releases the refcount for abandoned iterators;
            # _stop_pulse fires it explicitly on clean shutdown (finalize
            # callables are once-only, so the pair can't double-release)
            self._spool_finalizer = weakref.finalize(
                self, fleet.release_spool, self._spool_dir
            )
        # If the iterator is abandoned without close() (no with-block, early
        # break, GC after an error), the finalizer trips the stop event so
        # producer/dispatcher/worker threads exit and shard buffers free.
        # The producer is a module-level function, not a bound method: the
        # thread must hold no reference to this object, or GC could never
        # collect an abandoned iterator and the finalizer would never fire.
        self._finalizer = weakref.finalize(self, self._stop.set)
        self._thread = threading.Thread(
            target=_producer_loop,
            args=(dataset, state, self._queue, self._stop, self._control),
            daemon=True,
        )
        self._thread.start()

    def __iter__(self) -> "CheckpointableIterator":
        return self

    def __next__(self) -> ColumnarBatch:
        if self._finished is not None:
            raise self._finished if not isinstance(self._finished, bool) else StopIteration
        # Bound-ness sample BEFORE blocking: the queue's fill fraction as
        # the consumer arrives is the signal — full = producer keeps ahead
        # (consumer-bound), empty = the consumer is waiting on decode
        # (producer-bound).
        q = self._queue
        depth = q.qsize()
        self._occupancy.update(depth / q.maxsize)
        METRICS.gauge("prefetch.queue_depth", depth)
        t0_ns = time.perf_counter_ns()
        item = get_or_wait(q, self._stop, "tfr:starved.batch")
        if item is STOPPED:
            # close()d: iteration is over — the producer exits without
            # enqueuing its None sentinel, so never block forever (and a
            # batch racing into the queue during close() is not yielded).
            self._finished = True
            self._stop_pulse()
            raise StopIteration
        if item is None:
            self._finished = True
            self._stop.set()  # let any lingering pipeline threads exit
            self._stop_pulse()
            raise StopIteration
        if isinstance(item, BaseException):
            self._finished = item
            self._stop.set()
            self._stop_pulse()
            raise item
        batch, end_pos = item
        wait_ns = time.perf_counter_ns() - t0_ns
        wait_s = wait_ns / 1e9
        METRICS.add(
            "batch.wait", records=batch.num_rows, seconds=wait_s, latency=wait_s
        )
        telemetry.record_span("batch", t0_ns, wait_ns, rows=batch.num_rows)
        self._consumed_state = end_pos
        return batch

    def _stop_pulse(self) -> None:
        """Stop the telemetry pulse and release the fleet spool at end of
        iteration (exhausted, failed, or closed); the final tick/snapshot
        covers the tail interval."""
        pulse, self._pulse = self._pulse, None
        if pulse is not None:
            try:
                pulse.stop()
            except Exception:  # graftlint: swallow(telemetry teardown must not fail iterator close)
                pass
        if self._spool_dir is not None:
            try:
                self._spool_finalizer()  # once-only: safe vs the GC path
            except Exception:  # graftlint: swallow(telemetry teardown must not fail iterator close)
                pass

    def state(self) -> IteratorState:
        """Resume position of the last batch YIELDED, stamped with the
        dataset fingerprint so a later resume validates identity."""
        return replace(self._consumed_state, fingerprint=self._ds.fingerprint())

    def close(self, _empty=queue.Empty) -> None:
        # queue.Empty is bound as a default arg: close() can run during
        # interpreter shutdown (an abandoned iterator collected late), when
        # module globals — including our `queue` import — are already None.
        self._stop.set()
        self._stop_pulse()
        # Drain so the producer unblocks and exits.
        try:
            while True:
                self._queue.get_nowait()
        except _empty:
            pass

    def __enter__(self) -> "CheckpointableIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
