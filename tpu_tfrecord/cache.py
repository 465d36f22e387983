"""Columnar epoch cache: decode a shard once, mmap every epoch after.

Warm-dataset ingest is decode-bound, and multi-epoch training re-pays the
full tf.Example decode every epoch. tf.data's snapshot/materialization work
shows the canonical fix — persist the DECODED representation once and serve later epochs from
it. Our decoded representation (`ColumnarBatch`: dense values + offsets +
blob buffers) is already an mmap-friendly flat layout, so the cache reload
is near zero-cost: numpy views straight over one mmap of the cache file, no
frame parsing, no per-record CRC, no protobuf decode.

On-disk container (one entry file per (shard, decode-fingerprint)):

    [MAGIC "TFRCACH1"][u32 container version]
    section payloads, 8-byte aligned, appended chunk by chunk
    [footer JSON][u64 footer length][u32 crc32c(footer)][TAIL "TFRCEND1"]

The footer carries the decode-options fingerprint, the source shard's
identity (path + size + mtime_ns), the data schema JSON, and a per-chunk
section table: for every chunk (start record index, num_rows) the ordered
column list, and for every column the sections it populates (values /
offsets / inner_offsets / blob / blob_offsets / mask) with dtype, shape,
byte offset, byte length, and CRC32C. The footer is written LAST and the
file renamed into place atomically, so a partially-written entry is never
visible under the final name; staging lives under ``_temporary/<job>/``
with the writer's ``_JOB_META`` liveness marker, and commits sweep orphaned
staging with the writer's own ``sweep_orphan_jobs``.

Validation model: an entry is fully verified ONCE per process at first open
(header, footer CRC, fingerprint, source identity, every section CRC — one
sequential pass, far cheaper than a decode epoch); every epoch after serves
zero-copy views with no re-verification. Any failure falls back to the
ground-truth TFRecord decode for that shard and the entry is re-written —
never a crash, never wrong rows. Concurrent writers (multi-process hosts)
race benignly: distinct staging files, last atomic rename wins, and a
reader keeps its mmap of whichever inode it opened.

``cache_max_bytes`` bounds the cache directory with an LRU sweep (entries
are atime-touched on hit — mtime is identity, see the entry registry;
oldest-atime entries evicted first, the just-committed entry protected).

Cache-file opens go through ``fs.local_open`` — the seam the deterministic
chaos injector (tpu_tfrecord.faults) patches — so fault-injection tests
reach this path like every other read mode.
"""

from __future__ import annotations

import hashlib
import json
import mmap
import os
import struct
import tempfile
import threading
import time
import uuid
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from tpu_tfrecord import fs as _fs, telemetry, wire
from tpu_tfrecord.columnar import Column, ColumnarBatch
from tpu_tfrecord.io import paths as p
from tpu_tfrecord.metrics import METRICS, logger, timed

MAGIC = b"TFRCACH1"
TAIL_MAGIC = b"TFRCEND1"
#: Container format version: part of both the header check and the decode
#: fingerprint, so a bump invalidates (misses) every existing entry.
VERSION = 1
ENTRY_SUFFIX = ".tfrc"

_HEADER = struct.Struct("<8sI")  # magic + container version
_TAIL = struct.Struct("<QI8s")  # footer length + footer crc + tail magic
_ALIGN = 8


class CacheOpenError(Exception):
    """An entry cannot be served. ``kind`` says why:

    - ``absent``: no entry file (or unreadable — treated as a plain miss)
    - ``stale``: fingerprint / container version / source shard identity
      changed — the entry describes data that no longer exists
    - ``corrupt``: bad magic, CRC mismatch, or unparseable metadata — the
      case the corrupt-cache fallback guarantee is about
    """

    def __init__(self, kind: str, msg: str):
        super().__init__(msg)
        self.kind = kind


def default_cache_dir() -> str:
    """Per-host, per-USER default when ``cache="auto"`` is set without
    ``cache_dir``. The uid suffix keeps the directory private on multi-user
    hosts: a world-shared path with predictable entry names would let one
    user pre-stage crafted (self-consistently CRC'd) entries that another
    user's reads would validate and serve as training data."""
    uid = os.getuid() if hasattr(os, "getuid") else 0
    return os.path.join(tempfile.gettempdir(), f"tpu_tfrecord_cache-{uid}")


def _norm_path(path: str) -> str:
    return path if _fs.has_scheme(path) else os.path.abspath(path)


def decode_fingerprint(ident: Dict[str, Any]) -> str:
    """Digest of everything that affects decoded chunk CONTENT: the data
    schema, record type, hash_buckets/pack fusion, verify_crc,
    max_record_bytes, requested partition fields — plus the container
    version. Options that only change HOW rows are produced (batch_size,
    num_workers, prefetch, readahead, mmap, retries, deadlines) are
    deliberately excluded: changing them still hits."""
    ident = dict(ident, container_version=VERSION)
    blob = json.dumps(ident, sort_keys=True, default=str).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:16]


def entry_filename(shard_path: str, fingerprint: str) -> str:
    """``<sha(source path)>-<fingerprint>.tfrc``: option changes create NEW
    entries (old ones age out via LRU) instead of overwriting, while a
    changed source shard overwrites its own entry on repopulate."""
    key = hashlib.sha256(_norm_path(shard_path).encode("utf-8")).hexdigest()[:20]
    return f"{key}-{fingerprint}{ENTRY_SUFFIX}"


def source_stat(shard_path: str, size_hint: Optional[int] = None) -> Dict[str, Any]:
    """The source shard identity an entry is keyed on. Local shards use
    (size, mtime_ns); scheme'd (remote) shards ask the backing filesystem
    for a modification stamp too (fsspec ``info``: mtime / LastModified /
    ETag where the store provides one) so a same-size remote rewrite still
    invalidates. A store that exposes none degrades to size-only
    invalidation — disclosed in the README."""
    if _fs.has_scheme(shard_path):
        size = int(size_hint) if size_hint else 0
        stamp = 0
        try:
            info = _fs.filesystem_for(shard_path).info(shard_path)
            if not size:
                size = int(info.get("size") or 0)
            raw = (
                info.get("mtime")
                or info.get("LastModified")
                or info.get("last_modified")
                or info.get("created")
                or info.get("ETag")
                or info.get("etag")
                or 0
            )
            if hasattr(raw, "timestamp"):  # datetime
                raw = raw.timestamp()
            if isinstance(raw, (int, float)):
                stamp = int(raw * 1e9) if raw else 0
            elif raw:  # opaque version tag (ETag): hash it into the slot
                stamp = int(
                    hashlib.sha256(str(raw).encode()).hexdigest()[:15], 16
                )
        except (AttributeError, OSError, KeyError, TypeError, ValueError):
            pass
        return {"path": shard_path, "size": size, "mtime_ns": stamp}
    st = os.stat(shard_path)
    return {
        "path": _norm_path(shard_path),
        "size": int(st.st_size),
        "mtime_ns": int(st.st_mtime_ns),
    }


# ---------------------------------------------------------------------------
# Container codec
# ---------------------------------------------------------------------------


def _section_crc(arr: np.ndarray) -> int:
    """CRC32C over a contiguous array's buffer WITHOUT a tobytes() copy
    when the native library is available — populate and open-time
    verification both pass multi-MB sections through here."""
    try:
        from tpu_tfrecord import _native

        if _native.available():
            import ctypes

            lib = _native.load()
            return int(
                lib.tfr_crc32c(
                    arr.ctypes.data_as(ctypes.c_char_p), arr.nbytes
                )
            )
    except Exception:  # noqa: BLE001 — fall back to the bytes path  # graftlint: swallow(native CRC unavailable: bytes-path CRC below returns the same value)
        pass
    return wire.crc32c(arr.tobytes())


def _column_buffers(col: Column) -> List[Tuple[str, np.ndarray]]:
    """The (role, contiguous array) sections a column populates, in a fixed
    role order so rebuild is deterministic."""
    out: List[Tuple[str, np.ndarray]] = []
    if col.values is not None:
        out.append(("values", np.ascontiguousarray(col.values)))
    if col.offsets is not None:
        out.append(("offsets", np.ascontiguousarray(col.offsets)))
    if col.inner_offsets is not None:
        out.append(("inner_offsets", np.ascontiguousarray(col.inner_offsets)))
    if col.blob is not None:
        out.append(("blob", np.frombuffer(col.blob, dtype=np.uint8)))
    if col.blob_offsets is not None:
        out.append(("blob_offsets", np.ascontiguousarray(col.blob_offsets)))
    if col.mask is not None:
        out.append(("mask", np.ascontiguousarray(col.mask)))
    return out


# Public names for the chunk-section serialization primitives: the data
# service's wire protocol (tpu_tfrecord.service_protocol) frames decoded
# chunks with exactly the cache container's section layout and per-section
# CRCs, so both serializers stay one implementation.
column_buffers = _column_buffers
section_crc = _section_crc


class CachedShard:
    """One validated, mmap'd cache entry: rebuilds ColumnarBatch chunks as
    zero-copy numpy views (bytes-like blobs are the one copy — downstream
    native calls require ``bytes``). The mmap stays alive as long as any
    served view does (numpy base chain); eviction/overwrite of the
    directory entry cannot invalidate it (POSIX inode semantics)."""

    def __init__(self, path: str, footer: Dict[str, Any], mm: mmap.mmap):
        self.path = path
        self.footer = footer
        self._mm = mm
        self._arr = np.frombuffer(mm, dtype=np.uint8)
        self.chunks: List[Dict[str, Any]] = footer["chunks"]
        self.rows = int(footer.get("rows", 0))

    @property
    def num_chunks(self) -> int:
        return len(self.chunks)

    def chunk_span(self, i: int) -> Tuple[int, int]:
        meta = self.chunks[i]
        return int(meta["start"]), int(meta["num_rows"])

    def _section_array(self, sec: Dict[str, Any]) -> np.ndarray:
        off, nb = int(sec["off"]), int(sec["nbytes"])
        arr = self._arr[off : off + nb].view(np.dtype(sec["dtype"]))
        shape = sec.get("shape")
        if shape is not None and len(shape) != 1:
            arr = arr.reshape(shape)
        return arr

    def chunk_batch(self, i: int, dtype_of: Callable[[str], Any]) -> ColumnarBatch:
        """Materialize chunk ``i``: column buffers are views over the entry
        mmap; ``dtype_of(name)`` supplies the schema DataType (the
        fingerprint guarantees it matches what was cached)."""
        meta = self.chunks[i]
        cols: Dict[str, Column] = {}
        for cm in meta["columns"]:
            name = cm["name"]
            col = Column(name, dtype_of(name), hash_buckets=cm.get("hash_buckets"))
            for role, sec in cm["sections"]:
                if role == "blob":
                    off, nb = int(sec["off"]), int(sec["nbytes"])
                    col.blob = self._mm[off : off + nb]
                else:
                    setattr(col, role, self._section_array(sec))
            cols[name] = col
        return ColumnarBatch(cols, int(meta["num_rows"]))


def load_footer(path: str) -> Dict[str, Any]:
    """Parse (and CRC-check) an entry's footer without section verification.
    Raises CacheOpenError('corrupt'|'absent') — shared by the runtime open
    and the doctor's ``cache`` subcommand."""
    try:
        fh = _fs.local_open(path, "rb")
    except FileNotFoundError as e:
        raise CacheOpenError("absent", str(e)) from e
    except OSError as e:
        raise CacheOpenError("absent", f"unreadable cache entry {path}: {e}") from e
    with fh:
        header = wire.read_exact(fh, _HEADER.size)
        if len(header) < _HEADER.size:
            raise CacheOpenError("corrupt", f"cache entry too short: {path}")
        magic, version = _HEADER.unpack(header)
        if magic != MAGIC:
            raise CacheOpenError("corrupt", f"bad cache magic in {path}")
        if version != VERSION:
            raise CacheOpenError(
                "stale", f"cache container v{version} != v{VERSION} in {path}"
            )
        fh.seek(0, os.SEEK_END)
        size = fh.tell()
        if size < _HEADER.size + _TAIL.size:
            raise CacheOpenError("corrupt", f"cache entry truncated: {path}")
        fh.seek(size - _TAIL.size)
        tail_bytes = wire.read_exact(fh, _TAIL.size)
        if len(tail_bytes) < _TAIL.size:  # file shrank under us
            raise CacheOpenError("corrupt", f"cache entry truncated: {path}")
        flen, fcrc, tail = _TAIL.unpack(tail_bytes)
        if tail != TAIL_MAGIC or flen > size - _HEADER.size - _TAIL.size:
            raise CacheOpenError(
                "corrupt", f"bad cache tail in {path} (truncated write?)"
            )
        fh.seek(size - _TAIL.size - flen)
        blob = wire.read_exact(fh, flen)
        if len(blob) < flen or wire.crc32c(blob) != fcrc:
            raise CacheOpenError("corrupt", f"cache footer CRC mismatch in {path}")
        try:
            footer = json.loads(blob.decode("utf-8"))
        except ValueError as e:
            raise CacheOpenError(
                "corrupt", f"unparseable cache footer in {path}: {e}"
            ) from e
    if footer.get("version") != VERSION:
        raise CacheOpenError("stale", f"cache footer version mismatch in {path}")
    return footer


def open_entry_file(
    path: str,
    expect_fingerprint: Optional[str] = None,
    source: Optional[Dict[str, Any]] = None,
    verify_sections: bool = True,
    expect_columns: Optional[set] = None,
) -> CachedShard:
    """Open + validate one entry end to end: footer, fingerprint, source
    identity, (by default) every section CRC, and — when the caller knows
    its decode plan — that every chunk carries exactly ``expect_columns``.
    Raises CacheOpenError."""
    footer = load_footer(path)
    if expect_fingerprint is not None and footer.get("fingerprint") != expect_fingerprint:
        raise CacheOpenError(
            "stale",
            f"cache fingerprint {footer.get('fingerprint')} != "
            f"{expect_fingerprint} in {path}",
        )
    if source is not None and not _source_matches(footer, source):
        raise CacheOpenError(
            "stale", f"source shard changed since {path} was written"
        )
    try:
        fh = _fs.local_open(path, "rb")
    except OSError as e:
        raise CacheOpenError("absent", f"unreadable cache entry {path}: {e}") from e
    with fh:
        try:
            mm = mmap.mmap(fh.fileno(), 0, prot=mmap.PROT_READ)
        except (OSError, ValueError) as e:
            raise CacheOpenError("corrupt", f"cannot mmap {path}: {e}") from e
    try:
        entry = _verified_entry(path, footer, mm, verify_sections, expect_columns)
    except CacheOpenError:
        raise
    except Exception as e:  # noqa: BLE001
        # footer JSON that parsed and CRC-matched but has the wrong SHAPE
        # (missing keys, non-dict values — a foreign or buggy producer):
        # same contract as any corrupt entry, for the doctor and the
        # runtime alike
        raise CacheOpenError(
            "corrupt", f"malformed cache footer structure in {path}: {e}"
        ) from e
    return entry


def _verified_entry(
    path: str,
    footer: Dict[str, Any],
    mm: mmap.mmap,
    verify_sections: bool,
    expect_columns: Optional[set] = None,
) -> CachedShard:
    entry = CachedShard(path, footer, mm)
    if verify_sections:
        size = len(entry._arr)
        next_start = 0
        for meta in footer["chunks"]:
            start, num_rows = int(meta["start"]), int(meta["num_rows"])
            if start != next_start or num_rows < 0:
                # populate writes one contiguous fresh pass from record 0;
                # anything else is a malformed producer
                raise CacheOpenError(
                    "corrupt", f"non-contiguous chunk table in {path}"
                )
            next_start = start + num_rows
            if expect_columns is not None:
                names = {str(cm["name"]) for cm in meta["columns"]}
                if names != expect_columns:
                    # a fingerprint-matching entry whose columns differ from
                    # this dataset's decode plan must fall back, not KeyError
                    # in the serve path's dtype lookup
                    raise CacheOpenError(
                        "corrupt",
                        f"cached columns {sorted(names)} != expected "
                        f"{sorted(expect_columns)} in {path}",
                    )
            for cm in meta["columns"]:
                str(cm["name"])  # serve-time lookups must not KeyError
                roles = {role for role, _sec in cm["sections"]}
                for role, sec in cm["sections"]:
                    off, nb = int(sec["off"]), int(sec["nbytes"])
                    if off < 0 or nb < 0 or off + nb > size:
                        # nb < 0 would make every later check vacuous over
                        # an empty slice (crc32c(b"") == 0)
                        raise CacheOpenError(
                            "corrupt", f"section out of bounds in {path}"
                        )
                    # geometry must be self-consistent so serve-time view/
                    # reshape/row-indexing can never raise (a CRC-valid
                    # footer from a buggy producer must fall back, not
                    # crash the epoch)
                    try:
                        dt = np.dtype(sec["dtype"])
                    except TypeError as e:
                        raise CacheOpenError(
                            "corrupt", f"bad section dtype in {path}: {e}"
                        ) from e
                    shape = sec.get("shape")
                    n_items = 1
                    for dim in shape if shape is not None else ():
                        n_items *= int(dim)
                    if nb % dt.itemsize or (
                        shape is not None and n_items * dt.itemsize != nb
                    ):
                        raise CacheOpenError(
                            "corrupt",
                            f"section shape/dtype inconsistent with its "
                            f"byte length in {path}",
                        )
                    # per-row sections must cover exactly num_rows rows
                    # (offsets carry the +1 fence) — consumers index them
                    # by row without bounds checks
                    n = nb // dt.itemsize
                    first_dim = int(shape[0]) if shape else n
                    bad_rows = (
                        (role == "mask" and n != num_rows)
                        or (role == "offsets" and n != num_rows + 1)
                        or (
                            role == "values"
                            and "offsets" not in roles
                            and first_dim != num_rows
                        )
                        or (
                            role == "blob_offsets"
                            and "offsets" not in roles
                            and n != num_rows + 1
                        )
                    )
                    if bad_rows:
                        raise CacheOpenError(
                            "corrupt",
                            f"section row count inconsistent with chunk "
                            f"num_rows in {path}",
                        )
                    if _section_crc(entry._arr[off : off + nb]) != int(sec["crc"]):
                        raise CacheOpenError(
                            "corrupt",
                            f"section CRC mismatch at offset {off} in {path}",
                        )
    return entry


class CachePopulator:
    """Streams one shard's decoded chunks into a staging entry file and
    commits it atomically. IO failures KILL the populator silently (logged
    once) — cache writing must never fail an epoch."""

    def __init__(self, cache: "ShardCache", shard_path: str, source: Dict[str, Any]):
        self._cache = cache
        self._source = source
        self.source_path = shard_path
        self.final_path = os.path.join(
            cache.cache_dir, entry_filename(shard_path, cache.fingerprint)
        )
        self._job_id = uuid.uuid4().hex[:12]
        self._tmp_dir = os.path.join(cache.cache_dir, p.TEMP_PREFIX, self._job_id)
        os.makedirs(self._tmp_dir, exist_ok=True)
        try:
            self._write_marker()
            self._tmp_path = os.path.join(
                self._tmp_dir, os.path.basename(self.final_path)
            )
            self._fh = open(self._tmp_path, "wb")
            self._fh.write(_HEADER.pack(MAGIC, VERSION))
        except BaseException:
            # a failed setup must not strand the staging dir: the marker
            # names a LIVE pid, so sweep_orphan_jobs would never reclaim it
            import shutil

            fh = getattr(self, "_fh", None)
            if fh is not None:
                try:
                    fh.close()
                except OSError:
                    pass
            shutil.rmtree(self._tmp_dir, ignore_errors=True)
            raise
        self._pos = _HEADER.size
        self._chunks: List[Dict[str, Any]] = []
        self._rows = 0
        self._dead = False

    def _write_marker(self) -> None:
        # the writer's liveness marker, so sweep_orphan_jobs can reclaim
        # staging left by a crashed populate (same dead-pid / stale-lease
        # tests as write jobs)
        from tpu_tfrecord.io.writer import _JOB_MARKER, job_marker_payload

        try:
            with open(os.path.join(self._tmp_dir, _JOB_MARKER), "wb") as fh:
                fh.write(job_marker_payload())
        except OSError:
            pass

    def _kill(self, why: str) -> None:
        self._dead = True
        # the swallowed append/commit failures land here: one counter per
        # aborted populate job, so "caching never fails an epoch" stays
        # observable on the pulse/doctor instead of silently serving cold
        METRICS.count("cache.populate_errors")
        logger.warning(
            "tfrecord.cache populate of %s disabled: %s", self.final_path, why
        )
        self.abort()

    def _put(self, arr: np.ndarray) -> Dict[str, Any]:
        pad = (-self._pos) % _ALIGN
        if pad:
            self._fh.write(b"\0" * pad)
            self._pos += pad
        # arr is contiguous (see _column_buffers): write its buffer and CRC
        # it in place — no tobytes() copy of multi-MB sections
        self._fh.write(arr.data)
        sec = {
            "off": self._pos,
            "nbytes": arr.nbytes,
            "dtype": arr.dtype.str,
            "shape": list(arr.shape),
            "crc": _section_crc(arr),
        }
        self._pos += arr.nbytes
        return sec

    def append(self, batch: ColumnarBatch, start: int) -> None:
        """Serialize one decoded chunk (sections + table row)."""
        if self._dead:
            return
        try:
            cols_meta = []
            for name, col in batch.columns.items():
                sections = [
                    (role, self._put(arr)) for role, arr in _column_buffers(col)
                ]
                cols_meta.append(
                    {
                        "name": name,
                        "hash_buckets": col.hash_buckets,
                        "sections": sections,
                    }
                )
            self._chunks.append(
                {
                    "start": int(start),
                    "num_rows": int(batch.num_rows),
                    "columns": cols_meta,
                }
            )
            self._rows += batch.num_rows
        except Exception as e:  # noqa: BLE001 — caching never fails an epoch  # graftlint: swallow(counted in _kill (cache.populate_errors); caching never fails an epoch)
            self._kill(f"append failed: {e}")

    def commit(self) -> bool:
        """Footer + atomic rename into place; then staging hygiene and the
        LRU sweep. Returns True when the entry landed."""
        if self._dead:
            return False
        with timed("cache.commit", METRICS), \
                telemetry.span("cache.commit", shard=self.source_path):
            return self._commit_inner()

    def _commit_inner(self) -> bool:
        try:
            footer = {
                "version": VERSION,
                "fingerprint": self._cache.fingerprint,
                "source": self._source,
                "ident": self._cache.ident,
                "rows": self._rows,
                "chunks": self._chunks,
            }
            blob = json.dumps(footer, sort_keys=True, default=str).encode("utf-8")
            self._fh.write(blob)
            self._fh.write(_TAIL.pack(len(blob), wire.crc32c(blob), TAIL_MAGIC))
            self._fh.close()
            self._pos += len(blob) + _TAIL.size
            # the rename may REPLACE a previous generation (corrupt-entry
            # rewrite, changed source): the sweep's running total must see
            # the NET directory growth, not the full entry size
            try:
                replaced = os.path.getsize(self.final_path)
            except OSError:
                replaced = 0
            # resolved at call time so the chaos injector's rename faults
            # reach the cache commit like any writer commit
            _fs.filesystem_for(self._cache.cache_dir).rename(
                self._tmp_path, self.final_path
            )
        except Exception as e:  # noqa: BLE001 — caching never fails an epoch  # graftlint: swallow(counted in _kill (cache.populate_errors); caching never fails an epoch)
            self._kill(f"commit failed: {e}")
            return False
        METRICS.count("cache.bytes_written", self._pos)
        self._cleanup_staging()
        self._cache.sweep(
            protect=self.final_path, added_bytes=self._pos - replaced
        )
        return True

    def abort(self) -> None:
        try:
            if not self._fh.closed:
                self._fh.close()
        except OSError:
            pass
        self._cleanup_staging()

    def _cleanup_staging(self) -> None:
        from tpu_tfrecord.io.writer import sweep_orphan_jobs

        fs = _fs.filesystem_for(self._cache.cache_dir)
        try:
            fs.rmtree(self._tmp_dir, ignore_errors=True)
        except OSError:
            pass
        # reclaim staging orphaned by CRASHED populates (dead local pid or
        # stale cross-host lease), then drop the shared parent when empty
        sweep_orphan_jobs(fs, self._cache.cache_dir, keep=self._job_id)
        try:
            fs.rmdir(os.path.join(self._cache.cache_dir, p.TEMP_PREFIX))
        except OSError:
            pass


#: Process-wide registry of VALIDATED entries, so the common
#: dataset-per-epoch pattern (a fresh TFRecordDataset each epoch) does not
#: re-pay the full section-CRC verification pass per dataset object. Keyed
#: by (abspath, inode, size, mtime_ns): the atomic-rename commit gives a
#: rewritten entry a new inode, an in-place modification (corruption, a
#: byte-flip test) changes mtime, and the LRU hit-touch deliberately bumps
#: ONLY atime so it never invalidates the key. Inserts prune superseded
#: generations of the same path and evictions drop theirs, so the registry
#: stays bounded by the LIVE entry set (each value pins one mmap of clean,
#: evictable pages).
_REGISTRY_LOCK = threading.Lock()
_ENTRY_REGISTRY: Dict[Tuple[str, int, int, int], CachedShard] = {}


def _registry_key(path: str) -> Tuple[str, int, int, int]:
    st = os.stat(path)
    return (
        os.path.abspath(path),
        int(st.st_ino),
        int(st.st_size),
        int(st.st_mtime_ns),
    )


def _registry_put(key: Tuple[str, int, int, int], entry: CachedShard) -> None:
    """Insert, PRUNING any superseded generation of the same entry path —
    a rewritten/invalidated entry's old value must not pin its mmap (and
    the deleted inode's disk blocks) for the process lifetime."""
    with _REGISTRY_LOCK:
        for k in [k for k in _ENTRY_REGISTRY if k[0] == key[0] and k != key]:
            del _ENTRY_REGISTRY[k]
        _ENTRY_REGISTRY[key] = entry


def _registry_drop_path(path: str) -> None:
    """Forget every generation of one entry path (eviction, failed
    revalidation)."""
    apath = os.path.abspath(path)
    with _REGISTRY_LOCK:
        for k in [k for k in _ENTRY_REGISTRY if k[0] == apath]:
            del _ENTRY_REGISTRY[k]


def release_registry(cache_dir: Optional[str] = None) -> int:
    """Drop validated-entry registrations (all, or those under one cache
    dir), unpinning their mmaps — for callers that delete a cache dir
    out-of-band (a throwaway probe dir, tests): rmtree alone
    frees no disk while the registry still maps the inodes. Entries also
    held by live datasets stay alive through those references. Returns the
    number released."""
    with _REGISTRY_LOCK:
        if cache_dir is None:
            n = len(_ENTRY_REGISTRY)
            _ENTRY_REGISTRY.clear()
            return n
        prefix = os.path.abspath(cache_dir) + os.sep
        victims = [k for k in _ENTRY_REGISTRY if k[0].startswith(prefix)]
        for k in victims:
            del _ENTRY_REGISTRY[k]
        return len(victims)


def _touch_atime(path: str) -> None:
    """LRU usage stamp: bump atime, PRESERVE mtime (mtime is part of the
    registry identity — a plain utime would alias a hit with a rewrite)."""
    import time as _time

    try:
        st = os.stat(path)
        os.utime(path, ns=(_time.time_ns(), st.st_mtime_ns))
    except OSError:
        pass


def _source_matches(footer: Dict[str, Any], source: Dict[str, Any]) -> bool:
    src = footer.get("source") or {}
    return int(src.get("size", -1)) == int(source["size"]) and int(
        src.get("mtime_ns", -1)
    ) == int(source["mtime_ns"])


class ShardCache:
    """Per-dataset cache manager: one validated CachedShard per source
    shard, kept for the life of the dataset (epoch 2+ serves without
    re-verifying; fresh dataset objects reuse the process-level registry),
    plus populate / eviction plumbing. Thread-safe (parallel shard workers
    hit it concurrently)."""

    def __init__(
        self,
        cache_dir: str,
        ident: Dict[str, Any],
        max_bytes: Optional[int] = None,
        expect_columns: Optional[set] = None,
    ):
        self.cache_dir = os.fspath(cache_dir)
        if _fs.has_scheme(self.cache_dir):
            # the serve path mmaps entry files; a remote cache_dir would
            # fail far from the config error that caused it
            raise ValueError(
                f"cache_dir must be a local path (the cache is mmap-served); "
                f"got {self.cache_dir!r}"
            )
        self.ident = ident
        self.fingerprint = decode_fingerprint(ident)
        self.max_bytes = max_bytes
        # the exact column set a decoded chunk carries (data columns minus
        # pack members, plus group names and partition fields): entries
        # whose chunks differ are corrupt, not servable
        self.expect_columns = set(expect_columns) if expect_columns else None
        self._lock = threading.Lock()
        self._entries: Dict[str, CachedShard] = {}
        # source identity computed by the last open_entry MISS, consumed by
        # the populator() that follows it — for remote shards source_stat
        # is a metadata round-trip, paid once per miss and NEVER on the
        # held-entry (warm epoch) path
        self._miss_source: Dict[str, Dict[str, Any]] = {}
        # running directory size (None = not yet scanned): lets each
        # populate commit answer "under budget?" without re-listing and
        # re-statting the whole cache dir — O(1) per commit instead of the
        # O(entries) that made a 10k-shard populate epoch quadratic. Other
        # processes' commits drift it; every actual sweep rescans exactly.
        self._total_bytes: Optional[int] = None
        os.makedirs(self.cache_dir, exist_ok=True)

    def entry_path(self, shard_path: str) -> str:
        return os.path.join(
            self.cache_dir, entry_filename(shard_path, self.fingerprint)
        )

    def open_entry(
        self, shard, source: Optional[Dict[str, Any]] = None
    ) -> Optional[CachedShard]:
        """Serve-side lookup: a validated entry (hit) or None (miss —
        populate and decode from the source). ``source`` is the shard's
        precomputed identity (callers that also populate pass it so remote
        shards pay ONE metadata round-trip per miss, not two). Counts
        ``cache.hits`` / ``cache.misses`` per shard-epoch and
        ``cache.corrupt_fallbacks`` when the miss was a CRC/format
        failure."""
        path = self.entry_path(shard.path)
        with self._lock:
            entry = self._entries.get(shard.path)
        if entry is not None:
            _touch_atime(path)  # a served entry must look hot to the LRU
            METRICS.count("cache.hits")
            return entry
        try:
            if source is None:
                source = source_stat(shard.path, shard.size)
            with self._lock:
                self._miss_source[shard.path] = source
            key = None
            try:
                key = _registry_key(path)
            except OSError:
                pass
            if key is not None:
                with _REGISTRY_LOCK:
                    entry = _ENTRY_REGISTRY.get(key)
                if (
                    entry is not None
                    and entry.footer.get("fingerprint") == self.fingerprint
                    and _source_matches(entry.footer, source)
                ):
                    # already section-verified by an earlier dataset in
                    # this process; same inode+size+mtime => same bytes
                    with self._lock:
                        self._entries[shard.path] = entry
                    _touch_atime(path)
                    METRICS.count("cache.hits")
                    return entry
                if entry is not None:
                    _registry_drop_path(path)  # superseded: unpin its mmap
                entry = None
            # the once-per-process full section verification: worth a
            # latency histogram of its own — a slow first epoch on a big
            # cache is usually THIS, not decode. Timed by hand, NOT via
            # ``timed``: a routine cold miss raises CacheOpenError here,
            # and the error-counting exit would report cache.open.errors
            # on every perfectly healthy first epoch (the span still
            # self-marks failed=1, which a trace reader wants to see)
            _t0 = time.perf_counter()
            try:
                with telemetry.span("cache.open", shard=shard.path):
                    entry = open_entry_file(
                        path,
                        expect_fingerprint=self.fingerprint,
                        source=source,
                        expect_columns=self.expect_columns,
                    )
            finally:
                _dt = time.perf_counter() - _t0
                METRICS.add("cache.open", seconds=_dt, latency=_dt)
            if key is not None:
                _registry_put(key, entry)
        except CacheOpenError as e:
            if e.kind == "corrupt":
                METRICS.count("cache.corrupt_fallbacks")
                logger.warning(
                    "tfrecord.cache corrupt entry for %s — falling back to "
                    "TFRecord decode and rewriting: %s", shard.path, e,
                )
            METRICS.count("cache.misses")
            return None
        except OSError as e:
            # an injected/transient open fault is a miss, never a crash
            METRICS.count("cache.misses")
            logger.warning("tfrecord.cache open failed for %s: %s", path, e)
            return None
        except Exception as e:  # noqa: BLE001
            # metadata that parsed but has the wrong shape (a corruption
            # the ~2^-32 footer CRC false-negative window lets through):
            # same contract as any corrupt entry — fall back, rewrite
            METRICS.count("cache.corrupt_fallbacks")
            METRICS.count("cache.misses")
            logger.warning(
                "tfrecord.cache malformed entry for %s — falling back to "
                "TFRecord decode and rewriting: %s", shard.path, e,
            )
            return None
        with self._lock:
            self._entries[shard.path] = entry
        _touch_atime(path)  # LRU usage stamp
        METRICS.count("cache.hits")
        return entry

    def peek_entry(self, shard) -> bool:
        """Advisory probe: will ``open_entry`` (as the serve path is
        about to call it) be a hit? Used by the data service's
        shared-cache accounting — a decode worker stamps ``cached: true``
        on its eof so the dispatcher can count fleet-wide warm-cache
        completions per tenant. Deliberately side-effect-free: no
        ``cache.hits``/``cache.misses`` counters, no registry mutation,
        no section-CRC verification pass (a held or registry-known entry
        answers from memory; otherwise only the footer metadata is
        read). A True here that open_entry then fails to serve (entry
        corrupted in the microseconds between) merely overstates one
        counter — it can never affect served rows."""
        with self._lock:
            if shard.path in self._entries:
                return True
        path = self.entry_path(shard.path)
        try:
            key = _registry_key(path)
        except OSError:
            return False  # no entry file at all
        with _REGISTRY_LOCK:
            entry = _ENTRY_REGISTRY.get(key)
        try:
            source = source_stat(shard.path, shard.size)
            if entry is not None:
                return (
                    entry.footer.get("fingerprint") == self.fingerprint
                    and _source_matches(entry.footer, source)
                )
            footer = load_footer(path)
        except Exception:  # noqa: BLE001 — unreadable/corrupt = not cached  # graftlint: swallow(side-effect-free probe: unreadable reads as not-cached)
            return False
        return (
            footer.get("fingerprint") == self.fingerprint
            and _source_matches(footer, source)
        )

    def populator(
        self, shard, source: Optional[Dict[str, Any]] = None
    ) -> Optional[CachePopulator]:
        """Start a populate for one shard; None when staging cannot be set
        up (the epoch proceeds uncached). Reuses the source identity the
        preceding open_entry miss computed, so a miss costs one metadata
        round-trip total."""
        try:
            if source is None:
                with self._lock:
                    source = self._miss_source.pop(shard.path, None)
            if source is None:
                source = source_stat(shard.path, shard.size)
            return CachePopulator(self, shard.path, source)
        except OSError as e:
            logger.warning(
                "tfrecord.cache cannot stage entry for %s: %s", shard.path, e
            )
            return None

    def forget(self, shard_path: str) -> None:
        """Drop a held entry (tests / explicit invalidation)."""
        with self._lock:
            self._entries.pop(shard_path, None)

    def sweep(
        self, protect: Optional[str] = None, added_bytes: int = 0
    ) -> List[str]:
        """LRU eviction to ``max_bytes``: oldest-atime entries go first
        (hits re-stamp atime explicitly — reliable even under relatime);
        ``protect`` (the just-committed entry) is never evicted. The
        running-total fast path skips the full directory scan while the
        budget clearly holds (``added_bytes`` = what the caller just
        committed). Never raises."""
        if not self.max_bytes:
            return []
        with self._lock:
            if self._total_bytes is not None:
                self._total_bytes += added_bytes
                if self._total_bytes <= self.max_bytes:
                    return []
        evicted: List[str] = []
        try:
            entries = []
            for name in os.listdir(self.cache_dir):
                if not name.endswith(ENTRY_SUFFIX):
                    continue
                path = os.path.join(self.cache_dir, name)
                try:
                    st = os.stat(path)
                except OSError:
                    continue
                entries.append((st.st_atime_ns, st.st_size, path))
            total = sum(sz for _, sz, _ in entries)
            for _mt, sz, path in sorted(entries):
                if total <= self.max_bytes:
                    break
                if protect is not None and os.path.basename(path) == os.path.basename(protect):
                    continue
                try:
                    os.remove(path)
                except OSError:
                    continue
                _registry_drop_path(path)  # unpin the evicted mmap
                total -= sz
                evicted.append(path)
                METRICS.count("cache.evictions")
            with self._lock:
                self._total_bytes = total  # exact again after the rescan
        except OSError:
            pass
        return evicted


# ---------------------------------------------------------------------------
# Offline inspection (tools/tfrecord_doctor.py `cache` subcommand)
# ---------------------------------------------------------------------------


def inspect_entry(path: str) -> Dict[str, Any]:
    """Full offline report for one entry file: footer fields, section-CRC
    verification, and source-shard freshness. ``status`` is one of
    ``ok`` | ``corrupt`` | ``stale`` | ``source_missing``."""
    report: Dict[str, Any] = {
        "entry": path,
        "size_bytes": None,
        "status": "ok",
    }
    try:
        report["size_bytes"] = os.path.getsize(path)
    except OSError:
        pass
    try:
        entry = open_entry_file(path, verify_sections=True)
    except CacheOpenError as e:
        report["status"] = "stale" if e.kind == "stale" else "corrupt"
        report["error"] = str(e)
        try:  # a stale-but-parseable footer still carries useful identity
            footer = load_footer(path)
            report["fingerprint"] = footer.get("fingerprint")
            report["source"] = footer.get("source")
        except CacheOpenError:
            pass
        return report
    footer = entry.footer
    src = footer.get("source") or {}
    report.update(
        {
            "fingerprint": footer.get("fingerprint"),
            "source": src,
            "rows": entry.rows,
            "chunks": entry.num_chunks,
            "crc_verified": True,
        }
    )
    src_path = src.get("path")
    if src_path and _fs.has_scheme(src_path):
        # remote source: same freshness probe the runtime uses (backend
        # size + mtime/ETag stamp); an unreachable store must not claim
        # the shard vanished — report unverified instead
        try:
            if not _fs.filesystem_for(src_path).exists(src_path):
                report["status"] = "source_missing"
                return report
            if not _source_matches(footer, source_stat(src_path)):
                report["status"] = "stale"
        except Exception:  # noqa: BLE001 — store unavailable, not stale  # graftlint: swallow(doctor report discloses source_check=unavailable)
            report["source_check"] = "unavailable"
        return report
    if src_path:
        try:
            current = source_stat(src_path)
        except OSError:
            report["status"] = "source_missing"
            return report
        if not _source_matches(footer, current):
            report["status"] = "stale"
    return report


def iter_entry_reports(cache_dir: str) -> Iterator[Dict[str, Any]]:
    """One inspect_entry report per ``*.tfrc`` file under ``cache_dir``.
    An unreadable directory RAISES (OSError): an audit that silently
    reports zero entries would read as a healthy empty cache."""
    for name in sorted(os.listdir(cache_dir)):
        if name.endswith(ENTRY_SUFFIX):
            yield inspect_entry(os.path.join(cache_dir, name))
