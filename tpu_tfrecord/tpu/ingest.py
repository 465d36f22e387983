"""Columnar host batches -> sharded jax.Array pytrees on a mesh.

The "aha slice" of SURVEY.md §7.6: a schema maps to a pytree of
jax.ShapeDtypeStruct; each host turns its ColumnarBatch into dense numpy
arrays (ragged columns padded/bucketed, string columns hashed or skipped);
`jax.make_array_from_process_local_data` assembles the global array whose
batch dim is sharded over the mesh's 'data' axis. A double-buffered
DeviceIterator overlaps host decode with device compute so the input pipeline
stays off the critical path (the >=95% duty-cycle target, BASELINE.md).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_tfrecord import wire
from tpu_tfrecord.columnar import Column, ColumnarBatch, pad_ragged, pad_ragged2
from tpu_tfrecord.metrics import METRICS, timed
from tpu_tfrecord.tracing import STOPPED, get_or_wait, put_or_wait, trace
from tpu_tfrecord.schema import (
    ArrayType,
    BinaryType,
    DataType,
    StringType,
    StructType,
    numpy_dtype,
)

# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------


def _is_bytes_like(dt: DataType) -> bool:
    if isinstance(dt, (StringType, BinaryType)):
        return True
    if isinstance(dt, ArrayType):
        return _is_bytes_like(dt.element_type)
    return False


def _validate_cast(schema: StructType, cast: Dict[str, np.dtype]) -> None:
    """Every cast key must name a numeric schema column — a typo'd name
    would otherwise silently skip the cast (mirrors validate_hash_buckets'
    eager unknown-column error)."""
    castable = {
        f.name for f in schema if not _is_bytes_like(f.data_type)
    }
    for name in cast:
        if name not in castable:
            raise ValueError(
                f"cast: no castable data column named {name!r} "
                f"(numeric columns: {sorted(castable)})"
            )


def batch_spec(
    schema: StructType,
    batch_size: int,
    pad_to: Optional[Dict[str, Union[int, tuple]]] = None,
    hash_buckets: Optional[Dict[str, int]] = None,
    include_lengths: bool = True,
    cast: Optional[Dict[str, np.dtype]] = None,
) -> Dict[str, jax.ShapeDtypeStruct]:
    """Schema -> pytree of ShapeDtypeStruct for one global batch.

    - numeric scalar column            -> (B,) of its numpy dtype
    - numeric array column             -> (B, L) + '<name>_len' (B,) int32
    - array-of-array column            -> (B, Lo, Li) + '<name>_len' (B,)
                                          + '<name>_inner_len' (B, Lo)
    - string/binary column             -> (B,) int32 iff hashed via
                                          ``hash_buckets[name]``, else omitted
                                          (int32: embedding row indices —
                                          half the transfer bytes of int64)
    ``pad_to`` must give L (or (Lo, Li)) for every ragged column — static
    shapes are what let XLA tile the downstream compute onto the MXU.
    ``cast`` overrides a column's device dtype (e.g. ``{"frames":
    ml_dtypes.bfloat16}`` — halves link bytes; the fused native pad+cast
    makes it free on the host side).
    """
    pad_to = pad_to or {}
    hash_buckets = hash_buckets or {}
    cast = cast or {}
    _validate_cast(schema, cast)
    spec: Dict[str, jax.ShapeDtypeStruct] = {}

    def col_dtype(name: str, dt: DataType):
        return np.dtype(cast[name]) if name in cast else numpy_dtype(dt)

    for f in schema:
        dt = f.data_type
        if _is_bytes_like(dt):
            if f.name in hash_buckets:
                if isinstance(dt, ArrayType):  # multi-hot: [B, K] + lengths
                    k = pad_to[f.name]
                    spec[f.name] = jax.ShapeDtypeStruct((batch_size, k), np.int32)
                    if include_lengths:
                        spec[f.name + "_len"] = jax.ShapeDtypeStruct(
                            (batch_size,), np.int32
                        )
                else:
                    spec[f.name] = jax.ShapeDtypeStruct((batch_size,), np.int32)
            continue
        if isinstance(dt, ArrayType):
            if isinstance(dt.element_type, ArrayType):
                lo, li = pad_to[f.name]
                spec[f.name] = jax.ShapeDtypeStruct(
                    (batch_size, lo, li), col_dtype(f.name, dt)
                )
                if include_lengths:
                    spec[f.name + "_len"] = jax.ShapeDtypeStruct((batch_size,), np.int32)
                    spec[f.name + "_inner_len"] = jax.ShapeDtypeStruct(
                        (batch_size, lo), np.int32
                    )
            else:
                length = pad_to[f.name]
                spec[f.name] = jax.ShapeDtypeStruct(
                    (batch_size, length), col_dtype(f.name, dt)
                )
                if include_lengths:
                    spec[f.name + "_len"] = jax.ShapeDtypeStruct((batch_size,), np.int32)
        else:
            spec[f.name] = jax.ShapeDtypeStruct((batch_size,), col_dtype(f.name, dt))
    return spec


# ---------------------------------------------------------------------------
# Host-side densification
# ---------------------------------------------------------------------------


def hash_bytes_column(col_or_blobs, num_buckets: int) -> np.ndarray:
    """Deterministic CRC32C-based hashing of byte strings into buckets —
    the host-side categorical-feature path (strings never go to the TPU).
    Accepts a bytes-like Column (flat blob path, hashed in one native call)
    or a plain list of bytes."""
    if isinstance(col_or_blobs, Column):
        col = col_or_blobs
        try:
            from tpu_tfrecord import _native

            if _native.available():
                return _native.hash_blob(
                    col.blob, col.blob_offsets, num_buckets
                ).astype(np.int32)
        except Exception:  # graftlint: swallow(native hash unavailable: python path below is the oracle)
            pass
        blobs = col.blobs
    else:
        blobs = col_or_blobs
    out = np.empty(len(blobs), dtype=np.int32)
    c32 = wire.crc32c
    for i, b in enumerate(blobs):
        out[i] = c32(b) % num_buckets
    return out


def _pad_ragged_cast(col: Column, max_len: int, out_dtype) -> tuple:
    """One-level pad with optional dtype cast, native-fused when possible."""
    from tpu_tfrecord import _native

    if _native.available():
        res = _native.pad_ragged_dense(col.values, col.offsets, max_len, out_dtype)
        if res is not None:
            return res
    dense, lengths = pad_ragged(col.values, col.offsets, max_len)
    if out_dtype is not None and dense.dtype != np.dtype(out_dtype):
        dense = dense.astype(out_dtype)
    return dense, lengths


def _pad_ragged2_cast(col: Column, lo: int, li: int, out_dtype) -> tuple:
    """Two-level pad with optional dtype cast, native-fused when possible."""
    from tpu_tfrecord import _native

    if _native.available():
        res = _native.pad_ragged2_dense(
            col.values, col.inner_offsets, col.offsets, lo, li, out_dtype
        )
        if res is not None:
            return res
    dense, outer_len, inner_len = pad_ragged2(
        col.values, col.inner_offsets, col.offsets, lo, li
    )
    if out_dtype is not None and dense.dtype != np.dtype(out_dtype):
        dense = dense.astype(out_dtype)
    return dense, outer_len, inner_len


def host_batch_from_columnar(
    batch: ColumnarBatch,
    schema: StructType,
    pad_to: Optional[Dict[str, Union[int, tuple]]] = None,
    hash_buckets: Optional[Dict[str, int]] = None,
    include_lengths: bool = True,
    pack: Optional[Dict[str, List[str]]] = None,
    cast: Optional[Dict[str, np.dtype]] = None,
) -> Dict[str, np.ndarray]:
    """ColumnarBatch -> dict of dense numpy arrays matching batch_spec.

    ``pack`` groups same-dtype scalar columns into one [B, K] array
    (``{"dense": ["I1", ...], "cat": ["C1", ...]}``) — fewer, larger
    device transfers (one dispatch per group instead of per column) and the
    natural layout for MXU-bound consumers like the DLRM model.

    ``cast`` maps column name -> output dtype (e.g. bfloat16 for float
    frames). For ragged columns the pad and the cast run fused in the native
    kernel — the f32->bf16 conversion never materializes an f32 dense batch.

    The call is one ``tfr:pack`` span (rows, bytes out) in a profiler
    capture.
    """
    with trace("tfr:pack") as tr:
        out = _densify(
            batch, schema, pad_to or {}, hash_buckets or {}, include_lengths,
            pack, cast or {},
        )
        tr.set_metadata(
            rows=batch.num_rows, bytes=sum(a.nbytes for a in out.values())
        )
    return out


def _densify(
    batch: ColumnarBatch, schema: StructType, pad_to: dict, hash_buckets: dict,
    include_lengths: bool, pack: Optional[Dict[str, List[str]]], cast: dict,
) -> Dict[str, np.ndarray]:
    """The work of :func:`host_batch_from_columnar`."""
    _validate_cast(schema, cast)
    if cast and pack:
        # A pack group is ONE matrix with one dtype — a per-member cast
        # would be silently skipped when the group was materialized by the
        # native decoder, defeating _validate_cast's loud-failure contract.
        for group, names in pack.items():
            overlap = sorted(set(cast) & set(names))
            if overlap:
                raise ValueError(
                    f"cast: columns {overlap} are members of pack group "
                    f"{group!r}; casting packed members is not supported"
                )
    out: Dict[str, np.ndarray] = {}
    # Groups already materialized by the native decoder (pack pushed down):
    # take their matrices directly and skip the member fields.
    packed_members = set()
    if pack:
        for group, names in pack.items():
            if group in batch:
                out[group] = batch[group].values
                packed_members.update(names)
    for f in schema:
        if f.name in packed_members:
            continue
        col = batch[f.name]
        dt = f.data_type
        if _is_bytes_like(dt):
            if f.name in hash_buckets:
                if col.is_ragged:
                    # multi-hot categorical: ragged hashed indices pad to
                    # [B, K] + lengths (consumers mask/pool over K)
                    if f.name not in pad_to:
                        raise ValueError(
                            f"multi-hot column {f.name!r} requires pad_to[{f.name!r}]"
                        )
                    if col.values is not None:
                        # fused: already int32 indices — bucket counts must
                        # agree, same contract as the scalar path
                        if (
                            col.hash_buckets is not None
                            and col.hash_buckets != hash_buckets[f.name]
                        ):
                            raise ValueError(
                                f"{f.name}: decoded with hash_buckets="
                                f"{col.hash_buckets} but host batch requests "
                                f"{hash_buckets[f.name]}"
                            )
                        vals = col.values
                    else:
                        vals = hash_bytes_column(col, hash_buckets[f.name])
                    dense, lengths = pad_ragged(vals, col.offsets, pad_to[f.name])
                    out[f.name] = dense
                    if include_lengths:
                        out[f.name + "_len"] = lengths
                    continue
                if col.values is not None:
                    # already hashed during decode (fused native path)
                    if (
                        col.hash_buckets is not None
                        and col.hash_buckets != hash_buckets[f.name]
                    ):
                        raise ValueError(
                            f"{f.name}: decoded with hash_buckets="
                            f"{col.hash_buckets} but host batch requests "
                            f"{hash_buckets[f.name]}"
                        )
                    out[f.name] = col.values
                else:
                    out[f.name] = hash_bytes_column(col, hash_buckets[f.name])
            continue
        if isinstance(dt, ArrayType):
            if isinstance(dt.element_type, ArrayType):
                lo, li = pad_to[f.name]
                dense, outer_len, inner_len = _pad_ragged2_cast(
                    col, lo, li, cast.get(f.name)
                )
                out[f.name] = dense
                if include_lengths:
                    out[f.name + "_len"] = outer_len
                    out[f.name + "_inner_len"] = inner_len
            else:
                if f.name not in pad_to:
                    # Padding to the per-batch max would make shapes vary
                    # batch-to-batch (jit recompiles; per-host shapes diverge
                    # multi-host) — require an explicit static length, same
                    # as batch_spec.
                    raise ValueError(
                        f"ragged column {f.name!r} requires pad_to[{f.name!r}]"
                    )
                dense, lengths = _pad_ragged_cast(
                    col, pad_to[f.name], cast.get(f.name)
                )
                out[f.name] = dense
                if include_lengths:
                    out[f.name + "_len"] = lengths
        else:
            vals = col.values
            if f.name in cast and vals.dtype != np.dtype(cast[f.name]):
                vals = vals.astype(cast[f.name])
            out[f.name] = vals
    if pack:
        for group, names in pack.items():
            if group in out:
                continue  # decoded as a matrix already
            cols = [out.pop(n) for n in names]
            out[group] = np.stack(cols, axis=1)
    return out


# ---------------------------------------------------------------------------
# Global array assembly
# ---------------------------------------------------------------------------


def data_shardings(
    host_batch: Dict[str, np.ndarray], mesh: Mesh, axis: str = "data"
) -> Dict[str, NamedSharding]:
    """Batch-dim-on-``axis`` sharding for every array in a host batch.
    Precompute once per batch structure — sharding construction is pure
    Python overhead on the per-batch hot path."""
    return {
        name: NamedSharding(mesh, P(axis, *([None] * (arr.ndim - 1))))
        for name, arr in host_batch.items()
    }


def make_global_batch(
    host_batch: Dict[str, np.ndarray],
    mesh: Mesh,
    axis: str = "data",
    shardings: Optional[Dict[str, NamedSharding]] = None,
) -> Dict[str, jax.Array]:
    """Per-host numpy batch -> pytree of GLOBAL jax.Arrays sharded on
    ``axis``. Each host contributes its local rows; across P processes the
    global batch dim is P * local_batch (jax.make_array_from_process_local_data
    — the BASELINE.json north-star assembly path)."""
    single_process = jax.process_count() == 1
    with timed("h2d", METRICS) as t, trace("tfr:h2d") as tr:
        if shardings is None:
            shardings = data_shardings(host_batch, mesh, axis)
        if single_process:
            # local == global: ONE sharded device_put over the whole pytree —
            # a single dispatch instead of one per array
            out = jax.device_put(host_batch, shardings)
        else:
            out = {
                name: jax.make_array_from_process_local_data(shardings[name], arr)
                for name, arr in host_batch.items()
            }
        for arr in host_batch.values():
            t.bytes += arr.nbytes
        t.records += next(iter(host_batch.values())).shape[0] if host_batch else 0
        tr.set_metadata(rows=t.records, bytes=t.bytes)
    return out


class TokenPacker:
    """Ragged token documents -> packed causal-LM batches [B, L+1] int32.

    Three packing modes (the ``packing`` argument):

    - ``"slice"`` (default): documents are concatenated with an EOS
      separator and sliced into non-overlapping windows of L+1 tokens
      (the consumer reads ``row[:-1]`` and scores against ``row[1:]``),
      so every batch is fully dense — no padding, no masks, maximal MXU
      utilization — the standard packed-LM feed. The window boundary
      drops no tokens (the residual tail carries into the next batch)
      but DOES split documents across rows, and rows mix documents with
      no boundary signal: attention leaks across documents.
    - ``"first_fit"`` / ``"best_fit"``: bin packing. Each document (+
      its EOS; documents longer than L+1 are pre-split into L+1-sized
      chunks, each chunk its own segment) is placed whole into one of up
      to B open row-bins of capacity L+1 — first_fit takes the
      lowest-indexed bin it fits, best_fit the fitting bin with the
      LEAST remaining room (ties to the lowest index). When a chunk fits
      no bin and all B are open, the batch closes: rows pad to L+1 with
      EOS and ``pop()`` returns ``{"tokens": [B, L+1], "segment_ids":
      [B, L+1]}`` — ids number each row's documents 1..k in placement
      order, pad positions are 0 — the block-diagonal mask feed for
      `models.attention` ``segments``. Density (non-pad fraction,
      ``density()``) is < 1 but no document ever crosses a row.

    The carry (residual tokens / open bins + any already-packed-but-
    unpopped rows) is the ONLY state, exposed via ``state()``/
    ``restore()`` as a small JSON payload, so a training job checkpoints
    it NEXT TO the dataset's `IteratorState` and a kill -9/resume
    replays the packed stream byte-identically (pinned by
    examples/train_lm.py's harness test).

    ``noise=(block_length, mask_id, seed)`` (bin modes) is the input
    pipeline of a block-diffusion model: dynamic masking. Beside ``tokens``
    and ``segment_ids`` a batch then carries ``noised`` [B, L+1] int32, the
    row with a share of its tokens replaced by ``mask_id``, and
    ``noise_level`` [B, L+1] float32, each token's block's ``t`` (0 on
    pads). A document's blocks are ``block_length`` tokens counted from its
    OWN first token (its end id among them); block ``j`` draws ``t_j`` ~
    U(0, 1] and each of its tokens is masked with probability ``t_j``,
    independently. The draw is a counter-based generator (Philox) keyed by
    ``seed`` at the counter of the document's number in the stream, a
    block's ``t`` and a token's draw at their own places in that stream: a
    document's noise does not depend on the bin it lands in, and the number
    of documents placed rides ``state()`` / ``restore()``, so a restored
    packer replays all four columns byte for byte. Two things about the
    rows change with it: every document starts at a whole multiple of
    ``block_length`` in its row (pads of segment 0 between documents, under
    ``block_length`` a document), so that a block never straddles a
    multiple of it (an attention kernel's key tile, ``models.attention``);
    and a row's last column holds a pad or a document's END id, never
    another token (a longer document is pre-split into chunks of L, not
    L+1): such a model scores a token at its own position, so the consumer
    reads ``row[:-1]`` as input AND target, and only a document that fills
    its row goes without its end id scored.
    Without ``noise`` the packer is byte for byte what it was.
    """

    _MODES = ("slice", "first_fit", "best_fit")

    def __init__(
        self, batch_size: int, seq_len: int, eos_id: int = 0,
        packing: str = "slice", noise: Optional[Tuple[int, int, int]] = None,
    ):
        if batch_size < 1 or seq_len < 1:
            raise ValueError(
                f"batch_size and seq_len must be >= 1, got "
                f"({batch_size}, {seq_len})"
            )
        if packing not in self._MODES:
            raise ValueError(
                f"packing must be one of {self._MODES}, got {packing!r}"
            )
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.eos_id = int(eos_id)
        self.packing = packing
        if noise is not None:
            if packing == "slice":
                raise ValueError("noise needs a bin mode: a block is counted from its document's first token")
            block, mask_id, seed = (int(n) for n in noise)
            if block < 1 or mask_id == self.eos_id:
                raise ValueError(f"noise {noise}: a block of at least 1 token and a mask id that is not the end id")
            noise = (block, mask_id, seed)
        self.noise = noise
        self._align = noise[0] if noise else 1        # a document starts at a whole multiple of this in its row
        self._split = seq_len + (0 if noise else 1)   # the tokens of a chunk of a document longer than a row
        self._placed = 0                              # noise: documents (chunks) placed so far, the draw's counter
        self._numbers: List[List[int]] = []           # ... and each open bin's documents' numbers
        self._buf: List[np.ndarray] = []   # chunks, flattened lazily
        self._buf_len = 0
        # bin modes: open row-bins, each a list of document chunks
        self._bins: List[List[np.ndarray]] = []
        self._fill: List[int] = []         # tokens placed in each open bin
        self._pending: List[Any] = []  # ready [B, L+1] batches / dicts
        # density accounting (bin modes; slice mode is 1.0 by construction)
        self._emitted_tokens = 0
        self._emitted_nonpad = 0

    def feed_docs(self, docs: Iterable[np.ndarray]) -> None:
        """Append documents (1-D int arrays) to the stream, EOS after each."""
        if self.packing != "slice":
            self._feed_docs_bins(docs)
            return
        eos = np.asarray([self.eos_id], np.int32)
        for doc in docs:
            arr = np.asarray(doc).astype(np.int32, copy=False).reshape(-1)
            self._buf.append(arr)
            self._buf.append(eos)
            self._buf_len += arr.size + 1
        self._drain()

    def _feed_docs_bins(self, docs: Iterable[np.ndarray]) -> None:
        cap = self.seq_len + 1
        eos = np.asarray([self.eos_id], np.int32)
        n_docs, ready = 0, len(self._pending)
        # tracing.ANNOTATIONS: one span a call (a reader batch), never a document
        with trace("tfr:pack_tokens") as tr:
            for doc in docs:
                arr = np.asarray(doc).astype(np.int32, copy=False).reshape(-1)
                arr = np.concatenate([arr, eos])
                # long documents pre-split into cap-sized chunks; each chunk
                # is its own attention segment (they cannot share a row and
                # attend to each other anyway)
                step = cap if arr.size <= cap else self._split
                for at in range(0, arr.size, step):
                    self._place_chunk(arr[at : at + step])
                n_docs += 1
            rows = (len(self._pending) - ready) * self.batch_size
            tr.set_metadata(docs=n_docs, rows=rows, tokens=rows * cap)

    def _place_chunk(self, chunk: np.ndarray) -> None:
        cap, align = self.seq_len + 1, self._align
        if self.noise:
            number, self._placed = self._placed, self._placed + 1
        fit = -1
        if self.packing == "best_fit":
            best_room = cap + 1
            for i, used in enumerate(self._fill):
                room = cap - -(-used // align) * align    # from the next whole multiple on
                if chunk.size <= room < best_room:
                    fit, best_room = i, room
        else:  # first_fit — the greedy binning baseline
            for i, used in enumerate(self._fill):
                if chunk.size <= cap - -(-used // align) * align:
                    fit = i
                    break
        if fit < 0:
            if len(self._bins) == self.batch_size:
                self._close_bins()
            self._bins.append([])
            self._fill.append(0)
            if self.noise:
                self._numbers.append([])
        self._bins[fit].append(chunk)
        self._fill[fit] = -(-self._fill[fit] // align) * align + chunk.size
        if self.noise:
            self._numbers[fit].append(number)

    def flush(self) -> None:
        """End of a finite stream (bin modes): close the open bins into one
        last batch, rows that no bin reached all pad. ``slice`` mode keeps
        its residual: a partial window is no batch."""
        if self._bins:
            self._close_bins()

    def _close_bins(self) -> None:
        """Flush the B open bins into one pending {tokens, segment_ids}
        batch: rows pad to L+1 with EOS, pad segment id 0."""
        cap, align = self.seq_len + 1, self._align
        toks = np.full((self.batch_size, cap), self.eos_id, np.int32)
        segs = np.zeros((self.batch_size, cap), np.int32)
        nonpad, lies = 0, []     # lies: (row, first column, tokens) of every chunk, the bins' order
        for r, b in enumerate(self._bins):
            at = 0
            for s, chunk in enumerate(b):
                at = -(-at // align) * align
                toks[r, at : at + chunk.size] = chunk
                segs[r, at : at + chunk.size] = s + 1
                lies.append((r, at, chunk.size))
                at += chunk.size
                nonpad += chunk.size
        batch = {"tokens": toks, "segment_ids": segs}
        if self.noise:
            batch.update(self._noised(toks, lies))
        self._bins, self._fill, self._numbers = [], [], []
        self._pending.append(batch)
        self._emitted_tokens += self.batch_size * cap
        self._emitted_nonpad += nonpad
        METRICS.gauge("pack.density", round(self.density(), 4))

    def _noised(self, toks: np.ndarray, lies: List[Tuple[int, int, int]]) -> Dict[str, np.ndarray]:
        """The open bins' rows ``toks`` noised: ``noised`` and ``noise_level``
        (the class docstring has the law); ``lies``: (row, first column,
        tokens) of every document, the bins' order. A document's draws come
        from its number in the stream alone, so they are made here, as the
        batch closes, and the open bins carry no noise of their own."""
        block, mask_id, seed = self.noise
        noised = toks.copy()
        level = np.zeros(toks.shape, np.float32)
        masked = positions = 0
        # tracing.ANNOTATIONS: one span a batch
        with trace("tfr:noise") as tr:
            numbers = (number for bin_numbers in self._numbers for number in bin_numbers)
            for (r, at, n), number in zip(lies, numbers):
                rng = np.random.Generator(np.random.Philox(key=seed, counter=[0, 0, 0, number]))
                t = (1.0 - rng.random(-(-n // block))).astype(np.float32)     # (0, 1], a block
                mine = np.repeat(t, block)[:n]
                hit = rng.random(n, dtype=np.float32) < mine
                noised[r, at : at + n][hit] = mask_id
                level[r, at : at + n] = mine
                masked, positions = masked + int(hit.sum()), positions + n
            tr.set_metadata(rows=len(self._bins), positions=positions, masked=masked)
        METRICS.count("noise.masked", masked)
        METRICS.count("noise.positions", positions)
        METRICS.gauge("noise.masked_share", round(masked / max(positions, 1), 4))
        return {"noised": noised, "noise_level": level}

    def density(self) -> float:
        """Fraction of emitted batch tokens that are real document tokens
        (1.0 until a bin-mode batch closes; slice mode is 1.0 always —
        the window slicing leaves no padding)."""
        if not self._emitted_tokens:
            return 1.0
        return self._emitted_nonpad / self._emitted_tokens

    def feed_column(self, col) -> None:
        """Feed a ragged int Column straight from a ColumnarBatch: the
        flat values/offsets ARE the document boundaries."""
        values = np.asarray(col.values)
        offsets = np.asarray(col.offsets)
        self.feed_docs(
            values[offsets[i] : offsets[i + 1]]
            for i in range(len(offsets) - 1)
        )

    def _drain(self) -> None:
        need = self.batch_size * (self.seq_len + 1)
        if self._buf_len < need:
            return
        flat = np.concatenate(self._buf) if len(self._buf) > 1 else self._buf[0]
        n_batches = flat.size // need
        take = n_batches * need
        for i in range(n_batches):
            self._pending.append(
                flat[i * need : (i + 1) * need]
                .reshape(self.batch_size, self.seq_len + 1)
                .copy()
            )
        rest = flat[take:]
        self._buf = [rest] if rest.size else []
        self._buf_len = int(rest.size)

    def pop(self):
        """Next ready batch, or None when more docs are needed: a
        [B, L+1] int32 array in slice mode, a ``{"tokens": [B, L+1],
        "segment_ids": [B, L+1]}`` dict in the bin modes."""
        return self._pending.pop(0) if self._pending else None

    def state(self) -> dict:
        """JSON-able carry: checkpoint it WITH the dataset IteratorState
        taken at the same point so resume replays byte-identically. Slice
        mode keeps its historical {residual, pending} shape (old
        checkpoints restore unchanged); bin modes carry the open bins
        (per-row chunk lists), the pending {tokens, segment_ids} dicts,
        and the density accumulators."""
        if self.packing == "slice":
            flat = (
                np.concatenate(self._buf).tolist() if self._buf else []
            )
            return {
                "residual": flat,
                "pending": [b.tolist() for b in self._pending],
            }
        out = {
            "bins": [[c.tolist() for c in b] for b in self._bins],
            "pending": [
                {name: a.tolist() for name, a in d.items()}
                for d in self._pending
            ],
            "emitted_tokens": self._emitted_tokens,
            "emitted_nonpad": self._emitted_nonpad,
        }
        if self.noise:  # the draw's counter, and whose draws the open bins' documents will get
            out["noise"] = {"placed": self._placed, "numbers": [list(n) for n in self._numbers]}
        return out

    def restore(self, state: dict) -> None:
        if self.packing == "slice":
            residual = np.asarray(state.get("residual", []), np.int32)
            self._buf = [residual] if residual.size else []
            self._buf_len = int(residual.size)
            self._pending = [
                np.asarray(b, np.int32) for b in state.get("pending", [])
            ]
            return
        self._bins = [
            [np.asarray(c, np.int32) for c in b]
            for b in state.get("bins", [])
        ]
        self._fill = []
        for b in self._bins:
            used = 0
            for c in b:
                used = -(-used // self._align) * self._align + c.size
            self._fill.append(used)
        self._pending = [
            {name: np.asarray(a, np.float32 if name == "noise_level" else np.int32)
             for name, a in d.items()}
            for d in state.get("pending", [])
        ]
        if self.noise:
            said = state.get("noise", {})
            self._placed = int(said.get("placed", 0))
            self._numbers = [[int(n) for n in numbers] for numbers in said.get("numbers", [])]
        self._emitted_tokens = int(state.get("emitted_tokens", 0))
        self._emitted_nonpad = int(state.get("emitted_nonpad", 0))


class HostPrefetcher:
    """Run a host-batch iterator in a background thread behind a bounded
    queue.

    The dataset's decode already overlaps (its own producer thread, GIL
    released in the native codec), but the numpy tail of batch production —
    pad/pack/hash in ``host_batch_from_columnar`` — otherwise runs inline in
    the consumer thread, inside the device's input-wait. Wrapping the host
    batch generator here moves that work off the critical path too, which is
    what keeps the duty cycle >=95% when batch assembly is non-trivial
    (ragged padding, many columns). Iterate it, or use as a context manager;
    ``close()`` unblocks and joins the worker."""

    _DONE = object()

    def __init__(
        self,
        host_batches: Iterable[Dict[str, np.ndarray]],
        depth: int = 2,
        name: str = "host",
    ):
        import queue
        import threading

        self._queue: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._empty = queue.Empty  # shutdown-safe binding (module may be gone)
        self._finished: Optional[object] = None
        # the queue's name in a profiler capture: how long the worker sat on
        # it full, and the consumer on it empty (tracing.ANNOTATIONS)
        self._blocked, self._starved = f"tfr:blocked.{name}", f"tfr:starved.{name}"

        def _produce():
            try:
                for hb in host_batches:
                    if not put_or_wait(self._queue, hb, self._stop, self._blocked):
                        return
                self._queue.put(self._DONE)
            except BaseException as e:  # noqa: BLE001 — repropagated in consumer  # graftlint: swallow(exception forwarded to the consumer queue, repropagated)
                self._queue.put(e)

        self._thread = threading.Thread(target=_produce, daemon=True)
        self._thread.start()

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        # The sentinel/exception arrives on the queue exactly once — cache
        # it so a second next() after exhaustion re-raises instead of
        # blocking forever on an empty queue with a dead producer.
        if self._finished is not None:
            if self._finished is self._DONE:
                raise StopIteration
            raise self._finished
        item = get_or_wait(self._queue, self._stop, self._starved)
        if item is self._DONE or item is STOPPED:
            self._finished = self._DONE
            raise StopIteration
        if isinstance(item, BaseException):
            self._finished = item
            raise item
        return item

    def close(self) -> None:
        self._stop.set()
        if self._finished is None:
            # Post-close iteration must raise StopIteration, not park on a
            # queue whose producer is gone.
            self._finished = self._DONE
        try:
            while True:
                self._queue.get_nowait()
        except self._empty:
            pass
        self._thread.join(timeout=5.0)

    def __enter__(self) -> "HostPrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class DeviceIterator:
    """Double-buffered device feeder: host batches -> sharded global batches.

    Starts the transfer of batch N+1 while the consumer computes on batch N
    (dispatch is async in JAX, so `make_array_from_process_local_data` returns
    as soon as the transfer is enqueued). This is the device_put overlap the
    reference never needed (the JVM never touched an accelerator) but a TPU
    input pipeline lives or dies by (SURVEY.md §7 hard part e).

    ``transfer_thread=True`` moves the transfer into a dedicated worker that
    BLOCKS each copy to completion behind a bounded queue of device-resident
    batches, so the consumer only ever pops batches that are already there.
    On a local v5e the copy is asynchronous at dispatch (chip_smoke.py's
    transport probe: ``device_put`` of 256 MB returns in 0.5 ms and
    completes in 43 ms), so dispatch-ahead alone already overlaps copy and
    compute there; whether the worker thread still pays is ROADMAP D10's
    question, to be answered from ledger rows. Use ``close()`` (or a
    ``with`` block) to release the worker."""

    def __init__(
        self,
        host_batches: Iterable[Dict[str, np.ndarray]],
        mesh: Mesh,
        axis: str = "data",
        transfer_thread: bool = False,
        depth: int = 2,
    ):
        self._it = iter(host_batches)
        self._mesh = mesh
        self._axis = axis
        self._pending: Optional[Dict[str, jax.Array]] = None
        self._shardings: Optional[Dict[str, NamedSharding]] = None
        self._sharding_key: Optional[Dict[str, int]] = None
        self._pf: Optional[HostPrefetcher] = None
        #: Cumulative host-side seconds spent transferring batches to the
        #: device (dispatch, plus the block-to-completion in threaded
        #: mode). The training harness (examples/_harness.StepPhases)
        #: snapshots this around each ``next()`` to split the step's wait
        #: window into ``train.data_wait`` vs ``train.h2d`` — without it,
        #: every inline H2D copy would masquerade as input-pipeline wait
        #: and the training verdict would blame the wrong layer.
        self.transfer_seconds = 0.0
        if transfer_thread:
            # Delegate the thread/queue/sentinel protocol to HostPrefetcher
            # (it is item-type-agnostic); the generator below is what runs
            # on its worker: transfer + block each copy to completion, so
            # the consumer pops already-device-resident batches.
            def _transferred():
                for host in self._it:
                    t0 = time.perf_counter()
                    gb = self._transfer(host, _timed=False)
                    with trace("tfr:h2d_land"):
                        jax.block_until_ready(gb)
                    self.transfer_seconds += time.perf_counter() - t0
                    yield gb

            self._pf = HostPrefetcher(_transferred(), depth=depth, name="device")

    def _transfer(
        self, host: Dict[str, np.ndarray], _timed: bool = True
    ) -> Dict[str, jax.Array]:
        # Cache key includes each array's ndim: a same-named array changing
        # rank between batches must rebuild its NamedSharding (a stale
        # PartitionSpec of the wrong rank would shard incorrectly or fail).
        t0 = time.perf_counter()
        shape_key = {name: arr.ndim for name, arr in host.items()}
        if self._shardings is None or self._sharding_key != shape_key:
            self._shardings = data_shardings(host, self._mesh, self._axis)
            self._sharding_key = shape_key
        out = make_global_batch(host, self._mesh, self._axis, self._shardings)
        if _timed:  # threaded mode times transfer + block in one window
            self.transfer_seconds += time.perf_counter() - t0
        return out

    def __iter__(self) -> Iterator[Dict[str, jax.Array]]:
        return self

    def __next__(self) -> Dict[str, jax.Array]:
        if self._pf is not None:
            return next(self._pf)
        if self._pending is None:
            host = next(self._it)  # raises StopIteration at end
            self._pending = self._transfer(host)
        current = self._pending
        self._pending = None
        try:
            nxt = next(self._it)
        except StopIteration:
            return current
        self._pending = self._transfer(nxt)
        return current

    def close(self) -> None:
        """Release the transfer worker (no-op without ``transfer_thread``)."""
        if self._pf is not None:
            self._pf.close()

    def __enter__(self) -> "DeviceIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
