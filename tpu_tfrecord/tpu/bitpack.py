"""Transfer bit-packing: shrink host->device bytes for bounded-int columns.

Hashed categorical features are bucket indices in ``[0, hash_buckets)`` —
for the common 2**20-bucket embedding table that is 20 significant bits
carried in a 32-bit lane: 37.5% of every transferred byte is zero padding.
On TPU the host->device link (PCIe) can be
the scarcest resource in an ingest pipeline, while on-device bit
twiddling is effectively free once fused into the consumer's jit program.

``pack_bits`` packs the columns of an int32 matrix into ``bits``-wide lanes
inside a narrower int32 matrix on the host (one vectorized numpy pass);
``unpack_bits`` is its exact inverse built from jax ops — shifts, masks and
a (C_out x C_in) gather — that XLA fuses into whatever consumes the batch.
Round-trip is bit-exact for any values < 2**bits.

The reference framework never needed this: its JVM rows stayed on the host
(SURVEY.md L2/L3). It exists here because a TPU-first ingest path budgets
bytes-per-example against link bandwidth, the same way BASELINE.md's
north-star metric does.
"""

from __future__ import annotations

import numpy as np

from tpu_tfrecord.tracing import trace

__all__ = ["packed_width", "pack_bits", "pack_mixed", "unpack_bits"]

_LANE = 32  # packing lane width: int32, the narrowest common transfer dtype


def packed_width(n_cols: int, bits: int) -> int:
    """Number of int32 output columns for ``n_cols`` values of ``bits`` each."""
    if not 1 <= bits <= _LANE:
        raise ValueError(f"bits must be in [1, {_LANE}], got {bits}")
    return (n_cols * bits + _LANE - 1) // _LANE


def pack_bits(arr: np.ndarray, bits: int) -> np.ndarray:
    """Pack ``arr[:, j] < 2**bits`` (int32/int64, non-negative) into a dense
    [B, packed_width] int32 matrix, little-endian within and across lanes:
    value j occupies global bit positions [j*bits, (j+1)*bits).

    Values are masked to ``bits`` (callers hash/bucket first, which already
    guarantees the range); negatives are rejected — two's-complement lanes
    would silently corrupt neighbours. Round-trip restores the low ``bits``
    bit pattern; since the unpacked dtype is int32, values in
    ``[2**31, 2**32)`` (only possible at bits=32) come back as their int32
    reinterpretation.
    """
    if arr.ndim != 2:
        raise ValueError(f"pack_bits expects [B, C], got shape {arr.shape}")
    b, c = arr.shape
    w = packed_width(c, bits)
    if np.issubdtype(arr.dtype, np.signedinteger) and arr.size and arr.min() < 0:
        raise ValueError("pack_bits requires non-negative values")
    if bits == _LANE:
        return (
            (arr.astype(np.uint64) & 0xFFFFFFFF).astype(np.uint32).view(np.int32)
        )
    vals = arr.astype(np.uint64) & ((1 << bits) - 1)
    out = np.zeros((b, w), dtype=np.uint64)  # u64 scratch absorbs lane spill
    starts = np.arange(c, dtype=np.int64) * bits
    lanes = starts // _LANE
    offs = starts % _LANE
    for j in range(c):
        lane, off = int(lanes[j]), int(offs[j])
        out[:, lane] |= vals[:, j] << off
        spill = off + bits - _LANE
        if spill > 0:
            out[:, lane + 1] |= vals[:, j] >> (bits - spill)
    # low 32 bits of each u64 lane are the packed stream
    return (out & 0xFFFFFFFF).astype(np.uint32).view(np.int32)


def pack_mixed(arr: np.ndarray, keep: int, bits: int) -> np.ndarray:
    """Mixed-width wire matrix: the first ``keep`` int32 lanes of each row
    pass through verbatim, the remaining columns bit-pack to ``bits`` —
    the one-call form of ``concatenate([arr[:, :keep], pack_bits(arr[:,
    keep:], bits)])`` with a native single-pass kernel on the hot path
    (csrc tfr_pack_mixed; numpy fallback is bit-identical, pinned in
    tests/test_bitpack.py). The consumer unpacks the tail with
    ``unpack_bits(wire[:, keep:], C - keep, bits)``. One ``tfr:pack`` span
    (rows, bytes out) in a profiler capture.
    """
    if arr.ndim != 2:
        raise ValueError(f"pack_mixed expects [B, C], got shape {arr.shape}")
    if not 0 <= keep <= arr.shape[1]:
        raise ValueError(f"keep={keep} out of range for {arr.shape[1]} columns")
    packed_width(1, bits)  # validate bits BEFORE dispatching to the kernel
    with trace("tfr:pack") as tr:
        out = _pack_mixed(arr, keep, bits)
        tr.set_metadata(rows=out.shape[0], bytes=out.nbytes)
    return out


def _pack_mixed(arr: np.ndarray, keep: int, bits: int) -> np.ndarray:
    if arr.dtype == np.int32:
        # hot path (decode emits int32 group matrices): single native pass,
        # sign validation rides the kernel loop — no extra numpy scan
        try:
            from tpu_tfrecord import _native

            if _native.available():
                out = _native.pack_mixed(arr, keep, bits)
                if out is not None:
                    return out
        except ImportError:
            pass
    # pack_bits performs the negative-value rejection for the tail
    return np.concatenate(
        [np.ascontiguousarray(arr[:, :keep]).astype(np.int32),
         pack_bits(arr[:, keep:], bits)],
        axis=1,
    )


def unpack_bits(packed, n_cols: int, bits: int):
    """Inverse of :func:`pack_bits` as jax ops: [B, packed_width] int32 ->
    [B, n_cols] int32. Call inside the consumer's jit — XLA fuses the
    gather/shift/mask into the surrounding program, so the unpack costs no
    extra HBM round-trip.
    """
    import jax.numpy as jnp

    if bits == _LANE:
        return packed
    u = packed.astype(jnp.uint32)
    starts = np.arange(n_cols, dtype=np.int64) * bits
    lanes = (starts // _LANE).astype(np.int32)
    offs = (starts % _LANE).astype(np.int32)
    spill = offs + bits - _LANE  # >0 where a value straddles two lanes
    lo = u[:, lanes] >> jnp.asarray(offs, dtype=jnp.uint32)[None, :]
    # high part: next lane's low bits, shifted up; masked off when no spill
    next_lane = np.minimum(lanes + 1, packed.shape[1] - 1).astype(np.int32)
    hi_shift = np.where(spill > 0, bits - spill, 0).astype(np.int64)
    hi = u[:, next_lane] << jnp.asarray(hi_shift, dtype=jnp.uint32)[None, :]
    hi = jnp.where(jnp.asarray(spill > 0)[None, :], hi, jnp.zeros_like(hi))
    mask = jnp.uint32((1 << bits) - 1)
    return ((lo | hi) & mask).astype(jnp.int32)
