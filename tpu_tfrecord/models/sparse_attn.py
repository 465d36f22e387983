"""Learned sparse attention's selection: a lightning indexer's scores, the
exact top-k of them per query inside the query's own document, and YaRN's
frequencies for the rotary turns that both the indexer and the attention
take (models.lm's latent-attention layer calls these; the attention over
the chosen keys is ``attention.flash_attention_widths`` / ``blockwise_attention``
with ``keep``).

The indexer gives every token ONE key ``k^I`` of ``D`` columns and every
query ``H`` small heads ``q^I`` with a weight ``w`` each:

    I(t, s) = sum_j w(t, j) relu(q^I(t, j) . k^I(s)),   s <= t, same document
    S(t)    = the keys whose I(t, s) >= the k-th largest I(t, .)  (all of them
              where the query has k candidates or fewer; ties at the threshold
              are all kept)

Products of bfloat16 ``q^I`` and ``k^I`` accumulate in float32; the ReLU, the
weighted sum over the heads (head 0 first), the threshold and the comparison
are float32. The selection is exact: no approximate top-k, no block-level
choice. :func:`select_keys` returns it as a mask ``keep`` [B, L, L] int8
(1 = query row may see key column). On a TPU, for rows of whole blocks, one
Pallas kernel computes a block of queries' scores against every key block
at or before it, keeps them in the chip's fast memory as sortable integers
and finds each row's k-th largest by bisection on the bits (32 counting
passes over the block's scores): the scores never reach the chip's main
memory. Elsewhere the plain form: the same sums, ``lax.top_k`` for the
threshold (tests/test_dsa_lm.py holds the interpreted kernel to it).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_tfrecord.compile_cache import kernel_trace

_LANES, _SUBLANES = 128, 8
_INT_MIN = -(1 << 31)
_BLOCK_Q, _BLOCK_K = 256, 512  # the kernel's queries a grid step and keys a score block


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def yarn_blend(half: int, theta: float, scaling: Sequence[float]) -> np.ndarray:
    """What YaRN multiplies the ``half`` rotary frequencies ``theta ** (-i / half)``
    by, float32. ``scaling`` = (factor, original length, beta_fast, beta_slow):
    a frequency that turns more than ``beta_fast`` times over the original
    length stays, one that turns less than ``beta_slow`` times is divided by
    ``factor``, and between them a linear ramp over the index blends the two."""
    factor, original, beta_fast, beta_slow = scaling
    dim = 2 * half

    def index_turning(turns: float) -> float:
        return dim * math.log(original / (turns * 2.0 * math.pi)) / (2.0 * math.log(theta))

    low = max(math.floor(index_turning(beta_fast)), 0)
    high = min(math.ceil(index_turning(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return (ramp / factor + 1.0 - ramp).astype(np.float32)


def yarn_softmax_gain(scaling: Sequence[float]) -> float:
    """What YaRN multiplies the softmax scale by: ``(0.1 ln(factor) + 1) ** 2``
    (``mscale`` and ``mscale_all_dim`` 1, as the family publishes them)."""
    return (0.1 * math.log(scaling[0]) + 1.0) ** 2


# ---------------------------------------------------------------------------
# The selection, plain
# ---------------------------------------------------------------------------


def index_scores(q_idx, k_idx, w):
    """``I`` [B, Q, K] float32 of queries q_idx [B, H, Q, D] against keys
    k_idx [B, K, D] under the heads' weights w [B, Q, H] (float32): a head
    at a time, head 0 first, every sum in float32."""
    def one_head(acc, head):
        q, wj = head                                             # [B, Q, D], [B, Q]
        s = jnp.einsum("bqd,bkd->bqk", q, k_idx, preferred_element_type=jnp.float32)
        return acc + wj[..., None] * jnp.maximum(s, 0.0), None

    zero = jnp.zeros((q_idx.shape[0], q_idx.shape[2], k_idx.shape[1]), jnp.float32)
    acc, _ = jax.lax.scan(one_head, zero, (jnp.moveaxis(q_idx, 1, 0), jnp.moveaxis(w, 2, 0)))
    return acc


def _select_plain(q_idx, k_idx, w, segments, topk: int, block: int):
    b, _, l, _ = q_idx.shape
    at = jnp.arange(l)
    keep, kept = [], []
    for q0 in range(0, l, block):
        q1 = min(q0 + block, l)                                  # no key after q1 is a candidate
        valid = (segments[:, q0:q1, None] == segments[:, None, :q1]) & (
            at[None, :q1] <= at[q0:q1, None])[None]
        if topk < q1:
            scores = jnp.where(valid, index_scores(q_idx[:, :, q0:q1], k_idx[:, :q1],
                                                   w[:, q0:q1]), -jnp.inf)
            threshold = jax.lax.top_k(scores, topk)[0][..., -1:]
            valid = valid & (scores >= threshold)
        kept.append(valid.sum(axis=-1, dtype=jnp.int32))
        keep.append(jnp.pad(valid.astype(jnp.int8), ((0, 0), (0, 0), (0, l - q1))))
    return jnp.concatenate(keep, axis=1), jnp.concatenate(kept, axis=1)


# ---------------------------------------------------------------------------
# The selection as one kernel
# ---------------------------------------------------------------------------


def _select_kernel(qseg_ref, kseg_ref, q_ref, k_ref, w_ref, keep_ref, kept_ref, keys_ref, *,
                   topk: int, block_q: int, block_k: int):
    """Grid (row, query block, key block), the key blocks in turn: each step
    at or before the diagonal lays one [block_q, block_k] block of scores
    into ``keys_ref`` as integers that sort like the floats (a pair that is
    no candidate as the least integer); the last step finds every row's
    k-th largest by building its bits from the top (a bit stays if at least
    k keys are still at or above the number) and writes the row of the mask."""
    from jax.experimental import pallas as pl

    qi, ki, n_blocks = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    last = ((qi + 1) * block_q - 1) // block_k                  # the last key block with a candidate

    @pl.when(ki <= last)
    def _scores():
        k, w = k_ref[0], w_ref[0]
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(q_ref.shape[1]):
            s = jax.lax.dot_general(q_ref[0, j], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
        bits = jax.lax.bitcast_convert_type(acc + 0.0, jnp.int32)   # + 0.0: no negative zero
        key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
        rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        same = jnp.tile(qseg_ref[0], (1, block_k // _LANES)) == kseg_ref[0, :1]
        keys_ref[ki] = jnp.where(same & (cols <= rows), key, jnp.int32(_INT_MIN))

    @pl.when(ki == n_blocks - 1)
    def _select():
        def at_or_above(number):                                 # [block_q, 1] -> how many keys of a row
            def block(c, n):
                hit = (keys_ref[c] >= number).astype(jnp.int32)
                return n + jnp.sum(hit, axis=1, keepdims=True)

            return jax.lax.fori_loop(0, last + 1, block, jnp.zeros((block_q, 1), jnp.int32))

        zero = jnp.zeros((block_q, 1), jnp.int32)
        top = jnp.where(at_or_above(zero) >= topk, zero, jnp.int32(_INT_MIN))

        def one_bit(i, number):
            with_bit = number | jnp.left_shift(jnp.int32(1), 30 - i)
            return jnp.where(at_or_above(with_bit) >= topk, with_bit, number)

        kth = jnp.maximum(jax.lax.fori_loop(0, 31, one_bit, top), jnp.int32(_INT_MIN + 1))
        total = jnp.zeros((block_q, 1), jnp.int32)
        for c in range(keys_ref.shape[0]):
            hit = jnp.where(c <= last, (keys_ref[c] >= kth).astype(jnp.int32), 0)
            keep_ref[0, :, c * block_k:(c + 1) * block_k] = hit.astype(jnp.int8)
            total = total + jnp.sum(hit, axis=1, keepdims=True)
        kept_ref[0] = jnp.broadcast_to(total, kept_ref.shape[1:])


def _select_fused(q_idx, k_idx, w, segments, topk: int, tile: Tuple[int, int],
                  interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, l, d = q_idx.shape
    block_q, block_k = tile
    n_blocks = l // block_k

    def key_block(bi, qi, ki):
        """A step past the diagonal asks for the diagonal's block again: nothing is copied."""
        return jnp.minimum(ki, ((qi + 1) * block_q - 1) // block_k)

    kernel = functools.partial(_select_kernel, topk=topk, block_q=block_q, block_k=block_k)
    call = pl.pallas_call(
        kernel,
        grid=(b, l // block_q, n_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, _LANES), lambda bi, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, _SUBLANES, block_k), lambda bi, qi, ki: (bi, 0, key_block(bi, qi, ki))),
            pl.BlockSpec((1, h, block_q, d), lambda bi, qi, ki: (bi, 0, qi, 0)),
            pl.BlockSpec((1, block_k, d), lambda bi, qi, ki: (bi, key_block(bi, qi, ki), 0)),
            pl.BlockSpec((1, block_q, h), lambda bi, qi, ki: (bi, qi, 0)),
        ],
        out_specs=[pl.BlockSpec((1, block_q, l), lambda bi, qi, ki: (bi, qi, 0)),
                   pl.BlockSpec((1, block_q, _LANES), lambda bi, qi, ki: (bi, qi, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, l, l), jnp.int8),
                   jax.ShapeDtypeStruct((b, l, _LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((n_blocks, block_q, block_k), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            # the block's scores (4 bytes a pair), its row of the mask twice, its queries twice
            vmem_limit_bytes=block_q * l * 8 + 4 * h * block_q * d * 2 + (16 << 20)),
        interpret=interpret,
    )
    with kernel_trace("kernel.trace.dsa_index"):  # the body's trace, as a program is traced
        keep, kept = call(
            jnp.broadcast_to(segments[:, :, None], (b, l, _LANES)),
            jnp.broadcast_to(segments[:, None, :], (b, _SUBLANES, l)), q_idx, k_idx, w)
    return keep, kept[..., 0]


def select_tile(shape, topk: int) -> Optional[Tuple[int, int]]:
    """(queries a grid step, keys a score block) of the kernel for q_idx of
    ``shape`` [B, H, L, D], or None where the plain form runs: off a TPU (the
    kernel exists for no other backend), at an index width that is not whole
    lanes of 128, and for rows that are not whole blocks or hold no more
    than ``topk`` tokens (there every candidate is kept and nothing is scored)."""
    l, d = shape[2:]
    if jax.default_backend() != "tpu" or d % _LANES or l % _BLOCK_K or l <= topk:
        return None
    return _BLOCK_Q, _BLOCK_K


def select_keys(q_idx, k_idx, w, segments, topk: int, block: int = 1024):
    """The keys each query attends: (``keep`` [B, L, L] int8, 1 where query row
    t may see key column s; ``kept`` [B, L] int32, how many a query keeps).
    q_idx [B, H, L, D] and k_idx [B, L, D] (as the rotary turns left them),
    w [B, L, H] float32, segments [B, L]. A candidate is a key at or before
    the query in the query's own document; with ``topk`` candidates or fewer
    all are kept, else those whose score is at least the ``topk``-th largest.
    ``block``: the queries scored at a time by the plain form."""
    tile = select_tile(q_idx.shape, topk)
    if tile is not None:
        return _select_fused(q_idx, k_idx, w, segments, topk, tile)
    return _select_plain(q_idx, k_idx, w, segments, topk, block)
