"""The scoring head of the pattern model: for every token of a packed batch

    logprob(t) = logit(t, target(t)) - logsumexp_v logit(t, v),   logit = flat @ head

with ``flat`` [T, D] the normed activations, ``head`` [D, V] and ``target`` the
next token (``models.lm.score`` calls :func:`logprob` under ``tfr.lm_head``).
bfloat16 operands, float32 logits, maximum, sum and pick.

The plain form (:func:`logprob_blocks`) computes the logits a block of tokens
at a time: a float32 ``[block, V]`` array that XLA writes to the chip's main
memory after the product and reads again for the second reduction (at a
vocabulary of 163,840 that is 1.34 GB a block of 2,048 tokens, eight times a
step). On a TPU, for bfloat16 operands whose shape :func:`head_tile` takes, one
Pallas kernel keeps them on the chip: a grid of (token tiles, vocabulary
tiles), the vocabulary innermost; a token tile of ``flat`` stays in VMEM while
the tiles of ``head`` stream past it as they lie in memory (no padded or
transposed copy); a tile's logits come off the matrix unit a lane block of
128 columns at a time and go, still in VMEM, into three running numbers a
token AND LANE: the maximum, the sum of ``exp(logit - maximum)`` rescaled as
the maximum rises, and the target's logit (a column iota against the target).
Kept a lane, the three need no reduction across lanes until the last
vocabulary tile, which joins a token's 128 lanes and writes ``picked -
(maximum + log(sum))``. A vocabulary that is not whole tiles takes a ragged
last tile: its columns at or past ``V`` are set to ``-inf`` before anything
reads them, so what that part of the block holds does not matter.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_tfrecord.compile_cache import kernel_trace

_LANES = 128
_ROWS = (1024, 512, 256)   # tokens of a grid step, the largest that divides the batch
_COLUMNS = 512             # vocabulary columns of a grid step
_DEPTH = 4096              # channels of one product; a wider model's are cut, float32 sums in scratch
_VMEM_SPARE = 16 * 2 ** 20
# The float32 logits a step (4 * t * V bytes) under which the plain form keeps the head. What the
# kernel saves is their round trip through HBM, 8 bytes a logit: 26 ms of a step at [16,384 x
# 163,840], 2.6 ms at [16,384 x 16,160]. What it can cost lies outside it: with the head's
# temporaries gone the TPU's compiler schedules the REST of the step differently, and in the one
# cell measured end to end (PERF.md section 6, PR 50: Kimi-VL's, 10.7 GB of logits) the expert loops
# lost 13.0 ms of the 24.3 the head gained. Under this size the saving is of that cost's order:
# of the two cells tried (one pair each) Nemotron's (4.3 GB) netted +0.1% and Olmo's (6.6 GB)
# +2.35% at 118 MB more peak memory; four were never measured. The parent's program stands there.
_MIN_LOGITS = 8 * 2 ** 30


def logprob_blocks(flat, head, targets, block: int):
    """The plain form: ``block`` tokens' float32 logits at a time. flat [T, D],
    head [D, V], targets [T] int32 -> [T] float32."""
    out = []
    for t0 in range(0, flat.shape[0], block):
        logits = jnp.dot(flat[t0:t0 + block], head, preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, targets[t0:t0 + block, None], axis=-1)[:, 0]
        out.append(picked - jax.nn.logsumexp(logits, axis=-1))
    return jnp.concatenate(out)


def head_tile(t: int, d: int, v: int, dtype) -> Optional[Tuple[int, int, int]]:
    """(tokens, vocabulary columns, channels a product) of the kernel's grid step
    for ``flat`` [t, d] of ``dtype`` under a head of ``v`` columns, or None where
    the plain form runs: off a TPU (the kernel exists for no other backend),
    for operands that are not bfloat16, for a model width that is not whole
    lane blocks of 128 (a width over ``_DEPTH`` is cut in its largest equal
    parts of whole lane blocks), for a batch that is not whole token tiles, for
    a vocabulary narrower than one tile (a first tile that is ragged would
    leave lanes that have seen no column), and for a step whose float32 logits
    are under ``_MIN_LOGITS`` bytes (the kernel is the faster alone at every
    size tried; what is not measured under that size is the rest of the step
    around it). The vocabulary needs to be whole in nothing: the last tile may
    be ragged."""
    rows = next((r for r in _ROWS if t % r == 0), 0)
    if (jax.default_backend() != "tpu" or jnp.dtype(dtype) != jnp.bfloat16 or d % _LANES or not rows or v < _COLUMNS
            or 4 * t * v < _MIN_LOGITS):
        return None
    depth = next(k for k in range(min(d, _DEPTH), 0, -_LANES) if d % k == 0)   # 7,168 in two of 3,584
    return rows, _COLUMNS, depth


def logprob(flat, head, targets, block: int):
    """log p(targets) under ``flat @ head``: flat [T, D], head [D, V], targets
    [T] int32 -> ([T] float32, whether the kernel computed it): the kernel's
    where :func:`head_tile` gives a tiling, else the plain form's by blocks of
    ``block`` tokens. The one decision a program's head takes, so the caller's
    gauge reads what ran."""
    tile = head_tile(*flat.shape, head.shape[1], jnp.result_type(flat, head))
    if tile is not None:
        return _head_fused(flat, head, targets, tile), True
    return logprob_blocks(flat, head, targets, block), False


def _head_kernel(x_ref, w_ref, want_ref, out_ref, m_ref, l_ref, p_ref, *sums, vocab: int, depth: int,
                 by_column: bool):
    """Grid (token tile, vocabulary tile), the vocabulary tiles in turn. x_ref
    [rows, D] stays, w_ref [D, columns] is this step's tile of the head ([columns,
    D] ``by_column``: the tile of ``head.T``, for a head that lies column-major),
    want_ref [rows, 128] the targets (a token's along its lanes). m_ref, l_ref,
    p_ref [rows, 128] float32: for each token and lane the maximum, the
    rescaled sum and the target's logit over the columns of that lane seen so
    far (column c of a tile falls on lane c % 128). ``sums``: with a model
    width of several products, the [rows, 128] float32 scratch they add up in."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    j, last = pl.program_id(1), pl.num_programs(1) - 1
    rows, d = x_ref.shape
    columns = w_ref.shape[0 if by_column else 1]
    ragged = vocab % columns != 0

    @pl.when(j == 0)
    def _first():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        p_ref[...] = jnp.zeros(p_ref.shape, f32)

    def product(c: int):
        """The logits of the tile's lane block ``c``: [rows, 128] float32."""
        cols = slice(c * _LANES, (c + 1) * _LANES)

        def part_of(k0: int):
            ks = slice(k0, k0 + depth)
            w, over = (w_ref[cols, ks], 1) if by_column else (w_ref[ks, cols], 0)
            return jax.lax.dot_general(x_ref[:, ks], w, (((1,), (over,)), ((), ())), preferred_element_type=f32)

        if depth == d:
            return part_of(0)
        acc, = sums
        for k0 in range(0, d, depth):
            part = part_of(k0)
            if k0 == 0:
                acc[...] = part
            else:
                acc[...] += part
        return acc[...]

    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    left = vocab - j * columns            # the tile's columns that exist
    want = want_ref[...] - j * columns    # the targets, as columns of this tile

    def reduce(a, c: int):
        """Lane block ``c``'s logits ``a`` [rows, 128] into the running numbers."""
        at = lane + c * _LANES
        if ragged:
            a = jnp.where(at < left, a, -jnp.inf)
        m = m_ref[...]
        top = jnp.maximum(m, a)
        l_ref[...] = l_ref[...] * jnp.exp(m - top) + jnp.exp(a - top)
        m_ref[...] = top
        p_ref[...] += jnp.where(at == want, a, 0.0)

    # a lane block's reductions stand in the source after the NEXT block's product: the
    # two are independent, so the vector work is scheduled under the matrix unit's
    blocks = columns // _LANES
    before = product(0)
    for c in range(1, blocks):
        logits = product(c)
        reduce(before, c - 1)
        before = logits
    reduce(before, blocks - 1)

    @pl.when(j == last)
    def _last():
        m = m_ref[...]
        top = jnp.max(m, axis=1, keepdims=True)
        total = jnp.sum(l_ref[...] * jnp.exp(m - top), axis=1, keepdims=True)
        picked = jnp.sum(p_ref[...], axis=1, keepdims=True)
        out_ref[...] = jnp.broadcast_to(picked - (top + jnp.log(total)), out_ref.shape)


@functools.partial(jax.jit, static_argnames=("tile", "by_column", "interpret"))
def _head_fused(flat, head, targets, tile: Tuple[int, int, int], by_column: Optional[bool] = None,
                interpret: bool = False):
    """The kernel (jitted: a program's trace finds it built). ``tile``:
    :func:`head_tile`'s (tokens, vocabulary columns, channels a product).
    ``by_column``: read the head as ``head.T`` [V, D]; left out, where V is not
    whole lane blocks of 128. That is where the TPU's compiler lays a ``[D, V]``
    array column-major (its minor dimension would be padded), so the transpose is
    a bitcast of what lies there, and the row-major reading a padded copy a step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (t, d), v = flat.shape, head.shape[1]
    rows, columns, depth = tile
    if by_column is None:
        by_column = v % _LANES != 0
    if t % rows or d % depth or columns % _LANES or v < columns:
        raise ValueError(f"[{t}, {d}] under a head of {v} columns in tiles of {tile}: the kernel wants whole "
                         f"token tiles, whole products and a vocabulary of at least one tile")
    kernel = functools.partial(_head_kernel, vocab=v, depth=depth, by_column=by_column)
    state = pltpu.VMEM((rows, _LANES), jnp.float32)
    call = pl.pallas_call(
        kernel,
        grid=(t // rows, -(-v // columns)),
        in_specs=[pl.BlockSpec((rows, d), lambda i, j: (i, 0)),
                  (pl.BlockSpec((columns, d), lambda i, j: (j, 0)) if by_column
                   else pl.BlockSpec((d, columns), lambda i, j: (0, j))),
                  pl.BlockSpec((rows, _LANES), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((rows, _LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, _LANES), jnp.float32),
        scratch_shapes=[state] * (3 if depth == d else 4),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            # the token tile and the head's tile twice each, the targets, the output and the
            # running numbers, a tile's logits
            vmem_limit_bytes=(4 * (rows + columns) * d + 8 * rows * _LANES * 4 + rows * columns * 4
                              + _VMEM_SPARE)),
        interpret=interpret,
    )
    with kernel_trace("kernel.trace.lm_head"):  # the body's trace, as a program is traced
        out = call(flat, head.T if by_column else head, jnp.broadcast_to(targets.astype(jnp.int32)[:, None], (t, _LANES)))
    return out[:, 0]
