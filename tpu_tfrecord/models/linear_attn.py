"""Recurrent mixers over packed rows: a short causal convolution and three
chunked recurrences, all reset at every ``segment_ids`` boundary.

Three recurrences, two families. The gated DELTA RULE erases before it writes
(``S_t = (I - b k k^T) diag(a) S + b k v^T``, a state [d_k, d_v] that is
square where keys and values are as wide and need not be) and comes with
its decay in two forms: one number a CHANNEL of the key (Solar's ``kda``
layers: ``log_decay`` [B, H, L, D], nothing read grouped), or one number a
head and token (GigaChat's ``gdn`` layers: ``log_decay`` [B, H, L], laid out
as ``beta`` is, and q and k read GROUPED: ``Hk`` key heads under ``H`` value
heads, by block index). Both are :func:`delta_rule_chunked`, one kernel with
two bodies, head-major ``[B, H, L, D]``; the first part of this file. The
STATE-SPACE recurrence (Mamba-2's: the ``ssm`` layers) only decays and writes
(``S_t = a S + dt x B^T``, a [P x N] state that is not square, one decay and
one step a head and token, no triangle to invert) and reads B and C GROUPED:
``G`` groups under ``H`` heads, by block index out of the ONE token-major
array ``[x | B | C]`` the convolution wrote; :func:`ssm_chunked`, a kernel of
its own, the last part of this file (its note has the equations). It is a
kernel of its own and no third body of the delta rule's because nothing of
that body is left when the triangle's inverse is the identity: no levels of
decayed pairs, no inverse, no pseudo-values, a state of another shape in
another layout, operands that lie token-major; what the two share is the
segment bookkeeping (``carry``, ``in_last``, the id before a tile), a dozen
lines.

This is where the packer meets a model with a recurrent state. A row from
``TokenPacker``'s bin modes holds several documents; ``segment_ids``
numbers them 1..k (0 = pad). A document packed mid-row must come out as
it would alone at the start of a row, so neither the convolution's taps
nor the recurrent state may reach across a boundary.

Per head (keys and queries ``d_k`` wide, values and outputs ``d_v`` wide: the
two may differ, S is [d_k, d_v]), for the tokens of ONE document::

    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T     S_0 = 0
    o_t = S_t^T q_t * scale

with a decay ``a_t = exp(log_decay_t)`` in (0, 1] and ``b_t`` in (0, 2) (a
negative eigenvalue of the transition is allowed). The decay comes in two
forms, told apart by ``log_decay``'s shape: one number a CHANNEL of the key
(``[B, H, L, D]``, the equations as written) or one number a head and token
(``[B, H, L]``: ``diag(a_t)`` is ``a_t I``), laid out as ``beta`` is and never
spread over the channels in memory. Key heads may be fewer than value heads:
q and k ``[B, Hk, L, D]`` with ``Hk`` dividing v's ``H``, value head h reading
key head ``h // (H / Hk)`` from where it lies (a block index in the kernel, a
view of a group's heads in the plain form), never copied ``H / Hk`` times.
``delta_rule_recurrent`` walks that recurrence token by token (the oracle
the tests hold the chunked form to); ``delta_rule_chunked`` computes the
same thing ``chunk`` tokens at a time:

    G_t   = sum of log_decay over the chunk's tokens up to t
    A_ij  = sum_c k_ic k_jc e^(G_ic - G_jc)   for j < i in i's segment, else 0
    P_ij  = sum_c q_ic k_jc e^(G_ic - G_jc)   for j <= i in i's segment, else 0
    T     = (I + diag(b) A)^-1 diag(b)        unit lower-triangular inverse
    U     = T (V - carry * (k e^G) S)         the chunk's pseudo-values
    O     = carry * (q e^G) S + P U
    S'    = carry_end * e^G_end * S + (k e^(G_end - G) * in_last_segment)^T U

A pair's weight e^(G_i - G_j) is at most 1, but as a product of two
factors it has to be split somewhere: ``_decayed_pairs`` splits the block
of pairs between the two halves of a chunk at the boundary between them
(both factors at most 1, whatever the decay), halves again, and lets
blocks of 16 tokens refer to their own middle, where a factor reaches
e^(8 * max|log_decay|): the form holds for rates up to 10 a token (a decay
of e^-10 a token leaves nothing to remember), where one reference point
for a whole chunk of 64 overflowed float32 at 2.5. With one decay a head and
token nothing has to be split: a pair's weight is the one number
``e^(G_i - G_j)``, the exponent never positive for j <= i, times one product
over the channels, ``(x k^T) * e^(G_i - G_j)``; of the 1,792 rows of float32
products the kernel streams through the matrix unit for a pair of chunks and
a head, the three reference levels are 512, and this form streams 256 in their
place, once for the value heads that share a key head (1,536 a head alone,
1,408 where two share: the kernel streams fewer rows for the scalar form).

``carry`` is 1 for the tokens whose document began before the chunk did:
a token of a document that starts inside the chunk never sees the state
that came in. T, A and the masked products do not depend on S; only the
last three lines run in sequence. The state and everything that touches it
are float32, and their matrix products run at ``HIGHEST`` precision (on a
TPU a float32 product otherwise rounds its inputs to bfloat16, which is
the state kept in bfloat16 by another name).

Two forms of it. On a TPU, for rows of whole pairs of chunks of 64 and the
widths :func:`fused_tile` names (heads of whole 128s, or keys up to 128 under
values up to 256 that fill more than half their lane blocks, as 96 under 192
do: a tile is padded with zero lanes as it is read into VMEM and memory holds
the published widths alone), one Pallas kernel (``_delta_rule_fused``): a grid step
takes two heads' tile of a row from q, k, v, log_decay, beta and segments
to o with every intermediate in VMEM and the state in a scratch carried
from tile to tile. A layer hands that kernel its PROJECTIONS and their taps
(:func:`delta_rule_layer`) and the kernel prepares q, k and v itself, a
tile a grid step ahead of the recurrence (the four taps, SiLU, the unit
norm and both roundings, :func:`prepared`'s arithmetic on strips of rows):
a projection crosses memory once, where a convolution beside the kernel
read it through four shifted windows and wrote it twice. Everywhere else
(the CPU, other widths and chunks) the
plain JAX form (``_delta_rule_plain``), which lays the same intermediates
out in memory a group of ``_HEADS`` heads at a time and loops over the
chunks; the tests hold the interpreted kernel to it and both to the
token-by-token recurrence.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from tpu_tfrecord.compile_cache import kernel_trace

_HIGHEST = jax.lax.Precision.HIGHEST
_BLOCK = 16  # tokens of the blocks that the triangle's inverse and the decayed pairs bottom out in
_HEADS = 16  # heads of a row whose chunked form the plain form lays out in memory at a time


def short_conv(x, taps, segments, bias=None):
    """Causal depthwise convolution whose taps stop at a segment boundary.
    x [B, H, L, D], taps [K, H, D] (``taps[j]`` weighs the token j places
    back), segments [B, L] -> x's shape and dtype, accumulated in float32:
    ``y_t = sum_j taps[j] * x_{t-j}`` over the j with t-j in t's segment,
    plus ``bias`` [H, D] where the convolution has one."""
    f32, l = jnp.float32, x.shape[2]
    taps = taps.astype(f32)[:, None, :, None, :]                       # [K, 1, H, 1, D]
    out = x.astype(f32) * taps[0]
    for j in range(1, taps.shape[0]):
        back = jnp.pad(x, ((0, 0), (0, 0), (j, 0), (0, 0)))[:, :, :l]
        seg_back = jnp.pad(segments, ((0, 0), (j, 0)), constant_values=-1)[:, :l]
        same = (seg_back == segments)[:, None, :, None]
        out = out + jnp.where(same, back.astype(f32), 0.0) * taps[j]
    if bias is not None:
        out = out + bias.astype(f32)[None, :, None, :]
    return out.astype(x.dtype)


def unit_norm(x, eps: float = 1e-6):
    """x over its norm along the last axis (a head's channels)."""
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _activated(y, unit: bool):
    """What follows the convolution's rounded result ``y``: SiLU in float32,
    for q and k (``unit``) the unit norm, rounded to y's dtype once more. One
    spelling for the plain form's whole arrays and the kernel's strips."""
    s = jax.nn.silu(y.astype(jnp.float32))
    return (unit_norm(s) if unit else s).astype(y.dtype)


def prepared(x, taps, segments, unit: bool):
    """What a delta-rule layer's recurrence reads of one projection: x
    [B, H, L, D] as the projection wrote it under its taps [K, H * D] that stop
    at a document's start (:func:`short_conv`, rounded), SiLU, for q and k
    (``unit``) a unit norm over the head's channels, rounded once more to the
    dtype it came in: float32 arithmetic, two roundings. The plain form, on
    whole arrays; the kernel prepares a tile of the projection itself
    (:func:`_prepared_strip`) with the same arithmetic in the same order."""
    taps = taps.reshape(taps.shape[0], x.shape[1], x.shape[3])
    return _activated(short_conv(x, taps, segments), unit)


def delta_rule_recurrent(q, k, v, log_decay, beta, segments, scale):
    """The recurrence token by token. v [B, H, L, Dv], q, k [B, Hk, L, Dk]
    (``Hk`` divides ``H``; ``Dk`` and ``Dv`` may differ: the state is
    [Dk, Dv]), log_decay [B, H, L, Dk] or, one a head and token, [B, H, L],
    beta [B, H, L], segments [B, L] -> o [B, H, L, Dv] float32. The state is
    zeroed wherever ``segments`` changes. The oracle: it writes out both
    broadcasts (module docstring) that the chunked form does without."""
    b, h, l, d = v.shape
    f32 = jnp.float32
    if q.shape[1] != h:
        q, k = (jnp.repeat(a, h // a.shape[1], axis=1) for a in (q, k))
    if log_decay.ndim == 3:
        log_decay = log_decay[..., None]
    starts = jnp.concatenate(
        [jnp.ones((b, 1), bool), segments[:, 1:] != segments[:, :-1]], axis=1)

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t, new = xs  # [B, H, D] ..., new [B]
        state = jnp.where(new[:, None, None, None], 0.0, state)
        state = state * jnp.exp(g_t)[..., None]                       # diag(a) S
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=_HIGHEST) * scale

    xs = tuple(jnp.moveaxis(a.astype(f32), 2, 0) for a in (q, k, v, log_decay, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, q.shape[-1], d), f32), xs + (starts.T,))
    return jnp.moveaxis(o, 0, 2)


def _matmul(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


def _unit_lower_inverse(n):
    """(I + n)^-1 for strictly lower-triangular n [..., C, C], C a power
    of two. Blocks of ``_BLOCK`` on the diagonal by forward substitution,
    row i of the inverse from the rows before it (``x_i = e_i - n_i x``):
    fifteen small steps, and as exact as float32 allows whatever the keys
    (a product of powers, (I - n)(I + n^2)(I + n^4).., cancels terms of 1e6
    against each other where a document repeats one token and beta is near
    2, and lost 2e-4 of the output there). Then halves joined exactly:
    [[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]."""
    c = n.shape[-1]
    if c <= _BLOCK:
        inv = jnp.broadcast_to(jnp.eye(c, dtype=n.dtype), n.shape)
        for i in range(1, c):
            row = jnp.sum(n[..., i, :i, None] * inv[..., :i, :], axis=-2)
            inv = inv.at[..., i, :].add(-row)
        return inv
    h = c // 2
    a = _unit_lower_inverse(n[..., :h, :h])
    b = _unit_lower_inverse(n[..., h:, h:])
    low = -_matmul(_matmul(b, n[..., h:, :h]), a)
    top = jnp.concatenate([a, jnp.zeros_like(a)], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([low, b], axis=-1)], axis=-2)


def _decayed_pairs(x, k, g):
    """``sum_c x_ic k_jc exp(g_ic - g_jc)`` for j <= i: x, k, g [..., C, D]
    with g non-increasing along C (a running sum of log-decays), C a power
    of two -> [..., C, C]; what lies above the diagonal is not to be read.

    The block between the second half's rows and the first half's columns
    refers both sides to the first half's last token, so that both factors
    are at most 1; each half is treated the same way; a block of ``_BLOCK``
    on the diagonal refers to its own middle (module docstring). With one
    decay a token (g [..., C, 1]) nothing is split: the product over the
    channels times ``exp(g_i - g_j)``, the exponent held at 0 above the
    diagonal, where nothing is read."""
    c = x.shape[-2]
    if g.shape[-1] == 1 and x.shape[-1] != 1:
        weight = jnp.exp(jnp.minimum(g - jnp.swapaxes(g, -1, -2), 0.0))
        return jnp.einsum("...ik,...jk->...ij", x, k, precision=_HIGHEST) * weight

    def product(rows, cols, at):
        ref = g[..., at: at + 1, :]
        return jnp.einsum("...ik,...jk->...ij", x[..., rows, :] * jnp.exp(g[..., rows, :] - ref),
                          k[..., cols, :] * jnp.exp(ref - g[..., cols, :]), precision=_HIGHEST)

    if c <= _BLOCK:
        return product(slice(None), slice(None), (c - 1) // 2)
    h = c // 2
    first = _decayed_pairs(x[..., :h, :], k[..., :h, :], g[..., :h, :])
    second = _decayed_pairs(x[..., h:, :], k[..., h:, :], g[..., h:, :])
    low = product(slice(h, None), slice(None, h), h - 1)
    top = jnp.concatenate([first, jnp.zeros_like(first)], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([low, second], axis=-1)], axis=-2)


def _running_sum(g):
    """Running sum over the chunk axis of g [H, n, C, D]."""
    return jnp.cumsum(g, axis=2)


def _chunked_heads(q, k, v, g, beta, seg, scale):
    """The chunked form for some heads of ONE row: v [H, n, C, Dv], q, k
    [Hk, n, C, Dk] (a key head's value heads lie together), g (log-decay)
    [H, n, C, Dk] or [H, n, C, 1], beta [H, n, C], seg [n, C] -> o [H, n, C, Dv];
    the state between chunks is [H, Dk, Dv]."""
    h, n, chunk, d = v.shape
    if q.shape[0] != h:  # these heads' keys, once a value head: what this form lays out anyway
        q, k = (jnp.repeat(a, h // a.shape[0], axis=0) for a in (q, k))
    before = jnp.concatenate([jnp.full((1,), -2, seg.dtype), seg[:-1, -1]])   # the id before each chunk
    carry = seg == before[:, None]                                     # [n, C]
    same = seg[:, :, None] == seg[:, None, :]                          # [n, C, C]
    in_last = seg == seg[:, -1:]                                       # [n, C]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)

    g = _running_sum(g)                                                # log of the decay so far
    total = g[:, :, -1]                                                # [H, n, D]
    a = jnp.where(same & strict, _decayed_pairs(k, k, g), 0.0)
    p = jnp.where(same & tri, _decayed_pairs(q, k, g), 0.0) * scale
    t = _unit_lower_inverse(beta[..., None] * a) * beta[..., None, :]
    # against the state that comes in the decay counts from the chunk's start
    since_start = jnp.where(carry[..., None], jnp.exp(g), 0.0)
    tv = _matmul(t, v)
    tk = _matmul(t, k * since_start)
    qs = q * since_start * scale
    # what the chunk's last state keeps of each token: decayed from the
    # token to the chunk's end, tokens of the chunk's last document only
    keep = jnp.where(in_last[..., None], k * jnp.exp(total[:, :, None] - g), 0.0)
    decay = jnp.where(carry[:, -1:], jnp.exp(total), 0.0)              # [H, n, D]

    def at(x, i):
        return jax.lax.dynamic_index_in_dim(x, i, axis=1, keepdims=False)

    def step(i, carried):
        state, out = carried
        u = at(tv, i) - _matmul(at(tk, i), state)
        o = _matmul(at(qs, i), state) + _matmul(at(p, i), u)
        state = at(decay, i)[..., None] * state + jnp.einsum(
            "hck,hcv->hkv", at(keep, i), u, precision=_HIGHEST)
        return state, jax.lax.dynamic_update_index_in_dim(out, o, i, axis=1)

    return jax.lax.fori_loop(0, n, step, (jnp.zeros((h, q.shape[-1], d), q.dtype), jnp.zeros_like(tv)))[1]


# ---------------------------------------------------------------------------
# One TPU: the chunked form as a Pallas kernel, every intermediate in VMEM
# ---------------------------------------------------------------------------

_PAIR = 128           # tokens laid out at a time: two chunks side by side, one matrix-unit tile
_KERNEL_CHUNK = 64    # the chunk the kernel computes in
_TILES = (256, 128)   # tokens of a grid step, the larger that divides the row
_STRIP = 128          # rows of a tile the kernel prepares at a time (a trace's seconds go by the number of strips)
_HALO = 8             # rows kept of the tile before, for the taps that reach back: one float32 tile of rows


def _whole_lanes(width: int) -> int:
    """``width`` channels as the kernel lays them out: whole lane blocks of 128."""
    return -(-width // 128) * 128


def fused_tile(shape, chunk: int, key_width: int = 0):
    """Tokens a grid step of the kernel holds for v of ``shape`` [B, H, L, Dv]
    under keys ``key_width`` wide (0: as wide as v) cut in ``chunk``s, or None
    where the plain form runs: off a TPU (the kernel exists for no other
    backend), at another chunk than the kernel's, for rows that are not whole
    pairs of chunks (those fall back, they are not padded), and at widths the
    kernel does not lay out. It takes keys and values of DIFFERENT widths, and
    widths that fill no whole lane block: a head's channels a multiple of 8
    that fill more than half of the lane blocks they are laid out in (16 would
    compute eight times its width: the plain form's), the keys at most one
    lane block (or, as they always could be, as wide as the values in whole
    blocks of 128), the values at most two. What is narrower than its lane blocks is padded with
    zero lanes INSIDE VMEM, as a tile is read (``_lanes``): zero channels add
    nothing to a product over the channels or to a unit norm, a state's rows
    and columns that no channel writes stay zero, and memory holds the
    published widths alone. ``lane_fill`` says what the padding wastes."""
    l, d = shape[2:]
    dk = key_width or d
    if jax.default_backend() != "tpu" or chunk != _KERNEL_CHUNK:
        return None
    square = dk == d and d % 128 == 0           # the widths the kernel always took
    if not square and any(w % 8 or w > most or 2 * w <= _whole_lanes(w) for w, most in ((dk, 128), (d, 256))):
        return None
    return next((t for t in _TILES if l % t == 0), None)


def lane_fill(key_width: int, value_width: int) -> float:
    """Published channels over the lanes the kernel's tiles of q, k and v
    occupy in VMEM: 1.0 where every width is whole lane blocks, (96 + 96 +
    192) / (128 + 128 + 256) = 0.75 for keys of 96 under values of 192."""
    return (2 * key_width + value_width) / (2 * _whole_lanes(key_width) + _whole_lanes(value_width))


def _lanes(x, width: int):
    """x [..., w] at ``width`` lanes: as it is where it has them, its first
    ``width`` where it is wider, zero lanes behind it where it is narrower."""
    w = x.shape[-1]
    if w == width:
        return x
    if w > width:
        return x[..., :width]
    return jnp.concatenate([x, jnp.zeros(x.shape[:-1] + (width - w,), x.dtype)], axis=-1)


def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _roll(x, shift: int, axis: int = 0):
    """x's rows (columns) moved ``shift`` down (right), around the end."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift, axis)


def _held(x, every: int, at: int):
    """x [n, D] with each run of ``every`` rows replaced by its row ``at``."""
    n, d = x.shape
    picked = x.reshape(n // every, every, d)[:, at:at + 1]
    return jnp.broadcast_to(picked, (n // every, every, d)).reshape(n, d)


def _upper(x, half: int):
    """The rows of x [n, .] whose index has the bit ``half`` set, [n / 2, .]."""
    n, w = x.shape
    return x.reshape(n // (2 * half), 2, half, w)[:, 1].reshape(n // 2, w)


def _back(y, half: int):
    """:func:`_upper`'s rows where they came from, zero rows between."""
    m, w = y.shape
    y = y.reshape(m // half, 1, half, w)
    return jnp.concatenate([jnp.zeros_like(y), y], axis=1).reshape(2 * m, w)


def _pair_of_chunks(q, k, v, g, beta_row, seg_col, seg_row, before, scale, qk=None):
    """What two chunks of 64 tokens need that does not touch the state
    (module docstring's G, A, P, T and the factors against the state):
    q, k, v, g [128, D]; beta_row, seg_row [1, 128]; seg_col, before
    [128, D] int32 (a token's segment and the id before its chunk, the same
    in every lane). Returns qs, tk, tv [128, D], p [128, 128] (zero between
    the chunks), keep^T per chunk [D, 64] and each chunk's decay of the
    state [D, 1]. With one decay a token, g [1, 128] as ``beta_row`` lies and
    ``qk`` = [q; k] k^T [256, 128], the product that every value head of a key
    head shares: a pair's weight is one exponent (no levels, no product here).

    A generator: it yields after each matrix product, so that the kernel can
    emit several pairs' work in turn (:func:`_in_turn`).

    A pair's decay is split as ``_decayed_pairs`` splits it (16-token blocks
    on the diagonal around their own middle, the block pairs of a half and
    of the chunk around the boundary between them): three products, of all
    256 rows of [q; k] for the diagonal blocks and of the 128 rows after the
    boundary for the other two. The triangle's inverse doubles from single
    rows: [[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]] for every
    block pair of a level at once, ``X - X (N_off X)`` on the whole
    [128, 128]: as exact on keys that repeat as substitution in 16s joined
    by halves (tests). Blocks of 2 and 4 are joined on the vector unit (the
    inverse's d-th subdiagonal weighs ``N_off``'s columns d to the right,
    then the result's rows d above); from 8 on, the 64 rows of the lower
    blocks go through the matrix unit. The matrix unit takes a float32
    product as six passes of 128 rows a tile whatever the other two sizes,
    so what a product costs is the rows it streams."""
    n, d = q.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    tok = jax.lax.broadcasted_iota(jnp.int32, (n, d), 0)

    if qk is None:
        # the running sum of the log-decay inside each chunk
        for s in (1, 2, 4, 8, 16, 32):
            g = g + jnp.where((tok & (_KERNEL_CHUNK - 1)) >= s, _roll(g, s), 0.0)

        # [q; k] against k with the decay from key to query, level by level: [256, 128]
        both = jax.lax.broadcasted_iota(jnp.int32, (2 * n, n), 0) & (n - 1)         # q's rows, then k's
        across = jax.lax.broadcasted_iota(jnp.int32, (2 * n, n), 1)
        ref = _held(g, _BLOCK, (_BLOCK - 1) // 2)
        raw = _dot(jnp.concatenate([q, k]) * jnp.tile(jnp.exp(g - ref), (2, 1)), k * jnp.exp(ref - g),
                   ((1,), (1,)))
        raw = jnp.where(both >> 4 == across >> 4, raw, 0.0)
        for shift in (5, 6):                   # a half's two blocks; a chunk's two halves
            half = 1 << (shift - 1)
            ref = _held(g, 2 * half, half - 1)
            after = (tok & half) != 0
            e = jnp.exp(jnp.where(after, g - ref, ref - g))                     # both at most 1
            out = _dot(jnp.concatenate([_upper(q * e, half), _upper(k * e, half)]),
                       k * jnp.where(after, 0.0, e), ((1,), (1,)))
            # zero but for rows after and columns before a boundary: of those, one run's own
            raw = raw + jnp.where(both >> shift == across >> shift, _back(out, half), 0.0)
    else:
        # the running sum along the lanes, where the tokens of a row lie; then the same
        # sum down the rows (the diagonal of its own copy in every row), and from there
        # on g is what a per-channel decay's is: [128, D], here the same in every lane
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, n), 1)
        g = jnp.broadcast_to(g, (8, n))
        for s in (1, 2, 4, 8, 16, 32):
            g = g + jnp.where((lane & (_KERNEL_CHUNK - 1)) >= s, _roll(g, s, 1), 0.0)
        along = jnp.broadcast_to(g[:1], (n, n))                                 # G_j in column j
        g = jnp.sum(jnp.where(row == col, along, 0.0), axis=1, keepdims=True)   # G_i in row i
        # e^(G_i - G_j) inside a chunk; the exponent is held at 0 above the diagonal
        weight = jnp.where((row ^ col) < _KERNEL_CHUNK, jnp.exp(jnp.minimum(g - along, 0.0)), 0.0)
        raw = qk * jnp.concatenate([weight, weight])
        g = jnp.broadcast_to(g, (n, d))

    yield
    same = seg_col[:, :n] == seg_row
    eye = row == col
    p = jnp.where(same & (col <= row), raw[:n], 0.0) * scale
    beta = jnp.sum(jnp.where(eye, beta_row, 0.0), axis=1, keepdims=True)        # [128, 1]
    low = jnp.where(same & (col < row), raw[n:], 0.0) * beta                    # diag(b) A

    def between(shift):
        """``low`` where the row is in the upper half and the column in the
        lower half of one block of 2 << shift."""
        return jnp.where(((row >> (shift + 1)) == (col >> (shift + 1)))
                         & (((row >> shift) & 1) == 1) & (((col >> shift) & 1) == 0), low, 0.0)

    inv = jnp.where(eye, 1.0, 0.0) - between(0)
    for shift in (1, 2):
        bands = [jnp.where(row == col + dist, inv, 0.0) for dist in range(1, 1 << shift)]
        right = left = between(shift)
        for dist, band in enumerate(bands, 1):
            right = right + _roll(left, n - dist, 1) * jnp.sum(band, axis=0, keepdims=True)
        out = right
        for dist, band in enumerate(bands, 1):
            out = out + jnp.sum(band, axis=1, keepdims=True) * _roll(right, dist)
        inv = inv - out
    for shift in (3, 4, 5):
        half = 1 << shift
        right = _dot(_upper(between(shift), half), inv)
        yield
        inv = inv - _back(_dot(_upper(inv, half), _back(right, half)), half)
        yield

    carry = seg_col == before
    since_start = jnp.where(carry, jnp.exp(g), 0.0)
    tv = _dot(inv, v * beta)
    yield
    tk = _dot(inv, k * since_start * beta)
    yield
    qs = q * since_start * scale
    total = _held(g, _KERNEL_CHUNK, _KERNEL_CHUNK - 1)
    in_last = seg_col == _held(seg_col, _KERNEL_CHUNK, _KERNEL_CHUNK - 1)
    keep = jnp.where(in_last, k * jnp.exp(total - g), 0.0)
    ends = (_KERNEL_CHUNK - 1, n - 1)
    # the state lies [D_k, D_v]: a chunk's decay scales its rows
    in_d = (jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1))
    decay = [jnp.sum(jnp.where(in_d & carry[at:at + 1], jnp.exp(g[at:at + 1]), 0.0), axis=1,
                     keepdims=True) for at in ends]
    return qs, tk, tv, p, [keep[:_KERNEL_CHUNK].T, keep[_KERNEL_CHUNK:].T], decay


def _chunks_in_order(laid, state, o_ref, head: int):
    """The chunks of one head's tile against the state [D_k, D_v], in order:
    ``laid`` is :func:`_pair_of_chunks`' results for the tile's pairs.
    Writes ``o_ref[0, head]``, returns the state after the tile. A generator
    as :func:`_pair_of_chunks` is: two heads' chains go in turn."""
    half = _KERNEL_CHUNK
    for i, (qs, tk, tv, p, keep_t, decay) in enumerate(laid):
        u = jnp.zeros(tv.shape, jnp.float32)
        for c in range(2):
            own = slice(c * half, (c + 1) * half)
            seen = _dot(jnp.concatenate([qs[own], tk[own]]), state)                   # [128, D_v]
            yield
            u_c = tv[own] - seen[half:]
            state = state * decay[c] + _dot(keep_t[c], u_c)
            yield
            # p is zero between the chunks: the other chunk's rows of u count for nothing
            u = jnp.concatenate([u_c, u[half:]]) if c == 0 else jnp.concatenate([u[:half], u_c])
            o_ref[0, head, i * _PAIR + c * half: i * _PAIR + (c + 1) * half] = _lanes(
                seen[:half] + _dot(p[own], u), o_ref.shape[-1])
            yield
    return state


def _in_turn(generators):
    """Run the generators a step of each in turn; their return values. The
    TPU compiler schedules a kernel's straight line about in the order it
    is written: a pair's products wait on each other, and what fills the
    matrix unit meanwhile has to stand next to them (a layer of 2 x 64 heads
    of 8,192 tokens on a v5e: 33.3 ms one pair after the other, 23.0 four in
    turn, 18.7 with two heads' chains in turn as well)."""
    out, live = [None] * len(generators), list(enumerate(generators))
    while live:
        for i, gen in list(live):
            try:
                next(gen)
            except StopIteration as done:
                out[i] = done.value
                live.remove((i, gen))
    return out


@functools.partial(jax.jit, static_argnames=("unit", "dtype"))
def _prepared_strip(x, before, taps, same, *, unit: bool, dtype):
    """:func:`prepared` for ``_STRIP`` rows of one head: x [R, D] float32 (the
    projection's rows, widened), ``before`` [8, D] the rows that precede them
    (the last of the strip before), taps [K, D] float32, ``same[j - 1]`` [R, D]
    whether the token j places back is of the row's own document. The taps'
    sum in :func:`short_conv`'s order; a shift is a roll of the rows with the
    rows before them on top, never a second read. -> [R, D] ``dtype``. Jitted:
    a kernel's strips are traced once, whatever their number."""
    rows = jnp.concatenate([before, x])
    mixed = x * taps[:1]
    for j in range(1, taps.shape[0]):
        mixed = mixed + jnp.where(same[j - 1], _roll(rows, j)[_HALO:], 0.0) * taps[j:j + 1]
    return _activated(mixed.astype(dtype), unit)


def _prepared_tile(seg_col_ref, sources, halo_ref, seg_halo_ref):
    """The kernel's prologue: every head of a grid step's tile of q, k and v
    prepared as :func:`prepared` prepares whole arrays, ``_STRIP`` rows at a
    time. ``sources``: (x_ref [1, n, tile, D] the projection's block, taps_ref
    [n, K, D], out_ref as x_ref, unit) for q, k and v; ``halo_ref`` [heads, 8, D]
    float32 and ``seg_halo_ref`` [8, D] carry the last rows of the strip before
    and their segment ids from strip to strip and from tile to tile (-1 before
    a row's first tile: no tap counts there). What decides whether a tap counts
    is the same for every head: made once a strip. Returns the function of one
    strip, ``strip(at)`` for the rows from ``at * _STRIP`` (a number or a loop's
    index), and the number of strips; they have to run in order."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    reach = sources[0][1].shape[1] - 1
    heads = [(x_ref, taps_ref[i].astype(f32), out_ref, unit, i)
             for x_ref, taps_ref, out_ref, unit in sources for i in range(x_ref.shape[1])]

    def strip(at):
        rows = pl.ds(at * _STRIP if isinstance(at, int) else pl.multiple_of(at * _STRIP, _STRIP), _STRIP)
        seg = seg_col_ref[0, rows]
        ids = jnp.concatenate([seg_halo_ref[...], seg])
        same = [_roll(ids, j)[_HALO:] == seg for j in range(1, reach + 1)]
        seg_halo_ref[...] = seg[-_HALO:]
        for slot, (x_ref, taps, out_ref, unit, i) in enumerate(heads):
            # a head's rows at the lanes its place in VMEM has (zero lanes behind a width that fills no whole
            # block), and as many lanes of the halo, which is as wide as the widest head
            wide = out_ref.shape[-1]
            own = (slot,) if wide == halo_ref.shape[-1] else (slot, slice(None), slice(wide))
            x = _lanes(x_ref[0, i, rows].astype(f32), wide)
            out_ref[0, i, rows] = _prepared_strip(x, halo_ref[own], taps, [_lanes(s, wide) for s in same], unit=unit,
                                                  dtype=out_ref.dtype)
            halo_ref[own] = x[-_HALO:]

    return strip, seg_col_ref.shape[1] // _STRIP


def _tile_of_heads(seg_col_ref, seg_row_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state_ref, before_ref,
                   scale: float):
    """One tile of the heads of a grid step: every pair of chunks laid out in
    VMEM, then each head's chunks in order against its state, which a
    scratch carries from tile to tile. ``q_ref`` and ``k_ref`` hold the key
    heads these value heads read (as many, or one for all); ``g_ref`` lies as
    ``v_ref`` does (a decay a channel) or as ``beta_ref`` (one a token)."""
    heads, tile = v_ref.shape[1:3]
    d, dv = _whole_lanes(q_ref.shape[-1]), _whole_lanes(v_ref.shape[-1])   # the lanes keys and values are laid out in
    shared = heads // q_ref.shape[1]          # value heads of this step that read one key head
    scalar = len(g_ref.shape) == 5
    f32, half = jnp.float32, _KERNEL_CHUNK
    pairs, last = [], before_ref[:1]
    for at in range(0, tile, _PAIR):
        rows = slice(at, at + _PAIR)
        seg_col = _lanes(seg_col_ref[0, rows], d)
        before = jnp.concatenate([jnp.broadcast_to(last, (half, d)),
                                  jnp.broadcast_to(seg_col[half - 1:half], (half, d))])
        qk = {}
        if scalar:  # [q; k] k^T once a key head: a pair's decay multiplies it afterwards
            for kh in range(q_ref.shape[1]):
                q, k = _lanes(q_ref[0, kh, rows].astype(f32), d), _lanes(k_ref[0, kh, rows].astype(f32), d)
                qk[kh] = _dot(jnp.concatenate([q, k]), k, ((1,), (1,)))
        # q, k and v float32 from here on, whatever they lie as in memory
        pairs += [_pair_of_chunks(
            _lanes(q_ref[0, h // shared, rows].astype(f32), d), _lanes(k_ref[0, h // shared, rows].astype(f32), d),
            _lanes(v_ref[0, h, rows].astype(f32), dv),
            g_ref[0, h, 0, :, rows] if scalar else _lanes(g_ref[0, h, rows], d),
            beta_ref[0, h, 0, :, rows], seg_col, seg_row_ref[0, :1, rows], before, scale,
            qk.get(h // shared))
            for h in range(heads)]
        last = seg_col[_PAIR - 1:_PAIR]
    laid = _in_turn(pairs)
    states = _in_turn([_chunks_in_order(laid[h::heads], state_ref[h], o_ref, h)
                       for h in range(heads)])
    for h in range(heads):
        state_ref[h] = states[h]
    before_ref[...] = jnp.broadcast_to(last, before_ref.shape)


def _delta_rule_kernel(seg_col_ref, seg_row_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref,
                       state_ref, before_ref, *, scale: float):
    """A grid step handed q, k and v as the recurrence reads them: the state
    starts a row at zero, then :func:`_tile_of_heads`."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _first():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)
        before_ref[...] = jnp.full(before_ref.shape, -2, jnp.int32)

    _tile_of_heads(seg_col_ref, seg_row_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state_ref, before_ref,
                   scale)


def _delta_rule_kernel_from_projections(seg_next_ref, seg_col_ref, seg_row_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
                                        tq_ref, tk_ref, tv_ref, o_ref, *rest, scale: float, handed: bool):
    """A grid step handed the PROJECTIONS and their taps: the kernel prepares
    a tile itself (:func:`_prepared_tile`) and works a step behind. Step t of a
    row's n + 1 prepares tile t into one of two places in VMEM while the
    recurrence (:func:`_tile_of_heads`, the body it is without) runs over tile
    t - 1 from the other: the preparation waits for nothing the recurrence
    computes, so the two are one straight line and the compiler is free to
    put the preparation's vector work into bundles the recurrence's products
    leave room in (it hides half of it under one decay a token and a sixth
    under a decay a channel; where the strips stand in the source moves
    nothing). A row's first step prepares alone, in a loop over the strips;
    its last prepares its last tile once more, for nobody. ``q_ref``,
    ``k_ref``, ``v_ref``, the taps and ``seg_next_ref`` are tile t's; the other
    operands and ``o_ref`` tile t - 1's. ``handed``: three more outputs, tile
    t's q, k and v as prepared."""
    from jax.experimental import pallas as pl

    handed_refs, rest = (rest[:3], rest[3:]) if handed else ((), rest)
    state_ref, before_ref, halo_ref, seg_halo_ref, *ready = rest
    step, last_step = pl.program_id(2), pl.num_programs(2) - 1
    into, of = ([r.at[pl.ds(slot, 1)] for r in ready] for slot in (step % 2, 1 - step % 2))
    strip, strips = _prepared_tile(
        seg_next_ref, list(zip((q_ref, k_ref, v_ref), (tq_ref, tk_ref, tv_ref), into, (True, True, False))),
        halo_ref, seg_halo_ref)

    @pl.when(step == 0)
    def _first():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)
        before_ref[...] = jnp.full(before_ref.shape, -2, jnp.int32)
        halo_ref[...] = jnp.zeros(halo_ref.shape, jnp.float32)
        seg_halo_ref[...] = jnp.full(seg_halo_ref.shape, -1, jnp.int32)
        jax.lax.fori_loop(0, strips, lambda at, _: strip(at), None)

    @pl.when(step > 0)
    def _later():
        _tile_of_heads(seg_col_ref, seg_row_ref, *of, g_ref, beta_ref, o_ref, state_ref, before_ref, scale)
        for at in range(strips):  # in the same straight line: the compiler places them among the products
            strip(at)

    if handed:
        @pl.when(step < last_step)
        def _hand_back():
            for out_ref, ref in zip(handed_refs, into):
                out_ref[...] = _lanes(ref[...], out_ref.shape[-1])


def _delta_rule_fused(q, k, v, log_decay, beta, segments, scale, tile: int, interpret=False, taps=None,
                      handed: bool = False):
    """:func:`delta_rule_chunked` in chunks of 64 as one Pallas TPU kernel:
    grid (rows, pairs of heads, tiles of ``tile`` tokens), a head's tiles in
    order. Reads q, k, v, log_decay and beta once, writes o once: q, k and v
    in the dtype they come in (float32 in VMEM), a grid step's key heads by
    their block index where they are fewer than its value heads, a decay of
    one number a token in the layout ``beta`` has.

    With ``taps`` (q's, k's and v's, each [K, heads * D]) q, k and v are the
    projections as they were written and the kernel prepares each tile itself
    (:func:`prepared`'s arithmetic) a grid step ahead of the recurrence
    (:func:`_delta_rule_kernel_from_projections`: one step more a row): a
    projection crosses memory once. What it prepared stays in VMEM unless
    ``handed``: then -> (o, q, k, v), the three written out in the dtype they
    came in, for a caller that wants to read what the recurrence consumed."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, l, d = v.shape
    dk = q.shape[-1]           # keys may be narrower or wider than values: the state is [dk, d]
    dk_laid, d_laid = _whole_lanes(dk), _whole_lanes(d)    # the lanes a tile occupies in VMEM (``_lanes``)
    wide = max(dk_laid, d_laid)
    f32 = jnp.float32
    heads = 2 - h % 2          # two chains in turn keep the matrix unit busier than one
    segments = segments.astype(jnp.int32)
    tiles, lag = l // tile, taps is not None

    def now(ti):    # the tile the recurrence works on: a grid step behind where the kernel prepares
        return jnp.maximum(ti - 1, 0) if lag else ti

    def ahead(ti):  # the tile that is prepared
        return jnp.minimum(ti, tiles - 1) if lag else ti

    group = h // q.shape[1]    # value heads a key head
    key_heads = max(1, heads // group)

    def per_head(at, width=d):
        return pl.BlockSpec((1, heads, tile, width), lambda bi, hi, ti: (bi, hi, at(ti), 0))

    def per_key_head(at):
        return per_head(at, dk) if group == 1 else pl.BlockSpec(
            (1, key_heads, tile, dk), lambda bi, hi, ti: (bi, hi * heads // (group * key_heads), at(ti), 0))

    per_token = pl.BlockSpec((1, heads, 1, 1, tile), lambda bi, hi, ti: (bi, hi, now(ti), 0, 0))
    scalar = log_decay.ndim == 3

    def by_token(a):  # [B, H, L] a tile of tokens along the lanes
        return a.astype(f32).reshape(b, h, l // tile, 1, tile)

    projections = [per_key_head(ahead), per_key_head(ahead), per_head(ahead)]
    in_specs = [
        pl.BlockSpec((1, tile, wide), lambda bi, hi, ti: (bi, now(ti), 0)),
        pl.BlockSpec((1, 8, tile), lambda bi, hi, ti: (bi, 0, now(ti))),
        *projections, per_token if scalar else per_head(now, dk), per_token,
    ]
    operands = [jnp.broadcast_to(segments[:, :, None], (b, l, wide)), jnp.broadcast_to(segments[:, None, :], (b, 8, l)),
                q, k, v, by_token(log_decay) if scalar else log_decay.astype(f32), by_token(beta)]
    out_specs, out_shape = per_head(now), jax.ShapeDtypeStruct((b, h, l, d), f32)
    scratch = [pltpu.VMEM((heads, dk_laid, d_laid), f32), pltpu.VMEM((8, dk_laid), jnp.int32)]
    kernel = functools.partial(_delta_rule_kernel, scale=scale)
    if lag:
        kernel = functools.partial(_delta_rule_kernel_from_projections, scale=scale, handed=handed)
        # the segment ids of the tile that is prepared, beside those of the tile the recurrence works on
        in_specs.insert(0, pl.BlockSpec((1, tile, wide), lambda bi, hi, ti: (bi, ahead(ti), 0)))
        operands.insert(0, operands[0])
        for x, t, spec in zip((q, k, v), taps, projections):
            # a head's taps together, [heads, K, D] (D the lanes its tiles have in VMEM: the taps of the
            # zero lanes are zero): a block of some heads' has whole last two sizes
            laid = _whole_lanes(x.shape[-1])
            operands.append(_lanes(jnp.swapaxes(t.reshape(t.shape[0], x.shape[1], x.shape[-1]), 0, 1), laid))
            in_specs.append(pl.BlockSpec((spec.block_shape[1], t.shape[0], laid),
                                         lambda bi, hi, ti, at=spec.index_map: (at(bi, hi, ti)[1], 0, 0)))
        # the rows before a strip, a head-tile each, and their ids; two places for what is prepared
        scratch += [pltpu.VMEM((2 * key_heads + heads, _HALO, wide), f32), pltpu.VMEM((_HALO, wide), jnp.int32)]
        scratch += [pltpu.VMEM((2, *spec.block_shape[1:3], _whole_lanes(x.shape[-1])), x.dtype)
                    for spec, x in zip(projections, (q, k, v))]
        if handed:
            out_specs = [out_specs, *projections]
            out_shape = [out_shape] + [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)]

    call = pl.pallas_call(
        kernel,
        grid=(b, h // heads, tiles + lag),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    with kernel_trace("kernel.trace.kda_scan"):  # the body's trace, as a program is traced
        return call(*operands)


def _delta_rule_plain(q, k, v, log_decay, beta, segments, scale, chunk: int):
    """:func:`delta_rule_chunked` in plain JAX, for any chunk that is a power
    of two and any L (the tail is padded with a segment of its own). A row is
    cut into ``[B, H, n, C, D]`` by a reshape and the loop over the n chunks
    slices that axis where it lies: nothing is transposed. At most ``_HEADS``
    heads of a row are worked at a time (``lax.map`` over groups of heads,
    again a reshape; whole key heads' worth where key heads are fewer): what
    this form lays out, a dozen float32 arrays the size of v, is that many
    heads', whatever the batch."""
    b, h, l, d = v.shape
    f32 = jnp.float32
    group = h // q.shape[1]    # value heads a key head
    if log_decay.ndim == 3:
        log_decay = log_decay[..., None]
    pad = -l % chunk
    if pad:
        q, k, v, log_decay = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                              for a in (q, k, v, log_decay))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
        segments = jnp.pad(segments, ((0, 0), (0, pad)), constant_values=-1)
    n = (l + pad) // chunk
    heads = max(m for m in range(group, max(group, min(h, _HEADS)) + 1, group) if h % m == 0)
    groups = b * (h // heads)

    def cut(x):  # a group's heads together: ``heads`` of them, or the key heads those read
        return x.astype(f32).reshape(groups, x.shape[1] * heads // h, n, chunk, *x.shape[3:])

    seg = jnp.repeat(segments.reshape(b, n, chunk), h // heads, axis=0)
    o = jax.lax.map(lambda xs: _chunked_heads(*xs, scale),
                    (cut(q), cut(k), cut(v), cut(log_decay), cut(beta), seg))
    return o.reshape(b, h, n * chunk, d)[:, :, :l]


def delta_rule_chunked(q, k, v, log_decay, beta, segments, scale, chunk: int = 64):
    """The same recurrence ``chunk`` tokens at a time (module docstring).
    Shapes as :func:`delta_rule_recurrent` (either form of the decay, key
    heads as many as value heads or a divisor), head-major ``[B, H, L, D]`` as
    a projection writes them. On a TPU, for shapes the kernel takes
    (:func:`fused_tile`), one Pallas kernel; elsewhere the plain JAX form,
    the same algorithm with its intermediates in memory, which the tests
    hold the interpreted kernel to."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    tile = fused_tile(v.shape, chunk, q.shape[-1])
    if tile is not None:
        return _delta_rule_fused(q, k, v, log_decay, beta, segments, scale, tile)
    return _delta_rule_plain(q, k, v, log_decay, beta, segments, scale, chunk)


def delta_rule_layer(q, k, v, taps, log_decay, beta, segments, scale, chunk: int = 64, scope: str = "tfr.kda",
                     handed: bool = False):
    """A delta-rule layer from its projections to its recurrence's output, the
    one entry of both mixers: q, k ``[B, Hk, L, D]`` and v ``[B, H, L, D]`` as
    the projections wrote them, ``taps`` their three convolutions' ``[K, heads *
    D]``, the rest as :func:`delta_rule_chunked` takes it -> (o, None), or with
    ``handed`` (o, (q, k, v)): what the recurrence read, prepared and rounded
    as :func:`prepared` says, in the dtype the projections came in.

    On a TPU, for the shapes the kernel takes (:func:`fused_tile`), the kernel
    prepares its own tiles: the projections cross memory once, nothing runs
    under ``<scope>_conv``, and what is handed back is what the kernel wrote
    beside its output: a caller that walks the recurrence again must read what
    the kernel consumed, and XLA's preparation of the same projections is not
    that (inside a fusion the TPU compiler drops the convolution's rounding to
    bfloat16 before SiLU: 8% of q's and k's elements and a quarter of v's come
    out a bfloat16 place away; with that rounding forced the two agree in all
    but 5 of a million, Mosaic's and XLA's last float32 place). Elsewhere
    :func:`prepared` on whole arrays under ``<scope>_conv``, the three behind a
    barrier (the recurrence and the caller read those very arrays), then
    :func:`delta_rule_chunked` under ``<scope>_scan``."""
    tile = fused_tile(v.shape, chunk, q.shape[-1])
    if tile is not None:
        with jax.named_scope(f"{scope}_scan"):
            out = _delta_rule_fused(q, k, v, log_decay, beta, segments, scale, tile, taps=taps, handed=handed)
        return (out[0], tuple(out[1:])) if handed else (out, None)
    with jax.named_scope(f"{scope}_conv"):
        q, k, v = jax.lax.optimization_barrier(tuple(
            prepared(x, t, segments, unit) for x, t, unit in zip((q, k, v), taps, (True, True, False))))
    with jax.named_scope(f"{scope}_scan"):
        o = delta_rule_chunked(q, k, v, log_decay, beta, segments, scale=scale, chunk=chunk)
    return o, ((q, k, v) if handed else None)


# ---------------------------------------------------------------------------
# The state-space recurrence (Mamba-2's): no erasure, a [P x N] state, B and C by group
# ---------------------------------------------------------------------------
#
# Per head (P channels, a state of N), for the tokens of ONE document::
#
#     S_t = a_t S_{t-1} + dt_t x_t B_t^T        S [P x N] float32, S = 0 where the document begins
#     y_t = S_t C_t
#
# with ONE decay ``a_t = exp(log_decay_t)`` and one step ``dt_t`` a head and
# token, and B_t, C_t [N] shared by the ``H / G`` heads of a group (head h
# reads group ``h // (H / G)``). It is not the delta rule with something set
# to zero: nothing is erased before the write (the rule's ``beta`` scales both),
# so a chunk has no triangle to invert; the state is not square; and the
# operands lie token-major, ``[B, L, channels]``, as ONE array: the
# convolution's output ``[x | B | C]`` (H * P, then G * N twice), which a
# kernel's block index cuts where the mechanism does, so that neither a
# group's B and C nor a slice of x is ever copied. ``chunk`` tokens at a time:
#
#     G_t  = sum of log_decay over the chunk's tokens up to t          (float32)
#     W_ij = (C_i . B_j) e^(G_i - G_j) dt_j    for j <= i in i's segment, else 0
#     Y    = W X + carry * e^G * (C S^T)
#     S'   = carry_end * e^G_end * S + (X * e^(G_end - G) dt * in_last_segment)^T B
#
# ``C_i . B_j`` is one product a GROUP and chunk; the exponent is never
# positive. x, B and C are bfloat16 values, so their products are exact in
# float32; what multiplies them (W, the state, the decayed x) is float32 and
# goes through the matrix unit as three bfloat16 parts (high, middle, low:
# 24 bits), each product accumulated in float32: the precision of a float32
# product at half of ``HIGHEST``'s six passes.

_SSM_CHUNK = 128       # the chunk the kernel computes in: one matrix-unit tile of tokens
_SSM_LANES = 128       # x's channels a lane block: whole heads of P = 128 / k channels
_SSM_HEADS = 8         # heads a grid step, all of one group


def _ssm_cut(xbc, heads: int, groups: int, state: int):
    """(x [.., H * P], B [.., G * N], C [.., G * N], P) of ``xbc``'s columns."""
    gn = groups * state
    hp = xbc.shape[-1] - 2 * gn
    return xbc[..., :hp], xbc[..., hp:hp + gn], xbc[..., hp + gn:], hp // heads


def ssm_recurrent(xbc, dt, log_decay, segments, heads: int, groups: int, state: int):
    """The state-space recurrence token by token: the oracle. xbc [B, L, H * P
    + 2 * G * N] (x, then B, then C), dt and log_decay [B, L, H] float32,
    segments [B, L] -> y [B, L, H * P] float32. The state is zeroed wherever
    ``segments`` changes; B and C are copied to their group's heads here (what
    the chunked forms do without)."""
    b, l, _ = xbc.shape
    f32 = jnp.float32
    x, bm, cm, p = _ssm_cut(xbc, heads, groups, state)
    x = x.astype(f32).reshape(b, l, heads, p)
    of_head = jnp.arange(heads) // (heads // groups)
    bm, cm = (a.astype(f32).reshape(b, l, groups, state)[:, :, of_head] for a in (bm, cm))
    starts = jnp.concatenate(
        [jnp.ones((b, 1), bool), segments[:, 1:] != segments[:, :-1]], axis=1)

    def step(s, xs):
        x_t, b_t, c_t, dt_t, g_t, new = xs  # [B, H, P], [B, H, N] x 2, [B, H] x 2, [B]
        s = jnp.where(new[:, None, None, None], 0.0, s) * jnp.exp(g_t)[..., None, None]
        s = s + jnp.einsum("bhp,bhn->bhpn", x_t * dt_t[..., None], b_t)
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t, precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, bm, cm, dt.astype(f32), log_decay.astype(f32), starts))
    _, y = jax.lax.scan(step, jnp.zeros((b, heads, p, state), f32), xs)
    return jnp.moveaxis(y, 0, 1).reshape(b, l, heads * p)


def _ssm_plain(xbc, dt, log_decay, segments, heads: int, groups: int, state: int, chunk: int):
    """:func:`ssm_chunked` in plain JAX, for any chunk and any L (the tail is
    padded with a segment of its own): a scan over the chunks of all rows and
    heads at once, B and C at their groups (a head's group is an axis of the
    reshape, not a copy)."""
    b, l, _ = xbc.shape
    f32, per = jnp.float32, heads // groups
    pad = -l % chunk
    if pad:
        xbc, dt, log_decay = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (xbc, dt, log_decay))
        segments = jnp.pad(segments, ((0, 0), (0, pad)), constant_values=-1)
    n = (l + pad) // chunk

    def cut(a, *shape):  # [B, L, ..] -> [n, B, C, ..]
        return jnp.moveaxis(a.astype(f32).reshape(b, n, chunk, *shape), 1, 0)

    x, bm, cm, p = _ssm_cut(xbc, heads, groups, state)
    x, bm, cm = cut(x, groups, per, p), cut(bm, groups, state), cut(cm, groups, state)
    g = jnp.cumsum(cut(log_decay, groups, per), axis=2)                 # log of the decay so far
    step = cut(dt, groups, per)
    seg = jnp.moveaxis(segments.reshape(b, n, chunk), 1, 0)             # [n, B, C]
    before = jnp.concatenate([jnp.full((1, b), -2, seg.dtype), seg[:-1, :, -1]])
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))

    def one(s, xs):  # s [B, G, R, P, N]
        x_c, b_c, c_c, g_c, dt_c, seg_c, before_c = xs
        carry = seg_c == before_c[:, None]                              # [B, C]
        seen = (seg_c[:, :, None] == seg_c[:, None, :]) & tri           # [B, C(i), C(j)]
        in_last = seg_c == seg_c[:, -1:]
        cb = jnp.einsum("bign,bjgn->bgij", c_c, b_c, precision=_HIGHEST)
        decay = jnp.exp(jnp.minimum(g_c[:, :, None] - g_c[:, None, :], 0.0))   # [B, i, j, G, R]
        w = jnp.where(seen[..., None, None], decay * dt_c[:, None], 0.0) * jnp.moveaxis(cb, 1, -1)[..., None]
        y = jnp.einsum("bijgr,bjgrp->bigrp", w, x_c, precision=_HIGHEST)
        since = jnp.where(carry[..., None, None], jnp.exp(g_c), 0.0)    # [B, C, G, R]
        y = y + since[..., None] * jnp.einsum("bign,bgrpn->bigrp", c_c, s, precision=_HIGHEST)
        kept = jnp.where(in_last[..., None, None], jnp.exp(g_c[:, -1:] - g_c) * dt_c, 0.0)
        s = (jnp.where(carry[:, -1, None, None], jnp.exp(g_c[:, -1]), 0.0)[..., None, None] * s
             + jnp.einsum("bjgrp,bjgn->bgrpn", x_c * kept[..., None], b_c, precision=_HIGHEST))
        return s, y

    _, y = jax.lax.scan(one, jnp.zeros((b, groups, per, p, state), f32), (x, bm, cm, g, step, seg, before))
    return jnp.moveaxis(y, 0, 1).reshape(b, n * chunk, heads * p)[:, :l]


def ssm_tile(shape, dtype, heads: int, groups: int, state: int, chunk: int):
    """Tokens a grid step of the state-space kernel holds for xbc of ``shape``
    [B, L, H * P + 2 * G * N] and ``dtype``, or None where the plain form runs:
    off a TPU, for operands that are not bfloat16 (the kernel's products count
    on x, B and C being exact in it), at another chunk than the kernel's, for
    rows that are not whole chunks,
    and for geometries the kernel's blocks do not cut: heads whose P channels
    do not fill lane blocks of 128 evenly, a state that is not one lane block,
    groups of other than whole steps of ``_SSM_HEADS`` heads."""
    l, p = shape[1], (shape[2] - 2 * groups * state) // heads
    fits = (_SSM_LANES % p == 0 and state == _SSM_LANES and (heads // groups) % _SSM_HEADS == 0
            and (_SSM_HEADS * p) % _SSM_LANES == 0)
    if jax.default_backend() != "tpu" or jnp.dtype(dtype) != jnp.bfloat16 or chunk != _SSM_CHUNK or not fits:
        return None
    return next((t for t in _TILES if l % t == 0), None)


def _split3(a):
    """float32 a as three bfloat16 parts whose sum is a to 24 bits."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    high = a.astype(bf16)
    rest = a - high.astype(f32)
    middle = rest.astype(bf16)
    return high, middle, (rest - middle.astype(f32)).astype(bf16)


def _dot3(a, b):
    """a @ b in float32 where one of the two is bfloat16 (exact) and the other
    float32: the float32 one in three bfloat16 parts, three passes."""
    def dot(x, y):
        return jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if a.dtype == jnp.bfloat16:
        return sum(dot(a, part) for part in _split3(b))
    return sum(dot(part, b) for part in _split3(a))


def _ssm_kernel(seg_col_ref, seg_row_ref, x_ref, b_ref, c_ref, rows_ref, o_ref, state_ref, before_ref,
                *, p: int):
    """One tile of ``_SSM_HEADS`` heads of one group: each chunk's ``C B^T``
    once, then every head's weights against its x, the state's part and the
    state's update a lane block (128 / P heads side by side) at a time; the
    state ``[N, lanes]`` and the id before the tile in scratch from tile to
    tile. ``rows_ref`` [2 * heads, tile]: the heads' running log-decay inside
    each chunk, then their steps, tokens along the lanes; the same numbers are
    needed down the rows, and come from one transpose a chunk."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _first():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)
        before_ref[...] = jnp.full(before_ref.shape, -2, jnp.int32)

    tile, q, lanes, hs = x_ref.shape[1], _SSM_CHUNK, _SSM_LANES, _SSM_HEADS
    f32, bf16 = jnp.float32, jnp.bfloat16
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (q, lanes), 1)
    last = before_ref[:1]
    for at in range(0, tile, q):
        rows = slice(at, at + q)
        seg_col = seg_col_ref[0, rows]                                  # [q, lanes], a token's id in every lane
        c, b = c_ref[0, rows], b_ref[0, rows]                           # [q, N]
        cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        cb = jnp.where((seg_col == seg_row_ref[0, :1, rows]) & (col <= row), cb, 0.0)
        carry = seg_col == last
        in_last = seg_col == seg_col[q - 1:q]
        along = rows_ref[0, 0, :, rows]                                 # [2 hs, q]
        down = jnp.concatenate([along, jnp.zeros((lanes - 2 * hs, q), f32)]).T   # [q, lanes]: lane h, lane hs + h
        b_t = b.astype(f32).T.astype(bf16)                              # [N, q]
        for block in range(x_ref.shape[2] // lanes):
            x = x_ref[0, rows, block * lanes:(block + 1) * lanes]       # [q, lanes]: 128 / p heads
            y = jnp.zeros((q, lanes), f32)
            g_down = dt_down = jnp.zeros((q, lanes), f32)
            for k in range(lanes // p):
                h = block * (lanes // p) + k
                mine = (lane >= k * p) & (lane < (k + 1) * p)
                g_i = down[:, h:h + 1]
                w = cb * jnp.exp(jnp.minimum(g_i - along[h:h + 1], 0.0)) * along[hs + h:hs + h + 1]
                y = jnp.where(mine, _dot3(w, x), y)
                g_down = jnp.where(mine, g_i, g_down)
                dt_down = jnp.where(mine, down[:, hs + h:hs + h + 1], dt_down)
            s = state_ref[block]                                        # [N, lanes]
            o_ref[0, rows, block * lanes:(block + 1) * lanes] = y + jnp.where(
                carry, jnp.exp(g_down), 0.0) * _dot3(c, s)
            g_end = g_down[q - 1:q]
            kept = jnp.where(in_last, jnp.exp(g_end - g_down) * dt_down, 0.0)
            state_ref[block] = (jnp.where(seg_col[q - 1:q] == last, jnp.exp(g_end), 0.0) * s
                                + _dot3(b_t, x.astype(f32) * kept))
        last = seg_col[q - 1:q]
    before_ref[...] = jnp.broadcast_to(last, before_ref.shape)


@functools.partial(jax.jit, static_argnames=("heads", "groups", "state", "tile", "interpret"))
def _ssm_fused(xbc, dt, log_decay, segments, *, heads: int, groups: int, state: int, tile: int,
               interpret: bool = False):
    """:func:`ssm_chunked` in chunks of 128 as one Pallas TPU kernel: grid
    (rows, steps of 8 heads, tiles of ``tile`` tokens), a step's tiles in
    order. x, B and C are three block specs over the ONE array ``xbc``, in the
    dtype it comes in: a step's 8 heads of x by their block index, their
    group's B and C by theirs. The decay's running sum inside each chunk and
    the step are laid out with the tokens along the lanes, 8 heads' of each a
    block. jitted, so that a program's layers of one shape are traced once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, l, width = xbc.shape
    f32, hs, q = jnp.float32, _SSM_HEADS, _SSM_CHUNK
    p = (width - 2 * groups * state) // heads
    steps, per = heads // hs, heads // groups // hs       # grid steps of heads; of them a group
    first_b, first_c = heads * p // state, (heads * p + groups * state) // state
    segments = segments.astype(jnp.int32)
    g = jnp.cumsum(log_decay.astype(f32).reshape(b, l // q, q, heads), axis=2).reshape(b, l, heads)

    def along(a):  # [B, L, H] -> [B, steps, hs, L]: tokens along the lanes
        return jnp.swapaxes(a.astype(f32), 1, 2).reshape(b, steps, hs, l)

    call = pl.pallas_call(
        functools.partial(_ssm_kernel, p=p),
        grid=(b, steps, l // tile),
        in_specs=[
            pl.BlockSpec((1, tile, _SSM_LANES), lambda bi, hi, ti: (bi, ti, 0)),
            pl.BlockSpec((1, 8, tile), lambda bi, hi, ti: (bi, 0, ti)),
            pl.BlockSpec((1, tile, hs * p), lambda bi, hi, ti: (bi, ti, hi)),
            pl.BlockSpec((1, tile, state), lambda bi, hi, ti: (bi, ti, first_b + hi // per)),
            pl.BlockSpec((1, tile, state), lambda bi, hi, ti: (bi, ti, first_c + hi // per)),
            pl.BlockSpec((1, 1, 2 * hs, tile), lambda bi, hi, ti: (bi, hi, 0, ti)),
        ],
        out_specs=pl.BlockSpec((1, tile, hs * p), lambda bi, hi, ti: (bi, ti, hi)),
        scratch_shapes=[pltpu.VMEM((hs * p // _SSM_LANES, state, _SSM_LANES), f32),
                        pltpu.VMEM((8, _SSM_LANES), jnp.int32)],
        out_shape=jax.ShapeDtypeStruct((b, l, heads * p), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    with kernel_trace("kernel.trace.ssm_scan"):  # the body's trace, as a program is traced
        return call(
            jnp.broadcast_to(segments[:, :, None], (b, l, _SSM_LANES)),
            jnp.broadcast_to(segments[:, None, :], (b, 8, l)),
            xbc, xbc, xbc, jnp.concatenate([along(g), along(dt)], axis=2))


def ssm_chunked(xbc, dt, log_decay, segments, heads: int, groups: int, state: int, chunk: int = 128):
    """The state-space recurrence ``chunk`` tokens at a time (the section's
    note). Shapes as :func:`ssm_recurrent`: ``xbc`` the convolution's output
    as it lies, token-major. On a TPU, for geometries the kernel takes
    (:func:`ssm_tile`), one Pallas kernel; elsewhere the plain JAX form."""
    tile = ssm_tile(xbc.shape, xbc.dtype, heads, groups, state, chunk)
    if tile is not None:
        return _ssm_fused(xbc, dt, log_decay, segments, heads=heads, groups=groups, state=state, tile=tile)
    return _ssm_plain(xbc, dt, log_decay, segments, heads, groups, state, chunk)
