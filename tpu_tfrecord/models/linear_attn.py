"""Gated delta-rule linear attention over packed rows: a short causal
convolution and a chunked recurrence, both reset at every ``segment_ids``
boundary.

This is where the packer meets a model with a recurrent state. A row from
``TokenPacker``'s bin modes holds several documents; ``segment_ids``
numbers them 1..k (0 = pad). A document packed mid-row must come out as
it would alone at the start of a row, so neither the convolution's taps
nor the recurrent state may reach across a boundary.

Per head (d_k = d_v = head_dim), for the tokens of ONE document::

    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T     S_0 = 0
    o_t = S_t^T q_t * scale

with a per-channel decay ``a_t = exp(log_decay_t)`` in (0, 1] and
``b_t`` in (0, 2) (a negative eigenvalue of the transition is allowed).
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
for a whole chunk of 64 overflowed float32 at 2.5.

``carry`` is 1 for the tokens whose document began before the chunk did:
a token of a document that starts inside the chunk never sees the state
that came in. T, A and the masked products do not depend on S and are
made for all chunks at once; only the last three lines run in sequence
(``lax.scan`` over the chunks). The state and everything that touches it
are float32, and their matrix products run at ``HIGHEST`` precision (on a
TPU a float32 product otherwise rounds its inputs to bfloat16, which is
the state kept in bfloat16 by another name).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
_BLOCK = 16  # tokens of the blocks that the triangle's inverse and the decayed pairs bottom out in
_HEADS = 16  # heads of a row whose chunked form is laid out in memory at a time


def short_conv(x, taps, segments):
    """Causal depthwise convolution whose taps stop at a segment boundary.
    x [B, H, L, D], taps [K, H, D] (``taps[j]`` weighs the token j places
    back), segments [B, L] -> x's shape and dtype, accumulated in float32:
    ``y_t = sum_j taps[j] * x_{t-j}`` over the j with t-j in t's segment."""
    f32, l = jnp.float32, x.shape[2]
    taps = taps.astype(f32)[:, None, :, None, :]                       # [K, 1, H, 1, D]
    out = x.astype(f32) * taps[0]
    for j in range(1, taps.shape[0]):
        back = jnp.pad(x, ((0, 0), (0, 0), (j, 0), (0, 0)))[:, :, :l]
        seg_back = jnp.pad(segments, ((0, 0), (j, 0)), constant_values=-1)[:, :l]
        same = (seg_back == segments)[:, None, :, None]
        out = out + jnp.where(same, back.astype(f32), 0.0) * taps[j]
    return out.astype(x.dtype)


def delta_rule_recurrent(q, k, v, log_decay, beta, segments, scale):
    """The recurrence token by token. q, k, v, log_decay [B, H, L, D],
    beta [B, H, L], segments [B, L] -> o [B, H, L, D] float32. The state
    is zeroed wherever ``segments`` changes."""
    b, h, l, d = q.shape
    f32 = jnp.float32
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
    _, o = jax.lax.scan(step, jnp.zeros((b, h, d, d), f32), xs + (starts.T,))
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
    on the diagonal refers to its own middle (module docstring)."""
    c = x.shape[-2]

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
    """The chunked form for some heads of ONE row: q, k, v, g (log-decay)
    [H, n, C, D], beta [H, n, C], seg [n, C] -> o [H, n, C, D]."""
    h, n, chunk, d = q.shape
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

    return jax.lax.fori_loop(0, n, step, (jnp.zeros((h, d, d), q.dtype), jnp.zeros_like(tv)))[1]


def delta_rule_chunked(q, k, v, log_decay, beta, segments, scale, chunk: int = 64):
    """The same recurrence ``chunk`` tokens at a time (module docstring).
    Shapes as :func:`delta_rule_recurrent`, head-major ``[B, H, L, D]`` as a
    projection writes them; L need not be a multiple of ``chunk`` (the tail
    is padded with a segment of its own). A row is cut into
    ``[B, H, n, C, D]`` by a reshape and the loop over the n chunks slices
    that axis where it lies: nothing is transposed. At most ``_HEADS`` heads
    of a row are worked at a time (``lax.map`` over groups of heads, again a
    reshape): what the chunked form lays out, a dozen float32 arrays the
    size of q, is that many heads', whatever the batch."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    b, h, l, d = q.shape
    f32 = jnp.float32
    pad = -l % chunk
    if pad:
        q, k, v, log_decay = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                              for a in (q, k, v, log_decay))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
        segments = jnp.pad(segments, ((0, 0), (0, pad)), constant_values=-1)
    n = (l + pad) // chunk
    heads = max(m for m in range(1, min(h, _HEADS) + 1) if h % m == 0)
    groups = b * (h // heads)

    def cut(x):
        return x.astype(f32).reshape(groups, heads, n, chunk, *x.shape[3:])

    seg = jnp.repeat(segments.reshape(b, n, chunk), h // heads, axis=0)
    o = jax.lax.map(lambda xs: _chunked_heads(*xs, scale),
                    (cut(q), cut(k), cut(v), cut(log_decay), cut(beta), seg))
    return o.reshape(b, h, n * chunk, d)[:, :, :l]
