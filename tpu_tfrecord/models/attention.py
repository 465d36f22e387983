"""Sequence-parallel attention for long contexts: ring and all-to-all.

The long-context compute primitives this framework's ingestion feeds: a
sequence sharded over a mesh axis (the padded [B, L, ...] arrays produced by
tpu_tfrecord.tpu.ingest with L on a 'seq' axis) attends over its FULL length
while no device ever holds more than its L/P chunk of the INPUT.

Two TPU-idiomatic constructions (SURVEY.md: "ring attention or all-to-all
sequence/context parallelism"), same exact math, different collective
pattern — pick by sequence length and head count:

- `ring_attention`: `shard_map` over the sequence axis; K/V blocks rotate
  around the ring with `lax.ppermute` (neighbor hops ride the ICI torus;
  nothing goes through host or DCN). Flash-style online softmax keeps
  per-device memory O(L_chunk^2), so it scales to sequences that do not
  fit any single device. p-1 rotation steps inside one `lax.fori_loop`.
- `ulysses_attention` (DeepSpeed-Ulysses pattern, arXiv:2309.14509):
  two `lax.all_to_all` exchanges re-shard [B, L/p, H, D] -> [B, L, H/p, D],
  each device runs DENSE attention over the full sequence for its H/p head
  group, then the inverse exchange restores sequence sharding. Communication
  is 2 all-to-alls of the activations — O(B*L*H*D/p) per device, constant in
  p hops — vs the ring's p-1 K/V rotations, so it wins at moderate L with
  enough heads; per-device scores are O(B * H/p * L^2), so VERY long
  sequences still want the ring. Requires H % p == 0.

Both accept `lengths` to mask padded key positions — the `<name>_len`
arrays the ingest layer emits plug in directly, so pad tokens never receive
softmax mass — and `causal=True` for decoder/LM masking (the ring masks by
GLOBAL key position across rotated blocks; ulysses applies the standard
triangle locally after the exchange, where each device holds the full
sequence).

The causal ring has two layouts: the default contiguous one computes-
then-masks future blocks (device 0 ends with 1 useful block, device p-1
with p — the last device sets wall-clock), while ``zigzag=True`` re-
stripes internally (device i owns strip 2i AND its mirror 2p-1-2i) so every
device holds the same number of unmasked (q, k) pairs — the standard
balanced causal ring schedule — at the cost of one O(L*H*D) permute each
way; callers keep the contiguous contract on both sides.
`attention_reference` is the plain dense oracle used by the tests;
`blockwise_attention` is the single-device causal path for packed rows too
long for the oracle's `[B, H, L, L]` scores (models.lm's pattern model), and
`flash_attention_widths` its Pallas TPU kernel for keys wider than values.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from tpu_tfrecord.compile_cache import kernel_trace


_NEG = jnp.float32(-1e30)  # mask value; avoids inf-inf NaNs for empty rows


def _expand_kv(q, kv):
    """GQA: repeat K/V head groups to match q's head count (no-op for MHA).
    q [B,L,H,D], kv [B,M,Hkv,D] with H % Hkv == 0 -> [B,M,H,D]."""
    h, hkv = q.shape[2], kv.shape[2]
    if h == hkv:
        return kv
    if h % hkv:
        raise ValueError(
            f"GQA needs num_heads % num_kv_heads == 0 (got H={h}, Hkv={hkv})"
        )
    return jnp.repeat(kv, h // hkv, axis=2)


def attention_reference(
    q, k, v, lengths=None, scale: Optional[float] = None, causal: bool = False,
    segments=None, window: Optional[int] = None, blocks=None, noised=None,
):
    """Dense softmax attention oracle. q [B, L, H, D], k/v [B, L, Hkv, D]
    with Hkv == H (MHA) or H % Hkv == 0 (GQA/MQA: each K/V head serves
    H/Hkv query heads) -> [B, L, H, D]. ``causal`` masks keys after each
    query position (decoder/LM attention). ``segments`` [B, L] int makes
    the mask block-diagonal within the causal triangle: position i attends
    to j only when segments[b, i] == segments[b, j], so documents packed
    into one row (TokenPacker's bin modes) never leak mass across their
    boundaries. ``window`` (with ``causal``) keeps of those the keys j with
    i - j < window: the query's own and the ``window - 1`` before it.
    ``blocks`` [B, L] int (with ``segments``; not with ``causal``) is the mask
    of block diffusion in place of the triangle: each token's block number in
    its own document, and ``noised`` [B, L] bool which tokens belong to the
    noised stream (none where left out). A clean query sees the clean keys of
    its document whose block is at or before its own (its own block whole:
    keys after it too); a noised query the clean keys of blocks before its
    own and the noised keys of its own block; no clean query sees a noised
    key. Nothing here knows of rows' halves or tiles: two arrays a token."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    k, v = _expand_kv(q, k), _expand_kv(q, v)
    # the scale goes onto q, before the contraction (L·D multiplies, not
    # L·M). Left on the scores it sits next to the softmax's `- max` with
    # only the mask select between them, and the CPU backend contracts
    # the two into one fused multiply-add in one program and not in
    # another (a causal mask hoisted out of a loop body as loop-invariant
    # changes what LLVM sees): 1 ulp between the streamed and the batch
    # pipeline on jax 0.9.0, which owe each other bitwise equality
    scores = jnp.einsum("blhd,bmhd->bhlm", q * scale, k).astype(jnp.float32)
    if lengths is not None:
        valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]  # [B, M]
        scores = jnp.where(valid[:, None, None, :], scores, _NEG)
    if segments is not None:
        same = segments[:, :, None] == segments[:, None, :]       # [B, L, M]
        scores = jnp.where(same[:, None, :, :], scores, _NEG)
    if causal:
        l, m = q.shape[1], k.shape[1]
        tri = jnp.arange(m)[None, :] <= jnp.arange(l)[:, None]    # [L, M]
        if window is not None:
            tri = tri & (jnp.arange(l)[:, None] - jnp.arange(m)[None, :] < window)
        scores = jnp.where(tri[None, None, :, :], scores, _NEG)
    if blocks is not None:
        noised = jnp.zeros(blocks.shape, bool) if noised is None else noised
        q_at, k_at = blocks[:, :, None], blocks[:, None, :]
        q_noised, k_noised = noised[:, :, None], noised[:, None, :]
        seen = jnp.where(k_noised, q_noised & (k_at == q_at), jnp.where(q_noised, k_at < q_at, k_at <= q_at))
        scores = jnp.where(seen[:, None, :, :], scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhlm,bmhd->blhd", probs, v.astype(jnp.float32)).astype(q.dtype)


def _fold_keys(qb, k, v, k0: int, k1: int, seen, top, total, acc):
    """The keys ``k0`` to ``k1`` folded into a block of queries' running
    maximum, sum and weighted value (float32, flash-style): what both plain
    forms do a pair of blocks. ``seen()`` -> [B, queries, keys] bool, asked for
    after the scores, and the values cut where they are used, so that the
    traced program keeps its order. Every probability is multiplied by its
    mask: a row that has seen nothing yet carries zeros."""
    scores = jnp.einsum("bqkgd,bmkd->bkgqm", qb, k[:, k0:k1], preferred_element_type=jnp.float32)
    mask = seen()[:, None, None]
    new_top = jnp.maximum(top, jnp.where(mask, scores, _NEG).max(axis=-1))
    probs = jnp.where(mask, jnp.exp(scores - new_top[..., None]), 0.0)
    fade = jnp.exp(top - new_top)
    total = total * fade + probs.sum(axis=-1)
    acc = acc * fade[..., None] + jnp.einsum(
        "bkgqm,bmkd->bkgqd", probs.astype(v.dtype), v[:, k0:k1], preferred_element_type=jnp.float32)
    return new_top, total, acc


def blockwise_attention(q, k, v, segments, scale: Optional[float] = None, block: int = 1024,
                        keep=None, window: Optional[int] = None, blocks=None):
    """Causal softmax attention within ``segments``, by key blocks: the
    same answer as ``attention_reference(causal=True, segments=...)``
    without ever holding a ``[B, H, L, L]`` array. q [B, L, H, D], k/v
    [B, L, Hkv, D] (grouped: each K/V head serves H/Hkv query heads,
    never repeated in memory; v may be of another width Dv than q and k),
    segments [B, L] -> [B, L, H, Dv] in q's dtype.

    For each block of queries the key blocks at or before it (causal: the
    later ones are skipped when the program is built) are folded into a
    running maximum, sum and weighted value in float32, flash-style; a
    pair of ``block`` queries and ``block`` keys costs ``[B, H, block, block]``
    float32 scores. A key block of other documents only leaves the running
    sum untouched: every probability is multiplied by its mask, so a row
    that has seen nothing yet carries zeros, not exp(0). ``keep`` [B, L, L]
    (non-zero = query row may see key column) narrows the mask further: a
    learned selection (``sparse_attn.select_keys``); ``window`` keeps of a
    query's keys its own and the ``window - 1`` before it, and the key
    blocks wholly behind a query block's window are skipped like the later
    ones.

    ``blocks`` = (numbers [B, L] int, length): block diffusion's mask in the
    triangle's place (:func:`attention_reference` has the rule). The row is
    two streams of L / 2 tokens, the clean one then the noised one, the same
    documents at the same places in both; ``numbers`` is each token's block
    in its own document, of ``length`` tokens at most. A block of clean
    queries walks the clean keys up to its last query's block's end, a block
    of noised queries the clean keys before its last query and the noised
    keys within a block's length of its own: cost follows the pairs seen,
    and no ``[L, L]`` mask exists. Documents may start anywhere in a row."""
    if blocks is not None:
        if keep is not None or window is not None:
            raise ValueError("the block mask comes without a selection and without a window")
        return _blockwise_two_streams(q, k, v, segments, scale, block, *blocks)
    b, l, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    if h % hkv:
        raise ValueError(f"GQA needs num_heads % num_kv_heads == 0 (got H={h}, Hkv={hkv})")
    scale = scale if scale is not None else d ** -0.5
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(b, l, hkv, h // hkv, d)
    at = jnp.arange(l)
    out = []
    for q0 in range(0, l, block):
        q1 = min(q0 + block, l)
        qb, sq = qg[:, q0:q1], segments[:, q0:q1]
        shape = (b, hkv, h // hkv, q1 - q0)
        top = jnp.full(shape, _NEG)
        total = jnp.zeros(shape, jnp.float32)
        acc = jnp.zeros(shape + (dv,), jnp.float32)
        behind = 0 if window is None else max(0, q0 - window + 1) // block * block
        for k0 in range(behind, q1, block):
            k1 = min(k0 + block, l)
            def seen(q0=q0, q1=q1, k0=k0, k1=k1):
                mask = (sq[:, :, None] == segments[:, None, k0:k1]) & (
                    at[q0:q1, None] >= at[None, k0:k1])
                if window is not None and q1 - 1 - k0 >= window:  # the block reaches behind some query's window
                    mask = mask & (at[q0:q1, None] - at[None, k0:k1] < window)
                if keep is not None:
                    mask = mask & (keep[:, q0:q1, k0:k1] != 0)
                return mask

            top, total, acc = _fold_keys(qb, k, v, k0, k1, seen, top, total, acc)
        out.append(jnp.moveaxis(acc / total[..., None], 3, 1).reshape(b, q1 - q0, h, dv))
    return jnp.concatenate(out, axis=1).astype(q.dtype)


def _blockwise_two_streams(q, k, v, segments, scale, block: int, numbers, length: int):
    """:func:`blockwise_attention` under block diffusion's mask: a row of the
    clean stream then the noised one."""
    b, l, h, d = q.shape
    hkv, dv, half = k.shape[2], v.shape[-1], q.shape[1] // 2
    if h % hkv or l % 2:
        raise ValueError(f"a row of two streams of {h} heads over {hkv}, {l} tokens")
    scale = scale if scale is not None else d ** -0.5
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(b, l, hkv, h // hkv, d)
    rules = {"at_or_before": jnp.less_equal, "before": jnp.less, "own": jnp.equal}
    out = []
    for q0 in [*range(0, half, block), *range(half, l, block)]:
        q1 = min(q0 + block, half if q0 < half else l)
        qb, sq, nq = qg[:, q0:q1], segments[:, q0:q1], numbers[:, q0:q1]
        if q0 < half:   # clean queries: clean keys to the end of the last query's block
            runs = [(0, min(q1 + length - 1, half), "at_or_before")]
        else:           # noised queries: clean keys before them, noised keys of their own blocks
            runs = [(0, q1 - half, "before"), (max(half, q0 - length + 1), min(l, q1 + length - 1), "own")]
        shape = (b, hkv, h // hkv, q1 - q0)
        top = jnp.full(shape, _NEG)
        total = jnp.zeros(shape, jnp.float32)
        acc = jnp.zeros(shape + (dv,), jnp.float32)
        for first, end, rule in runs:
            for k0 in range(first, end, block):
                k1 = min(k0 + block, end)
                def seen(k0=k0, k1=k1, rule=rule):
                    return (sq[:, :, None] == segments[:, None, k0:k1]) & rules[rule](
                        numbers[:, None, k0:k1], nq[:, :, None])

                top, total, acc = _fold_keys(qb, k, v, k0, k1, seen, top, total, acc)
        out.append(jnp.moveaxis(acc / total[..., None], 3, 1).reshape(b, q1 - q0, h, dv))
    return jnp.concatenate(out, axis=1).astype(q.dtype)


# ---------------------------------------------------------------------------
# Packed rows on one TPU: a flash kernel whose keys are wider than its values
# ---------------------------------------------------------------------------

_LANES, _SUBLANES = 128, 8
_MASKED = -1e30  # _NEG as a Python number: a kernel captures no array
# Queries a pass of the pair body takes, and query heads a grid step takes (the first that
# divides). One layer of 128 heads over 16,384 tokens under a selection on a v5e, alone, read
# while a pass still walked its keys in strips of 128 (PERF.md section 6, PR 35): passes of
# 128 / 256 / 512 queries 86.6 / 86.6 / 89.0 ms; one, two, four heads a step 90.3 / 86.6 / 185
# (a step costs 0.4 microseconds and its mask's block 1 MB); the parent 113.2
_ROWS, _HEADS = 256, (2, 1)
_VMEM_LIMIT = 64 * 2 ** 20  # two heads' blocks, twice, and their scratch: 11 MB beside a pass's scores


def _flash_widths_kernel(lo_ref, hi_ref, qi_ref, ki_ref, qseg_ref, kseg_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, acc_ref, *, scale: float, block_q: int,
                         block_k: int, keep_ref=None, window: Optional[int] = None,
                         q_rope_ref=None, k_rope_ref=None, streams: Optional[tuple] = None):
    """One (query block, key block) pair of the grid step's heads: scores
    stay on the chip, the running maximum and sum are kept 128 lanes wide
    (every lane the same), the weighted values are divided by the sum once,
    at the query block's last key block. The grid walks the pairs at or
    under the diagonal and no others (``qi_ref`` / ``ki_ref``: the blocks of
    the step's pair).

    What a pair costs is decided from scalars, before any vector work
    (``lo_ref`` / ``hi_ref``: the least and the largest segment id of each
    block of ``block_k`` tokens). *Skipped*: a key block that holds no
    document of the query block's. *Under the diagonal*: every key lies
    before every query, so no position is compared: segment ids alone (and
    where both blocks lie inside one document, :func:`pair_kinds`' plain
    pairs, that compare changes nothing: it rides under the products, 6,440
    bundles a pair with it and 6,537 without, so it has no body of its
    own). *On the diagonal*: a pass of ``_ROWS`` queries takes the keys up
    to its last query and no others (a square block computes 10 of its 16
    [256, 256] tiles) and compares positions too. ``keep_ref`` [1, block_q,
    block_k] int8, where given, narrows either kind's mask to the pairs it
    marks non-zero: one block of it serves the step's heads.
    ``q_rope_ref`` / ``k_rope_ref``, where given, are a second part of the
    queries and keys (latent attention's rotary part, the keys' of one head
    for all or of one a key head): a pass's scores are the product over the
    first part plus the product over the second, float32 both, before the
    scale and the mask; without them the body is what it was, operation for
    operation. With a
    ``window`` (a query sees its own key and the ``window - 1`` before it)
    the grid walks the band alone (:func:`_grid_pairs`) and a fourth kind
    joins: *the band's trailing blocks*, which reach behind some row's
    window: positions are compared against it, and a pass takes the keys
    from its first row's window on (10 of 16 tiles where the window is whole
    blocks); the blocks between those and the diagonal stay what they were.

    Inside a kind, a pass takes ``_ROWS`` queries: their product with the
    keys they need, the scale and the mask; then the rows' maximum, the
    subtraction, ``exp``, sum, cast and product with the values; no
    [block_q, block_k] float32 array exists. A pass's second half is
    written after the first half of the next: the TPU compiler schedules a
    kernel's straight line about in the order it is written, and those
    products fill the matrix unit while the vector unit is at the
    soft-max. Scores, maximum, sum and accumulator are float32, the scale
    multiplies float32 scores, the probabilities enter the second product
    in the values' type: every element's answer is what one pass over the
    whole pair gives, up to the order of the float32 additions inside a
    row's sum. The body is kept small in operations (whole passes, not
    strips of them; the step's heads in a loop): on the chip's host every
    program that holds the kernel pays its trace and lowering again."""
    from jax import lax
    from jax.experimental import pallas as pl

    bi, qi, ki = pl.program_id(0), qi_ref[pl.program_id(2)], ki_ref[pl.program_id(2)]
    per = block_q // block_k  # key-sized blocks a query block spans
    rows, heads = min(_ROWS, block_q), q_ref.shape[1]
    needed, _ = _pair_kind(lo_ref, hi_ref, bi, qi, ki, per)

    # the query block's first pair: key block 0, or the first that its first row's window reaches
    @pl.when(ki == (0 if window is None else lax.div(lax.max(qi * block_q - (window - 1), 0), block_k)))
    def _first():
        m_ref[...] = jnp.full(m_ref.shape, _MASKED, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def held(head, ref=k_ref):
        """The key head of ``ref`` a query head of the step attends over."""
        share = heads // ref.shape[1]
        return head if share == 1 else lax.div(head, share)

    def pair(origin, far=None, rule=None):
        """The body of one kind of pair. ``origin``: where the key block starts,
        counted from the query block's first row (None: wholly before it, and
        no position is compared). ``far``: how far before that row it starts,
        where it reaches behind some row's window (None: it does not).
        ``rule`` (under ``streams``, with ``origin`` 0): which of the block
        mask's three compares stands in the diagonal's place."""
        whole = min(rows, block_k)    # a pass's keys start on a whole one of these
        passes = [(at, 0 if far is None else max(0, far + at - window + 1) // whole * whole,
                   block_k if origin is None else min(block_k, at + rows - origin))
                  for at in range(0, block_q, rows)]
        if rule == "own":             # the keys that face the pass's rows, no others
            passes = [(at, at, at + rows) for at in range(0, block_q, rows)]
        # rows before the block's first key, or whose windows end after its last
        passes = [(at, skip, keys) for at, skip, keys in passes if keys > skip]

        def scores(head, at, skip, keys):
            """A pass's first half: a head's ``rows`` queries from ``at`` against
            the block's keys ``skip`` to ``keys``, scaled and masked. (``lax`` by name
            in the two halves: a ``jnp`` function is a jitted one, and tracing
            hundreds of them is seconds of every program's set-up on the chip's host.)"""
            out = lax.dot_general(
                q_ref[0, head, at:at + rows], k_ref[0, held(head), skip:keys],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            if q_rope_ref is not None:    # the two parts' products, summed in float32
                out = lax.add(out, lax.dot_general(
                    q_rope_ref[0, head, at:at + rows], k_rope_ref[0, held(head, k_rope_ref), skip:keys],
                    (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32))
            out = lax.mul(out, scale)
            wide = keys - skip
            seen = [jnp.tile(qseg_ref[0, at:at + rows], (1, wide // _LANES)) == kseg_ref[0, :1, skip:keys]]
            if rule is not None:      # block diffusion: a compare of blocks, not of positions
                across, down = (lax.broadcasted_iota(jnp.int32, (rows, wide), n) for n in (1, 0))
                if rule == "own":     # the key's block is the query's (skip == at, whole blocks both)
                    seen.append(lax.bitwise_xor(across, down) < streams[0])
                else:                 # keys to the end of the query's block, or before its start
                    ends = lax.bitwise_or(down, jnp.int32(streams[0] - 1)) + (
                        at - skip - (streams[0] if rule == "before" else 0))
                    seen.append(across <= ends)
            elif origin is not None:    # the pass's last keys are its own rows
                seen.append(lax.broadcasted_iota(jnp.int32, (rows, wide), 1)
                            - lax.broadcasted_iota(jnp.int32, (rows, wide), 0) <= at - origin - skip)
            if far is not None:       # its first keys lie behind its last rows' windows
                seen.append(lax.broadcasted_iota(jnp.int32, (rows, wide), 1)
                            - lax.broadcasted_iota(jnp.int32, (rows, wide), 0) > far + at - skip - window)
            if keep_ref is not None:
                seen.append(keep_ref[0, at:at + rows, skip:keys].astype(jnp.int32) != 0)
            # a row that has met no key of its document yet weighs what it sees by
            # exp(0); the first real score sends that to exp(-1e30) = 0
            return lax.select(functools.reduce(jnp.logical_and, seen), out,
                              lax.full_like(out, _MASKED))

        def fold(head, at, skip, keys, masked):
            """A pass's second half: the rows' maximum, the probabilities against
            the values, the running sum and the accumulator brought up to date."""
            at = pl.ds(at, rows)
            m_prev = m_ref[head, at]
            m_next = jnp.maximum(m_prev, masked.max(axis=1, keepdims=True))
            probs = lax.exp(lax.sub(masked, jnp.tile(m_next, (1, (keys - skip) // _LANES))))
            v = v_ref[0, held(head), skip:keys]
            weighted = lax.dot_general(lax.convert_element_type(probs, v.dtype), v,
                                       (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            fade = jnp.exp(m_prev - m_next)
            l_ref[head, at] = l_ref[head, at] * fade + probs.sum(axis=1, keepdims=True)
            m_ref[head, at] = m_next
            acc_ref[head, at] = (acc_ref[head, at] * jnp.tile(fade, (1, weighted.shape[-1] // _LANES))
                                 + weighted)

        def one_head(head, carried):
            # a pass's second half is written after the next pass's first: its products fill
            # the matrix unit while the vector unit is at the soft-max
            ahead = scores(head, *passes[0])
            for now, later in zip(passes, passes[1:] + [None]):
                masked, ahead = ahead, later and scores(head, *later)
                fold(head, *now, masked)
            return carried

        # the step's heads one after the other, in a loop of the kernel's: one body to trace,
        # lower and compile whatever their number
        lax.fori_loop(0, heads, one_head, 0)

    if streams is not None:    # the block mask: the diagonal's place is taken by three kinds
        noised = qi >= streams[1]
        place = lax.select(noised, qi - streams[1], qi)        # the query block's place in its own stream
        pl.when(needed & (ki < place))(lambda: pair(None))     # clean keys under either stream's queries
        pl.when((ki == qi) & jnp.logical_not(noised))(functools.partial(pair, 0, None, "at_or_before"))
        pl.when((ki == place) & noised)(functools.partial(pair, 0, None, "before"))
        pl.when((ki == qi) & noised)(functools.partial(pair, 0, None, "own"))
    else:
        under = needed & (ki < qi * per)                           # every key before every query
        if window is not None:
            behind = qi * block_q - ki * block_k                   # ... its first key so far before the first
            under = under & (behind + block_q - 1 < window)        # ... and every key inside every row's window
            for far in range(block_k, window + block_k - 1, block_k):  # the band's trailing blocks
                if far + block_q - 1 >= window:
                    pl.when(needed & (behind == far))(functools.partial(pair, None, far))
        pl.when(under)(lambda: pair(None))
        for j in range(per):    # the key blocks a query block's own rows cross
            far = None if window is None or block_q - 1 - j * block_k < window else -j * block_k
            pl.when(ki == qi * per + j)(functools.partial(pair, j * block_k, far))

    @pl.when(ki == (qi + 1) * per - 1)
    def _last():
        total = jnp.tile(l_ref[...], (1, 1, acc_ref.shape[-1] // _LANES))
        o_ref[0] = (acc_ref[...] / total).astype(o_ref.dtype)


def _flash_widths_kernel_with(*names):
    """:func:`_flash_widths_kernel` with the inputs ``names`` (of ``q_rope_ref``,
    ``k_rope_ref``, ``keep_ref``) after ``v_ref``, in that order."""
    def kernel(*refs, **cut):
        # the four tables and five inputs every call has; these; the output and the scratch
        fixed, more, rest = refs[:9], refs[9:9 + len(names)], refs[9 + len(names):]
        _flash_widths_kernel(*fixed, *rest, **dict(zip(names, more)), **cut)
    return kernel


def _pair_kind(lo, hi, bi, qi, ki, per: int):
    """(needed, one_document) of the pair of query block ``qi`` (``per``
    key-sized blocks) and key block ``ki``, from each key-sized block's least
    and largest segment id ``lo`` / ``hi`` [B, blocks] (refs in the kernel,
    arrays in :func:`pair_kinds`). Needed: the key block holds a key some
    query may see: one at or before the block's last query, of a document
    the query block holds. Two blocks whose ranges of segment ids are
    disjoint share no document, whatever the order of the ids; a query's own
    key block always passes. One document: every token of both blocks
    carries the same id."""
    q_lo, q_hi = lo[bi, qi * per], hi[bi, qi * per]
    for j in range(1, per):
        q_lo = jnp.minimum(q_lo, lo[bi, qi * per + j])
        q_hi = jnp.maximum(q_hi, hi[bi, qi * per + j])
    k_lo, k_hi = lo[bi, ki], hi[bi, ki]
    needed = (ki < (qi + 1) * per) & (k_lo <= q_hi) & (k_hi >= q_lo)
    return needed, (q_lo == q_hi) & (k_lo == k_hi) & (q_lo == k_lo)


def _grid_pairs(l: int, block_q: int, block_k: int, window: Optional[int] = None,
                streams: bool = False):
    """[pairs, 2] int32: the (query block, key block) pairs at or under the
    diagonal, a query block's in order: what the kernel's grid walks. With a
    ``window`` the band alone: no key block wholly behind the window of a
    query block's first row. With ``streams`` (square blocks; the row is a
    clean stream then a noised one) a clean query block's pairs at or under
    its own stream's diagonal, and a noised one's with the clean key blocks
    at or under its own place, then its own noised key block."""
    if streams:
        each = l // 2 // block_q
        return np.array([(qi, ki) for qi in range(each) for ki in range(qi + 1)]
                        + [(each + qi, ki) for qi in range(each) for ki in (*range(qi + 1), each + qi)],
                        np.int32)
    per = block_q // block_k
    reach = l if window is None else window - 1     # how far behind its first row a query block sees
    return np.array([(qi, ki) for qi in range(l // block_q)
                     for ki in range(max(0, qi * block_q - reach) // block_k, (qi + 1) * per)],
                    np.int32)


def pair_kinds(segments, block_q: int = 1024, block_k: int = 1024, window: Optional[int] = None,
               streams: bool = False):
    """(skipped, plain, masked): how many of the block pairs at or under the
    diagonal of ``segments`` [B, L] (with a ``window``: of its band) the
    kernel skips, computes with every key seen (*plain*: wholly under the
    diagonal and inside every row's window, one document; no compare is
    needed there) and computes under a mask that hides something (the
    diagonal's, the window's, or several documents'), by the rule its
    scalars apply. With ``streams`` (``segments`` the row of two streams the
    kernel is handed, square blocks) five counts: (skipped, plain, masked,
    before, own), the third now the pairs under a mask of segment ids or of
    the clean stream's own diagonal (a query sees to the end of its block),
    the last two the block mask's own kinds, one each a noised query block:
    the clean key block at its own place (the keys before a query's block)
    and its own noised key block."""
    segments = np.asarray(segments, np.int32)
    b, l = segments.shape
    block_q, block_k = min(block_q, l), min(block_k, l)
    by_block = segments.reshape(b, l // block_k, block_k)
    per = block_q // block_k
    qi, ki = _grid_pairs(l, block_q, block_k, window, streams).T
    needed, one_document = _pair_kind(by_block.min(axis=-1), by_block.max(axis=-1),
                                      np.arange(b)[:, None], qi, ki, per)
    if streams:
        each = l // 2 // block_q
        place = np.where(qi >= each, qi - each, qi)
        own, before = (qi >= each) & (ki == qi), (qi >= each) & (ki == place)
        under = ki < place
        plain = needed & one_document & under
        masked = (needed & under & ~plain) | ((qi < each) & (ki == qi))
        return tuple(int(np.sum(n & np.ones((b, 1), bool))) for n in (
            under & ~needed, plain, masked, before, own))
    plain = needed & one_document & (ki < qi * per)
    if window is not None:
        plain = plain & ((qi + 1) * block_q - 1 - ki * block_k < window)
    return tuple(int(np.sum(n)) for n in (~needed, plain, needed & ~plain))


def flash_attention_widths(q, k, v, segments, scale: float, block_q: int = 1024,
                           block_k: int = 1024, keep=None, window: Optional[int] = None,
                           q_rope=None, k_rope=None, diffusion_block: Optional[int] = None):
    """Causal attention inside ``segments`` as a Pallas TPU kernel, for
    queries and keys of one width and values of another (JAX's own flash
    kernel takes one width, and only 128s).
    q [B, H, L, D], k [B, Hkv, L, D], v [B, Hkv, L, Dv], segments [B, L]
    -> [B, H, L, Dv] in q's dtype. L is whole blocks, ``block_q`` whole
    ``block_k``s; ``block_k`` and Dv are whole 128s. ``q_rope`` [B, H, L, R]
    and ``k_rope`` [B, 1 or Hkv, L, R] are a second part of the queries and
    keys (latent attention: 128 plain columns in ``q`` and ``k``, 64 rotary
    ones here, the keys' the same for every head): a score is the product
    over ``D`` plus the product over ``R``, summed in float32, so the parts
    are never joined in memory, the one rotary key head is never copied to
    H, and each operand is read as the projection wrote it; ``scale``
    multiplies the sum. The grid walks the
    block pairs at or under the diagonal; what a pair costs follows what it
    holds (:func:`_flash_widths_kernel`: nothing where the blocks share no
    document, as most pairs under the diagonal of packed rows of many
    documents; segment ids alone under the diagonal, no position; the seen
    half on the diagonal). ``keep`` [B, L, L] int8 tells the kernel which keys a
    query may see beside that (a learned selection: a pair it marks 0 is
    masked; one block of it is read a pair of blocks and grid step).
    ``window``: a query sees its own key and the ``window - 1`` before it,
    and the grid walks that band of block pairs alone (150 of a 32,768-token
    document's 528 at 4,096 keys); none: the program it always was.
    ``diffusion_block``: block diffusion's mask in the triangle's place
    (:func:`attention_reference` has the rule). The row is two streams of
    L / 2 tokens, the clean one then the noised one, ``segments`` the same in
    both; every document starts at a whole multiple of ``diffusion_block`` (a
    power of two that divides the tile) in its stream, as
    ``TokenPacker(noise=)`` places them, so a token's block is its place over
    the block length and no mask is handed in: the grid walks the clean
    stream's triangle, the noised queries' clean key blocks and each noised
    query block's own noised key block, (n + 1) n + n pairs where a causal row
    of 2 n blocks has (2 n + 1) n; none: the program it always was.
    Forward only. One trace and one lowering for a program's calls of one
    shape: the call sits in a jitted function."""
    l, dv = q.shape[2], v.shape[-1]
    block_q, block_k = min(block_q, l), min(block_k, l)
    if l % block_q or block_q % block_k or block_k % _LANES or dv % _LANES:
        raise ValueError(f"rows of {l} in blocks of {block_q} x {block_k}, values of {dv}: "
                         f"the kernel wants whole blocks and whole {_LANES}s")
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} keys: a query sees at least its own")
    if (q_rope is None) != (k_rope is None) or (
            k_rope is not None and k_rope.shape[1] not in (1, k.shape[1])):
        raise ValueError("a second part of the queries comes with one of the keys, of one head "
                         f"or of the keys' {k.shape[1]}")
    if diffusion_block is None:
        return _flash_widths_call(q, k, v, segments, keep, q_rope, k_rope, scale=float(scale),
                                  block_q=block_q, block_k=block_k, window=window)
    n = int(diffusion_block)
    block_q = block_k = min(block_q, l // 2)
    if (keep is not None or window is not None or q_rope is not None or n < 1 or n & (n - 1)
            or l % (2 * block_q) or block_q % _LANES or min(_ROWS, block_q) % n):
        raise ValueError(f"a block mask of {n} over two streams of {l // 2} tokens in tiles of {block_q}: a power "
                         "of two that divides the tile, whole tiles a stream, no selection, window or second part")
    return _flash_widths_call(q, k, v, segments, None, scale=float(scale), block_q=block_q,
                              block_k=block_k, streams=(n, l // 2 // block_q))


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k", "window", "streams"))
def _flash_widths_call(q, k, v, segments, keep, q_rope=None, k_rope=None, *, scale: float,
                       block_q: int, block_k: int, window: Optional[int] = None,
                       streams: Optional[tuple] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, l, d = q.shape
    rep, dv = h // k.shape[1], v.shape[-1]
    by_block = segments.astype(jnp.int32).reshape(b, l // block_k, block_k)
    lo, hi = by_block.min(axis=-1), by_block.max(axis=-1)
    per = block_q // block_k
    heads = next(n for n in _HEADS if h % n == 0 and (rep % n == 0 or n % rep == 0))
    pairs = _grid_pairs(l, block_q, block_k, window, streams is not None)     # the grid's third axis

    def key_block(bi, t, lo_ref, hi_ref, qi_ref, ki_ref):
        """A skipped pair asks for the query block's own last key block, which
        a later pair needs: nothing is copied for it."""
        qi, ki = qi_ref[t], ki_ref[t]
        return jnp.where(_pair_kind(lo_ref, hi_ref, bi, qi, ki, per)[0], ki, (qi + 1) * per - 1)

    def queries(bi, hi, t, lo_ref, hi_ref, qi_ref, ki_ref):
        return bi, hi, qi_ref[t], 0

    def keys_of(a):
        """The block of ``a`` [B, heads of its own, L, .] a grid step's query heads
        attend over: one head for all, or one a group of them."""
        share = h // a.shape[1]
        held = max(1, heads // share)     # key heads a grid step's query heads attend over
        return pl.BlockSpec((1, held, block_k, a.shape[-1]), lambda bi, hi, t, *tables: (
            bi, hi * heads // share // held, key_block(bi, t, *tables), 0))

    more = {}     # the inputs a call may have beside q, k and v
    if q_rope is not None:
        more["q_rope_ref"] = (q_rope, pl.BlockSpec((1, heads, block_q, q_rope.shape[-1]), queries))
        more["k_rope_ref"] = (k_rope, keys_of(k_rope))
    if keep is not None:
        more["keep_ref"] = (keep, pl.BlockSpec(
            (1, block_q, block_k), lambda bi, hi, t, *r: (bi, r[2][t], key_block(bi, t, *r))))
    kernel = functools.partial(
        _flash_widths_kernel_with(*more) if more else _flash_widths_kernel,
        scale=scale, block_q=block_q, block_k=block_k, window=window, streams=streams)
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, h // heads, len(pairs)),
            in_specs=[
                pl.BlockSpec((1, block_q, _LANES), lambda bi, hi, t, *r: (bi, r[2][t], 0)),
                pl.BlockSpec((1, _SUBLANES, block_k),
                             lambda bi, hi, t, *r: (bi, 0, key_block(bi, t, *r))),
                pl.BlockSpec((1, heads, block_q, d), queries),
                keys_of(k),
                keys_of(v),
                *[spec for _, spec in more.values()],
            ],
            out_specs=pl.BlockSpec((1, heads, block_q, dv), queries),
            scratch_shapes=[pltpu.VMEM((heads, block_q, _LANES), jnp.float32),
                            pltpu.VMEM((heads, block_q, _LANES), jnp.float32),
                            pltpu.VMEM((heads, block_q, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, l, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
    )
    with kernel_trace("kernel.trace.mla_attn"):  # the body's trace, as a program is traced
        return call(
            lo, hi, pairs[:, 0], pairs[:, 1], jnp.broadcast_to(segments[:, :, None], (b, l, _LANES)),
            jnp.broadcast_to(segments[:, None, :], (b, _SUBLANES, l)), q, k, v,
            *[a for a, _ in more.values()])


def _ring_attention_local(
    q, k, v, lengths, scale: float, axis_name: str, causal: bool = False,
    zigzag: bool = False, segments=None,
):
    """Per-device body (inside shard_map): q,k,v are the local sequence
    chunks [B, Lc, H, D]; K/V rotate one neighbor per step.

    ``zigzag`` (causal only): the balanced causal-ring schedule. One
    ppermute involution swaps second chunk-halves between device j and
    p-1-j, so device j owns strip 2j AND its mirror 2p-1-2j (strip size
    Lc/2). Every (device, step) then needs exactly HALF the score matrix
    — either one k-half against all q rows or all keys against one
    q-half, both strictly unmasked by construction — computed via
    lax.cond'd half-block einsums (the diagonal step keeps the full
    masked block). Work is balanced per step AND per device, at half the
    dense FLOPs; the output swaps back before return, so callers keep the
    contiguous [B, L, ...] contract end to end.

    ``segments`` [B, Lc] (the local chunk of a [B, L] per-position segment
    id array) adds the packed-document block-diagonal mask: a segment
    block rides every K/V rotation (and the zigzag restripe), and EVERY
    fold path applies it — the zigzag half blocks are causally unmasked
    by construction but still cross document boundaries, so the segment
    mask is orthogonal to the causal one there."""
    p = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    if zigzag:
        swap = [(j, p - 1 - j) for j in range(p)]
        half = q.shape[1] // 2

        def restripe(x):
            other = jax.lax.ppermute(x[:, half:], axis_name, swap)
            return jnp.concatenate([x[:, :half], other], axis=1)

        q, k, v = restripe(q), restripe(k), restripe(v)
        if segments is not None:
            segments = restripe(segments)
    b, lc, h, d = q.shape
    positions = jnp.arange(lc)

    def dev_pos(dev):
        """Global positions of device ``dev``'s local rows."""
        if zigzag:
            s = lc // 2
            half_ar = jnp.arange(s)
            return jnp.concatenate(
                [2 * dev * s + half_ar, (2 * p - 1 - 2 * dev) * s + half_ar]
            )
        return dev * lc + positions

    def online_update(scores, v_rows, m, l, o):
        """One online-softmax fold of ``scores`` [B,H,R,K] with values
        ``v_rows`` [B,K,H,D] into accumulators covering the same R rows —
        the ONE implementation every path (full, half-k, half-q) folds
        through."""
        blk_max = scores.max(axis=-1)
        new_m = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - new_m)                             # rescale old sums
        probs = jnp.exp(scores - new_m[..., None])
        l = l * corr + probs.sum(axis=-1)
        upd = jnp.einsum("bhlm,bmhd->blhd", probs, v_rows.astype(jnp.float32))
        o = o * corr.transpose(0, 2, 1)[..., None] + upd
        return new_m, l, o

    def accumulate(step_i, k_blk, v_blk, seg_blk, m, l, o):
        # GQA: the rotating blocks carry only Hkv heads (comm-optimal);
        # repeat to H locally — XLA fuses the broadcast into the einsum
        scores = (
            jnp.einsum("blhd,bmhd->bhlm", q, _expand_kv(q, k_blk)).astype(
                jnp.float32
            )
            * scale
        )  # [B, H, Lc, Lk]
        # the block arriving at ring step s originated on device
        # (idx - s) mod p: its keys cover that device's global positions
        src = jax.lax.rem(idx - step_i + p, p)
        key_pos = dev_pos(src)                                # [Lk]
        if lengths is not None:
            valid = key_pos[None, :] < lengths[:, None]       # [B, Lk]
            scores = jnp.where(valid[:, None, None, :], scores, _NEG)
        if segments is not None:
            same = segments[:, :, None] == seg_blk[:, None, :]  # [B, Lq, Lk]
            scores = jnp.where(same[:, None, :, :], scores, _NEG)
        if causal:
            # mask by GLOBAL positions; a fully-future block masks to _NEG
            # everywhere and contributes ~0 mass (the m0=-1e30 floor keeps
            # the online softmax finite)
            q_pos = dev_pos(idx)                              # [Lq]
            tri = key_pos[None, :] <= q_pos[:, None]          # [Lq, Lk]
            scores = jnp.where(tri[None, None, :, :], scores, _NEG)
        return online_update(scores, _expand_kv(q, v_blk), m, l, o)

    def accumulate_zigzag(step_i, k_blk, v_blk, seg_blk, m, l, o):
        """Balanced causal step for NON-diagonal blocks (step_i >= 1; step
        0 is the device's own block — the causal diagonal — folded once
        through ``accumulate`` before the loop): exactly HALF the score
        matrix is needed and that half is strictly unmasked (CAUSALLY) by
        strip construction, so only it is computed; the segment mask still
        applies to it — packed-document boundaries do not follow strips."""
        s = lc // 2
        src = jax.lax.rem(idx - step_i + p, p)
        key_pos = dev_pos(src)

        def len_mask(scores, kp):
            if lengths is None:
                return scores
            valid = kp[None, :] < lengths[:, None]
            return jnp.where(valid[:, None, None, :], scores, _NEG)

        def seg_mask(scores, sq, sk):
            # sq [B, R] query-side ids, sk [B, K] key-side ids for exactly
            # the rows/keys this half fold touches
            if segments is None:
                return scores
            same = sq[:, :, None] == sk[:, None, :]
            return jnp.where(same[:, None, :, :], scores, _NEG)

        # both half-starts share the same selector: the EARLY half when the
        # block comes from a lower rank, the LATE half otherwise
        start = jnp.where(src < idx, 0, s)

        def half_k(m, l, o):
            # one k-half against ALL q rows (strictly unmasked quadrants)
            kh = jax.lax.dynamic_slice_in_dim(k_blk, start, s, axis=1)
            vh = jax.lax.dynamic_slice_in_dim(v_blk, start, s, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(key_pos, start, s, axis=0)
            scores = (
                jnp.einsum("blhd,bmhd->bhlm", q, _expand_kv(q, kh)).astype(
                    jnp.float32
                )
                * scale
            )
            scores = len_mask(scores, kp)
            if segments is not None:
                skh = jax.lax.dynamic_slice_in_dim(seg_blk, start, s, axis=1)
                scores = seg_mask(scores, segments, skh)
            return online_update(scores, _expand_kv(q, vh), m, l, o)

        def half_q(m, l, o):
            # all keys against ONE q-half: fold into that half's slice of
            # the accumulators only
            qh = jax.lax.dynamic_slice_in_dim(q, start, s, axis=1)
            scores = (
                jnp.einsum("blhd,bmhd->bhlm", qh, _expand_kv(q, k_blk)).astype(
                    jnp.float32
                )
                * scale
            )
            scores = len_mask(scores, key_pos)
            if segments is not None:
                sqh = jax.lax.dynamic_slice_in_dim(segments, start, s, axis=1)
                scores = seg_mask(scores, sqh, seg_blk)
            ms = jax.lax.dynamic_slice_in_dim(m, start, s, axis=2)
            ls = jax.lax.dynamic_slice_in_dim(l, start, s, axis=2)
            os_ = jax.lax.dynamic_slice_in_dim(o, start, s, axis=1)
            ms, ls, os_ = online_update(scores, _expand_kv(q, v_blk), ms, ls, os_)
            return (
                jax.lax.dynamic_update_slice_in_dim(m, ms, start, axis=2),
                jax.lax.dynamic_update_slice_in_dim(l, ls, start, axis=2),
                jax.lax.dynamic_update_slice_in_dim(o, os_, start, axis=1),
            )

        # half-k when (src < idx) agrees with (src + idx <= p - 1); the
        # complementary off-diagonal cases are half-q (derivation in the
        # PARITY zigzag note)
        pred_a = (src < idx) == (src + idx <= p - 1)
        return jax.lax.cond(
            pred_a, lambda t: half_k(*t), lambda t: half_q(*t), (m, l, o)
        )

    # Accumulators are per-device state: derive them from q so they carry
    # exactly q's varying axes (seq, and data when the batch is sharded) —
    # a fresh constant would mismatch the fori_loop carry type.
    zero_bhl = jnp.moveaxis(q[..., 0], 1, 2).astype(jnp.float32) * 0.0  # [B,H,Lc]
    m0 = zero_bhl + _NEG
    l0 = zero_bhl
    o0 = q.astype(jnp.float32) * 0.0
    perm = [(j, (j + 1) % p) for j in range(p)]
    # Step 0 is always the device's OWN block — the causal diagonal — so
    # the full masked fold happens exactly once, hoisted out of the loop;
    # the loop body then carries only the half-block program under zigzag.
    m, l, o = accumulate(0, k, v, segments, m0, l0, o0)
    if p > 1:
        rest = accumulate_zigzag if (zigzag and causal) else accumulate
        # rotate K/V one neighbor around the ring (ICI hop); p-1 hops in
        # total — the final block needs no outgoing hop. Segment ids ride
        # the same hops so every arriving block knows its document ids.
        k_blk = jax.lax.ppermute(k, axis_name, perm)
        v_blk = jax.lax.ppermute(v, axis_name, perm)
        s_blk = (
            jax.lax.ppermute(segments, axis_name, perm)
            if segments is not None else None
        )

        def step(i, carry):
            if segments is None:
                k_blk, v_blk, m, l, o = carry
                s_cur = None
            else:
                k_blk, v_blk, s_cur, m, l, o = carry
            m, l, o = rest(i, k_blk, v_blk, s_cur, m, l, o)
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            if segments is None:
                return k_blk, v_blk, m, l, o
            s_cur = jax.lax.ppermute(s_cur, axis_name, perm)
            return k_blk, v_blk, s_cur, m, l, o

        carry0 = (
            (k_blk, v_blk, m, l, o) if segments is None
            else (k_blk, v_blk, s_blk, m, l, o)
        )
        out_carry = jax.lax.fori_loop(1, p - 1, step, carry0)
        if segments is None:
            k_blk, v_blk, m, l, o = out_carry
            s_blk = None
        else:
            k_blk, v_blk, s_blk, m, l, o = out_carry
        m, l, o = rest(p - 1, k_blk, v_blk, s_blk, m, l, o)
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    if zigzag:
        out = restripe(out)  # the half-swap is an involution: swap back
    return out.astype(q.dtype)


def _shard_map_attention(
    local_fn, q, k, v, mesh, seq_axis, data_axis, lengths, scale,
    causal=False, segments=None, **local_kwargs,
):
    """Shared dispatch for both SP flavors: one shard_map over the sequence
    axis (batch optionally on ``data_axis`` — an unsharded spec on a sharded
    batch would silently gather it to every device), ``lengths`` riding
    along per-batch and ``segments`` [B, L] per-position (sharded like the
    sequence itself) when given."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    spec = P(data_axis, seq_axis, None, None)
    in_specs = [spec, spec, spec]
    args = [q, k, v]
    if lengths is not None:
        in_specs.append(P(data_axis))
        args.append(lengths)
    if segments is not None:
        in_specs.append(P(data_axis, seq_axis))
        args.append(segments)

    def body(*arrs):
        qb, kb, vb = arrs[:3]
        j = 3
        lb = sb = None
        if lengths is not None:
            lb = arrs[j]
            j += 1
        if segments is not None:
            sb = arrs[j]
        return local_fn(
            qb, kb, vb, lengths=lb, scale=scale, axis_name=seq_axis,
            causal=causal, segments=sb, **local_kwargs,
        )

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs), out_specs=spec
    )
    return fn(*args)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    seq_axis: str = "seq",
    data_axis: Optional[str] = None,
    lengths: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    causal: bool = False,
    zigzag: bool = False,
    segments: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact attention over a sequence sharded on ``mesh[seq_axis]``.

    q: [B, L, H, D]; k,v: [B, L, Hkv, D] with Hkv == H (MHA) or any
    positive divisor of H (GQA/MQA — only the Hkv heads rotate the ring,
    the group repeat fuses locally). L divisible by the axis size. Pass
    ``data_axis`` to keep the batch dim sharded. ``lengths`` [B] masks
    padded key positions (the ingest layer's ``<name>_len`` output).
    ``segments`` [B, L] int ids make the mask block-diagonal across
    packed documents (see `attention_reference`); the ids shard on the
    sequence axis and ride the K/V ring rotations.

    ``zigzag`` (causal only): the balanced causal-ring schedule. One
    ppermute involution inside the kernel swaps second chunk-halves
    between device j and p-1-j, giving each device one early strip and
    its mirror; every non-diagonal ring step then computes only the half
    of the score matrix that is unmasked by construction (lax.cond'd
    half-block einsums) — HALF the dense causal FLOPs, balanced per step
    and per device — and the output swaps back, so callers keep the
    contiguous [B, L, ...] contract on both sides. Needs
    L % (2 * axis size) == 0. The swap moves O(L*H*D/p) bytes per device
    each way vs the O(L^2) attention it balances.
    """
    if zigzag:
        if not causal:
            raise ValueError(
                "zigzag re-striping only changes anything for causal "
                "attention; pass causal=True or drop zigzag"
            )
        if q.shape[1] % (2 * mesh.shape[seq_axis]):
            raise ValueError(
                f"zigzag needs sequence length % (2 * mesh['{seq_axis}']) "
                f"== 0 (got L={q.shape[1]}, axis size "
                f"{mesh.shape[seq_axis]})"
            )
    if segments is not None and segments.shape != q.shape[:2]:
        raise ValueError(
            f"segments shape {segments.shape} != batch/sequence dims "
            f"{q.shape[:2]} of q"
        )
    return _shard_map_attention(
        _ring_attention_local, q, k, v, mesh, seq_axis, data_axis, lengths,
        scale, causal, segments=segments, zigzag=zigzag,
    )


def _ulysses_attention_local(
    q, k, v, lengths, scale: float, axis_name: str, causal: bool = False,
    segments=None,
):
    """Per-device body (inside shard_map): q,k,v are the local sequence
    chunks [B, Lc, H, D]. Two all-to-alls re-shard sequence<->heads; the
    attention itself is plain dense math over the full sequence for this
    device's H/p head group."""
    # [B, Lc, H, D] -> [B, L, H/p, D]: every device sends each peer its
    # chunk of that peer's head group — one tiled all_to_all on the ICI
    qh, kh, vh = (
        jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)
        for x in (q, k, v)
    )
    if segments is not None:
        # post-exchange attention spans the full sequence, so every device
        # needs every segment id — an all_gather of [B, Lc] ints, trivial
        # next to the activation all-to-alls
        segments = jax.lax.all_gather(
            segments, axis_name, axis=1, tiled=True
        )
    # post-exchange each device holds the FULL sequence for its head
    # group, so the dense oracle's local causal mask IS the global one
    out = attention_reference(
        qh, kh, vh, lengths=lengths, scale=scale, causal=causal,
        segments=segments,
    )
    # inverse exchange: [B, L, H/p, D] -> [B, Lc, H, D]
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    seq_axis: str = "seq",
    data_axis: Optional[str] = None,
    lengths: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    causal: bool = False,
    segments: Optional[jax.Array] = None,
) -> jax.Array:
    """Exact attention over a sequence sharded on ``mesh[seq_axis]`` via the
    all-to-all (DeepSpeed-Ulysses) pattern — same contract and results as
    :func:`ring_attention` (including ``segments`` packed-document
    masking), different collective/memory profile (see module docstring
    for when to pick which).

    q: [B, L, H, D]; k,v: [B, L, Hkv, D] (GQA: Hkv a positive divisor of
    H). L, H, AND Hkv must all be divisible by the axis size — each device
    owns a head group while attending over the full sequence, so MQA
    (Hkv=1) on a >1 axis is ring-only. ``lengths`` [B] masks padded key
    positions.
    """
    p = mesh.shape[seq_axis]
    h, hkv = q.shape[2], k.shape[2]
    if h % p or hkv % p:
        raise ValueError(
            f"ulysses_attention needs num_heads % mesh['{seq_axis}'] == 0 "
            f"for q AND k/v (got H={h}, Hkv={hkv}, axis size {p}); use "
            f"ring_attention when heads cannot cover the sequence axis"
        )
    # H % Hkv is guarded once, in _expand_kv (shared with the ring flavor)
    return _shard_map_attention(
        _ulysses_attention_local, q, k, v, mesh, seq_axis, data_axis, lengths,
        scale, causal, segments=segments,
    )
