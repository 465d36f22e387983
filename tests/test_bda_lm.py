"""Block diffusion's scoring half (``models.lm.score`` with the ``bda`` mixer:
two streams through every layer under a block mask, a softmax router, a score
that is not shifted) against its plain reference, at sizes a CPU walks in
seconds: packed rows of a noising packer against each document alone
(documents that begin mid-tile, one shorter than a block, a row of one
document); the Pallas kernel, interpreted, and ``blockwise_attention`` against
the dense oracle under the mask for every kind of pair (documents that start
anywhere, so that a block straddles a tile); the pairs the kernel's grid
walks; the softmax router against float64; the packer's four columns through
``state()`` / ``restore()``, the noise's independence of the bin a document
lands in, and the packer without noise as it always was; the lowered step
without an ``[L, L]`` mask."""

import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import sdar_moe as ref
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.models import lm, moe
from tpu_tfrecord.models.attention import (
    _grid_pairs, attention_reference, blockwise_attention, flash_attention_widths, pair_kinds)
from tpu_tfrecord.tpu.ingest import TokenPacker

from test_pattern_lm import init_params, reference_weights

CFG = {
    "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "rope_theta": 1000000, "num_experts": 16, "n_routed_experts_held": 16,
    "held_offset": 0, "num_experts_per_tok": 4, "moe_intermediate_size": 16, "rms_norm_eps": 1e-6,
    "vocab_size": 64,
}
L, BLOCK, MASK_ID = 64, 4, 63
MIX = {"block_length": BLOCK, "mask_id": MASK_ID, "row_tokens": L}
SAMPLE_AT = jnp.asarray([[0, 5, 19, 41], [2, 8, 29, 60], [1, 30, 33, 63]], jnp.int32)


def program_cfg(dtype=jnp.float32, **cut):
    cfg = {**CFG, "program": {"attn_block": 16, "expert_tile": 8, "head_block": 32, **cut}}
    return lm.PatternLMConfig(**{**ref.program(cfg, MIX).__dict__, "dtype": dtype})


def seeded_gains(tree, rng):
    """Norm weights of 1 +- 0.1: a gain of exactly one hides a norm left out."""
    for name, leaf in tree.items():
        if name.endswith("norm"):
            tree[name] = leaf * jnp.asarray(1.0 + 0.1 * rng.uniform(-1, 1, leaf.shape), leaf.dtype)


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.PRNGKey(5), program_cfg())
    rng = np.random.default_rng(5)
    seeded_gains(p, rng)
    for layer in p["layers"]:
        seeded_gains(layer, rng)
    return p


def packed_rows(seed=0):
    """Three rows of a noising packer: documents that begin mid-tile (tiles of
    16), one of 1 token + its end id (shorter than a block), one that fills its
    row (its end id in the last column, which the model never reads)."""
    rng = np.random.default_rng(seed)
    docs = [rng.integers(1, MASK_ID, size=n).astype(np.int32) for n in (L, 21, 9, 1, 13, 6, 30, 11, 2)]
    packer = TokenPacker(3, L, packing="best_fit", noise=(BLOCK, MASK_ID, 11))
    packer.feed_docs(docs)
    packer.flush()
    batch = packer.pop()
    assert batch is not None and packer.pop() is None
    return batch


def documents_of(batch):
    """[(row, start, the tokens the model reads, their noised copy, their levels)]."""
    out = []
    for r, segs in enumerate(batch["segment_ids"]):
        for s in range(1, segs.max() + 1):
            at = np.flatnonzero(segs[:-1] == s)
            out.append((r, at[0], *(batch[c][r, at] for c in ("tokens", "noised", "noise_level"))))
    return out


score = jax.jit(lm.score, static_argnames=("cfg",))


@pytest.fixture(scope="module")
def scored(params):
    batch = packed_rows()
    out = score(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, cfg=program_cfg(),
                probe_head=jnp.int32(3), noised=batch["noised"])
    return batch, jax.tree.map(np.asarray, out)


def test_the_parameters_are_the_models(params):
    cfg = program_cfg()
    assert cfg.layer_pattern == ("bda", "bda") and cfg.router_scoring == "softmax" and cfg.diffusion_block == 4
    assert set(params["layers"][0]) == {"attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "moe_norm",
                                        "router", "w_gate", "w_up", "w_down"}   # no gate, no shared expert
    with pytest.raises(ValueError, match="two streams through EVERY layer"):
        lm.pattern_param_shapes(lm.PatternLMConfig(layer_pattern=("bda", "gqa"), diffusion_block=4))
    with pytest.raises(ValueError, match="two streams through EVERY layer"):
        lm.pattern_param_shapes(lm.PatternLMConfig(layer_pattern=("bda",)))
    with pytest.raises(ValueError, match="router_scoring"):
        lm.pattern_param_shapes(lm.PatternLMConfig(router_scoring="tanh"))


def test_six_published_layers_hold_the_parameters_the_cut_counted():
    """The guide's share test is trivial here (the group that divides a layer is 1);
    in its place: six layers of ``pattern_param_shapes`` at the published widths,
    the embedding and the head are 4,361,027,584 parameters in matrices."""
    import os

    from benchmark import run as bench_run

    with open(os.path.join(bench_run.HERE, "configs", "sdar_30b_a3b_pp8.json")) as f:
        cfg = json.load(f)
    shapes = lm.pattern_param_shapes(ref.program(cfg, bench_run.load_json("traffic", "score_docs_bd.json")))
    leaves = jax.tree.leaves(shapes, is_leaf=lm._is_shape)
    assert sum(int(np.prod(shape)) for shape, _ in leaves if len(shape) >= 2) == 4_361_027_584
    layer = sum(int(np.prod(shape)) for shape, _ in shapes["layers"][0].values() if len(shape) >= 2)
    assert layer == 623_116_288 and len(shapes["layers"]) == 6 and "shared" not in shapes["layers"][0]
    assert sum(int(np.prod(shape)) for shape, _ in leaves if len(shape) < 2) == 6 * (2 * 2048 + 2 * 128) + 2048


def test_the_rows_hold_what_the_cases_need():
    batch = packed_rows()
    starts = sorted((r, int(s)) for r, s, *_ in documents_of(batch))
    assert any(s % 16 for _, s in starts), "no document begins mid-tile"
    assert any(len(t) < BLOCK for *_, t in documents_of(batch)), "no document shorter than a block"
    assert (batch["segment_ids"][0] == 1).all() and batch["tokens"][0, -1] == 0   # one document fills row 0
    assert all(s % BLOCK == 0 for _, s in starts)


def test_a_packed_row_scores_each_document_as_the_reference_scores_it_alone(params, scored):
    batch, out = scored
    docs = documents_of(batch)
    at = [[int(p) - start for p in np.asarray(SAMPLE_AT)[r] if start <= p < start + len(doc)]
          for r, start, doc, _, _ in docs]
    want = ref.reference_score(CFG, [d for _, _, d, _, _ in docs], reference_weights(params), at,
                               probe_head=3, noised=[z for _, _, _, z, _ in docs], block_length=BLOCK)
    covered, seen, masked_seen = np.zeros_like(out["logprob"], bool), 0, 0
    scan, routed = out["probes"]["scan"], out["probes"]["router"]
    for (r, start, doc, copy, _), logp, places, logits, w_scan, w_routed in zip(
            docs, want["logprob"], at, want["logits"], want["scan"], want["router"]):
        n, masked = len(doc), copy == MASK_ID
        got = out["logprob"][r, start:start + n]
        np.testing.assert_allclose(got[masked], logp[masked], atol=2e-4)
        assert (got[~masked] == 0).all()          # a position left as it was scores nothing
        covered[r, start:start + n], masked_seen = True, masked_seen + int(masked.sum())
        inside = [list(np.asarray(SAMPLE_AT)[r]).index(p + start) for p in places]
        np.testing.assert_allclose(out["logits"][r, inside], logits, atol=3e-4)
        seen += len(places)
        for name in ("k_bda", "v_bda", "k_bda_noised", "v_bda_noised"):
            np.testing.assert_allclose(scan[name][r, start:start + n], w_scan[name], atol=2e-5)
        for name in ("q_bda", "att_bda", "q_bda_clean", "att_bda_clean"):
            np.testing.assert_allclose(routed[name][:, r, inside], w_routed[name], atol=2e-5)
        assert (routed["bda_pos"][:, r, inside] == w_routed["bda_pos"]).all()
        assert (routed["bda_block"][:, r, inside] == w_routed["bda_pos"] // BLOCK).all()
        np.testing.assert_allclose(routed["u"][:, r, inside], w_routed["u"], atol=2e-4)
    assert (out["logprob"][~covered] == 0).all() and masked_seen > 40 and seen >= 8
    # BOTH streams' real positions visit the experts, as one batch; no pad of either does
    real = int((batch["segment_ids"][:, :-1] != 0).sum())
    assert out["visits"].shape == (2, 16) and out["dropped"].sum() == 0
    assert (out["visits"].sum(axis=1) == 2 * real * CFG["num_experts_per_tok"]).all()
    assert METRICS.gauge_value("bda.kernel_layers") == 0 and METRICS.gauge_value("bda.block") == 4
    # rows of 64 in tiles of 16: 4 x 5 + 4 pairs walked where two causal triangles hold 2 x 10
    assert METRICS.gauge_value("bda.pairs_walked_share") == 1.2


def test_the_probes_own_walk_in_float64_agrees_and_tells_another_mask(scored):
    batch, out = scored
    scans, routed = [], []
    for r, start, doc, _, _ in documents_of(batch):
        n = len(doc)
        inside = [i for i, p in enumerate(np.asarray(SAMPLE_AT)[r]) if start <= p < start + n]
        scans.append({k: a[r, start:start + n] for k, a in out["probes"]["scan"].items()})
        routed.append({k: a[:, r, inside] for k, a in out["probes"]["router"].items()})
    # no layer's router is asked for: the attention's probe alone
    numbers = ref.probe_numbers({**CFG, "num_hidden_layers": 0}, 0, scans, routed, BLOCK)
    assert numbers["bda_attn_gap"] < 1e-5 and numbers["bda_keys_wrong"] == 0
    # the same outputs read against a mask of blocks of 8 are another mask's: the probe says so
    other = ref.probe_numbers({**CFG, "num_hidden_layers": 0}, 0, scans, routed, 8)
    assert other["bda_attn_gap"] > 0.05


def test_a_document_scores_the_same_wherever_its_row_puts_it(params):
    """The same stream under another packing lands its documents at other places
    of other rows: each one's masked scores do not move (its noise is its number
    in the stream's, its positions and blocks its own)."""
    rng = np.random.default_rng(0)
    docs = [rng.integers(1, MASK_ID, size=n).astype(np.int32) for n in (40, 50, 9, 19, 5)]
    seen = {}
    for packing in ("best_fit", "first_fit"):
        packer = TokenPacker(3, L, packing=packing, noise=(BLOCK, MASK_ID, 3))
        packer.feed_docs(docs)
        packer.flush()
        batch = packer.pop()
        at = jnp.zeros((3, 1), jnp.int32)
        out = np.asarray(score(params, batch["tokens"], batch["segment_ids"], at, cfg=program_cfg(),
                               noised=batch["noised"])["logprob"])
        for r, start, doc, copy, _ in documents_of(batch):
            seen.setdefault(doc.tobytes(), []).append((start, copy, out[r, start:start + len(doc)]))
    moved = 0
    for (s0, z0, a), (s1, z1, b) in seen.values():
        assert (z0 == z1).all()
        np.testing.assert_allclose(a, b, atol=2e-5)
        moved += s0 != s1
    assert moved >= 2


# --------------------------------------------------------------------------- the mask, three ways


def two_streams(lengths, l, n, aligned=True):
    """(segments, block numbers, noised) [1, 2 l] of a row's clean stream then
    its noised one; documents start at whole multiples of ``n`` or, not
    ``aligned``, right after one another (a block then straddles a multiple)."""
    seg, num, at = np.zeros(l, np.int32), np.zeros(l, np.int32), 0
    for s, ln in enumerate(lengths, 1):
        at = -(-at // n) * n if aligned else at
        seg[at:at + ln], num[at:at + ln] = s, np.arange(ln) // n
        at += ln
    assert at <= l
    return (np.tile(seg, 2)[None], np.tile(num, 2)[None],
            np.concatenate([np.zeros(l, bool), np.ones(l, bool)])[None])


def operands(seed, l, h=4, hkv=2, d=128, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((1, 2 * l, heads, d)), dtype) for heads in (h, hkv, hkv))


ROWS = {"many_documents": [37, 5, 3, 120, 1, 64, 130, 90], "one_document": [512],
        "two_tiles_each": [250, 260], "mostly_pads": [100], "short_ones": [1, 2, 3, 4, 5, 6, 7] * 8}


@pytest.mark.parametrize("aligned", (True, False), ids=("aligned", "anywhere"))
@pytest.mark.parametrize("case", ROWS)
def test_blockwise_attention_is_the_dense_oracle_under_the_block_mask(case, aligned):
    """Documents that start anywhere: a block of 4 straddles a key block of 32."""
    l, n = 512, 4
    seg, num, noised = two_streams(ROWS[case], l, n, aligned)
    q, k, v = operands(1, l, d=16)
    want = attention_reference(q, k, v, segments=jnp.asarray(seg), blocks=jnp.asarray(num),
                               noised=jnp.asarray(noised))
    got = jax.jit(lambda q, k, v, seg, num: blockwise_attention(q, k, v, seg, block=32, blocks=(num, n)))(
        q, k, v, jnp.asarray(seg), jnp.asarray(num))
    np.testing.assert_allclose(np.asarray(got)[0, seg[0] != 0], np.asarray(want)[0, seg[0] != 0], atol=2e-6)
    if not aligned and case == "many_documents":   # the straddle is there: a block on both sides of a multiple of 32
        firsts = np.flatnonzero(np.diff(seg[0, :l]) != 0) + 1
        assert any((f % 32) % n for f in firsts)


@pytest.mark.parametrize("case", ROWS)
def test_each_kind_of_pair_of_the_kernel_is_blockwise_attention(case):
    """The kernel a TPU runs, interpreted: clean on clean (a query sees to the
    end of its block), noised on clean (the keys before its block), noised on
    noised (its own block), and under the diagonal plain, masked and skipped pairs."""
    from jax.experimental.pallas import tpu as pltpu

    l, n = 512, 4
    seg, num, _ = two_streams(ROWS[case], l, n)
    q, k, v = operands(2, l, dtype=jnp.bfloat16)
    with pltpu.force_tpu_interpret_mode():
        got = flash_attention_widths(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)), jnp.asarray(seg),
                                     128 ** -0.5, 128, 128, diffusion_block=n)
    want = blockwise_attention(q, k, v, jnp.asarray(seg), block=128, blocks=(jnp.asarray(num), n))
    gap = np.abs(np.asarray(jnp.swapaxes(got, 1, 2), np.float32) - np.asarray(want, np.float32))
    assert gap[0, seg[0] != 0].max() < 0.04          # bfloat16 probabilities and outputs
    kinds = pair_kinds(seg, 128, 128, streams=True)
    assert kinds[3:] == (4, 4) and sum(kinds) == len(_grid_pairs(2 * l, 128, 128, streams=True)) == 24
    expected = {"many_documents": (0, 0, 16), "one_document": (0, 12, 4), "two_tiles_each": (4, 2, 10),
                "mostly_pads": (0, 6, 10), "short_ones": (0, 0, 16)}
    assert kinds[:3] == expected[case]


def test_a_block_is_counted_by_one_rule_on_every_backend(params):
    """``lm._attend`` takes a token's block from its PLACE in its stream, in the
    plain form as in the kernel a TPU runs. On a row whose documents start
    anywhere the two still agree with each other (a row scores the same on the
    CPU and on the chip), neither is the mask of blocks counted from a
    document's own first token, and ``lm.score`` counts the documents at fault."""
    from jax.experimental.pallas import tpu as pltpu

    l, n = 512, 4
    seg, num, noised = two_streams(ROWS["many_documents"], l, n, aligned=False)
    q, k, v = (jnp.swapaxes(a, 1, 2) for a in operands(3, l, dtype=jnp.bfloat16))
    with pltpu.force_tpu_interpret_mode():
        kernel = flash_attention_widths(q, k, v, jnp.asarray(seg), 128 ** -0.5, 128, 128, diffusion_block=n)
    plain = lm._attend(q, k, v, jnp.asarray(seg), 128, diffusion=n)
    real = seg[0] != 0
    gap = lambda a, b: np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))[0, :, real].max()  # noqa: E731
    assert gap(kernel, plain) < 0.04                  # bfloat16 probabilities and outputs
    own = attention_reference(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)), segments=jnp.asarray(seg),
                              blocks=jnp.asarray(num), noised=jnp.asarray(noised))
    assert gap(plain, jnp.swapaxes(own, 1, 2)) > 0.5  # another mask: a document's own blocks lie elsewhere
    # the step says so: of the three documents of a row, two start off a whole block
    tokens = np.zeros((1, L + 1), np.int32)
    segs = np.zeros((1, L + 1), np.int32)
    for s, (a, z) in enumerate(((0, 10), (10, 21), (23, 40)), 1):
        tokens[0, a:z - 1], segs[0, a:z] = 1 + np.arange(z - 1 - a) % 50, s
    at = jnp.zeros((1, 1), jnp.int32)
    out = score(params, tokens, segs, at, cfg=program_cfg(), noised=np.where(tokens % 3 == 1, MASK_ID, tokens))
    assert int(out["starts_off_block"]) == 2
    aligned = np.zeros_like(segs)
    aligned[0, :10], aligned[0, 12:23], aligned[0, 24:41] = 1, 2, 3
    assert int(score(params, tokens, aligned, at, cfg=program_cfg(), noised=tokens)["starts_off_block"]) == 0


def test_the_kernel_refuses_what_it_cannot_walk():
    q, k, v = (jnp.swapaxes(a, 1, 2) for a in operands(0, 256))
    seg = jnp.ones((1, 512), jnp.int32)
    for kw in ({"diffusion_block": 3}, {"diffusion_block": 4, "window": 8}):
        with pytest.raises(ValueError, match="a block mask of"):
            flash_attention_widths(q, k, v, seg, 1.0, 128, 128, **kw)
    with pytest.raises(ValueError, match="without a selection"):
        blockwise_attention(*operands(0, 256), seg, blocks=(seg, 4), window=3)


def test_with_no_block_mask_the_kernel_is_traced_as_it_was():
    """``diffusion_block=None`` adds nothing to the traced body (tests/test_mla_lm.py
    holds the older programs' hashes; here the operation count beside the block mask's)."""
    from jax.experimental.pallas import tpu as pltpu
    from test_pattern_lm import equations

    q, k, v = (jnp.swapaxes(a, 1, 2) for a in operands(0, 256, dtype=jnp.bfloat16))
    seg = jnp.ones((1, 512), jnp.int32)
    with pltpu.force_tpu_interpret_mode():
        plain = jax.make_jaxpr(lambda *a: flash_attention_widths(*a, 1.0, 128, 128))(q, k, v, seg)
        masked = jax.make_jaxpr(lambda *a: flash_attention_widths(*a, 1.0, 128, 128, diffusion_block=4))(
            q, k, v, seg)
    names = lambda j: [e.primitive.name for e in equations(j.jaxpr)]  # noqa: E731
    assert "xor" not in names(plain) and "or" not in names(plain)
    assert "xor" in names(masked) and names(masked).count("cond") == 4 + 2  # four kinds, first and last


# --------------------------------------------------------------------------- the router


def test_the_softmax_router_is_float64s():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((200, 32)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((32, 16)) * 0.5, jnp.float32)
    experts, gates = jax.jit(functools.partial(moe.route_top_k, top_k=4, scoring="softmax"))(x, router)
    logits = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    p = np.exp(logits - logits.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    chosen = np.argsort(-p, axis=1, kind="stable")[:, :4]
    top = np.take_along_axis(p, chosen, axis=1)
    assert (np.asarray(experts) == chosen).all()
    np.testing.assert_allclose(np.asarray(gates), top / top.sum(axis=1, keepdims=True), atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 1.0, atol=1e-6)
    # sigmoid: the function it always was, and another answer
    sig = moe.route_top_k(x, router, 4)[1]
    assert np.abs(np.asarray(sig) - np.asarray(gates)).max() > 0.01
    with pytest.raises(ValueError, match="scoring"):
        moe.route_top_k(x, router, 4, scoring="tanh")


# --------------------------------------------------------------------------- the packer


def stream(seed=0, count=150):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 99, size=rng.integers(1, 70)).astype(np.int32) for _ in range(count)]


def drained(packer, docs, flush=False):
    packer.feed_docs(docs)
    if flush:
        packer.flush()
    out = []
    while (batch := packer.pop()) is not None:
        out.append(batch)
    return out


def test_a_restored_packer_replays_all_four_columns_byte_for_byte():
    docs = stream()
    whole = drained(TokenPacker(2, 64, packing="best_fit", noise=(4, 999, 7)), docs, flush=True)
    first = TokenPacker(2, 64, packing="best_fit", noise=(4, 999, 7))
    before = drained(first, docs[:61])
    state = json.loads(json.dumps(first.state()))          # as a checkpoint would hold it
    assert state["noise"]["placed"] >= 61 and len(state["noise"]["numbers"]) == len(state["bins"])
    second = TokenPacker(2, 64, packing="best_fit", noise=(4, 999, 7))
    second.restore(state)
    after = drained(second, docs[61:], flush=True)
    assert len(whole) == len(before) + len(after) > 20
    for a, b in zip(whole, before + after):
        assert list(a) == ["tokens", "segment_ids", "noised", "noise_level"]
        for name in a:
            assert a[name].dtype == b[name].dtype and a[name].tobytes() == b[name].tobytes(), name
    assert whole[0]["noised"].dtype == np.int32 and whole[0]["noise_level"].dtype == np.float32


def test_the_noise_obeys_its_law_and_the_rows_their_rule():
    batches = drained(TokenPacker(4, 128, packing="best_fit", noise=(4, 999, 1)), stream(1, 600), flush=True)
    masked = levels = 0
    for b in batches:
        toks, segs, z, t = (b[c] for c in ("tokens", "segment_ids", "noised", "noise_level"))
        assert ((z == toks) | (z == 999)).all() and not (toks == 999).any()
        assert (t[segs == 0] == 0).all() and (z[segs == 0] == 0).all()
        assert ((t[segs != 0] > 0) & (t[segs != 0] <= 1)).all()
        for r in range(4):
            for s in range(1, segs[r].max() + 1):
                at = np.flatnonzero(segs[r] == s)
                assert at[0] % 4 == 0                              # a document starts on a whole block
                level = t[r, at]
                assert (level == np.repeat(level[::4], 4)[: len(at)]).all()   # one level a block of the document
        assert ((toks[:, -1] == 0)).all()                          # the last column: a pad or an end id
        masked, levels = masked + int((z == 999).sum()), levels + float(t.sum())
    assert abs(masked - levels) < 4 * np.sqrt(levels) and masked > 5000
    from benchmark.loops import score_docs_bd
    kept = [{"segment_ids": b["segment_ids"], "noised": b["noised"], "noise_level": b["noise_level"]} for b in batches]
    assert score_docs_bd.noise_numbers(kept, 999, 4)["noise_off_law"] < 5
    # a feed whose noise ignores t (a flat half) masks the right COUNT and is off the law all the same
    rng = np.random.default_rng(0)
    flat = [{**k, "noised": np.where((rng.random(k["noised"].shape) < 0.5) & (k["segment_ids"] != 0), 999, 0)}
            for k in kept]
    assert score_docs_bd.noise_numbers(flat, 999, 4)["noise_off_law"] > 20


def test_a_documents_noise_does_not_depend_on_the_bin_it_lands_in():
    docs = stream(2, 80)
    seen = {}
    for rows, packing in ((1, "best_fit"), (3, "best_fit"), (4, "first_fit")):
        for b in drained(TokenPacker(rows, 96, packing=packing, noise=(4, 999, 5)), docs, flush=True):
            for r in range(rows):
                for s in range(1, b["segment_ids"][r].max() + 1):
                    at = np.flatnonzero(b["segment_ids"][r] == s)
                    key = b["tokens"][r, at].tobytes()
                    seen.setdefault(key, []).append((int(at[0]), b["noised"][r, at].tobytes(),
                                                     b["noise_level"][r, at].tobytes()))
    assert len(seen) >= 70
    moved = 0
    for copies in seen.values():
        assert len(copies) == 3 and len({c[1:] for c in copies}) == 1
        moved += len({c[0] for c in copies}) > 1
    assert moved > 30


def test_a_packer_without_noise_is_what_it_was():
    docs = stream(3, 60)
    plain = TokenPacker(2, 64, packing="best_fit")
    batches = drained(plain, docs)
    assert all(list(b) == ["tokens", "segment_ids"] for b in batches)
    assert list(plain.state()) == ["bins", "pending", "emitted_tokens", "emitted_nonpad"]
    # documents lie right after one another, and the last column is a document's like any other
    segs = batches[0]["segment_ids"][0]
    used = int((segs != 0).sum())
    assert (segs[:used] != 0).all() and not segs[used:].any()
    assert any(b["segment_ids"][:, -1].any() for b in batches)
    with pytest.raises(ValueError, match="bin mode"):
        TokenPacker(2, 64, noise=(4, 999, 0))
    with pytest.raises(ValueError, match="mask id"):
        TokenPacker(2, 64, packing="best_fit", noise=(4, 0, 0))


# --------------------------------------------------------------------------- no [L, L] mask


def test_the_lowered_step_holds_no_mask_of_the_rows_square(params):
    """Two integers a token decide the mask: no operand [.., L, L] or [.., 2L, 2L]."""
    batch = packed_rows()
    lowered = score.lower(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, cfg=program_cfg(),
                          probe_head=jnp.int32(3), noised=batch["noised"]).as_text()
    shapes = set(re.findall(r"tensor<([0-9x]+)x(?:i1|i8|i32|f32|bf16)>", lowered))
    square = [s for s in shapes if re.search(rf"(^|x)({L}x{L}|{2 * L}x{2 * L})$", s)]
    assert not square, square
    assert any(s.endswith("16x16") for s in shapes)     # the tiles' own masks are there
