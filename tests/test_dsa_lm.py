"""The sparse latent-attention decoder (``models.lm.score`` with ``mla`` mixers
that compress their queries, turn by YaRN's frequencies and attend the keys a
lightning indexer chose, under a group-limited router) against its plain
reference, at sizes a CPU walks in seconds: ragged rows of several documents
against each document alone; a document shorter than ``index_topk`` against
latent attention without an indexer; the selection inside its own document;
YaRN against a NumPy transcription of the published formula; the group limit
against a loop-written router; 16 shares of 16 experts against the uncut
layer; both kernels, interpreted, against their plain forms; and the cut's
parameter count against ISSUE 33's arithmetic."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import deepseek_v32 as ref
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.models import lm, moe, sparse_attn

from test_mla_lm import interpreted_kernel, kernel_inputs, mixer, plain_path, scopes_held
from test_pattern_lm import (SAMPLE_AT, documents_of, flat, held_experts, init_params,
                             packed_rows as older_rows, reference_weights, score)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a configuration with the published names, tiny: one dense layer, two expert layers;
#: documents of 9 to 30 tokens (test_pattern_lm.packed_rows) against 6 keys a query
CFG = {
    "hidden_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "kv_lora_rank": 16, "q_lora_rank": 24, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "original_max_position_embeddings": 16,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "index_n_heads": 4, "index_head_dim": 8, "index_topk": 6, "intermediate_size": 48,
    "n_routed_experts": 16, "n_routed_experts_held": 16, "held_offset": 0, "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 3, "moe_intermediate_size": 16, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-6, "vocab_size": 64,
}
L = 48


def program_cfg(cfg=CFG, dtype=jnp.float32, **cut):
    cut = {"attn_block": 16, "expert_tile": 8, "head_block": 32, "max_len": L, **cut}
    n, yarn = cfg["num_hidden_layers"], cfg.get("rope_scaling")
    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"], layer_pattern=("mla",) * n,
        ffn_pattern=tuple(ref.ffn_kinds(cfg)), n_heads=cfg["num_attention_heads"],
        qk_nope_dim=cfg["qk_nope_head_dim"], qk_rope_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"], q_rank=cfg["q_lora_rank"],
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=(yarn["factor"], yarn["original_max_position_embeddings"], yarn["beta_fast"],
                      yarn["beta_slow"]) if yarn else (),
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"], d_dense=cfg["intermediate_size"],
        n_experts=cfg["n_routed_experts"], experts_held=cfg["n_routed_experts_held"],
        held_offset=cfg["held_offset"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], n_shared=cfg["n_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"], router_bias=True, n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], norm_eps=cfg["rms_norm_eps"], dtype=dtype, **cut)


def packed_rows():
    return older_rows()[0]


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.PRNGKey(5), program_cfg())
    for layer in p["layers"][1:]:  # a bias large enough to change who is chosen
        layer["router_bias"] = layer["router_bias"] * 4.0
    return p


@pytest.fixture(scope="module")
def scored(params):
    batch = packed_rows()
    out = score(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, program_cfg(), jnp.int32(1))
    return batch, SAMPLE_AT, jax.tree.map(np.asarray, out)


def test_the_parameters_are_the_models(params):
    first = params["layers"][0]
    assert "wq" not in first and first["wq_a"].shape == (32, 24) and first["q_norm"].shape == (24,)
    assert first["wq_b"].shape == (24, 4 * 12) and first["wq_idx"].shape == (24, 4 * 8)
    assert first["wk_idx"].shape == (32, 8) and first["w_idx"].shape == (32, 4)
    assert first["k_idx_norm"].dtype == first["k_idx_bias"].dtype == jnp.float32
    with pytest.raises(ValueError, match="index_topk needs q_rank"):
        lm.pattern_param_shapes(lm.PatternLMConfig(layer_pattern=("mla",), index_topk=4))


def test_ragged_rows_score_each_document_as_the_reference_scores_it_alone(params, scored):
    batch, sample_at, out = scored
    docs = documents_of(batch)
    assert max(len(d) for _, _, d in docs) > 4 * CFG["index_topk"]      # the selection bites
    at = [[int(p) - start for p in np.asarray(sample_at)[r]
           if start <= p < start + len(doc) - 1] for r, start, doc in docs]
    want = ref.reference_score(CFG, [d for _, _, d in docs], reference_weights(params), at)
    covered, seen = np.zeros_like(out["logprob"], bool), 0
    for (r, start, doc), logp, places, logits in zip(docs, want["logprob"], at, want["logits"]):
        np.testing.assert_allclose(out["logprob"][r, start:start + len(doc) - 1], logp, atol=3e-4)
        covered[r, start:start + len(doc) - 1] = True
        for p, w in zip(places, logits):
            s = list(np.asarray(sample_at)[r]).index(p + start)
            np.testing.assert_allclose(out["logits"][r, s], w, atol=4e-4)
            seen += 1
    assert (out["logprob"][~covered] == 0).all() and covered.sum() > 80 and seen >= 6
    assert out["visits"].shape == (2, 16) and out["dropped"].sum() == 0


def test_the_probes_carry_the_first_expert_layers_selection(params, scored):
    batch, sample_at, out = scored
    router, scan = out["probes"]["router"], out["probes"]["scan"]
    assert router["u"].shape == (2, 2, 4, 32) and scan["k_index"].shape == (2, L, 8)
    assert router["q_index"].shape == (1, 2, 4, 4, 8) and router["w_index"].shape == (1, 2, 4, 4)
    assert router["kept"].shape == (1, 2, 4, L) and router["kept"].dtype == np.int8
    segs = np.asarray(batch["segment_ids"])[:, :-1]
    for r in range(2):
        for s, p in enumerate(np.asarray(sample_at)[r]):
            pos, start = router["index_pos"][0, r, s], router["index_start"][0, r, s]
            if segs[r, p] == 0:
                continue
            assert start + pos == p and segs[r, start] == segs[r, p]
            assert start == 0 or segs[r, start - 1] != segs[r, p]
            kept = np.flatnonzero(router["kept"][0, r, s])
            # the program's own inputs in float64: the same keys but for ties a rounding apart
            scores = np.einsum("h,hk->k", router["w_index"][0, r, s].astype(np.float64), np.maximum(
                router["q_index"][0, r, s].astype(np.float64)
                @ scan["k_index"][r, start:p + 1].astype(np.float64).T, 0.0))
            want = start + np.flatnonzero(scores >= np.sort(scores)[-min(6, pos + 1)] - 1e-6)
            assert set(kept) <= set(want) and len(kept) >= min(6, pos + 1)
    # three layers select: what they kept of what they chose from
    selected = out["selected"]
    assert selected.shape == (3, 2) and (selected[:, 0] < selected[:, 1]).all()
    # a document's queries: its tokens and its end id, where the row's inputs hold it
    lengths = np.array([(segs[r] == s).sum() for r in range(2) for s in range(1, segs[r].max() + 1)])
    assert (selected[:, 1] == (lengths * (lengths + 1) // 2).sum()).all()
    # at least its 6 best, and more only by a tie (with 4 heads a score is exactly 0 now and then)
    least = sum(min(t + 1, 6) for n in lengths for t in range(n))
    assert (selected[:, 0] >= least).all() and (selected[:, 0] <= least + 8).all()
    share = lm.record_selected(selected)
    assert METRICS.gauge_value("dsa.selected_share") == round(share, 6) and 0.2 < share < 0.5
    assert METRICS.gauge_value("dsa.kernel_layers") == 0          # off a TPU: the plain form


def test_the_mixer_against_the_reference(params):
    cfg, layer = program_cfg(), params["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((1, L, 32)), jnp.float32)
    got = mixer(layer, x, jnp.ones((1, L), jnp.int32), cfg)

    def reference(**lower):  # one program a control
        return jax.jit(lambda p, x: ref.ref_dsa(p, ref.ref_norm(x, p["attn_norm"], 1e-6), CFG, lower))(
            flat(layer), x[0])

    with jax.default_matmul_precision("highest"):
        want, record = reference()
        every, _ = reference(no_selection=True)
        unscaled, _ = reference(no_yarn=True)
    np.testing.assert_allclose(got[0], want, atol=3e-5)
    kept = np.asarray(record["kept"]).sum(axis=1)
    assert (kept >= np.minimum(np.arange(L) + 1, 6)).all() and kept.sum() <= 6 * L - 15 + 6
    assert np.abs(np.asarray(want) - np.asarray(every)).max() > 1e-2     # the selection bites
    assert np.abs(np.asarray(want) - np.asarray(unscaled)).max() > 1e-3  # and so does YaRN


def test_a_document_shorter_than_index_topk_reads_like_latent_attention_without_an_indexer(params):
    """``index_topk`` 32 over documents of at most 30 tokens: every candidate
    is kept, nothing is scored, and the layer is the one without an indexer."""
    batch = packed_rows()
    tokens, segs = batch["tokens"], batch["segment_ids"]
    at = jnp.zeros((2, 1), jnp.int32)
    import dataclasses

    loose = program_cfg({**CFG, "index_topk": 32})
    none = dataclasses.replace(loose, index_topk=0, index_heads=0, index_dim=0)
    bare = {**params, "layers": [{k: v for k, v in layer.items() if "idx" not in k}
                                 for layer in params["layers"]]}
    got = score(params, tokens, segs, at, loose)
    want = score(bare, tokens, segs, at, none)
    np.testing.assert_array_equal(got["logprob"], want["logprob"])
    assert "selected" not in want and (np.asarray(got["selected"])[:, 0]
                                       == np.asarray(got["selected"])[:, 1]).all()
    tight = score(params, tokens, segs, SAMPLE_AT, program_cfg(), jnp.int32(1))  # the fixture's program
    assert np.abs(np.asarray(tight["logprob"]) - np.asarray(want["logprob"])).max() > 1e-3


def selection_inputs(seed=0, b=2, l=256, h=4, d=128, dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, h, l, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, l, d)), dtype)
    w = jnp.asarray(rng.standard_normal((b, l, h)), jnp.float32)
    segs = np.zeros((b, l), np.int32)
    segs[0, :100], segs[0, 100:130], segs[0, 130:250] = 1, 2, 3
    segs[1:] = 1
    return q, k, w, jnp.asarray(segs)


select_keys = jax.jit(sparse_attn.select_keys, static_argnums=4, static_argnames="block")


def test_no_key_of_another_document_is_ever_kept():
    q, k, w, segs = selection_inputs()
    keep, kept = map(np.asarray, select_keys(q, k, w, segs, 16, block=64))
    s = np.asarray(segs)
    same = s[:, :, None] == s[:, None, :]
    causal = np.tril(np.ones((256, 256), bool))
    assert not (keep.astype(bool) & ~(same & causal[None])).any()
    pos = np.asarray(lm.segment_positions(segs))
    assert (kept == keep.sum(axis=-1)).all()
    assert (kept[s != 0] >= np.minimum(pos[s != 0] + 1, 16)).all()
    assert (kept[s != 0] > 16).mean() < 0.1                     # more than 16 only by a tie
    # by hand, one long row: the candidates at or above the 16th largest score
    scores = np.asarray(sparse_attn.index_scores(q[1:], k[1:], w[1:]))[0]
    for t in (0, 15, 16, 200, 255):
        mine = scores[t, :t + 1]
        want = np.flatnonzero(mine >= np.sort(mine)[-min(16, t + 1)])
        assert set(np.flatnonzero(keep[1, t])) == set(want)


def test_ties_at_the_threshold_are_all_kept():
    q = jnp.ones((1, 1, 8, 128), jnp.bfloat16)
    k = jnp.ones((1, 8, 128), jnp.bfloat16)
    keep, kept = select_keys(q, k, jnp.ones((1, 8, 1)), jnp.ones((1, 8), jnp.int32), 3, block=4)
    assert (np.asarray(keep[0]) == np.tril(np.ones((8, 8), np.int8))).all()
    assert (np.asarray(kept[0]) == np.arange(1, 9)).all()


@pytest.mark.parametrize("topk", [16, 100])
def test_the_selection_kernel_interpreted_is_the_plain_form(topk):
    from jax.experimental.pallas import tpu as pltpu

    q, k, w, segs = selection_inputs(seed=topk)
    with pltpu.force_tpu_interpret_mode():
        keep, kept = jax.jit(lambda *a: sparse_attn._select_fused(*a, topk, (64, 128)))(q, k, w, segs)
    want_keep, want_kept = jax.jit(lambda *a: sparse_attn._select_plain(*a, topk, 64))(q, k, w, segs)
    np.testing.assert_array_equal(np.asarray(keep), np.asarray(want_keep))
    np.testing.assert_array_equal(np.asarray(kept), np.asarray(want_kept))
    assert sparse_attn.select_tile(q.shape, topk) is None       # off a TPU


def test_the_attention_kernel_interpreted_under_a_selection_is_the_plain_path():
    rng = np.random.default_rng(3)
    b, h, l = 1, 2, 256
    q = jnp.asarray(rng.standard_normal((b, h, l, 192)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, l, 192)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, l, 128)), jnp.float32)
    qi, ki, w, _ = selection_inputs(seed=9, b=1)
    segs = jnp.asarray(np.repeat([1, 2], [160, 96])[None].astype(np.int32))
    keep, _ = select_keys(qi, ki, w, segs, 24, block=64)
    got = interpreted_kernel(q, k, v, segs, 0.11, 128, 128, keep=keep)
    want = plain_path(q, k, v, segs, 0.11, keep=keep)
    np.testing.assert_allclose(got, want, atol=2e-5)
    dense = plain_path(q, k, v, segs, 0.11)
    assert np.abs(np.asarray(want) - np.asarray(dense)).max() > 0.1


def a_selection(segs, seed=6, share=0.3):
    """keep [1, L, L] int8: a query's own key, and ``share`` of the keys before
    it in its document, at random."""
    segs = np.asarray(segs)[0]
    at = np.arange(len(segs))
    inside = (segs[:, None] == segs[None, :]) & (at[None, :] <= at[:, None])
    keep = inside & (np.random.default_rng(seed).random(inside.shape) < share)
    return (keep | np.eye(len(segs), dtype=bool))[None].astype(np.int8)


#: selections that force each kind of pair on the kernel under a mask of kept keys:
#: (lengths of the row's documents, row length, blocks, what to do to the selection)
KEPT_KINDS = {
    "one document over three blocks": ([768], 768, (256, 256), None),
    "a boundary inside a block and one on an edge": ([300, 212, 256], 768, (256, 256), None),
    "a strip of keys empty for some rows": ([768], 768, (256, 256), "strip"),
    "rows that keep a single key": ([400, 368], 768, (256, 256), "single"),
    "a query block of two passes": ([1024], 1024, (512, 512), "strip"),
    "a query block of two key blocks": ([600, 424], 1024, (512, 256), None),
}


@pytest.mark.parametrize("case", KEPT_KINDS)
def test_each_kind_of_pair_under_a_selection_is_the_plain_path(case):
    """The kernel's kinds of pair (tests/test_mla_lm.py has them without a
    selection) apply the kept keys beside their own mask: under the diagonal
    no position is compared, on it a pass takes the keys its rows reach."""
    lengths, l, blocks, change = KEPT_KINDS[case]
    q, k, v, segs = kernel_inputs(lengths, l, seed=8)
    keep = a_selection(segs)
    if change == "strip":       # rows 300-339 and 600-700 keep nothing of keys 128-255; one block's rows none of another's
        keep[0, 300:340, 128:256] = keep[0, 600:701, 128:256] = 0
        keep[0, 512:768, 0:256] = 0
    if change == "single":      # a document's last rows and a block's first see themselves alone
        for row in (*range(390, 400), *range(512, 530), 767):
            keep[0, row] = 0
            keep[0, row, row] = 1
    keep = jnp.asarray(keep)
    got = interpreted_kernel(q, k, v, segs, 0.09, *blocks, keep=keep)
    want = plain_path(q, k, v, segs, 0.09, keep=keep)
    real = np.asarray(segs[0] != 0)
    np.testing.assert_allclose(np.asarray(got)[:, :, real], np.asarray(want)[:, :, real], atol=2e-5)
    assert np.abs(np.asarray(want) - np.asarray(plain_path(q, k, v, segs, 0.09))).max() > 0.1


def test_yarn_against_the_published_formula():
    """``precompute_freqs_cis`` and the softmax scale of the family's inference
    code, transcribed in NumPy, at the published numbers."""
    dim, base, factor, original, beta_fast, beta_slow = 64, 10000.0, 40, 4096, 32, 1

    def find_correction_dim(num_rotations):
        return dim * math.log(original / (num_rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    assert (low, high) == (10, 23)
    smooth = 1 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    freqs = 1.0 / (base ** (np.arange(0, dim, 2) / dim))
    freqs = freqs / factor * (1 - smooth) + freqs * smooth
    scaling = (factor, original, beta_fast, beta_slow)
    np.testing.assert_allclose(freqs, base ** (-np.arange(32) / 32)
                               * sparse_attn.yarn_blend(32, base, scaling), rtol=1e-6)
    blend, gain = ref.ref_yarn({"rope_theta": 10000, "rope_scaling": {
        "factor": 40, "original_max_position_embeddings": 4096, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1}}, 32)
    np.testing.assert_array_equal(blend, sparse_attn.yarn_blend(32, base, scaling))
    mscale = 0.1 * 1 * math.log(factor) + 1.0
    assert gain == pytest.approx(mscale * mscale) and sparse_attn.yarn_softmax_gain(scaling) == gain
    assert 192 ** -0.5 * gain == pytest.approx(0.13523, rel=1e-4)
    # the program turns by these frequencies
    x = jnp.ones((1, 1, 3, 64), jnp.float32)
    at = jnp.asarray([[0, 7, 9000]])
    got = np.asarray(lm.rotary(x, at, base, scaling))[0, 0]
    angle = np.asarray(at)[0][:, None] * freqs
    np.testing.assert_allclose(got[:, :32], np.cos(angle) - np.sin(angle), atol=2e-3)
    assert np.abs(got - np.asarray(lm.rotary(x, at, base))[0, 0]).max() > 0.5


def loop_router(scores, bias, k, groups, stay):
    """The group limit written as loops over tokens and runs."""
    chosen = []
    for s in np.asarray(scores, np.float64):
        biased = s + bias
        size = len(s) // groups
        run_score = [np.sort(biased[g * size:(g + 1) * size])[-2:].sum() for g in range(groups)]
        best = sorted(range(groups), key=lambda g: -run_score[g])[:stay]
        allowed = [e for g in best for e in range(g * size, (g + 1) * size)]
        chosen.append(sorted(allowed, key=lambda e: -biased[e])[:k])
    return np.array(chosen)


def test_group_limited_routing_against_a_loop_written_router():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((32, 32)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(32) * 0.2, jnp.float32)
    experts, gates = moe.route_top_k(x, router, 4, 2.5, bias, n_group=8, topk_group=3)
    scores = 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64) @ np.asarray(router, np.float64)))
    want = loop_router(scores, np.asarray(bias, np.float64), 4, 8, 3)
    assert (np.sort(np.asarray(experts), axis=1) == np.sort(want, axis=1)).all()
    top = np.take_along_axis(scores, np.asarray(experts), axis=1)
    np.testing.assert_allclose(gates, top / top.sum(axis=1, keepdims=True) * 2.5, rtol=1e-5)
    assert max(len(set(row // 4)) for row in np.asarray(experts)) <= 3
    free, _ = moe.route_top_k(x, router, 4, 2.5, bias)
    assert (np.sort(np.asarray(free), axis=1) != np.sort(want, axis=1)).any()   # the limit bites
    ref_chosen, ref_gates = ref.ref_route_grouped(
        x, router, bias, {"num_experts_per_tok": 4, "routed_scaling_factor": 2.5, "n_group": 8,
                          "topk_group": 3})
    np.testing.assert_array_equal(ref_chosen, experts)
    np.testing.assert_allclose(ref_gates, gates, rtol=1e-6)
    # without a bias a run's score is its largest score
    plain, _ = moe.route_top_k(x, router, 4, 2.5, None, n_group=8, topk_group=3)
    runs = scores.reshape(64, 8, 4).max(axis=-1)
    allowed = np.argsort(-runs, axis=1, kind="stable")[:, :3]
    assert all(set(np.asarray(plain)[t] // 4) <= set(allowed[t]) for t in range(64))


def test_the_shares_of_256_experts_held_16_by_16_add_up_to_the_uncut_layer():
    """Sixteen chips of 16 experts each under the group-limited router (8 runs
    of 32, 4 stay, 8 chosen), the shared expert counted once, against the
    reference told that it holds all 256."""
    cfg = {**CFG, "n_routed_experts": 256, "n_routed_experts_held": 256, "num_experts_per_tok": 8,
           "n_group": 8, "topk_group": 4, "num_hidden_layers": 2}    # up to the layer taken
    p = init_params(jax.random.PRNGKey(1), program_cfg(cfg))["layers"][1]
    p["router_bias"] = p["router_bias"] * 4.0
    x = jnp.asarray(np.random.default_rng(1).standard_normal((96, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _, (chosen, _) = ref.ref_moe_grouped(flat(p), x, cfg)
        free, _, (anywhere, _) = ref.ref_moe_grouped(flat(p), x, cfg, no_group_limit=True)
        shared = ref.ref_ffn(x, *(jnp.asarray(p["shared"][k]) for k in ("w_gate", "w_up", "w_down")))
    assert (np.asarray(chosen) != np.asarray(anywhere)).any()      # the limit is at work
    assert max(len(set(row // 32)) for row in np.asarray(chosen)) <= 4
    total, visits = -15 * shared, 0
    for first in range(0, 256, 16):
        share = {**p, **{k: p[k][first:first + 16] for k in ("w_gate", "w_up", "w_down")}}
        y, n, dropped, _ = held_experts(share, x, held_offset=first, top_k=8,
                                        routed_scale=2.5, tile=8, n_group=8, topk_group=4)
        total, visits = total + y, visits + int(n.sum())
        assert int(dropped) == 0
    assert visits == x.shape[0] * 8
    np.testing.assert_allclose(total, whole, atol=3e-5)
    assert np.abs(np.asarray(total) - np.asarray(free)).max() > 1e-2


def test_tiles_added_to_their_tokens_are_the_read_back():
    """A chip that holds under an eighth of the experts adds each tile's rows
    to their tokens as the tile is computed; one that holds more reads a
    buffer back: the same sums, here 2 of 32 experts against the same two
    told that the router has 16 outputs' worth of company (both paths see the
    same router, the same visits)."""
    cfg = program_cfg({**CFG, "n_routed_experts": 32, "n_routed_experts_held": 32,
                       "num_hidden_layers": 2})                 # up to the layer taken
    p = init_params(jax.random.PRNGKey(2), cfg)["layers"][1]
    x = jnp.asarray(np.random.default_rng(4).standard_normal((96, 32)), jnp.float32)
    valid = jnp.arange(96) % 7 != 0
    kw = dict(top_k=3, routed_scale=2.5, valid=valid, n_group=4, topk_group=2)
    whole, visits, dropped, _ = held_experts(p, x, held_offset=0, tile=8, **kw)  # read back
    assert int(dropped) == 0 and int(visits.sum()) == int(valid.sum()) * 3
    shared = moe.gated_ffn(x, *(p["shared"][k] for k in ("w_gate", "w_up", "w_down")))
    for tile in (8, 32):
        total, seen = -15 * shared, 0
        for first in range(0, 32, 2):                              # 2 of 32: added as they come
            share = {**p, **{k: p[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")}}
            y, n, lost, _ = held_experts(share, x, held_offset=first, tile=tile, **kw)
            total, seen = total + y, seen + int(n.sum())
            assert int(lost) == 0 and (np.asarray(n) == np.asarray(visits)[first:first + 2]).all()
        assert seen == int(visits.sum())
        np.testing.assert_allclose(total, whole, atol=2e-5)


def published():
    import json

    with open(os.path.join(ROOT, "benchmark", "configs", "deepseek_v32_exp_ep16.json")) as f:
        return json.load(f)


def test_the_cut_holds_the_parameters_issue_33_counted():
    """``pattern_param_shapes`` of the benchmark's configuration: every norm
    and bias counted; no difference from the issue's arithmetic."""
    cfg = published()
    shapes = lm.pattern_param_shapes(ref.program(cfg, {"row_tokens": 16384}))

    def count(tree):
        return sum(int(np.prod(shape)) for shape, _ in jax.tree.leaves(tree, is_leaf=lm._is_shape))

    dense, expert = shapes["layers"][0], shapes["layers"][1]
    mixer = {k: v for k, v in dense.items() if k not in ("ffn_norm", "dense")}
    indexer = {k: v for k, v in mixer.items() if "idx" in k}
    assert count(mixer) - count(indexer) == 187_114_496 and count(indexer) == 13_959_424
    assert count(dense) == 597_442_816 and count(expert) == 951_599_616
    assert count({k: expert[k] for k in ("w_gate", "w_up", "w_down")}) == 16 * 44_040_192
    assert count({k: shapes[k] for k in ("embed", "head", "final_norm")}) == 231_676_928
    assert len(shapes["layers"]) == 5 and count(shapes) == 4_635_518_208
    # the benchmark's own count of the same cut
    assert sum(int(np.prod(shape)) for part in ["embed", "head", *range(5)]
               for shape, *_ in ref.weight_specs(cfg, part).values()) == 4_635_518_208


@pytest.mark.parametrize("loads, held, cap, target, want", [
    # the sum nearest the target among the sets of 3 light ones: 5 + 6 + 9
    ([5, 40, 6, 9, 1, 30], 3, 15, 20, [0, 2, 3]),
    # two equally near (19 and 21): the lower
    ([5, 40, 6, 8, 10, 30], 3, 15, 20, [0, 2, 3]),
    # the target out of reach: the most the light ones give
    ([5, 40, 6, 9, 1, 30], 3, 15, 100, [0, 2, 3]),
    # an idle expert is no more this chip's than one over the cap
    ([0, 0, 7, 16, 3, 4], 3, 15, 12, [2, 4, 5]),
    # too few light ones: they, then the lightest of the rest, idle ones last
    ([0, 20, 7, 16, 0, 40], 3, 15, 12, [1, 2, 3]),
    ([0, 0, 7, 16, 0, 0], 3, 15, 12, [0, 2, 3]),
])
def test_the_benchmarks_placement_picks_light_experts_near_the_share(loads, held, cap, target, want):
    assert ref.pick_experts(loads, held, cap, target) == want


@pytest.mark.parametrize("seed", [5, 6])
def test_naming_a_groups_experts_anew_changes_no_tokens_routing(seed):
    """What the benchmark's placement does to the router: the columns of ONE
    group in another order. Every token keeps its experts (under their new
    names) and its gates, so the layer computes what it computed."""
    rng = np.random.default_rng(seed)
    d, e, groups, k = 24, 16, 4, 3
    x = jnp.asarray(rng.normal(size=(40, d)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(d, e)) * d ** -0.5, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(e,)) * 0.05, jnp.float32)
    order = np.arange(e)
    order[4:8] = 4 + rng.permutation(4)                      # group 1's four, shuffled
    assert (order != np.arange(e)).any()
    was = moe.route_top_k(x, router, k, 2.5, bias, n_group=groups, topk_group=2)
    now = moe.route_top_k(x, router[:, order], k, 2.5, bias[order], n_group=groups, topk_group=2)
    named = order[np.asarray(now[0])]                        # the new names, read as the old
    for t in range(x.shape[0]):
        a, b = np.argsort(np.asarray(was[0][t])), np.argsort(named[t])
        assert (np.asarray(was[0][t])[a] == named[t][b]).all()
        np.testing.assert_allclose(np.asarray(was[1][t])[a], np.asarray(now[1][t])[b], rtol=1e-6)


def test_the_compiled_program_holds_every_scope(params):
    from tpu_tfrecord import tracing

    held = scopes_held(params, packed_rows(), program_cfg())
    assert held == {"tfr.embed", "tfr.mla_proj", "tfr.mla_attn", "tfr.dsa_proj", "tfr.dsa_index",
                    "tfr.dense_ffn", "tfr.moe_route", "tfr.moe_experts", "tfr.moe_shared",
                    "tfr.lm_head"}
    assert held <= set(tracing.ANNOTATIONS)
