"""The sandwich-norm decoder with window and full attention mixed
(``models.lm.score`` with ``swa`` and ``gqa`` mixers, Q/K norm, branch
norms, a scaled embedding, a leading dense layer and a biased router)
against its plain reference, at sizes a CPU walks in seconds: the two kinds
of mixer and the (sliding, sliding, full, sliding, sliding) x (dense, moe x
4) model; a packed row against each of its documents alone; the windowed
Pallas kernel, interpreted, and ``blockwise_attention(window=)`` against the
dense oracle on rows whose documents are shorter than, equal to and longer
than the window; the band the kernel's grid walks; one head's record of what
its attention was given and gave; the shares of 64 experts held as 8 x 8
against the uncut layer; and the residual path with branch norms under the
other mixers."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import trinity_large as ref
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.models import lm
from tpu_tfrecord.models.attention import (
    attention_reference, blockwise_attention, flash_attention_widths, pair_kinds)

from test_pattern_lm import (SAMPLE_AT, documents_of, equations, flat, held_experts, init_params,
                             packed_rows as older_rows, reference_weights, score)

#: a configuration with the published names, tiny: published layers 1-5 of a (sliding x 3,
#: full) period, the first of them dense; documents of up to 30 tokens against 8 keys
CFG = {
    "hidden_size": 32, "num_hidden_layers": 5, "first_layer": 1, "num_dense_layers": 1,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 2,
    "sliding_window": 8, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "rope_theta": 10000, "intermediate_size": 48, "num_experts": 16, "n_routed_experts_held": 16,
    "held_offset": 0, "num_experts_per_tok": 4, "moe_intermediate_size": 16,
    "num_shared_experts": 1, "route_scale": 2.448, "rms_norm_eps": 1e-5, "vocab_size": 64,
}
L = 48


def program_cfg(cfg=CFG, dtype=jnp.float32, **cut):
    cut = {"attn_block": 16, "expert_tile": 8, "head_block": 32, **cut}
    plan = ref.layer_plan(cfg)
    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_pattern=tuple("swa" if sliding else "gqa" for sliding, _ in plan),
        ffn_pattern=tuple(ffn for _, ffn in plan), n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], qk_norm=True, branch_norms=True, embed_scale=True,
        rope_theta=float(cfg["rope_theta"]), d_dense=cfg["intermediate_size"],
        n_experts=cfg["num_experts"], experts_held=cfg["n_routed_experts_held"],
        held_offset=cfg["held_offset"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], n_shared=cfg["num_shared_experts"],
        routed_scale=cfg["route_scale"], router_bias=True, norm_eps=cfg["rms_norm_eps"],
        max_len=L, dtype=dtype, **cut)


def packed_rows():
    return older_rows()[0]


def seeded_gains(tree, rng):
    """Norm weights of 1 +- 0.1: a gain of exactly one hides a norm left out."""
    for name, leaf in tree.items():
        if isinstance(leaf, dict):
            seeded_gains(leaf, rng)
        elif name.endswith("norm"):
            tree[name] = leaf * jnp.asarray(1.0 + 0.1 * rng.uniform(-1, 1, leaf.shape), leaf.dtype)


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.PRNGKey(3), program_cfg())
    rng = np.random.default_rng(3)
    p["embed"] = p["embed"] * CFG["hidden_size"] ** -0.5       # unit rows after the scale
    seeded_gains(p, rng)
    for layer in p["layers"]:
        if "router_bias" in layer:  # a bias large enough to change who is chosen
            layer["router_bias"] = layer["router_bias"] * 4.0
    return p


@pytest.fixture(scope="module")
def scored(params):
    batch = packed_rows()
    out = score(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, program_cfg(), jnp.int32(3))
    return batch, SAMPLE_AT, jax.tree.map(np.asarray, out)


def test_the_parameters_are_the_models(params):
    cfg = program_cfg()
    assert cfg.layer_pattern == ("swa", "swa", "gqa", "swa", "swa")
    assert lm.ffn_kinds(cfg) == ("dense", "moe", "moe", "moe", "moe")
    first, later = params["layers"][0], params["layers"][2]
    mixer = {"attn_norm", "wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm", "post_attn_norm"}
    assert set(first) == mixer | {"ffn_norm", "dense", "post_ffn_norm"}
    assert set(later) == mixer | {"moe_norm", "router", "router_bias", "w_gate", "w_up", "w_down",
                                  "shared", "post_ffn_norm"}
    assert first["q_norm"].shape == first["k_norm"].shape == (8,)   # one weight for all heads
    assert first["wq"].shape == (32, 32) and first["wk"].shape == (32, 16)
    assert first["post_attn_norm"].shape == later["post_ffn_norm"].shape == (32,)
    with pytest.raises(ValueError, match="needs cfg.window"):
        lm.pattern_param_shapes(lm.PatternLMConfig(layer_pattern=("swa",)))


def test_a_packed_row_scores_each_document_as_the_reference_scores_it_alone(params, scored):
    batch, sample_at, out = scored
    docs = documents_of(batch)
    at = [[int(p) - start for p in np.asarray(sample_at)[r]
           if start <= p < start + len(doc) - 1] for r, start, doc in docs]
    want = ref.reference_score(CFG, [d for _, _, d in docs], reference_weights(params), at,
                               probe_head=3)
    covered, seen = np.zeros_like(out["logprob"], bool), 0
    scan, routed = out["probes"]["scan"], out["probes"]["router"]
    for (r, start, doc), logp, places, logits, w_scan, w_routed in zip(
            docs, want["logprob"], at, want["logits"], want["scan"], want["router"]):
        n = len(doc) - 1
        np.testing.assert_allclose(out["logprob"][r, start:start + n], logp, atol=2e-4)
        covered[r, start:start + n] = True
        inside = [list(np.asarray(sample_at)[r]).index(p + start) for p in places]
        np.testing.assert_allclose(out["logits"][r, inside], logits, atol=3e-4)
        seen += len(places)
        # the probed head of the first sliding layer: what its attention was given and gave
        for name in ("k_swa", "v_swa"):
            np.testing.assert_allclose(scan[name][r, start:start + n], w_scan[name], atol=2e-5)
        for name in ("q_swa", "att_swa"):
            np.testing.assert_allclose(routed[name][:, r, inside], w_routed[name], atol=2e-5)
        assert (routed["swa_pos"][:, r, inside] == w_routed["swa_pos"]).all()
    assert (out["logprob"][~covered] == 0).all() and covered.sum() > 80 and seen >= 6
    # four expert layers report, the dense one has no experts to visit
    assert out["visits"].shape == (4, 16) and out["dropped"].sum() == 0
    real = int((batch["segment_ids"][:, :-1] != 0).sum())
    assert (out["visits"].sum(axis=1) == real * CFG["num_experts_per_tok"]).all()
    assert routed["u"].shape == (4, 2, 4, 32) and routed["q_swa"].shape == (1, 2, 4, 8)
    # off a TPU no layer takes the kernel; rows of 48 in blocks of 16 against 8 keys: 5 of 6 pairs
    assert METRICS.gauge_value("swa.kernel_layers") == 0 and METRICS.gauge_value("gqa.kernel_layers") == 0
    assert METRICS.gauge_value("swa.pairs_walked_share") == round(5 / 6, 6)


#: ``gqa_mixer`` as one program a configuration, shape and kind of layer
mixer = jax.jit(lm.gqa_mixer, static_argnums=(3, 4))


@functools.partial(jax.jit, static_argnames=("sliding", "lower"))
def reference_mixer(layer, x, sliding, lower=()):
    """The reference's mixer of one document's inputs, as one program."""
    with jax.default_matmul_precision("highest"):
        return ref.ref_swa(layer, ref.ref_norm(x, layer["attn_norm"], 1e-5), CFG, sliding,
                           dict(lower))[0]


@pytest.mark.parametrize("sliding", [True, False], ids=["sliding", "full"])
def test_the_mixer_against_the_reference(params, sliding):
    cfg, layer = program_cfg(), params["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((1, L, 32)), jnp.float32)
    got = mixer(layer, x, jnp.ones((1, L), jnp.int32), cfg, sliding)
    want = reference_mixer(flat(layer), x[0], sliding)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    # what a sliding layer adds, one piece at a time, moves it; a full layer has no such piece
    moved = {name: float(np.abs(np.asarray(reference_mixer(flat(layer), x[0], sliding, lower))
                                - np.asarray(want)).max())
             for name, lower in {"window": (("window", None),), "one_key_more": (("window", 9),),
                                 "no_rotary": (("no_rotary", True),),
                                 "rotary_on_full": (("rotary_on_full", True),),
                                 "no_qk_norm": (("no_qk_norm", True),)}.items()}
    still = {"rotary_on_full"} if sliding else {"window", "one_key_more", "no_rotary"}
    assert all((moved[name] == 0.0) == (name in still) for name in moved), moved


def test_a_document_in_a_row_is_the_document_alone(params):
    """Positions, the window and the mask all restart at a boundary: the second
    document of a row scores as it does alone at the row's start."""
    cfg = program_cfg()
    rng = np.random.default_rng(2)
    a, b = (rng.integers(1, 64, size=n).astype(np.int32) for n in (17, 30))
    packed = np.zeros((2, L + 1), np.int32)
    segs = np.zeros((2, L + 1), np.int32)
    packed[0, :17], packed[0, 18:48], segs[0, :18], segs[0, 18:49] = a, b, 1, 2
    packed[1, :30], segs[1, :31] = b, 1
    out = score(params, packed, segs, SAMPLE_AT, cfg, jnp.int32(0))
    np.testing.assert_allclose(out["logprob"][0, 18:48], out["logprob"][1, :30], atol=1e-5)


# ---------------------------------------------------------------------------
# The window in the kernel and in the plain path
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def interpreted_kernel(q, k, v, segs, scale, block_q, block_k, window):
    """``flash_attention_widths`` under a window, interpreted, one program a shape."""
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        return flash_attention_widths(q, k, v, segs, scale, block_q, block_k, window=window)


@functools.partial(jax.jit, static_argnames=("window", "block"))
def plain_path(q, k, v, segs, window, block=64):
    return jnp.swapaxes(blockwise_attention(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), segs, scale=0.09,
        block=block, window=window), 1, 2)


@functools.partial(jax.jit, static_argnames="window")
def dense_oracle(q, k, v, segs, window):
    return jnp.swapaxes(attention_reference(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), scale=0.09,
        causal=True, segments=segs, window=window), 1, 2)


def window_inputs(lengths, l, heads=4, kv=2, seed=4):
    """q [1, heads, l, 128], k, v [1, kv, l, 128] float32 and a row of documents
    of ``lengths`` (ids from 1; pads, id 0, to the row's end)."""
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.standard_normal((1, heads, l, 128)), jnp.float32)
    k, v = (jnp.asarray(r.standard_normal((1, kv, l, 128)), jnp.float32) for _ in range(2))
    ids = np.repeat(np.arange(1, len(lengths) + 1), lengths)
    return q, k, v, jnp.asarray(np.pad(ids, (0, l - len(ids)))[None].astype(np.int32))


#: rows that put the window where it can go wrong: (lengths of the row's documents, row
#: length, blocks, window, the band's pairs where all documents are one)
WINDOWS = {
    "a document longer than the window, whole blocks": ([768], 768, (256, 256), 256, 5),
    "a window that is not whole blocks": ([768], 768, (256, 256), 300, 6),
    "a window inside one block": ([768], 768, (256, 256), 100, 5),
    "the band's plain blocks between the trailing one and the diagonal": ([768], 768, (128, 128), 512, 20),
    "a boundary inside the band": ([300, 468], 768, (256, 256), 256, 5),
    "documents shorter than, equal to and longer than the window": (
        [100, 150, 30, 270, 218], 768, (128, 128), 150, 15),
    "a query block of two key blocks": ([1024], 1024, (512, 256), 300, 6),
    "grouped heads, pads to the row's end": ([600, 300], 1024, (256, 128), 257, 14),
    "one key: the query's own": ([768], 768, (256, 256), 1, 3),
    "a window longer than the row": ([768], 768, (256, 256), 5000, 6),
}


@pytest.mark.parametrize("case", WINDOWS)
def test_the_windowed_kernel_and_the_plain_path_are_the_dense_oracle(case):
    """``lm._attend`` under a window runs ``flash_attention_widths`` on a TPU and
    ``blockwise_attention`` elsewhere: the kernel, interpreted here, and the
    plain path against the mask written out; the grid walks the band alone."""
    lengths, l, blocks, window, band = WINDOWS[case]
    q, k, v, segs = window_inputs(lengths, l)
    assert sum(pair_kinds(np.ones((1, l), np.int32), *blocks, window)) == band
    want = np.asarray(dense_oracle(q, k, v, segs, window))
    real = np.asarray(segs[0] != 0)
    for got in (interpreted_kernel(q, k, v, segs, 0.09, *blocks, window),
                plain_path(q, k, v, segs, window)):
        np.testing.assert_allclose(np.asarray(got)[:, :, real], want[:, :, real], atol=2e-5)
    if window < max(lengths):
        unbounded = np.asarray(dense_oracle(q, k, v, segs, None))
        assert np.abs(unbounded - want)[:, :, real].max() > 1e-2


def test_the_band_of_one_long_document_is_150_pairs_of_528():
    """``trinity_large_ep8.score``'s rows: one document of 32,768 tokens in blocks
    of 1,024 under 4,096 keys: a query block's own block, the three before it
    plain, the fourth compared against the window."""
    one = np.ones((1, 32768), np.int32)
    assert pair_kinds(one) == (0, 496, 32)
    assert pair_kinds(one, window=4096) == (0, 90, 60)           # 32 diagonal + 28 trailing masked
    assert pair_kinds(one, window=4097) == (0, 90, 60)           # one key more: block qi - 4's first
    assert pair_kinds(one, window=4098) == (0, 90, 87)           # two: a fifth block behind
    with pytest.raises(ValueError, match="at least its own"):
        flash_attention_widths(jnp.zeros((1, 2, 256, 128)), jnp.zeros((1, 2, 256, 128)),
                               jnp.zeros((1, 2, 256, 128)), jnp.ones((1, 256), jnp.int32), 1.0,
                               window=0)


def operations(jaxpr):
    """How many equations ``jaxpr`` and every jaxpr inside them hold."""
    return sum(1 for _ in equations(jaxpr))


@pytest.mark.parametrize("window, at_the_parent, with_parts", [(None, 187, 202), (300, 336, 366)])
def test_without_a_rotary_part_the_kernels_body_is_the_one_it_was(window, at_the_parent, with_parts):
    """The kernel takes latent attention's queries and keys in two parts since
    PR 40; handed one, as by this file's layers and by every caller before, its
    traced body has the operations it had at PR 39 (counted there, on this call:
    the same text too, by its hash then), and the second product only with a
    second part: five operations a pass more (two reads, the key head's index,
    the product, the sum), in each of the three kinds of pair here and of the
    six under a window."""
    q, k, v, segs = window_inputs([512], 512)

    def body(**parts):
        program = jax.make_jaxpr(lambda q, k, v: flash_attention_widths(
            q, k, v, segs, 0.09, 256, 128, window=window, **parts))(q, k, v).jaxpr
        (call,) = program.eqns       # the jitted call site
        (kernel,) = [e for e in call.params["jaxpr"].jaxpr.eqns if e.primitive.name == "pallas_call"]
        return operations(kernel.params["jaxpr"])

    assert body() == at_the_parent
    assert body(q_rope=q[..., :64], k_rope=k[:, :1, :, :64]) == with_parts


def test_the_kernel_is_what_a_tpu_runs_under_a_window(monkeypatch, params):
    """On a TPU ``_attend`` hands a windowed layer and, since PR 43, a full one to the
    repo's one kernel, the full one without a window; stubbed here, the dispatch and
    the gauges read."""
    seen = []
    monkeypatch.setattr(lm.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(lm, "flash_attention_widths",
                        lambda q, k, v, segs, scale, bq, bk, keep=None, window=None:
                        seen.append(("own", window)) or jnp.zeros(q.shape[:3] + v.shape[-1:], q.dtype))
    cfg = lm.PatternLMConfig(
        vocab_size=64, d_model=32, layer_pattern=("swa", "gqa", "swa"), ffn_pattern=("dense",) * 3,
        n_heads=2, n_kv_heads=1, head_dim=128, window=256, qk_norm=True, branch_norms=True,
        d_dense=16, max_len=512, attn_block=128, head_block=512, dtype=jnp.float32)
    p = jax.eval_shape(lambda: lm.pattern_init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, 513), jnp.int32)
    jax.eval_shape(lambda p, t: lm.score(p, t, t, jnp.zeros((1, 2), jnp.int32), cfg), p, tokens)
    assert seen == [("own", 256), ("own", None), ("own", 256)]
    assert METRICS.gauge_value("swa.kernel_layers") == 2
    assert METRICS.gauge_value("gqa.kernel_layers") == 1
    assert METRICS.gauge_value("swa.pairs_walked_share") == round(9 / 10, 6)


# ---------------------------------------------------------------------------
# The share of the experts, lower precision, the other mixers' residual path
# ---------------------------------------------------------------------------


def test_the_shares_of_64_experts_held_8_by_8_add_up_to_the_uncut_layer():
    """Eight chips of 8 experts each under the biased router (4 of 64, gates x
    2.448), the shared expert counted once, against the reference told that
    it holds all 64: ``trinity_large_ep8``'s cut, an eighth of the experts."""
    cfg = {**CFG, "num_experts": 64, "n_routed_experts_held": 64, "num_hidden_layers": 2}
    p = init_params(jax.random.PRNGKey(1), program_cfg(cfg))["layers"][1]
    p["router_bias"] = p["router_bias"] * 4.0
    x = jnp.asarray(np.random.default_rng(1).standard_normal((96, 32)), jnp.float32)
    moe_cfg = {**cfg, "routed_scaling_factor": cfg["route_scale"]}
    with jax.default_matmul_precision("highest"):
        whole, _, _ = ref.ref_moe_biased(flat(p), x, moe_cfg)
        shared = ref.ref_ffn(x, *(jnp.asarray(p["shared"][k]) for k in ("w_gate", "w_up", "w_down")))
    total, visits = -7 * shared, 0
    for first in range(0, 64, 8):
        share = {**p, **{k: p[k][first:first + 8] for k in ("w_gate", "w_up", "w_down")}}
        y, n, dropped, _ = held_experts(share, x, held_offset=first, top_k=4,
                                        routed_scale=2.448, tile=8)
        total, visits = total + y, visits + int(n.sum())
        assert int(dropped) == 0
    assert visits == x.shape[0] * 4
    np.testing.assert_allclose(total, whole, atol=3e-5)


def test_bfloat16_stays_near_the_float32_program(params):
    batch = packed_rows()
    outs = []
    for dtype in (jnp.float32, jnp.bfloat16):
        cfg = program_cfg(dtype=dtype)
        p = jax.tree.map(lambda a, s: a.astype(s[1]), params, lm.pattern_param_shapes(cfg))
        outs.append(np.asarray(score(p, batch["tokens"], batch["segment_ids"], SAMPLE_AT,
                                     cfg)["logprob"]))
    gap = np.abs(outs[0] - outs[1])      # four routers deep, a flipped choice moves a token far
    assert 0 < np.median(gap[gap > 0]) < 0.03 and gap.max() < 1.5


@pytest.mark.parametrize("lower", ["no_branch_norms", "no_embed_scale", "no_window", "no_rotary"])
def test_a_part_left_out_of_the_reference_moves_the_score(params, lower):
    docs = [d for _, _, d in documents_of(packed_rows())][:1]
    departure = {"no_window": {"window": None}}.get(lower, {lower: True})
    sound, broken = (ref.reference_score(CFG, docs, reference_weights(params), lower=how)["logprob"][0]
                     for how in (None, departure))
    assert np.abs(sound - broken).max() > 1e-3


def test_branch_norms_join_every_kind_of_mixer():
    """The residual path is the pattern's, not one mixer's: with ``branch_norms``
    a delta-rule and a latent-attention layer join the stream through a norm
    of their own too, and a gain of zero on it takes the branch away."""
    cfg = lm.PatternLMConfig(layer_pattern=("kda", "mla"), ffn_pattern=("moe", "dense"),
                             branch_norms=True, max_len=L, dtype=jnp.float32, attn_block=16,
                             kda_chunk=8, expert_tile=8, head_block=32)
    p = init_params(jax.random.PRNGKey(2), cfg)
    assert all({"post_attn_norm", "post_ffn_norm"} <= set(layer) for layer in p["layers"])
    batch = packed_rows()
    hidden = jax.jit(lambda p: lm.pattern_hidden(p, batch["tokens"], batch["segment_ids"], cfg)[0])
    for layer in p["layers"]:
        layer["post_attn_norm"], layer["post_ffn_norm"] = (jnp.zeros_like(layer[k]) for k in (
            "post_attn_norm", "post_ffn_norm"))
    np.testing.assert_array_equal(hidden(p), p["embed"][batch["tokens"][:, :-1]])


def test_the_compiled_program_holds_every_scope(params):
    from test_mla_lm import scopes_held
    from tpu_tfrecord import tracing

    held = scopes_held(params, packed_rows(), program_cfg())
    assert held == {"tfr.embed", "tfr.swa_proj", "tfr.swa_attn", "tfr.gqa", "tfr.dense_ffn",
                    "tfr.moe_route", "tfr.moe_experts", "tfr.moe_shared", "tfr.lm_head"}
    assert held <= set(tracing.ANNOTATIONS)
