"""The gated delta-net / gated latent-attention decoder (``models.lm.score``
with ``gdn`` and ``mla`` mixers, zero-centred sandwich norms, an output gate on
latent attention, YaRN and clipped gated units) against its plain reference,
at sizes a CPU walks in seconds: the (gdn, mla, gdn, gdn, gdn) x (dense, moe x
4) model on packed rows against each document alone, once where the clip never
fires and once at a scale where it does; the probed head's recurrence walked
again; a state carried across a boundary; the delta-net layer that takes the
kernel; what reaches the recurrence (no copy of q or k to the value heads, no
decay spread over the channels); the sixteen shares of 64 experts against the
uncut layer. The rule itself under one decay a token is
tests/test_delta_rule.py's; Solar's kernel left as it was, tests/test_mla_lm.py's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import gigachat35 as ref
from benchmark.models.solar_open2 import ref_delta_rule
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.models import linear_attn, lm

from test_pattern_lm import (SAMPLE_AT, documents_of, flat, held_experts, init_params,
                             packed_rows as older_rows, reference_weights, score)

#: a configuration with the published names, tiny: published layers 2-6 of a (linear x 3,
#: full) period, the first of them dense; 2 key heads under 4 value heads
CFG = {
    "hidden_size": 32, "num_hidden_layers": 5, "first_layer": 2, "first_k_dense_replace": 1,
    "full_attention_layers": [3, 7], "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
    "num_attention_heads": 4, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "kv_lora_rank": 16, "q_lora_rank": 24, "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "intermediate_size": 48, "n_routed_experts": 16, "n_routed_experts_held": 16, "held_offset": 0,
    "num_experts_per_tok": 4, "moe_intermediate_size": 16, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "swiglu_limit": 10, "rms_norm_eps": 1e-6, "vocab_size": 64,
}
L = 48
#: the published clip, which seeded weights behind a norm never reach, and one they do
LIMITS = (10, 0.25)


def program_cfg(cfg=CFG, dtype=jnp.float32, **cut):
    cut = {"attn_block": 16, "kda_chunk": 8, "expert_tile": 8, "head_block": 32, **cut}
    plan, yarn = ref.layer_plan(cfg), cfg["rope_scaling"]
    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_pattern=tuple(mixer for mixer, _ in plan), ffn_pattern=tuple(ffn for _, ffn in plan),
        kda_heads=cfg["linear_num_value_heads"], gdn_key_heads=cfg["linear_num_key_heads"],
        kda_head_dim=cfg["linear_key_head_dim"], conv_taps=cfg["linear_conv_kernel_dim"],
        n_heads=cfg["num_attention_heads"], qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"],
        q_rank=cfg["q_lora_rank"], attn_gate=True, rope_theta=float(cfg["rope_theta"]),
        rope_scaling=(float(yarn["factor"]), float(yarn["original_max_position_embeddings"]),
                      float(yarn["beta_fast"]), float(yarn["beta_slow"])),
        d_dense=cfg["intermediate_size"], n_experts=cfg["n_routed_experts"],
        experts_held=cfg["n_routed_experts_held"], held_offset=cfg["held_offset"],
        top_k=cfg["num_experts_per_tok"], d_expert=cfg["moe_intermediate_size"],
        n_shared=cfg["n_shared_experts"], routed_scale=cfg["routed_scaling_factor"], router_bias=True,
        norm_eps=cfg["rms_norm_eps"], centred_norms=True, branch_norms=True,
        swiglu_limit=float(cfg["swiglu_limit"]), max_len=L, dtype=dtype, **cut)


def packed_rows():
    return older_rows()[0]


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.PRNGKey(3), program_cfg())
    for layer in p["layers"]:
        if "router_bias" in layer:  # a bias large enough to change who is chosen
            layer["router_bias"] = layer["router_bias"] * 4.0
    return p


@pytest.fixture(scope="module", params=LIMITS)
def scored(request, params):
    cfg = {**CFG, "swiglu_limit": request.param}
    batch = packed_rows()
    out = score(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, program_cfg(cfg), jnp.int32(3))
    return cfg, batch, jax.tree.map(np.asarray, out)


def test_the_parameters_are_the_models(params):
    cfg = program_cfg()
    assert cfg.layer_pattern == ("gdn", "mla", "gdn", "gdn", "gdn")
    assert lm.ffn_kinds(cfg) == ("dense", "moe", "moe", "moe", "moe")
    first, second = params["layers"][:2]
    assert set(first) == {"attn_norm", "wq", "wk", "wv", "wz", "conv_q", "conv_k", "conv_v", "w_a",
                          "dt_bias", "a_log", "w_beta", "o_norm", "wo", "post_attn_norm", "ffn_norm",
                          "dense", "post_ffn_norm"}
    assert set(second) == {"attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wg", "wo",
                           "post_attn_norm", "moe_norm", "router", "router_bias", "w_gate", "w_up",
                           "w_down", "shared", "post_ffn_norm"}
    assert first["wq"].shape == first["wk"].shape == (32, 16)      # 2 key heads of 8
    assert first["wv"].shape == first["wz"].shape == (32, 32)      # under 4 value heads
    assert first["w_a"].shape == first["w_beta"].shape == (32, 4)  # one decay, one beta a head
    assert first["conv_q"].shape == (4, 16) and first["conv_v"].shape == (4, 32)
    assert second["wg"].shape == (32, 32)                          # a gate a head and channel
    # zero-centred gains are drawn about 0, a head's own norm is a plain gain of 1
    assert abs(float(first["attn_norm"].mean())) < 0.2 and float(first["o_norm"].min()) == 1.0
    with pytest.raises(ValueError, match="has to divide"):
        lm.pattern_param_shapes(lm.PatternLMConfig(layer_pattern=("gdn",), gdn_key_heads=3))


def test_a_packed_row_scores_each_document_as_the_reference_scores_it_alone(params, scored):
    cfg, batch, out = scored
    docs = documents_of(batch)
    at = [[int(p) - start for p in np.asarray(SAMPLE_AT)[r]
           if start <= p < start + len(doc) - 1] for r, start, doc in docs]
    weights = reference_weights(params)
    want = ref.reference_score(cfg, [d for _, _, d in docs], weights, at, probe_head=3)
    covered, seen = np.zeros_like(out["logprob"], bool), 0
    scan, routed = out["probes"]["scan"], out["probes"]["router"]
    for (r, start, doc), logp, places, logits, w_scan, w_routed in zip(
            docs, want["logprob"], at, want["logits"], want["scan"], want["router"]):
        n = len(doc) - 1
        np.testing.assert_allclose(out["logprob"][r, start:start + n], logp, atol=2e-4)
        covered[r, start:start + n] = True
        inside = [list(np.asarray(SAMPLE_AT)[r]).index(p + start) for p in places]
        np.testing.assert_allclose(out["logits"][r, inside], logits, atol=3e-4)
        seen += len(places)
        # value head 3 of the first delta-net layer reads key head 1: what the rule was given and gave
        for name in ("q", "k", "v", "log_decay", "beta", "o"):
            np.testing.assert_allclose(scan[name][r, start:start + n], w_scan[name], atol=2e-5)
        np.testing.assert_allclose(routed["u"][:, r, inside], w_routed["u"], atol=2e-4)
        assert (routed["experts"][:, r, inside] == w_routed["experts"]).all()
    assert (out["logprob"][~covered] == 0).all() and covered.sum() > 80 and seen >= 6
    assert scan["log_decay"].shape == scan["beta"].shape == (2, L) and scan["q"].shape == (2, L, 8)
    assert out["visits"].shape == (4, 16) and out["dropped"].sum() == 0
    real = int((batch["segment_ids"][:, :-1] != 0).sum())
    assert (out["visits"].sum(axis=1) == real * CFG["num_experts_per_tok"]).all()
    # and the clip is at work exactly where it is meant to be
    unclipped = ref.reference_score({**cfg, "swiglu_limit": 1e9}, [d for _, _, d in docs], weights)
    moved = max(float(np.abs(a - b).max()) for a, b in zip(want["logprob"], unclipped["logprob"]))
    assert (moved > 1e-2) == (cfg["swiglu_limit"] < 1), moved
    # off a TPU no layer takes the kernel; two value heads read a key head
    assert METRICS.gauge_value("gdn.fused_layers") == 0 and METRICS.gauge_value("gdn.key_group") == 2


#: what each departure of the reference moves when it is planted: every one is seen
DEPARTURES = [dict(lower={"per_key_head_off": True}), dict(lower={"decay_per_channel": True}),
              dict(lower={"beta_times_2": True}), dict(lower={"no_attn_gate": True}),
              dict(lower={"no_yarn": True}), dict(lower={"no_branch_norms": True}),
              dict(lower={"plain_norm_gain": True}), dict(lower={"state_dtype": jnp.bfloat16}),
              dict(carry_state=True)]


@pytest.fixture(scope="module")
def sound(params):
    docs = [d for r, _, d in documents_of(packed_rows()) if r == 0]
    assert len(docs) >= 2
    return docs, ref.reference_score(CFG, docs, reference_weights(params))["logprob"]


@pytest.mark.parametrize("departure", DEPARTURES, ids=lambda d: next(iter(d.get("lower", d))))
def test_a_departure_of_the_reference_is_seen(params, sound, departure):
    """Each control's planted fault moves a row's documents (a state carried
    over, all but the first, which has no past; a bfloat16 state by less)."""
    docs, want = sound
    got = ref.reference_score(CFG, docs, reference_weights(params), **departure)["logprob"]
    if "carry_state" in departure:
        np.testing.assert_allclose(got[0], want[0], atol=1e-6)
    moved = max(float(np.abs(g - w).max()) for g, w in zip(got[1:], want[1:]))
    assert moved > (1e-4 if "state_dtype" in departure.get("lower", {}) else 5e-3), moved


def test_a_state_carried_across_a_boundary_is_not_what_the_program_gives(params, scored):
    cfg, batch, out = scored
    docs = documents_of(batch)
    row0 = [d for r, _, d in docs if r == 0]
    carried = ref.reference_score(cfg, row0, reference_weights(params), carry_state=True)["logprob"]
    start = next(s for r, s, _ in docs[1:] if r == 0)
    got = out["logprob"][0, start:start + len(row0[1]) - 1]
    assert np.abs(got - carried[1]).max() > 5e-3


def test_the_probed_recurrence_walked_again_is_what_the_layer_gave(scored):
    """One value head's probe: the rule token by token from an empty state over
    the very q, k, v, decay and beta it was given, document by document."""
    _, batch, out = scored
    scan, checked = out["probes"]["scan"], 0
    with jax.default_matmul_precision("highest"):
        for r, start, doc in documents_of(batch):
            n = len(doc) - 1
            if n < 1:
                continue
            q, k, v, b = (jnp.asarray(scan[name][r, start:start + n])[:, None] for name in ("q", "k", "v", "beta"))
            g = jnp.broadcast_to(jnp.asarray(scan["log_decay"][r, start:start + n])[:, None, None], v.shape)
            want, _ = ref_delta_rule(q, k, v, g, b, 8 ** -0.5)
            np.testing.assert_allclose(scan["o"][r, start:start + n], want[:, 0], atol=1e-5)
            checked += n
    assert checked > 80


def test_what_reaches_the_recurrence_is_what_the_mechanism_has(monkeypatch, params):
    """q and k at their own heads, in the dtype the convolution wrote them, a
    decay and a beta of one number a head and token: nothing copied to the
    value heads, nothing spread over the channels, nothing widened outside."""
    seen = []

    def rule(q, k, v, log_decay, beta, segments, scale, chunk):
        seen.append([(a.shape, a.dtype) for a in (q, k, v, log_decay, beta)])
        return jnp.zeros(v.shape, jnp.float32)

    monkeypatch.setattr(lm._la, "delta_rule_chunked", rule)
    cfg = program_cfg(dtype=jnp.bfloat16)
    layer = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, s[1]), params["layers"][0],
                         lm.pattern_param_shapes(cfg)["layers"][0])
    x = jax.ShapeDtypeStruct((2, L, 32), jnp.bfloat16)
    jax.eval_shape(lambda p, x: lm.gdn_mixer(p, x, jnp.ones((2, L), jnp.int32), cfg, jnp.int32(1)), layer, x)
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert seen == [[((2, 2, L, 8), bf16), ((2, 2, L, 8), bf16), ((2, 4, L, 8), bf16),
                     ((2, 4, L), f32), ((2, 4, L), f32)]]


def test_a_delta_net_layer_that_takes_the_kernel_is_the_layer_and_is_counted(monkeypatch):
    """``gdn_mixer`` at the kernel's width with the dispatch answering as it
    would on a TPU and Pallas interpreting: the model the plain form gives, two
    value heads reading one key head in a grid step, and ``score`` counts the
    pattern's delta-net layers (two here) as fused."""
    from jax.experimental.pallas import tpu as pltpu

    wide = {**CFG, "num_hidden_layers": 3, "first_layer": 2, "linear_num_key_heads": 1,
            "linear_num_value_heads": 2, "linear_key_head_dim": 128}
    cfg = lm.PatternLMConfig(**{**program_cfg(wide, kda_chunk=64, attn_block=32).__dict__, "max_len": 128})
    assert cfg.layer_pattern == ("gdn", "mla", "gdn")
    params = init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(6)
    tokens = jnp.asarray(rng.integers(1, 64, (1, 129)), jnp.int32)
    segs = jnp.asarray([[1] * 50 + [2] * 70 + [0] * 9], jnp.int32)
    at = jnp.zeros((1, 1), jnp.int32)

    def traced_anew():  # the dispatch and the gauge are read as a program is traced
        return jax.jit(lambda *a: lm.score(*a, cfg))(params, tokens, segs, at)["logprob"]

    plain = traced_anew()
    assert METRICS.gauge_value("gdn.fused_layers") == 0
    monkeypatch.setattr(linear_attn, "fused_tile",
                        lambda shape, chunk, key_width=0: 128 if shape[-1] == 128 and chunk == 64 else None)
    with pltpu.force_tpu_interpret_mode():
        fused = traced_anew()
    assert METRICS.gauge_value("gdn.fused_layers") == 2 and METRICS.gauge_value("gdn.key_group") == 2
    np.testing.assert_allclose(fused, plain, atol=2e-5)
    assert np.abs(np.asarray(plain)).max() > 1


@pytest.mark.parametrize("limit", LIMITS)
def test_the_sixteen_shares_of_64_experts_add_up_to_the_uncut_layer(limit):
    """Sixteen chips of 4 experts each under the biased router (8 of 64, gates
    x 2.5), every unit clipped, the shared expert counted once, against the
    reference told that it holds all 64: ``gigachat35_ep16``'s cut, a
    sixteenth of the experts (the form that adds a tile's rows to their tokens)."""
    cfg = {**CFG, "n_routed_experts": 64, "n_routed_experts_held": 64, "num_experts_per_tok": 8,
           "num_hidden_layers": 2, "swiglu_limit": limit}
    p = init_params(jax.random.PRNGKey(1), program_cfg(cfg))["layers"][1]
    p["router_bias"] = p["router_bias"] * 4.0
    x = jnp.asarray(np.random.default_rng(1).standard_normal((96, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = ref.ref_moe_clipped(flat(p), x, cfg)
        loose, _, _ = ref.ref_moe_clipped(flat(p), x, {**cfg, "swiglu_limit": 1e9})
        shared = ref.ref_clipped_ffn(x, *(jnp.asarray(p["shared"][k]) for k in ("w_gate", "w_up", "w_down")),
                                     float(limit))
    total, visits = -15 * shared, 0
    for first in range(0, 64, 4):
        share = {**p, **{k: p[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}}
        y, n, dropped, _ = held_experts(share, x, held_offset=first, top_k=8, routed_scale=2.5, tile=8,
                                        limit=float(limit))
        total, visits = total + y, visits + int(n.sum())
        assert int(dropped) == 0
    assert visits == x.shape[0] * 8
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert (float(np.abs(np.asarray(whole) - np.asarray(loose)).max()) > 1e-2) == (limit < 1)


def test_the_compiled_program_holds_every_scope(params):
    import re

    from tpu_tfrecord import tracing

    batch = packed_rows()
    compiled = score.lower(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, program_cfg(),
                           jnp.int32(3)).compile()
    op_names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    held = {tok for name in op_names for tok in re.findall(r"tfr\.\w+", name)}
    assert held == {"tfr.embed", "tfr.gdn_proj", "tfr.gdn_conv", "tfr.gdn_scan", "tfr.mla_proj",
                    "tfr.mla_attn", "tfr.dense_ffn", "tfr.moe_route", "tfr.moe_experts", "tfr.moe_shared",
                    "tfr.lm_head"}
    assert held <= set(tracing.ANNOTATIONS)
