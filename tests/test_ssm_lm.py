"""The state-space decoder (``models.lm.score`` with ``ssm`` and ``gqa``
mixers and squared-ReLU experts, every layer ONE branch) against its plain
reference, at sizes a CPU walks in seconds: the chunked recurrence, plain and
as the interpreted kernel, against the token-by-token one on packed rows; the
(M E M E M * E M E M E M *) model on packed rows against each document alone;
the probed head walked again; a state and taps carried across a boundary; what
reaches the recurrence; the layer that takes the kernel; the two shares of 16
experts against the uncut layer; which patterns are refused. The older
patterns' programs left as they were is tests/test_mla_lm.py's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import nemotron_h as ref
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.models import linear_attn, lm

from test_pattern_lm import (SAMPLE_AT, documents_of, flat, held_experts, init_params, packed_rows,
                             reference_weights, score)

#: a configuration with the published names, tiny: the cell's own thirteen letters
CFG = {
    "hidden_size": 32, "num_hidden_layers": 13, "first_layer": 0,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*", "mamba_num_heads": 4, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 8, "n_routed_experts": 16, "n_routed_experts_held": 16,
    "held_offset": 0, "num_experts_per_tok": 4, "moe_intermediate_size": 16,
    "moe_shared_expert_intermediate_size": 32, "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "layer_norm_epsilon": 1e-5, "vocab_size": 64, "time_step_min": 0.001, "time_step_max": 0.1,
}
L = 48

recurrent = jax.jit(linear_attn.ssm_recurrent, static_argnames=("heads", "groups", "state"))
chunked = jax.jit(linear_attn.ssm_chunked, static_argnames=("heads", "groups", "state", "chunk"))
interpreted_kernel = functools.partial(linear_attn._ssm_fused, interpret=True)


def program_cfg(cfg=CFG, dtype=jnp.float32, **cut):
    cut = {"attn_block": 16, "kda_chunk": 8, "expert_tile": 8, "head_block": 32, **cut}
    return lm.PatternLMConfig(**{**ref.program(cfg, {"row_tokens": L}).__dict__, "dtype": dtype, **cut})


def recurrence_inputs(seed, length, heads, groups, p, state, b=2, rates=(1e-3, 1.0), dtype=jnp.float32,
                      longest=40):
    """xbc, dt, log_decay, segments of packed rows: documents of 3 to ``longest``
    tokens (most shorter than a chunk, boundaries wherever they fall), steps
    log-uniform in 0.001-0.1, decays of ``exp(-rate)`` a token with the rate
    log-uniform in ``rates``."""
    r = np.random.default_rng(seed)
    xbc = jnp.asarray(r.standard_normal((b, length, heads * p + 2 * groups * state)), dtype)
    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (b, length, heads))), jnp.float32)
    rate = jnp.asarray(np.exp(r.uniform(*np.log(rates), (b, length, heads))), jnp.float32)
    segs = np.zeros((b, length), np.int32)
    for row in range(b):
        at, nth = 0, 1
        while at < length - 4:
            n = int(r.integers(3, longest))
            segs[row, at:at + n], at, nth = nth, at + n, nth + 1
    return xbc, dt, -rate, jnp.asarray(segs)


@pytest.mark.parametrize("rates", [(1e-3, 1.0), (1e-6, 1e-5), (2.0, 8.0)],
                         ids=["as_drawn", "decays_near_1", "decays_near_0"])
@pytest.mark.parametrize("heads,groups", [(8, 1), (4, 4)], ids=["8_heads_a_group", "1_head_a_group"])
@pytest.mark.parametrize("chunk,length", [(16, 70), (64, 150), (128, 128)])
def test_the_chunked_recurrence_is_the_token_by_token_one(chunk, length, heads, groups, rates):
    args = recurrence_inputs(chunk + length, length, heads, groups, 8, 16, rates=rates)
    geometry = dict(heads=heads, groups=groups, state=16)
    with jax.default_matmul_precision("highest"):
        want = recurrent(*args, **geometry)
        got = chunked(*args, **geometry, chunk=chunk)
    assert got.shape == want.shape == (2, length, heads * 8) and float(jnp.abs(want).max()) > 1e-3
    np.testing.assert_allclose(got, want, atol=2e-5 * max(1.0, float(jnp.abs(want).max())))


@pytest.mark.parametrize("rates", [(1e-3, 1.0), (1e-6, 1e-5), (2.0, 8.0)],
                         ids=["as_drawn", "decays_near_1", "decays_near_0"])
@pytest.mark.parametrize("length,tile,heads,groups,longest", [
    (128, 128, 8, 1, 40), (256, 128, 16, 2, 40), (256, 256, 16, 1, 300), (512, 256, 8, 1, 200)])
def test_the_kernel_is_the_token_by_token_recurrence_and_the_plain_form(length, tile, heads, groups, longest,
                                                                         rates):
    """The Pallas kernel a TPU runs at the cell's geometry (heads of 64 over a
    state of 128, 8 or 16 heads a group, bfloat16 operands), interpreted:
    documents shorter than a chunk, boundaries inside chunks and on their
    edges, one document across tiles."""
    args = recurrence_inputs(length + tile, length, heads, groups, 64, 128, b=1, rates=rates,
                             dtype=jnp.bfloat16, longest=longest)
    geometry = dict(heads=heads, groups=groups, state=128)
    with jax.default_matmul_precision("highest"):
        want = recurrent(*args, **geometry)
        plain = chunked(*args, **geometry, chunk=128)
    got = interpreted_kernel(*args, **geometry, tile=tile)
    scale = max(1.0, float(jnp.abs(want).max()))
    np.testing.assert_allclose(got, want, atol=2e-5 * scale)
    np.testing.assert_allclose(got, plain, atol=2e-5 * scale)


def test_which_shapes_take_the_kernel(monkeypatch):
    monkeypatch.setattr(linear_attn.jax, "default_backend", lambda: "tpu")
    cell, bf16 = (2, 8192, 64 * 64 + 2 * 8 * 128), jnp.bfloat16
    assert linear_attn.ssm_tile(cell, bf16, 64, 8, 128, 128) == 256
    assert linear_attn.ssm_tile((2, 128 * 3, cell[2]), bf16, 64, 8, 128, 128) == 128
    assert linear_attn.ssm_tile(cell, jnp.float32, 64, 8, 128, 128) is None      # operands not exact in bfloat16
    assert linear_attn.ssm_tile(cell, bf16, 64, 8, 128, 64) is None              # another chunk
    assert linear_attn.ssm_tile((2, 8200, cell[2]), bf16, 64, 8, 128, 128) is None  # not whole chunks
    assert linear_attn.ssm_tile((2, 8192, 64 * 64 + 2 * 64 * 128), bf16, 64, 64, 128, 128) is None  # 1 head a group
    assert linear_attn.ssm_tile((2, 8192, 64 * 64 + 2 * 8 * 64), bf16, 64, 8, 64, 128) is None   # half a lane block
    monkeypatch.undo()
    assert linear_attn.ssm_tile(cell, bf16, 64, 8, 128, 128) is None             # off a TPU


def test_taps_with_a_bias_stop_at_a_boundary():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((1, 1, 20, 6)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((4, 1, 6)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((1, 6)), jnp.float32)
    segs = jnp.asarray([[1] * 7 + [2] * 9 + [0] * 4], jnp.int32)
    got = linear_attn.short_conv(x, taps, segs, bias)[0, 0]
    for a, z in ((0, 7), (7, 16)):
        np.testing.assert_allclose(got[a:z], ref.ref_conv(x[0, 0, a:z], taps[:, 0]) + bias, atol=1e-6)
    np.testing.assert_allclose(linear_attn.short_conv(x, taps, segs)[0, 0], got - bias, atol=1e-6)


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.PRNGKey(3), program_cfg())
    for layer in p["layers"]:
        if "router_bias" in layer:  # a bias large enough to change who is chosen
            layer["router_bias"] = layer["router_bias"] * 4.0
        if "a_log" in layer:        # A in 1-16 as the cell draws it, a skip that is not 1
            layer["a_log"] = jnp.log(jnp.linspace(1.0, 16.0, layer["a_log"].shape[0]))
            layer["d_skip"] = jnp.linspace(0.5, 1.5, layer["d_skip"].shape[0])
    return p


@pytest.fixture(scope="module")
def scored(params):
    batch = packed_rows()[0]
    out = score(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, program_cfg(), jnp.int32(3))
    return batch, jax.tree.map(np.asarray, out)


def test_the_parameters_are_the_models(params):
    cfg = program_cfg()
    assert cfg.layer_pattern == ("ssm", "none", "ssm", "none", "ssm", "gqa", "none", "ssm", "none", "ssm",
                                 "none", "ssm", "gqa")
    assert lm.ffn_kinds(cfg) == tuple("moe" if kind == "none" else "none" for kind in cfg.layer_pattern)
    first, second, softmax = params["layers"][0], params["layers"][1], params["layers"][5]
    assert set(first) == {"attn_norm", "w_in", "conv_x", "conv_bias", "a_log", "d_skip", "dt_bias", "o_norm",
                          "wo"}
    assert set(second) == {"moe_norm", "router", "router_bias", "w_up", "w_down", "shared"}   # no mixer, no gate
    assert set(second["shared"]) == {"w_up", "w_down"}
    assert set(softmax) == {"attn_norm", "wq", "wk", "wv", "wo"}                            # no wg
    assert first["w_in"].shape == (32, 32 + (32 + 2 * 32) + 4)     # [z | x B C | dt]
    assert first["conv_x"].shape == (4, 96) and first["conv_bias"].shape == (96,)
    assert first["o_norm"].shape == (32,) and first["wo"].shape == (32, 32)
    assert second["w_up"].shape == (16, 32, 16) and second["shared"]["w_up"].shape == (32, 32)
    assert lm.MIXERS == ("gqa", "kda", "mla", "swa", "gdn", "ssm", "bda")


@pytest.mark.parametrize("patterns,refused", [
    ((("ssm", "none"), ("none", "none")), "a mixer, a feed-forward part or both"),   # a layer that is no layer
    ((("ssm", "gqa"), ("moe",)), "each of the 2 layers"),
    ((("ssm",), ("none",)), None), ((("none",), ("moe",)), None), ((("ssm",), ("dense",)), None),
    ((("none", "gqa"), ("dense", "moe")), None)])
def test_none_in_both_places_of_a_layer_is_refused_and_in_one_or_neither_runs(patterns, refused):
    cfg = lm.PatternLMConfig(layer_pattern=patterns[0], ffn_pattern=patterns[1])
    if refused:
        with pytest.raises(ValueError, match=refused):
            lm.pattern_param_shapes(cfg)
        return
    shapes = lm.pattern_param_shapes(cfg)["layers"]
    for mixer, ffn, layer in zip(*patterns, shapes):
        assert ("attn_norm" in layer) == (mixer != "none")
        assert ("router" in layer) == (ffn == "moe") and ("dense" in layer) == (ffn == "dense")
    with pytest.raises(ValueError, match="ssm_groups"):
        lm.pattern_param_shapes(lm.PatternLMConfig(layer_pattern=("ssm",), ssm_groups=3))
    with pytest.raises(ValueError, match="expert_unit"):
        lm.pattern_param_shapes(lm.PatternLMConfig(expert_unit="relu"))


def test_a_packed_row_scores_each_document_as_the_reference_scores_it_alone(params, scored):
    batch, out = scored
    docs = documents_of(batch)
    at = [[int(p) - start for p in np.asarray(SAMPLE_AT)[r]
           if start <= p < start + len(doc) - 1] for r, start, doc in docs]
    want = ref.reference_score(CFG, [d for _, _, d in docs], reference_weights(params), at, probe_head=3)
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
        # head 3 of the first state-space layer reads group 1: what the recurrence was given and gave
        for name in ("x", "b", "c", "dt", "log_decay", "o"):
            np.testing.assert_allclose(scan[name][r, start:start + n], w_scan[name], atol=2e-5)
        np.testing.assert_allclose(routed["u"][:, r, inside], w_routed["u"], atol=2e-4)
        assert (routed["experts"][:, r, inside] == w_routed["experts"]).all()
    assert (out["logprob"][~covered] == 0).all() and covered.sum() > 80 and seen >= 6
    assert scan["dt"].shape == scan["log_decay"].shape == (2, L)
    assert scan["x"].shape == scan["o"].shape == (2, L, 8) and scan["b"].shape == scan["c"].shape == (2, L, 16)
    assert out["visits"].shape == (5, 16) and out["dropped"].sum() == 0     # five expert layers of thirteen
    real = int((batch["segment_ids"][:, :-1] != 0).sum())
    assert (out["visits"].sum(axis=1) == real * CFG["num_experts_per_tok"]).all()
    # off a TPU no layer takes the kernel; two heads read a group's B and C
    assert METRICS.gauge_value("ssm.fused_layers") == 0 and METRICS.gauge_value("ssm.group") == 2


#: what each departure of the reference moves when it is planted: every one is seen
DEPARTURES = [dict(lower={"group_off": True}), dict(lower={"no_conv_bias": True}), dict(lower={"no_skip": True}),
              dict(lower={"norm_before_gate": True}), dict(lower={"no_dt_bias": True}),
              dict(lower={"relu_not_squared": True}), dict(lower={"attn_gate_on": True}),
              dict(lower={"state_dtype": jnp.bfloat16}), dict(carry="state"), dict(carry="taps")]


@pytest.fixture(scope="module")
def sound(params):
    docs = [d for r, _, d in documents_of(packed_rows()[0]) if r == 0]
    assert len(docs) >= 2
    return docs, ref.reference_score(CFG, docs, reference_weights(params))["logprob"]


@pytest.mark.parametrize("departure", DEPARTURES, ids=lambda d: str(next(iter(d.get("lower", d.values())))))
def test_a_departure_of_the_reference_is_seen(params, sound, departure):
    """Each control's planted fault moves a row's documents (a state or taps
    carried over, all but the first, which has no past; a bfloat16 state by less)."""
    docs, want = sound
    got = ref.reference_score(CFG, docs, reference_weights(params), **departure)["logprob"]
    if "carry" in departure:
        np.testing.assert_allclose(got[0], want[0], atol=1e-6)
    moved = max(float(np.abs(g - w).max()) for g, w in zip(got[1:], want[1:]))
    assert moved > (1e-5 if "state_dtype" in departure.get("lower", {}) else 2e-3), moved


@pytest.mark.parametrize("carry", ["state", "taps"])
def test_what_is_carried_across_a_boundary_is_not_what_the_program_gives(params, scored, carry):
    batch, out = scored
    docs = documents_of(batch)
    row0 = [d for r, _, d in docs if r == 0]
    carried = ref.reference_score(CFG, row0, reference_weights(params), carry=carry)["logprob"]
    start = next(s for r, s, _ in docs[1:] if r == 0)
    got = out["logprob"][0, start:start + len(row0[1]) - 1]
    assert np.abs(got - carried[1]).max() > 2e-3


def test_the_probed_recurrence_walked_again_is_what_the_layer_gave(scored):
    """One head's probe: the recurrence token by token in float64 from an
    empty state over the very x, B, C, step and decay it was given."""
    batch, out = scored
    scan, checked = out["probes"]["scan"], 0
    for r, start, doc in documents_of(batch):
        n = len(doc) - 1
        if n < 1:
            continue
        cut = {name: scan[name][r, start:start + n] for name in scan}
        want = ref.walk_head(*(cut[name] for name in ("x", "b", "c", "dt", "log_decay")))
        np.testing.assert_allclose(cut["o"], want, atol=1e-5)
        checked += n
    assert checked > 80
    numbers = ref.probe_numbers({**CFG, "hybrid_override_pattern": "M" * 13}, 0, [
        {name: scan[name][0, :20] for name in scan}], [])
    assert 0 < numbers["scan_state_gap"] < 1e-5


def test_what_reaches_the_recurrence_is_what_the_mechanism_has(monkeypatch, params):
    """ONE array, the convolution's output in the dtype it was written: x at
    its heads, B and C at their GROUPS; a step and a decay of one number a head
    and token: nothing copied to the heads, nothing widened outside."""
    seen = []

    def rule(xbc, dt, log_decay, segments, heads, groups, state, chunk):
        seen.append([(a.shape, a.dtype) for a in (xbc, dt, log_decay)] + [(heads, groups, state, chunk)])
        return jnp.zeros(xbc.shape[:2] + (heads * 8,), jnp.float32)

    monkeypatch.setattr(lm._la, "ssm_chunked", rule)
    cfg = program_cfg(dtype=jnp.bfloat16)
    layer = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, s[1]), params["layers"][0],
                         lm.pattern_param_shapes(cfg)["layers"][0])
    x = jax.ShapeDtypeStruct((2, L, 32), jnp.bfloat16)
    jax.eval_shape(lambda p, x: lm.ssm_mixer(p, x, jnp.ones((2, L), jnp.int32), cfg, jnp.int32(1)), layer, x)
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert seen == [[((2, L, 32 + 2 * 32), bf16), ((2, L, 4), f32), ((2, L, 4), f32), (4, 2, 16, 8)]]


def test_a_state_space_layer_that_takes_the_kernel_is_the_layer_and_is_counted(monkeypatch):
    """``ssm_mixer`` at the kernel's geometry with the dispatch answering as it
    would on a TPU and Pallas interpreting: the model the plain form gives, and
    ``score`` counts the pattern's state-space layers (two here) as fused."""
    wide = {**CFG, "hybrid_override_pattern": "MEM", "num_hidden_layers": 3, "mamba_num_heads": 8,
            "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 1}
    cfg = lm.PatternLMConfig(**{**program_cfg(wide, jnp.bfloat16, kda_chunk=128, attn_block=32).__dict__,
                                "max_len": 256})
    params = init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(6)
    tokens = jnp.asarray(rng.integers(1, 64, (1, 257)), jnp.int32)
    segs = jnp.asarray([[1] * 50 + [2] * 140 + [3] * 57 + [0] * 10], jnp.int32)
    at = jnp.zeros((1, 1), jnp.int32)

    def traced_anew():  # the dispatch and the gauge are read as a program is traced
        return jax.jit(lambda *a: lm.score(*a, cfg))(params, tokens, segs, at)["logprob"]

    plain = traced_anew()
    assert METRICS.gauge_value("ssm.fused_layers") == 0
    monkeypatch.setattr(linear_attn.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(lm, "_takes_kernel", lambda l, dv, block: False)   # the softmax kernel is not this test's
    monkeypatch.setattr(linear_attn, "_ssm_fused", functools.partial(linear_attn._ssm_fused, interpret=True))
    fused = traced_anew()
    assert METRICS.gauge_value("ssm.fused_layers") == 2 and METRICS.gauge_value("ssm.group") == 8
    # the same bfloat16 program around two forms of one recurrence: a rounding apart, here and there
    apart = np.abs(np.asarray(fused) - np.asarray(plain))
    assert np.median(apart) < 2e-3 and apart.max() < 0.1 and np.abs(np.asarray(plain)).max() > 1


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_of_16_squared_relu_experts_add_up_to_the_uncut_layer(shares):
    """Two chips of 8 experts each (``nemotron_twotower_ep2``'s cut: half the
    experts, the form that reads a buffer back) and four of 4, under the biased
    router (4 of 16, gates x 2.5), every unit ``relu(.)^2`` of two matrices, the
    shared expert counted once, against the reference told that it holds all 16."""
    p = init_params(jax.random.PRNGKey(1), program_cfg({**CFG, "num_hidden_layers": 2}))["layers"][1]
    p["router_bias"] = p["router_bias"] * 4.0
    x = jnp.asarray(np.random.default_rng(1).standard_normal((96, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _, _ = ref.ref_moe_unit(flat(p), x, CFG)
        shared = ref.ref_unit(x, jnp.asarray(p["shared"]["w_up"]), jnp.asarray(p["shared"]["w_down"]))
        gated, _, _ = ref.ref_moe_unit({**flat(p), "w_gate": flat(p)["w_up"], "shared.w_gate": flat(p)["shared.w_up"]},
                                       x, CFG)
    total, visits, held = -(shares - 1) * shared, 0, 16 // shares
    for first in range(0, 16, held):
        share = {**p, **{k: p[k][first:first + held] for k in ("w_up", "w_down")}}
        y, n, dropped, _ = held_experts(share, x, held_offset=first, top_k=4, routed_scale=2.5, tile=8)
        total, visits = total + y, visits + int(n.sum())
        assert int(dropped) == 0
    assert visits == x.shape[0] * 4
    np.testing.assert_allclose(total, whole, atol=5e-5)
    assert float(np.abs(np.asarray(whole) - np.asarray(gated)).max()) > 1e-2   # a gated unit is another layer


def test_the_compiled_program_holds_every_scope(params):
    import re

    from tpu_tfrecord import tracing

    batch = packed_rows()[0]
    compiled = score.lower(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, program_cfg(),
                           jnp.int32(3)).compile()
    op_names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    held = {tok for name in op_names for tok in re.findall(r"tfr\.\w+", name)}
    assert held == {"tfr.embed", "tfr.ssm_proj", "tfr.ssm_conv", "tfr.ssm_scan", "tfr.gqa", "tfr.moe_route",
                    "tfr.moe_experts", "tfr.moe_shared", "tfr.lm_head"}
    assert held <= set(tracing.ANNOTATIONS)
