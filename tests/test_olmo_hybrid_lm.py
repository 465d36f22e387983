"""The dense hybrid decoder (``models.lm.score`` with ``gdn`` layers whose state
is NOT square, position-free ``gqa`` layers under a Q/K norm over the whole
projection, branches normed on their way out alone, no expert anywhere) against
its plain reference, ``benchmark.models.olmo_hybrid``, at sizes a CPU walks in
seconds: the delta rule at ``d_k != d_v`` in every form (the chunked plain form
and the interpreted kernel against the token-by-token rule, both forms of the
decay, beta up to 2 on documents that repeat one token, a document mid-row
against the same document alone); the (gdn x 3, gqa) model on packed rows
against each document alone, within tolerances that a bfloat16 state or the
other reading of any ``assumed`` item fails; a pattern without experts through
``score``; the layer that takes the kernel at 96 under 192 and what ``score``
says of it. The square programs left as they were: tests/test_mla_lm.py's and
tests/test_gdn_lm.py's hashes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import olmo_hybrid as ref
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.models import linear_attn, lm

from test_delta_rule import bits, from_projections, interpreted_kernel as interpreted, plainly_prepared, recurrent
from test_pattern_lm import SAMPLE_AT, documents_of, init_params, packed_rows, reference_weights, score

#: a configuration with the published names, tiny: one period, keys of 8 under values of 16
CFG = {
    "hidden_size": 32, "num_hidden_layers": 4, "first_layer": 0, "intermediate_size": 48,
    "layer_types": ["linear_attention", "linear_attention", "linear_attention", "full_attention"] * 2,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rms_norm_eps": 1e-6, "vocab_size": 64,
}
L = 48
TOL = 2e-4   # the float32 program against the float32 reference, in nats


def program_cfg(cfg=CFG, dtype=jnp.float32, **cut):
    cut = {"attn_block": 16, "kda_chunk": 8, "head_block": 32, **cut}
    return lm.PatternLMConfig(**{**ref.program({**cfg, "program": cut}, {"row_tokens": L}).__dict__, "dtype": dtype})


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.PRNGKey(3), program_cfg())
    rng = np.random.default_rng(4)
    for layer in p["layers"]:  # gains that are not all 1: a norm read another way has to show
        for name in ("q_norm", "k_norm", "post_attn_norm", "post_ffn_norm", "o_norm"):
            if name in layer:
                layer[name] = jnp.asarray(1.0 + 0.3 * rng.standard_normal(layer[name].shape), jnp.float32)
    return p


@pytest.fixture(scope="module")
def scored(params):
    batch, _ = packed_rows()
    out = score(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, program_cfg(), jnp.int32(3))
    return batch, jax.tree.map(np.asarray, out)


# ---------------------------------------------------------------------------
# The delta rule at d_k != d_v
# ---------------------------------------------------------------------------

plain = jax.jit(linear_attn._delta_rule_plain, static_argnums=(6, 7))


def wide_inputs(seed, length, dk, dv, b=2, h=4, hk=4, scalar=True, dtype=jnp.float32, repeat=False):
    """q, k unit [b, hk, l, dk], v [b, h, l, dv], a decay of either form, beta up
    to 2, and rows of several documents with pads at the end. ``repeat``: every
    document repeats ONE key (the case that cancels worst where beta nears 2)."""
    rng = np.random.default_rng(seed)
    q, k = (rng.standard_normal((b, hk, length, dk)) for _ in range(2))
    if repeat:
        k = np.broadcast_to(k[:, :, :1], k.shape) + 1e-3 * rng.standard_normal(k.shape)
    q, k = (a / np.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    v = rng.standard_normal((b, h, length, dv))
    shape = (b, h, length) if scalar else (b, h, length, dk)
    log_decay = -rng.uniform(0.0, 0.3, shape)
    beta = rng.uniform(1.5 if repeat else 0.0, 2.0, (b, h, length))
    cuts = np.sort(rng.choice(np.arange(1, length - 8), size=3, replace=False))
    segs = np.zeros((b, length), np.int32)
    for r in range(b):
        edges = [0, *(cuts + r), length - 5]
        for i, (a, z) in enumerate(zip(edges[:-1], edges[1:])):
            segs[r, a:z] = i + 1
    as_f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype), jnp.asarray(v, dtype), as_f32(log_decay), as_f32(beta),
            jnp.asarray(segs))


@pytest.mark.parametrize("repeat", [False, True], ids=["random_keys", "one_key_repeated"])
@pytest.mark.parametrize("hk, scalar", [(4, True), (2, True), (4, False)],
                         ids=["a_token_own_keys", "a_token_shared_keys", "a_channel"])
def test_the_chunked_rule_at_keys_of_24_under_values_of_48_is_the_token_by_token_rule(hk, scalar, repeat):
    args = wide_inputs(7 + hk, 80, 24, 48, hk=hk, scalar=scalar, repeat=repeat)
    want = recurrent(*args, scale=24 ** -0.5)
    got = plain(*args, 24 ** -0.5, 16)
    assert got.shape == (2, 4, 80, 48) and float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5 if repeat else 2e-6)
    dispatched = jax.jit(linear_attn.delta_rule_chunked, static_argnames=("scale", "chunk"))(
        *args, scale=24 ** -0.5, chunk=16)
    np.testing.assert_array_equal(dispatched, got)      # off a TPU the dispatch is the plain form


@pytest.mark.parametrize("scalar", [True, False], ids=["a_token", "a_channel"])
def test_the_interpreted_kernel_at_96_under_192_is_the_token_by_token_rule(scalar):
    """One row of the cell's widths through the kernel a TPU runs (its tiles of
    q and k padded to 128 lanes and of v to 256 inside VMEM): the rule token
    by token, the plain chunked form, and zero wherever no document is."""
    args = wide_inputs(3, 256, 96, 192, b=1, h=2, hk=2, scalar=scalar, dtype=jnp.bfloat16)
    want = recurrent(*args, scale=96 ** -0.5)
    got = interpreted(*args, 96 ** -0.5, 128)
    assert got.shape == (1, 2, 256, 192) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=5e-6)
    np.testing.assert_allclose(got, plain(*args, 96 ** -0.5, 64), atol=5e-6)


def test_the_kernel_prepares_projections_of_96_and_192_as_the_plain_preparation_does():
    """The kernel's prologue at widths that fill no whole lane block: taps,
    SiLU, the unit norm over a head's 96 PUBLISHED channels (zero lanes add
    nothing to it) and both roundings equal ``linear_attn.prepared`` bit for
    bit, what it hands back has the published widths, and the recurrence over
    it is the token-by-token rule."""
    _, _, _, log_decay, beta, segs = wide_inputs(5, 256, 96, 192, b=1, h=2, hk=2)
    rng = np.random.default_rng(6)
    q, k, v = (jnp.asarray(1.5 * rng.standard_normal((1, 2, 256, w)), jnp.bfloat16) for w in (96, 96, 192))
    taps = tuple(jnp.asarray(0.5 * rng.standard_normal((4, 2 * w)), jnp.bfloat16) for w in (96, 96, 192))
    o, *handed = from_projections(q, k, v, taps, log_decay, beta, segs, 96 ** -0.5, 128)
    want = plainly_prepared(q, k, v, taps, segs)
    for name, got, prepared in zip("qkv", handed, want):
        assert got.shape == prepared.shape and got.dtype == jnp.bfloat16, name
        assert np.array_equal(bits(got), bits(prepared)), name
    np.testing.assert_allclose(np.linalg.norm(np.asarray(handed[0], np.float32), axis=-1), 1, atol=1e-2)
    np.testing.assert_allclose(o, recurrent(*want, log_decay, beta, segs, scale=96 ** -0.5), atol=5e-6)


def test_a_document_mid_row_is_the_same_document_alone():
    """Neither the state [24 x 48] nor the decay's running sums reach across a
    boundary: the second document of a row, cut out and put at the start of a
    row of its own, gives the outputs it gave mid-row, in both chunked forms."""
    q, k, v, log_decay, beta, segs = wide_inputs(11, 128, 24, 48, b=1)
    a, z = (int(np.flatnonzero(np.asarray(segs[0]) == s)[0]) for s in (2, 3))

    def alone(x):  # the document's tokens first, pads behind them
        return jnp.concatenate([x[:, :, a:z], jnp.zeros_like(x[:, :, : x.shape[2] - (z - a)])], axis=2)

    own = jnp.asarray((np.arange(128) < z - a).astype(np.int32))[None]
    for rule in (lambda *args: plain(*args, 24 ** -0.5, 16), lambda *args: interpreted(*args, 24 ** -0.5, 128)):
        packed = rule(q, k, v, log_decay, beta, segs)[:, :, a:z]
        single = rule(alone(q), alone(k), alone(v), alone(log_decay[..., None])[..., 0], alone(beta[..., None])[..., 0],
                      own)[:, :, : z - a]
        assert packed.shape == (1, 4, z - a, 48) and float(jnp.abs(packed).max()) > 0.1
        np.testing.assert_allclose(packed, single, atol=2e-6)


def test_which_widths_the_kernel_takes(monkeypatch):
    """On a TPU: the cell's 96 under 192, the square widths it always took, and
    nothing else (those run the plain form); ``lane_fill`` is what the padding
    inside VMEM wastes."""
    assert linear_attn.fused_tile((2, 30, 8192, 192), 64, 96) is None        # off a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert linear_attn.fused_tile((2, 30, 8192, 192), 64, 96) == 256
    assert linear_attn.fused_tile((2, 30, 8192, 128), 64) == linear_attn.fused_tile((2, 30, 8192, 128), 64, 128) == 256
    assert linear_attn.fused_tile((1, 2, 384, 256), 64) == 128
    for dk, dv in [(96, 320), (192, 192), (160, 192), (16, 32), (64, 128), (96, 100), (100, 192)]:
        assert linear_attn.fused_tile((2, 30, 8192, dv), 64, dk) is None, (dk, dv)
    assert linear_attn.fused_tile((2, 30, 8192, 192), 16, 96) is None
    assert linear_attn.fused_tile((2, 30, 8192 + 64, 192), 64, 96) is None
    assert linear_attn.lane_fill(96, 192) == 0.75 and linear_attn.lane_fill(128, 128) == 1.0


# ---------------------------------------------------------------------------
# The model against its reference
# ---------------------------------------------------------------------------


def test_the_parameters_are_the_models(params):
    cfg = program_cfg()
    assert cfg.layer_pattern == ("gdn", "gdn", "gdn", "gqa") and lm.ffn_kinds(cfg) == ("dense",) * 4
    first, last = params["layers"][0], params["layers"][3]
    # no norm on a branch's way in: neither attn_norm nor ffn_norm exists
    assert set(first) == {"wq", "wk", "wv", "wz", "conv_q", "conv_k", "conv_v", "w_a", "dt_bias", "a_log",
                          "w_beta", "o_norm", "wo", "post_attn_norm", "dense", "post_ffn_norm"}
    assert set(last) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm", "post_attn_norm", "dense", "post_ffn_norm"}
    assert first["wq"].shape == first["wk"].shape == (32, 32)       # 4 key heads of 8
    assert first["wv"].shape == first["wz"].shape == (32, 64)       # 4 value heads of 16
    assert first["conv_q"].shape == (4, 32) and first["conv_v"].shape == (4, 64)
    assert first["o_norm"].shape == (16,) and first["wo"].shape == (64, 32)
    assert last["q_norm"].shape == last["k_norm"].shape == (32,)    # a weight a channel of the projection
    with pytest.raises(ValueError, match="gdn_gate"):
        lm.pattern_param_shapes(lm.PatternLMConfig(layer_pattern=("gdn",), gdn_gate="tanh"))
    with pytest.raises(ValueError, match="one of the two"):
        lm.pattern_param_shapes(lm.PatternLMConfig(qk_norm=True, qk_norm_whole=True))


def test_a_packed_row_scores_each_document_as_the_reference_scores_it_alone(params, scored):
    batch, out = scored
    docs = documents_of(batch)
    at = [[int(p) - start for p in np.asarray(SAMPLE_AT)[r]
           if start <= p < start + len(doc) - 1] for r, start, doc in docs]
    want = ref.reference_score(CFG, [d for _, _, d in docs], reference_weights(params), at, probe_head=3)
    covered, seen, scan = np.zeros_like(out["logprob"], bool), 0, out["probes"]["scan"]
    for (r, start, doc), logp, places, logits, w_scan in zip(docs, want["logprob"], at, want["logits"], want["scan"]):
        n = len(doc) - 1
        np.testing.assert_allclose(out["logprob"][r, start:start + n], logp, atol=TOL)
        covered[r, start:start + n] = True
        inside = [list(np.asarray(SAMPLE_AT)[r]).index(p + start) for p in places]
        np.testing.assert_allclose(out["logits"][r, inside], logits, atol=3e-4)
        seen += len(places)
        for name in ("q", "k", "v", "log_decay", "beta", "o"):   # head 3 of the first delta-net layer
            np.testing.assert_allclose(scan[name][r, start:start + n], w_scan[name], atol=2e-5)
    assert (out["logprob"][~covered] == 0).all() and covered.sum() > 80 and seen >= 6
    assert scan["q"].shape == scan["k"].shape == (2, L, 8) and scan["v"].shape == scan["o"].shape == (2, L, 16)
    assert scan["log_decay"].shape == scan["beta"].shape == (2, L)
    assert 1.0 < scan["beta"].max() < 2.0 and scan["beta"].min() > 0.0     # a negative eigenvalue is allowed
    assert want["router"] == [{}] * len(docs)


def test_a_pattern_without_experts_goes_through_score(params, scored):
    """What such a pattern returns, and nothing made up beside it: ``visits``
    [0, experts_held], ``dropped`` [0], the recurrence's probe and no router's;
    off a TPU no layer takes the kernel."""
    _, out = scored
    assert out["visits"].shape == (0, program_cfg().experts_held) and out["dropped"].shape == (0,)
    assert sorted(out["probes"]) == ["scan"]
    assert METRICS.gauge_value("gdn.fused_layers") == 0 and METRICS.gauge_value("gdn.key_group") == 1
    assert METRICS.gauge_value("gdn.state_shape") == 0 and METRICS.gauge_value("gdn.lane_fill") == 0.0
    assert METRICS.gauge_value("gqa.kernel_layers") == 0


#: the other reading of each ``assumed`` item, a lower precision and the planted faults:
#: (what is handed to the reference, the probed head's ``o`` is what tells it apart)
DEPARTURES = {
    "pre_norm_gdn": (dict(lower={"pre_norm_gdn": True}), False),
    "per_head_qk_norm": (dict(lower={"per_head_qk_norm": True}), False),
    "no_qk_norm": (dict(lower={"no_qk_norm": True}), False),
    "rotary_on_full": (dict(lower={"rotary_on_full": True}), False),
    "sigmoid_gate": (dict(lower={"sigmoid_gate": True}), False),
    "beta_times_1": (dict(lower={"beta_times_1": True}), False),
    "scale_by_dv": (dict(lower={"scale_by_dv": True}), True),
    "bf16_state": (dict(lower={"state_dtype": jnp.bfloat16}), True),
    "carried_state": (dict(carry="state"), False),
    "carried_taps": (dict(carry="taps"), False),
}


@pytest.mark.parametrize("name", DEPARTURES)
def test_the_tolerances_refuse_the_other_reading(params, scored, name):
    """The program stays within ``TOL`` of the reference as written (the test
    above) and is NOT within it of the reference under any departure: each
    other reading of an ``assumed`` item moves some document's
    log-probabilities by more than 20 times the tolerance; the state's
    precision and the scale of q, which a head's own norm hides from the
    log-probabilities, move the probed head's output by more than 20 times
    its tolerance."""
    batch, out = scored
    departure, by_probe = DEPARTURES[name]
    docs = [(r, s, d) for r, s, d in documents_of(batch) if r == 0]
    got = ref.reference_score(CFG, [d for _, _, d in docs], reference_weights(params), probe_head=3, **departure)
    if by_probe:
        moved = max(float(np.abs(out["probes"]["scan"]["o"][r, s:s + len(d) - 1] - scan["o"]).max())
                    for (r, s, d), scan in zip(docs, got["scan"]))
        assert moved > 20 * 2e-5, moved
    else:
        moved = [float(np.abs(out["logprob"][r, s:s + len(d) - 1] - logp).max())
                 for (r, s, d), logp in zip(docs, got["logprob"])]
        if name.startswith("carried"):                   # the row's first document has no past
            assert moved[0] < TOL and max(moved[1:]) > 20 * TOL, moved
        else:
            assert max(moved) > 20 * TOL, moved


def test_bfloat16_stays_near_the_float32_program(params, scored):
    batch, out = scored
    low = score(jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 and a.shape[0] != 4 else a, params),
                batch["tokens"], batch["segment_ids"], SAMPLE_AT, program_cfg(dtype=jnp.bfloat16), jnp.int32(3))
    real = out["logprob"] != 0
    assert np.abs(np.asarray(low["logprob"]) - out["logprob"])[real].mean() < 0.05


# ---------------------------------------------------------------------------
# The layer that takes the kernel
# ---------------------------------------------------------------------------


def test_a_layer_of_96_under_192_that_takes_the_kernel_is_the_layer_and_is_counted(monkeypatch):
    """The model at the cell's head widths (2 heads of 96 under 192) with the
    dispatch answering as it would on a TPU and Pallas interpreting: the scores
    the plain form gives, and ``score`` says of itself what the benchmark reads:
    the delta-net layers that took the kernel, the state they keep and the share
    of the kernel's lanes that is published."""
    from jax.experimental.pallas import tpu as pltpu

    wide = {**CFG, "num_hidden_layers": 4, "linear_num_key_heads": 2, "linear_num_value_heads": 2,
            "linear_key_head_dim": 96, "linear_value_head_dim": 192}
    cfg = lm.PatternLMConfig(**{**program_cfg(wide, kda_chunk=64, attn_block=32).__dict__, "max_len": 128})
    params = init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(6)
    tokens = jnp.asarray(rng.integers(1, 64, (1, 129)), jnp.int32)
    segs = jnp.asarray(np.concatenate([np.full(50, 1), np.full(60, 2), np.zeros(19)]).astype(np.int32)[None])
    at = jnp.asarray([[3, 70]], jnp.int32)

    def traced_anew():  # the gauges are set as a program is traced
        return jax.jit(lambda *a: lm.score(*a, cfg, jnp.int32(1)))(params, tokens, segs, at)

    plain_out = traced_anew()
    assert METRICS.gauge_value("gdn.fused_layers") == 0 and METRICS.gauge_value("gdn.lane_fill") == 0.0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(lm, "_takes_kernel", lambda *a: False)      # this is about the recurrence alone
    with pltpu.force_tpu_interpret_mode():
        fused = traced_anew()
    assert METRICS.gauge_value("gdn.fused_layers") == 3 and METRICS.gauge_value("conv.kernel_layers") == 3
    assert METRICS.gauge_value("gdn.state_shape") == 96 * 192 and METRICS.gauge_value("gdn.lane_fill") == 0.75
    assert METRICS.gauge_value("kda.fused_layers") == 0
    np.testing.assert_allclose(fused["logprob"], plain_out["logprob"], atol=5e-5)
    for name in ("q", "k", "v", "o"):                    # the probe reads what the kernel prepared and gave
        np.testing.assert_allclose(fused["probes"]["scan"][name], plain_out["probes"]["scan"][name], atol=1e-5)
    assert fused["probes"]["scan"]["q"].shape == (1, 128, 96) and fused["probes"]["scan"]["o"].shape == (1, 128, 192)
    assert np.abs(np.asarray(plain_out["logprob"])).max() > 1
