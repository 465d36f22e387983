"""The latent-attention decoder (``models.lm.score`` with ``mla`` mixers, a
leading dense layer and a router whose bias picks and never weighs) against
its plain reference, at sizes a CPU walks in seconds: the mixer, the dense
layer, the biased router and the 1 + 2-layer model; a packed row against
each of its documents alone and the positions that restart at a boundary;
the Pallas kernel for 192-wide keys against 128-wide values, joined and in
their two parts, interpreted, against the plain path; the shares of 64 experts held as 8 x 8 against the
uncut layer; and the pattern model's older configurations left as they were."""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import kimi_vl_lm as ref
from tpu_tfrecord.models import linear_attn, lm, moe
from tpu_tfrecord.models.attention import blockwise_attention, flash_attention_widths, pair_kinds

from test_pattern_lm import (SAMPLE_AT, documents_of, flat, held_experts, init_params,
                             packed_rows as older_rows, reference_weights, score)

#: a configuration with the published names, tiny: one dense layer, two expert layers
CFG = {
    "hidden_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "kv_lora_rank": 16, "rope_theta": 800000, "intermediate_size": 48,
    "n_routed_experts": 16, "n_routed_experts_held": 16, "held_offset": 0,
    "num_experts_per_tok": 3, "moe_intermediate_size": 16, "n_shared_experts": 2,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5, "vocab_size": 64,
}
L = 48


def program_cfg(cfg=CFG, dtype=jnp.float32, **cut):
    cut = {"attn_block": 16, "expert_tile": 8, "head_block": 32, **cut}
    n = cfg["num_hidden_layers"]
    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"], layer_pattern=("mla",) * n,
        ffn_pattern=tuple(ref.ffn_kinds(cfg)), n_heads=cfg["num_attention_heads"],
        qk_nope_dim=cfg["qk_nope_head_dim"], qk_rope_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"],
        rope_theta=float(cfg["rope_theta"]), d_dense=cfg["intermediate_size"],
        n_experts=cfg["n_routed_experts"], experts_held=cfg["n_routed_experts_held"],
        held_offset=cfg["held_offset"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], n_shared=cfg["n_shared_experts"],
        routed_scale=cfg["routed_scaling_factor"], router_bias=True,
        norm_eps=cfg["rms_norm_eps"], max_len=L, dtype=dtype, **cut)


def packed_rows():
    return older_rows()[0]


@pytest.fixture(scope="module")
def params():
    p = init_params(jax.random.PRNGKey(3), program_cfg())
    for layer in p["layers"][1:]:  # a bias large enough to change who is chosen
        layer["router_bias"] = layer["router_bias"] * 4.0
    return p


@pytest.fixture(scope="module")
def scored(params):
    batch = packed_rows()
    out = score(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, program_cfg(), jnp.int32(1))
    return batch, SAMPLE_AT, jax.tree.map(np.asarray, out)


def test_the_parameters_are_the_models(params):
    first, later = params["layers"][0], params["layers"][1]
    assert set(first) == {"attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "ffn_norm", "dense"}
    assert {"router", "router_bias", "moe_norm", "shared", "w_gate"} <= set(later)
    assert first["wq"].shape == (32, 4 * 12) and first["wkv_a"].shape == (32, 16 + 4)
    assert first["wkv_b"].shape == (16, 4 * 16) and first["wo"].shape == (4 * 8, 32)
    assert first["dense"]["w_gate"].shape == (32, 48)
    assert later["shared"]["w_gate"].shape == (32, 2 * 16)      # two shared experts, one unit
    assert later["router_bias"].shape == (16,) and later["router_bias"].dtype == jnp.float32


def test_a_packed_row_scores_each_document_as_the_reference_scores_it_alone(params, scored):
    batch, sample_at, out = scored
    docs = documents_of(batch)
    at = [[int(p) - start for p in np.asarray(sample_at)[r]
           if start <= p < start + len(doc) - 1] for r, start, doc in docs]
    want = ref.reference_score(CFG, [d for _, _, d in docs], reference_weights(params), at)
    covered, seen = np.zeros_like(out["logprob"], bool), 0
    for (r, start, doc), logp, places, logits in zip(docs, want["logprob"], at, want["logits"]):
        np.testing.assert_allclose(out["logprob"][r, start:start + len(doc) - 1], logp, atol=2e-4)
        covered[r, start:start + len(doc) - 1] = True
        for p, w in zip(places, logits):
            s = list(np.asarray(sample_at)[r]).index(p + start)
            np.testing.assert_allclose(out["logits"][r, s], w, atol=3e-4)
            seen += 1
    assert (out["logprob"][~covered] == 0).all() and covered.sum() > 80 and seen >= 6
    # two expert layers report, the dense one has no experts to visit
    assert out["visits"].shape == (2, 16) and out["dropped"].sum() == 0
    real = int((batch["segment_ids"][:, :-1] != 0).sum())
    assert (out["visits"].sum(axis=1) == real * CFG["num_experts_per_tok"]).all()
    assert out["probes"]["scan"] == {} and out["probes"]["router"]["u"].shape == (2, 2, 4, 32)


#: ``mla_mixer`` as one program a configuration and shape (bare, it runs primitive by primitive)
mixer = jax.jit(lm.mla_mixer, static_argnums=3)


def reference_mixer(layer, x, **kw):
    """The reference's mixer of one document's normed inputs, as one program."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, x: ref.ref_mla(p, ref.ref_norm(x, p["attn_norm"], 1e-5), CFG, **kw))(
            flat(layer), x)


def test_the_mixer_against_the_reference(params):
    cfg, layer = program_cfg(), params["layers"][1]
    x = jnp.asarray(np.random.default_rng(5).standard_normal((1, L, 32)), jnp.float32)
    got = mixer(layer, x, jnp.ones((1, L), jnp.int32), cfg)
    np.testing.assert_allclose(got[0], reference_mixer(layer, x[0]), atol=2e-5)


def test_positions_restart_at_every_document():
    segs = jnp.asarray([[1, 1, 1, 2, 2, 3, 0, 0], [1, 2, 2, 2, 2, 2, 2, 0]], jnp.int32)
    got = np.asarray(lm.segment_positions(segs))
    assert got.tolist() == [[0, 1, 2, 0, 1, 0, 0, 0], [0, 0, 1, 2, 3, 4, 5, 0]]
    assert (got != np.arange(8)).any()                 # not the index in the row


def test_a_document_in_a_row_is_the_document_alone_and_its_keys_count_from_its_start(params):
    """A row of three documents through the mixer against each alone; rotary
    scores depend on the DIFFERENCE of two positions, so a document whose
    positions are all shifted reads the same to rounding, and the fault that
    shows is a restart on one side only (the reference's ``key_start``)."""
    cfg, layer = program_cfg(), params["layers"][0]
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, L, 32)), jnp.float32)
    segs = jnp.asarray([[1] * 17 + [2] * 20 + [3] * 9 + [0] * 2], jnp.int32)
    row = mixer(layer, x, segs, cfg)[0]
    shifted = mixer(layer, x[:, 17:37], jnp.ones((1, 20), jnp.int32), cfg)[0]
    np.testing.assert_allclose(row[17:37], shifted, atol=2e-5)
    alone, one_sided = reference_mixer(layer, x[0, 17:37]), reference_mixer(layer, x[0, 17:37], key_start=17)
    np.testing.assert_allclose(row[17:37], alone, atol=2e-5)
    assert np.abs(one_sided - alone).max() > 1e-2
    # and a mask that let the second document see the first would show too
    whole = mixer(layer, x, jnp.ones((1, L), jnp.int32), cfg)[0]
    assert np.abs(whole[17:37] - row[17:37]).max() > 1e-2


def test_rotary_pairs_turn_by_the_position_times_the_frequency():
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 2, 5, 8)), jnp.float32)
    at = jnp.asarray([[0, 1, 2, 7, 100]], jnp.int32)
    got = np.asarray(lm.rotary(x, at, 800000.0))
    np.testing.assert_allclose(got[:, :, 0], x[:, :, 0], atol=1e-7)       # position 0: unturned
    for i in range(4):
        angle = np.asarray(at[0], np.float64) * 800000.0 ** (-i / 4)
        a, b = np.asarray(x[0, 1, :, i], np.float64), np.asarray(x[0, 1, :, i + 4], np.float64)
        np.testing.assert_allclose(got[0, 1, :, i], a * np.cos(angle) - b * np.sin(angle), atol=2e-5)
        np.testing.assert_allclose(got[0, 1, :, i + 4], b * np.cos(angle) + a * np.sin(angle), atol=2e-5)
    want = ref.ref_rope(jnp.swapaxes(x[0], 0, 1), at[0], 800000.0)
    np.testing.assert_allclose(jnp.swapaxes(got[0], 0, 1), want, atol=1e-6)


def test_the_dense_layer_against_the_reference(params):
    """Layer 0 whole: the mixer, then the dense gated unit, and no expert."""
    cfg = lm.PatternLMConfig(**{**program_cfg().__dict__, "layer_pattern": ("mla",),
                                "ffn_pattern": ("dense",)})
    one = {**params, "layers": params["layers"][:1]}
    tokens = jnp.asarray(np.random.default_rng(8).integers(1, 64, (1, L + 1)), jnp.int32)
    x, visits, dropped, probes = jax.jit(lambda p, t: lm.pattern_hidden(p, t, jnp.ones_like(t), cfg))(
        one, tokens)
    assert visits.shape == (0, 16) and dropped.shape == (0,) and "router" not in probes
    with jax.default_matmul_precision("highest"):
        x0 = jnp.asarray(params["embed"], jnp.float32)[tokens[0, :-1]]
        want, u = jax.jit(lambda p, x: ref.ref_layer_front("dense", p, x, CFG))(
            flat(params["layers"][0]), x0)
    assert u is None
    np.testing.assert_allclose(x[0], want, atol=3e-5)


@pytest.mark.parametrize("flip", [False, True])
def test_the_bias_picks_and_never_weighs(flip):
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((32, 16)) * 32 ** -0.5, jnp.float32)
    plain_e, plain_g = moe.route_top_k(x, router, 3, 2.446)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(x, router, precision="highest")), np.float64)
    bias = np.zeros(16, np.float32)
    if flip:  # lift each token's fourth choice of the first ten tokens over its third
        order = np.argsort(-scores, axis=1)
        gap = scores[np.arange(40), order[:, 2]] - scores[np.arange(40), order[:, 3]]
        bias[order[0, 3]] = gap[0] + 1e-3
    experts, gates = moe.route_top_k(x, router, 3, 2.446, jnp.asarray(bias))
    if not flip:  # a zero bias chooses as no bias does, and weighs the same
        assert (np.asarray(experts) == np.asarray(plain_e)).all()
        np.testing.assert_allclose(gates, plain_g, atol=1e-7)
        return
    assert set(np.asarray(experts)[0]) != set(np.asarray(plain_e)[0])
    assert order[0, 3] in np.asarray(experts)[0]
    # the gates are the chosen experts' SCORES renormalised: the bias is not in them
    chosen = np.take_along_axis(scores, np.asarray(experts), axis=1)
    np.testing.assert_allclose(gates, chosen / chosen.sum(axis=1, keepdims=True) * 2.446, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(axis=1), 2.446, atol=1e-5)
    want_e, want_g = ref.ref_route_biased(x, router, jnp.asarray(bias), CFG)
    assert (np.asarray(want_e) == np.asarray(experts)).all()
    np.testing.assert_allclose(gates, want_g, atol=1e-6)


def test_the_references_router_takes_its_controls():
    """Without the bias another expert is chosen somewhere; in bfloat16 the gates move."""
    rng = np.random.default_rng(9)
    u = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((32, 16)) * 32 ** -0.5, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.2, jnp.float32)
    sound_e, sound_g = ref.ref_route_biased(u, router, bias, CFG)
    plain_e, _ = ref.ref_route_biased(u, router, jnp.zeros(16), CFG)
    low_e, low_g = ref.ref_route_biased(u, router, bias, CFG, jnp.bfloat16)
    assert (np.asarray(sound_e) != np.asarray(plain_e)).any()
    same = (np.asarray(sound_e) == np.asarray(low_e)).all(axis=1)
    assert same.sum() > 32 and 1e-4 < np.abs(np.asarray(sound_g) - np.asarray(low_g))[same].max() < 0.05


def test_bfloat16_stays_near_the_float32_program(params):
    batch, outs = packed_rows(), []
    for dtype in (jnp.float32, jnp.bfloat16):
        cfg = program_cfg(dtype=dtype)
        p = jax.tree.map(lambda a, s: a.astype(s[1]), params, lm.pattern_param_shapes(cfg))
        outs.append(np.asarray(score(p, batch["tokens"], batch["segment_ids"], SAMPLE_AT,
                                     cfg)["logprob"]))
    assert 0 < np.abs(outs[0] - outs[1]).max() < 0.25


def joined(q, k, q_rope, k_rope):
    """The two parts of q and of k as one array each, the rotary key head copied to every head."""
    wide = jnp.broadcast_to(k_rope, k.shape[:1] + (k.shape[1],) + k_rope.shape[2:])
    return jnp.concatenate([q, q_rope], axis=-1), jnp.concatenate([k, wide], axis=-1)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (512, 256)])
def test_the_kernel_for_wide_keys_is_blockwise_attention(blocks):
    """``lm._attend`` hands ``flash_attention_widths`` 128 plain and 64 rotary
    columns of the queries and keys as arrays of their own on a TPU, against
    128-wide values, and joins them for ``blockwise_attention`` elsewhere: the
    kernel, interpreted here (on a chip, outside pytest's conftest, this
    function runs it as it is), against the plain path on a packed row whose
    one rotary key head serves every query head."""
    r = np.random.default_rng(1)
    q = jnp.asarray(r.standard_normal((1, 4, 512, 128)), jnp.float32)      # [B, H, L, D]
    k = jnp.asarray(r.standard_normal((1, 2, 512, 128)), jnp.float32)
    q_rope = jnp.asarray(r.standard_normal((1, 4, 512, 64)), jnp.float32)
    k_rope = jnp.asarray(r.standard_normal((1, 1, 512, 64)), jnp.float32)  # one head, for all
    v = jnp.asarray(r.standard_normal((1, 2, 512, 128)), jnp.float32)
    segs = np.zeros((1, 512), np.int32)
    segs[0, :100], segs[0, 100:130], segs[0, 130:400] = 1, 2, 3
    segs = jnp.asarray(segs)
    want = plain_path(*joined(q, k, q_rope, k_rope), v, segs, None)
    assert want.shape == (1, 4, 512, 128)
    if jax.default_backend() == "tpu":
        got = lm._attend((q, q_rope), (k, k_rope), v, segs, blocks[0])
    else:
        got = interpreted_kernel(q, k, v, segs, 192 ** -0.5, *blocks, q_rope=q_rope, k_rope=k_rope)
    real = np.asarray(segs[0] != 0)
    np.testing.assert_allclose(np.asarray(got)[:, :, real], np.asarray(want)[:, :, real],
                               atol=3e-2 if jax.default_backend() == "tpu" else 2e-5)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def interpreted_kernel(q, k, v, segs, scale, block_q, block_k, keep=None, q_rope=None, k_rope=None):
    """``flash_attention_widths`` interpreted, one program a shape."""
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        return flash_attention_widths(q, k, v, segs, scale, block_q, block_k, keep=keep,
                                      q_rope=q_rope, k_rope=k_rope)


@pytest.mark.parametrize("documents", ["one document", "packed rows"])
@pytest.mark.parametrize("rotary_keys", ["one shared head", "one a head"])
@pytest.mark.parametrize("selection", ["no selection", "a selection"])
def test_the_kernel_handed_parts_is_blockwise_attention_on_their_concatenation(
        selection, rotary_keys, documents):
    """What a latent-attention layer hands the kernel on a TPU: plain queries and
    keys, rotary queries, and the rotary keys of one head for all (or of one a
    key head), never joined: the score is the sum of the two products in float32.
    Interpreted, against the plain path on the joined arrays; a row of one
    document (every pair under the diagonal plain) and packed rows of several
    (skipped, masked and diagonal pairs), with and without a mask of kept keys."""
    r = np.random.default_rng(5)
    b, h, l = (1, 2, 512) if documents == "one document" else (2, 2, 512)
    q, k, v = (jnp.asarray(r.standard_normal((b, h, l, 128)), jnp.float32) for _ in range(3))
    q_rope = jnp.asarray(r.standard_normal((b, h, l, 64)), jnp.float32)
    k_rope = jnp.asarray(r.standard_normal((b, 1 if rotary_keys == "one shared head" else h, l, 64)),
                         jnp.float32)
    rows = [[512]] if documents == "one document" else [[100, 30, 270, 112], [256, 200]]
    segs = jnp.asarray([np.pad(np.repeat(np.arange(1, len(n) + 1), n), (0, l - sum(n))) for n in rows],
                       jnp.int32)
    keep = None
    if selection == "a selection":   # a third of the keys and every query's own
        keep = jnp.asarray((r.random((b, l, l)) < 0.33) | np.eye(l, dtype=bool)[None], jnp.int8)
    got = interpreted_kernel(q, k, v, segs, 0.08, 128, 128, keep=keep, q_rope=q_rope, k_rope=k_rope)
    want = plain_path(*joined(q, k, q_rope, k_rope), v, segs, 0.08, keep=keep)
    real = np.asarray(segs != 0)[:, None, :, None]
    np.testing.assert_allclose(np.where(real, got, 0), np.where(real, want, 0), atol=2e-5)
    # the second part is at work: without it another answer
    bare = interpreted_kernel(q, k, v, segs, 0.08, 128, 128, keep=keep)
    assert np.abs(np.where(real, bare - want, 0)).max() > 1e-2


def test_a_tpu_hands_the_kernel_the_parts_and_nothing_joined(monkeypatch):
    """On a TPU ``_attend`` passes a latent-attention layer's four arrays and its
    one rotary key head through to the kernel as they are; elsewhere it joins
    them, the rotary key head copied to every head there and only there. The
    kernel stubbed here; the dispatch and the gauge read."""
    from tpu_tfrecord.metrics import METRICS

    seen = []

    def stub(q, k, v, segs, scale, bq, bk, keep=None, window=None, q_rope=None, k_rope=None):
        seen.append((q.shape, k.shape, v.shape, q_rope.shape, k_rope.shape, round(scale, 6)))
        return jnp.zeros(q.shape[:3] + v.shape[-1:], q.dtype)

    monkeypatch.setattr(lm, "flash_attention_widths", stub)
    cfg = lm.PatternLMConfig(
        vocab_size=64, d_model=32, layer_pattern=("mla",) * 3, ffn_pattern=("dense",) * 3, n_heads=2,
        qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, kv_rank=16, d_dense=16, max_len=256,
        attn_block=128, head_block=256, dtype=jnp.float32)
    p = jax.eval_shape(lambda: lm.pattern_init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, 257), jnp.int32)

    def trace():
        jax.eval_shape(lambda p, t: lm.score(p, t, t, jnp.zeros((1, 2), jnp.int32), cfg), p, tokens)

    trace()     # off a TPU: the plain path on the joined arrays, the kernel never asked
    assert seen == [] and METRICS.gauge_value("mla.split_layers") == 0
    monkeypatch.setattr(lm.jax, "default_backend", lambda: "tpu")
    trace()
    part, rope = (1, 2, 256, 128), (1, 2, 256, 64)
    assert seen == [(part, part, part, rope, (1, 1, 256, 64), round(192 ** -0.5, 6))] * 3
    assert METRICS.gauge_value("mla.split_layers") == 3
    # and what the plain path is given elsewhere: one array as wide as both parts, every head its own copy
    monkeypatch.undo()
    given = []
    monkeypatch.setattr(lm, "blockwise_attention", lambda q, k, v, segs, **kw:
                        given.append((q.shape, k.shape, v.shape)) or jnp.zeros(q.shape[:3] + v.shape[-1:], q.dtype))
    q, k_rope = jnp.zeros(part), jnp.zeros((1, 1, 256, 64))
    lm._attend((q, jnp.zeros(rope)), (q, k_rope), q, jnp.ones((1, 256), jnp.int32), 128)
    assert given == [((1, 256, 2, 192), (1, 256, 2, 192), (1, 256, 2, 128))]


def kernel_inputs(lengths, l, seed=4, heads=2):
    """q, k, v [1, heads, l, 192 / 128] float32 and a row of documents of
    ``lengths`` (ids from 1; pads, id 0, to the row's end)."""
    r = np.random.default_rng(seed)
    q, k = (jnp.asarray(r.standard_normal((1, heads, l, 192)), jnp.float32) for _ in range(2))
    v = jnp.asarray(r.standard_normal((1, heads, l, 128)), jnp.float32)
    ids = np.repeat(np.arange(1, len(lengths) + 1), lengths)
    return q, k, v, jnp.asarray(np.pad(ids, (0, l - len(ids)))[None].astype(np.int32))


@functools.partial(jax.jit, static_argnames="scale")
def plain_path(q, k, v, segs, scale, keep=None):
    return jnp.swapaxes(blockwise_attention(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), segs, scale=scale,
        block=64, keep=keep), 1, 2)


def kinds_by_the_dense_mask(segs, block_q, block_k):
    """(skipped, plain, masked) of the block pairs at or under the diagonal,
    from the mask written out: none of a pair's elements seen, all, some."""
    segs = np.asarray(segs)
    at = np.arange(segs.shape[1])
    count = [0, 0, 0]
    for row in segs:
        seen = (row[:, None] == row[None, :]) & (at[None, :] <= at[:, None])
        for q0 in range(0, len(row), block_q):
            for k0 in range(0, q0 + block_q, block_k):
                part = seen[q0:q0 + block_q, k0:k0 + block_k]
                count[0 if not part.any() else 1 if part.all() else 2] += 1
    return tuple(count)


#: rows that force each kind of pair on the kernel, with the kinds they must hold
#: (lengths of the row's documents, row length, blocks, (skipped, plain, masked))
KINDS = {
    "one document over three blocks": ([768], 768, (256, 256), (0, 3, 3)),
    "a boundary inside a block": ([300, 468], 768, (256, 256), (1, 0, 5)),
    "a boundary on a block's edge": ([256, 512], 768, (256, 256), (2, 1, 3)),
    "short documents, most pairs skipped": ([100, 30, 270, 40, 200, 128], 768, (128, 128), None),
    "a query block of two passes": ([1024], 1024, (512, 512), (0, 1, 2)),
    "a query block of two key blocks": ([600, 424], 1024, (512, 256), None),
    "pads to the row's end": ([256, 300], 768, (256, 256), None),
}


@pytest.mark.parametrize("case", KINDS)
def test_each_kind_of_pair_is_blockwise_attention(case):
    """The kernel decides from scalars what a pair of blocks costs (skipped;
    under the diagonal by segment ids alone, plain where they all agree; on
    the diagonal by positions too, over the keys its rows reach): each, interpreted, against
    the plain path, and ``pair_kinds`` against the mask written out."""
    lengths, l, blocks, kinds = KINDS[case]
    q, k, v, segs = kernel_inputs(lengths, l)
    if kinds is not None:
        assert pair_kinds(segs, *blocks) == kinds
    if sum(lengths) == l:   # a row's pads widen its last block's range of ids: more is computed than seen
        assert pair_kinds(segs, *blocks) == kinds_by_the_dense_mask(segs, *blocks)
    else:
        assert pair_kinds(segs, *blocks)[0] <= kinds_by_the_dense_mask(segs, *blocks)[0]
    got = interpreted_kernel(q, k, v, segs, 0.09, *blocks)
    real = np.asarray(segs[0] != 0)
    np.testing.assert_allclose(np.asarray(got)[:, :, real],
                               np.asarray(plain_path(q, k, v, segs, 0.09))[:, :, real], atol=2e-5)


def test_the_plain_share_of_one_long_document_is_120_of_136():
    """``deepseek_v32_exp_ep16.score``'s rows: one document of 16,384 tokens in
    blocks of 1,024; and a packed row of short documents, where few pairs are plain."""
    from tpu_tfrecord.metrics import METRICS

    cfg = program_cfg(attn_block=1024)
    assert pair_kinds(np.ones((1, 16384), np.int32)) == (0, 120, 16)
    share = lm.record_pair_kinds(np.ones((1, 16385), np.int32), cfg)
    assert round(share, 3) == 0.882 and METRICS.gauge_value("mla.plain_pair_share") == round(120 / 136, 6)
    packed = np.repeat(np.arange(1, 26), 656)[None, :16385]
    assert lm.record_pair_kinds(packed, cfg) == 0.0
    assert METRICS.gauge_value("mla.plain_pair_share") == 0.0


def test_the_kernel_refuses_rows_that_are_not_whole_blocks():
    q = jnp.zeros((1, 2, 384, 192))
    with pytest.raises(ValueError, match="whole blocks"):
        flash_attention_widths(q, q, jnp.zeros((1, 2, 384, 128)), jnp.ones((1, 384), jnp.int32),
                               1.0, 256, 128)


@pytest.mark.parametrize("k_rope_heads", [None, 3])
def test_the_kernel_refuses_a_second_part_of_the_queries_without_the_keys(k_rope_heads):
    """Rotary queries alone, or rotary keys of a number of heads that is neither
    one for all nor the keys' own."""
    q = jnp.zeros((1, 6, 256, 128))
    k_rope = None if k_rope_heads is None else jnp.zeros((1, k_rope_heads, 256, 64))
    with pytest.raises(ValueError, match="second part"):
        flash_attention_widths(q, q, q, jnp.ones((1, 256), jnp.int32), 1.0, 128, 128,
                               q_rope=jnp.zeros((1, 6, 256, 64)), k_rope=k_rope)


def test_the_shares_of_64_experts_held_8_by_8_add_up_to_the_uncut_layer():
    """Eight chips of 8 experts each under the biased router, the two shared
    experts counted once, against the reference told that it holds all 64."""
    cfg = {**CFG, "n_routed_experts": 64, "n_routed_experts_held": 64, "num_experts_per_tok": 6,
           "num_hidden_layers": 2}                              # up to the layer taken
    p = init_params(jax.random.PRNGKey(1), program_cfg(cfg))["layers"][1]
    p["router_bias"] = p["router_bias"] * 4.0
    x = jnp.asarray(np.random.default_rng(1).standard_normal((96, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _, (chosen, _) = ref.ref_moe_biased(flat(p), x, cfg)
        unbiased, _, (plain, _) = ref.ref_moe_biased(flat(p), x, cfg, no_bias=True)
        shared = ref.ref_ffn(x, *(jnp.asarray(p["shared"][k]) for k in ("w_gate", "w_up", "w_down")))
    assert (np.asarray(chosen) != np.asarray(plain)).any()      # the bias is at work
    total, visits = -7 * shared, 0
    for first in range(0, 64, 8):
        share = {**p, **{k: p[k][first:first + 8] for k in ("w_gate", "w_up", "w_down")}}
        y, n, dropped, _ = held_experts(share, x, held_offset=first, top_k=6,
                                        routed_scale=2.446, tile=8)
        total, visits = total + y, visits + int(n.sum())
        assert int(dropped) == 0
    assert visits == x.shape[0] * 6
    np.testing.assert_allclose(total, whole, atol=3e-5)
    assert np.abs(np.asarray(total) - np.asarray(unbiased)).max() > 1e-2


def test_an_unknown_mixer_or_feed_forward_part_is_named_with_the_ones_there_are():
    with pytest.raises(ValueError, match=r"\('gqa', 'kda', 'mla', 'swa', 'gdn', 'ssm', 'bda'\)"):
        lm.pattern_param_shapes(lm.PatternLMConfig(layer_pattern=("mla", "rope")))
    with pytest.raises(ValueError, match=r"\('moe', 'dense'\)"):
        lm.pattern_param_shapes(lm.PatternLMConfig(layer_pattern=("mla",), ffn_pattern=("ffn",)))
    with pytest.raises(ValueError, match="each of the 2 layers"):
        lm.pattern_param_shapes(lm.PatternLMConfig(layer_pattern=("mla", "gqa"), ffn_pattern=("moe",)))


#: sha256 of ``str(jax.make_jaxpr(f))`` for the programs a PR that means to change
#: ONE pattern's program must leave as they were, operation for operation:
#: ``solar`` test_pattern_lm's softmax / delta-rule program (as PR 42 left it: its
#: delta-rule layers hand q, k and v over behind a barrier in the dtype they were
#: written, as ``gigachat``'s did already; off a TPU the delta rule runs its plain
#: form), ``gigachat`` test_gdn_lm's delta-net / latent-attention program in bfloat16
#: (recorded at PR 42 from PR 41's tree: the shared hand-over left it operation for
#: operation), as PR 31 left them the recommender's ``forward`` and
#: ``sparse_train_step``, and as PR 40 left it ``kimi``, this file's latent-attention
#: program (its mixer's four projections each an array of its own, joined off a TPU alone).
#: A PR that means to change one of them records its own. PR 47 gave the expert loop tail
#: tiles of 256 rows under tiles that are several such units; these three are traced at
#: ``expert_tile=8``, under the unit, where there is one loop and the program it was: no
#: hash moved. PR 48 meant to change ``solar`` and ``gigachat`` and changed them on a TPU
#: alone, where the delta-rule kernel is handed the projections and prepares its own q, k
#: and v (tests/test_tpu_compile.py reads that program): off a TPU the preparation moved
#: from ``lm._handed_over`` to ``linear_attn.prepared`` and is traced operation for
#: operation as it was, barrier and all, so both hashes stand as recorded.
#: PR 50 recorded the three token programs anew for ONE equation's place: the head's blocks
#: are joined inside ``models.head.logprob`` (which answers with the [T] array and whether
#: the kernel ran), so the ``concatenate`` stands before the mask's ``eq``, ``ne`` and ``and``
#: and not after them; every other equation is the parent's, in the parent's order.
#: PR 52 recorded the three token programs anew for the expert layer alone: a tile of
#: ``moe.held_experts_apply``'s loops reads its row of a table made where the routing is and
#: slices its visits out of the padded order, where it searched and gathered (tests/
#: test_pattern_lm.py holds the table to the arithmetic it replaced and the layer to the
#: reference); ``dlrm_forward``, the train step and ``SOLARS_KERNEL`` read as they did.
OLDER_PROGRAMS = {
    "solar": "a7fd7648656de9a78a939e9816d39b8a5253ca97e32c820609dcf1f7bc571981",
    "gigachat": "a5bee9d1c8f65d5c29a9c498938f04967294758c4867f2c24807acab9f81bbfb",
    "kimi": "0e7c8bc704658f1a7d5327b2cebbfad28983c2f641311f166c9898e84f268759",
    "dlrm_forward": "74937f331a59e45e91ba132bca04da279ac57e7cdc91574931627704728350cc",
    "sparse_train_step": "ea35280a10973a3d8af6c8c0a1a8b17679edde3e8d4005f6012a10f3f8360d9d",
}


def shapes_of_params(cfg):
    """The parameters' shapes and dtypes, which is all that a trace reads of them."""
    return jax.eval_shape(lambda: lm.pattern_init_params(jax.random.PRNGKey(3), cfg))


def older_program(name):
    import optax

    from tpu_tfrecord.models import dlrm

    at = jnp.asarray([[0, 5, 19, 25], [2, 8, 29, 40]], jnp.int32)
    if name == "solar":
        import test_pattern_lm as older

        cfg = older.program_cfg()
        assert lm.ffn_kinds(cfg) == ("moe",) * 4 and not cfg.router_bias
        params = shapes_of_params(cfg)
        assert all("router_bias" not in layer and "dense" not in layer for layer in params["layers"])
        batch, _ = older.packed_rows()
        return jax.make_jaxpr(lambda p, t, s, a, h: lm.score(p, t, s, a, cfg, h))(
            params, batch["tokens"], batch["segment_ids"], at, jnp.int32(2))
    if name == "gigachat":
        import test_gdn_lm as newer

        cfg = newer.program_cfg(dtype=jnp.bfloat16)
        rows = jax.ShapeDtypeStruct((2, cfg.max_len + 1), jnp.int32)
        return jax.make_jaxpr(lambda p, t, s, a, h: lm.score(p, t, s, a, cfg, h))(
            shapes_of_params(cfg), rows, rows, at, jnp.int32(1))
    if name == "kimi":
        cfg, batch = program_cfg(), packed_rows()
        params = shapes_of_params(cfg)
        return jax.make_jaxpr(lambda p, t, s, a, h: lm.score(p, t, s, a, cfg, h))(
            params, batch["tokens"], batch["segment_ids"], at, jnp.int32(1))
    cfg = dlrm.DLRMConfig(num_dense=13, num_categorical=4, vocab_size=64, embed_dim=128,
                          bottom_mlp=(32, 128), top_mlp=(32, 1), interaction="dot")
    params = dlrm.init_params(jax.random.key(0), cfg)
    batch = {"label": jnp.zeros((16,), jnp.float32), "dense": jnp.ones((16, 13), jnp.float32),
             "cat": jnp.arange(64, dtype=jnp.int32).reshape(16, 4) % 7}
    if name == "dlrm_forward":
        return jax.make_jaxpr(functools.partial(dlrm.forward, cfg=cfg))(
            params, {k: batch[k] for k in ("dense", "cat")})
    tx = optax.sgd(1e-2)
    return jax.make_jaxpr(functools.partial(dlrm.sparse_train_step, cfg=cfg, tx=tx))(
        params, dlrm.sparse_opt_init(params, cfg, tx), batch)


@pytest.mark.parametrize("name", list(OLDER_PROGRAMS))
def test_the_older_patterns_program_is_the_one_it_was(name):
    program = older_program(name)
    assert hashlib.sha256(str(program).encode()).hexdigest() == OLDER_PROGRAMS[name]


#: sha256 of ``str(jax.make_jaxpr(f))`` for ``linear_attn._delta_rule_fused`` handed Solar's
#: operands (a decay a channel, as many key heads as value heads, float32): two heads a grid step
#: at tiles of 256, one at 128, recorded at PR 40, before the kernel took its second form. The
#: whole call: the body, the block specs and their index maps.
SOLARS_KERNEL = {
    (2, 256, 512): "976ccc2265517c85ad1fdcf51a5a667a3b2d0d3a74262538325ef19e95940c57",
    (3, 128, 256): "dcaac5e29124947d8dd8f63f5da1fff96080f848bb2927216c166adc0869ade9",
}


@pytest.mark.parametrize("heads,tile,length", list(SOLARS_KERNEL))
def test_solars_kernel_is_the_one_it_was(heads, tile, length):
    """A decay of one number a token and shared key heads are static properties
    of the operands: handed a decay a channel and its own key head for every
    value head, the kernel is traced operation for operation as it was."""
    x = jax.ShapeDtypeStruct((1, heads, length, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, heads, length), jnp.float32)
    segs = jax.ShapeDtypeStruct((1, length), jnp.int32)
    program = jax.make_jaxpr(lambda q, k, v, g, b, s: linear_attn._delta_rule_fused(
        q, k, v, g, b, s, 0.25, tile, interpret=True))(x, x, x, x, beta, segs)
    assert hashlib.sha256(str(program).encode()).hexdigest() == SOLARS_KERNEL[heads, tile, length]


def scopes_held(params, batch, cfg):
    """The ``tfr.*`` scopes that the compiled score program's operations are named under."""
    import re

    compiled = score.lower(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, cfg).compile()
    op_names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    return {tok for name in op_names for tok in re.findall(r"tfr\.\w+", name)}


def test_the_compiled_program_holds_every_scope(params):
    from tpu_tfrecord import tracing

    held = scopes_held(params, packed_rows(), program_cfg())
    assert held == {"tfr.embed", "tfr.mla_proj", "tfr.mla_attn", "tfr.dense_ffn", "tfr.moe_route",
                    "tfr.moe_experts", "tfr.moe_shared", "tfr.lm_head"}
    assert held <= set(tracing.ANNOTATIONS)
