"""The pattern model (``models.lm.score``: softmax and gated delta-rule
layers by a pattern, a share of the experts, packed rows) against the plain
reference, at sizes a CPU walks in seconds: each layer kind and the period,
a packed row against each of its documents alone, which form of the delta
rule a layer takes (the rule itself is tests/test_delta_rule.py's), the shares
of the experts against the uncut layer, dropless routing under a skewed
router, and the packer at L 8192."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import solar_open2 as ref
from tpu_tfrecord.models import linear_attn, lm, moe
from tpu_tfrecord.models.attention import (
    attention_reference, blockwise_attention, flash_attention_widths, pair_kinds,
)
from tpu_tfrecord.tpu.ingest import TokenPacker

from test_delta_rule import delta_rule_inputs, kernel_inputs

#: One program a shape for the process: cases that differ in their data find it
#: built (bare, these run primitive by primitive, each a compile). A case that reads
#: what a TRACE leaves behind (a gauge, a patched dispatch) wraps the function anew.
init_params = jax.jit(lm.pattern_init_params, static_argnums=1)
score = jax.jit(lm.score, static_argnums=4)
held_experts = jax.jit(moe.held_experts_apply, static_argnames=(
    "top_k", "routed_scale", "tile", "n_group", "topk_group"))  # ``held_offset`` is data: one program for every share

#: a configuration with the published names, tiny
CFG = {
    "hidden_size": 32, "num_hidden_layers": 4, "gqa_layers": [0],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "linear_attn_config": {"num_heads": 4, "head_dim": 8, "short_conv_kernel_size": 4},
    "n_routed_experts": 16, "n_routed_experts_held": 4, "held_offset": 4,
    "num_experts_per_tok": 4, "moe_intermediate_size": 16, "n_shared_experts": 1,
    "routed_scaling_factor": 1, "rms_norm_eps": 1e-5, "vocab_size": 64,
}
L = 48


def program_cfg(cfg=CFG, dtype=jnp.float32, **cut):
    lin = cfg["linear_attn_config"]
    kinds = ref.layer_kinds(cfg)
    cut = {"attn_block": 16, "kda_chunk": 8, "expert_tile": 8, "head_block": 32, **cut}
    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"], layer_pattern=tuple(kinds),
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_taps=lin["short_conv_kernel_size"], gate_rank=8,
        n_experts=cfg["n_routed_experts"], experts_held=cfg["n_routed_experts_held"],
        held_offset=cfg["held_offset"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], n_shared=cfg["n_shared_experts"],
        norm_eps=cfg["rms_norm_eps"], max_len=L, dtype=dtype, **cut)


def flat(tree: dict) -> dict:
    """The program's tree under the reference's flat names, float32."""
    out = {}
    for name, leaf in tree.items():
        if isinstance(leaf, dict):
            out.update({f"{name}.{k}": jnp.asarray(v, jnp.float32) for k, v in leaf.items()})
        else:
            out[name] = jnp.asarray(leaf, jnp.float32)
    return out


def reference_weights(params):
    def weights(part):
        if isinstance(part, int):
            return flat(params["layers"][part])
        return {k: jnp.asarray(params[k], jnp.float32) for k in ("embed", "head", "final_norm")}
    return weights


def packed_rows(seed=0, rows=2, eos=0):
    """Rows of L + 1 tokens as the packer emits them, and their documents."""
    rng = np.random.default_rng(seed)
    docs = [rng.integers(1, CFG["vocab_size"], size=n).astype(np.int32)
            for n in (20, 9, 13, 30, 11, 1, 3)]
    packer = TokenPacker(rows, L, eos_id=eos, packing="best_fit")
    packer.feed_docs(docs)
    packer.flush()
    batch = packer.pop()
    assert batch is not None and packer.pop() is None
    return batch, docs


def documents_of(batch):
    """[(row, start, tokens with the end-of-document id)] of a packed batch."""
    out = []
    for r, (toks, segs) in enumerate(zip(batch["tokens"], batch["segment_ids"])):
        for s in range(1, segs.max() + 1):
            at = np.flatnonzero(segs == s)
            out.append((r, at[0], toks[at]))
    return out


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(3), program_cfg())


SAMPLE_AT = jnp.asarray([[0, 5, 19, 25], [2, 8, 29, 40]], jnp.int32)


@pytest.fixture(scope="module")
def scored(params):
    batch, _ = packed_rows()
    out = score(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT, program_cfg())
    return batch, SAMPLE_AT, jax.tree.map(np.asarray, out)


def test_a_packed_row_scores_each_document_as_the_reference_scores_it_alone(params, scored):
    batch, sample_at, out = scored
    docs = documents_of(batch)
    want = ref.reference_score(CFG, [d for _, _, d in docs], reference_weights(params))
    covered = np.zeros_like(out["logprob"], bool)
    for (r, start, doc), logp in zip(docs, want["logprob"]):
        got = out["logprob"][r, start:start + len(doc) - 1]
        np.testing.assert_allclose(got, logp, atol=2e-4)
        covered[r, start:start + len(doc) - 1] = True
    # pads and each document's last token score 0
    assert (out["logprob"][~covered] == 0).all() and covered.sum() > 80
    assert out["dropped"].sum() == 0 and (out["visits"].sum(axis=1) > 0).all()


def test_sampled_logits_are_the_references(params, scored):
    batch, sample_at, out = scored
    docs = documents_of(batch)
    at = [[int(p) - start for p in np.asarray(sample_at)[r]
           if start <= p < start + len(doc) - 1] for r, start, doc in docs]
    want = ref.reference_score(CFG, [d for _, _, d in docs], reference_weights(params), at)
    seen = 0
    for (r, start, _), places, logits in zip(docs, at, want["logits"]):
        for p, w in zip(places, logits):
            s = list(np.asarray(sample_at)[r]).index(p + start)
            np.testing.assert_allclose(out["logits"][r, s], w, atol=3e-4)
            seen += 1
    assert seen >= 6


def reference_mixer(kind, layer, x):
    """The reference's mixer of one document's normed inputs, as one program."""
    def mixer(p, x):
        u = ref.ref_norm(x, p["attn_norm"], CFG["rms_norm_eps"])
        return ref.ref_gqa(p, u, CFG) if kind == "gqa" else ref.ref_kda(p, u, CFG)[0]
    with jax.default_matmul_precision("highest"):
        return jax.jit(mixer)(flat(layer), x)


@pytest.mark.parametrize("kind", ["gqa", "kda"])
def test_a_layer_of_each_kind_against_the_reference(params, kind):
    cfg = program_cfg()
    layer = params["layers"][0 if kind == "gqa" else 1]
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((1, L, CFG["hidden_size"])), jnp.float32)
    segs = jnp.ones((1, L), jnp.int32)
    got = jax.jit(lambda p, x: lm.gqa_mixer(p, x, segs, cfg) if kind == "gqa"
                  else lm.kda_mixer(p, x, segs, cfg)[0])(layer, x)
    np.testing.assert_allclose(got[0], reference_mixer(kind, layer, x[0]), atol=2e-5)


def test_bfloat16_stays_near_the_float32_program(params):
    batch, _ = packed_rows()
    outs = []
    for dtype in (jnp.float32, jnp.bfloat16):
        cfg = program_cfg(dtype=dtype)
        # each leaf in the dtype the program's own table gives it
        p = jax.tree.map(lambda a, s: a.astype(s[1]), params, lm.pattern_param_shapes(cfg))
        outs.append(np.asarray(score(p, batch["tokens"], batch["segment_ids"], SAMPLE_AT,
                                     cfg)["logprob"]))
    assert 0 < np.abs(outs[0] - outs[1]).max() < 0.25


def test_a_state_carried_across_a_boundary_is_seen(params, scored):
    """The planted fault: the delta-rule layers start a document from the
    last document's final state. The program agrees with the sound
    reference, not with this one."""
    batch, _, out = scored
    docs = documents_of(batch)
    row0 = [d for r, _, d in docs if r == 0]
    assert len(row0) >= 2
    sound = ref.reference_score(CFG, row0, reference_weights(params))["logprob"]
    carried = ref.reference_score(CFG, row0, reference_weights(params), carry_state=True)["logprob"]
    np.testing.assert_allclose(sound[0], carried[0], atol=1e-6)     # the first has no past
    assert np.abs(sound[1] - carried[1]).max() > 1e-2
    start = next(s for r, s, _ in docs[1:] if r == 0)
    got = out["logprob"][0, start:start + len(row0[1]) - 1]
    assert np.abs(got - sound[1]).max() < 2e-4 < np.abs(got - carried[1]).max()


def test_the_probes_are_what_the_layers_were_given_and_gave(params):
    """``score``'s probes: one head of the first delta-rule layer's recurrence,
    walked again token by token from an empty state, document by document,
    gives what the chunked form gave (a state kept in bfloat16, or carried
    over from the document before, does not); the router's inputs at the
    sampled positions give its experts and gates again."""
    batch, _ = packed_rows()
    cfg = program_cfg()
    out = jax.tree.map(np.asarray, score(params, batch["tokens"], batch["segment_ids"],
                                         SAMPLE_AT, cfg, jnp.int32(2)))
    assert "scan" not in score(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT,
                               cfg)["probes"]
    scan, routed = out["probes"]["scan"], out["probes"]["router"]
    scale, last, worst = cfg.kda_head_dim ** -0.5, None, 0.0
    with jax.default_matmul_precision("highest"):
        for r, start, doc in documents_of(batch):
            n = len(doc) - 1
            if n < 1:
                continue
            q, k, v, g, b = (jnp.asarray(scan[name][r, start:start + n])[:, None]
                             for name in ("q", "k", "v", "log_decay", "beta"))
            want, state = ref.ref_delta_rule(q, k, v, g, b, scale)
            np.testing.assert_allclose(scan["o"][r, start:start + n], want[:, 0], atol=1e-5)
            rounded, _ = ref.ref_delta_rule(q, k, v, g, b, scale, state_dtype=jnp.bfloat16)
            worst = max(worst, float(np.abs(rounded - want).max()))
            if last is not None and last[0] == r and n > 4:
                carried, _ = ref.ref_delta_rule(q, k, v, g, b, scale, state0=last[1])
                assert np.abs(carried - want).max() > 1e-3
            last = (r, state)
        assert worst > 1e-4
        for i, layer in enumerate(params["layers"]):
            u = jnp.asarray(routed["u"][i].reshape(-1, CFG["hidden_size"]))
            chosen, gates = ref.ref_route(u, jnp.asarray(layer["router"], jnp.float32), CFG)
            assert (np.asarray(chosen) == routed["experts"][i].reshape(-1, 4)).all()
            np.testing.assert_allclose(gates, routed["gates"][i].reshape(-1, 4), atol=1e-6)


def test_what_reaches_the_recurrence_is_what_the_mechanism_has(monkeypatch, params):
    """q, k and v in the dtype the convolution wrote them, the decay float32 of
    v's shape (it is not bfloat16-exact), beta one number a head and token:
    nothing widened outside the recurrence (tests/test_gdn_lm.py's twin)."""
    seen = []

    def rule(q, k, v, log_decay, beta, segments, scale, chunk):
        seen.append([(a.shape, a.dtype) for a in (q, k, v, log_decay, beta)])
        return jnp.zeros(v.shape, jnp.float32)

    monkeypatch.setattr(lm._la, "delta_rule_chunked", rule)
    cfg = program_cfg(dtype=jnp.bfloat16)
    layer = jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, s[1]), params["layers"][1],
                         lm.pattern_param_shapes(cfg)["layers"][1])
    x = jax.ShapeDtypeStruct((2, L, 32), jnp.bfloat16)
    _, probe = jax.eval_shape(
        lambda p, x: lm.kda_mixer(p, x, jnp.ones((2, L), jnp.int32), cfg, jnp.int32(1)), layer, x)
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert seen == [[((2, 4, L, 8), bf16)] * 3 + [((2, 4, L, 8), f32), ((2, 4, L), f32)]]
    assert {name: (a.shape, a.dtype) for name, a in probe.items()} == {
        **{name: ((2, L, 8), f32) for name in ("q", "k", "v", "log_decay", "o")}, "beta": ((2, L), f32)}


def the_parents_three_lines(p, q, k, v, segments):
    """The hand-over as both mixers spelled it until PR 42: the oracle of the one there is."""
    def conv_silu(a, taps):
        taps = taps.reshape(taps.shape[0], a.shape[1], a.shape[3])
        return jax.nn.silu(linear_attn.short_conv(a, taps, segments).astype(jnp.float32))

    q = linear_attn.unit_norm(conv_silu(q, p["conv_q"])).astype(q.dtype)
    k = linear_attn.unit_norm(conv_silu(k, p["conv_k"])).astype(k.dtype)
    return q, k, conv_silu(v, p["conv_v"]).astype(v.dtype)


def the_hand_over(p, q, k, v, segments):
    """What ``linear_attn.delta_rule_layer`` prepares off a TPU (since PR 48 the preparation lives
    beside ``short_conv``, one function an array: ``linear_attn.prepared``)."""
    return tuple(linear_attn.prepared(a, p[name], segments, unit)
                 for a, name, unit in ((q, "conv_q", True), (k, "conv_k", True), (v, "conv_v", False)))


@pytest.mark.parametrize("key_heads", [4, 2], ids=["a_key_head_a_value_head", "shared_key_heads"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
def test_the_hand_over_is_the_parents_three_lines_bit_for_bit(dtype, key_heads):
    """Packed rows with a boundary at every tap distance (documents of 1, 2, 3
    and 4 tokens, then longer ones, then pads): the shared preparation gives q,
    k and v as the three lines it replaced gave them, to the bit, in the dtype
    they came in, with key heads as many as value heads or half."""
    rng = np.random.default_rng(7)
    lengths = [1, 2, 3, 4, 1, 1, 2, 5, 9, 3, 17]
    row = np.repeat(np.arange(1, len(lengths) + 1), lengths)
    segs = jnp.asarray(np.stack([np.r_[row, np.zeros(64 - len(row), np.int32)],
                                 np.r_[np.ones(30, np.int32), row[:30] + 1, np.zeros(4, np.int32)]]), jnp.int32)
    q, k, v = (jnp.asarray(rng.standard_normal((2, h, 64, 8)), dtype) for h in (key_heads, key_heads, 4))
    p = {name: jnp.asarray(rng.standard_normal((4, h * 8)), jnp.float32)
         for name, h in (("conv_q", key_heads), ("conv_k", key_heads), ("conv_v", 4))}
    got = jax.jit(the_hand_over)(p, q, k, v, segs)
    want = jax.jit(the_parents_three_lines)(p, q, k, v, segs)
    for name, a, b in zip("qkv", got, want):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape, name
        bits = np.uint16 if dtype == jnp.bfloat16 else np.uint32
        assert np.array_equal(np.asarray(a).view(bits), np.asarray(b).view(bits)), name
    np.testing.assert_allclose(np.linalg.norm(np.asarray(got[0], np.float32), axis=-1), 1, atol=1e-2)
    assert np.abs(np.asarray(got[2], np.float32)).max() > 1


def test_off_a_tpu_and_at_other_shapes_the_plain_form_runs(monkeypatch, params):
    """The dispatch reads the backend and the shape, nothing else: here (the
    CPU) every shape takes the plain form and ``kda.fused_layers`` reads 0;
    told that the backend is a TPU it takes whole pairs of chunks of 64 at
    widths of whole 128s, in the largest tile that divides the row (a row
    that is no whole number of pairs falls back: it is not padded)."""
    from tpu_tfrecord.metrics import METRICS

    cell = (2, 64, 8192, 128)
    assert jax.default_backend() != "tpu" and linear_attn.fused_tile(cell, 64) is None
    called = []
    monkeypatch.setattr(linear_attn, "_delta_rule_fused", lambda *a, **k: called.append(a))
    for d in (8, 16):
        args = delta_rule_inputs(d, 128, d=d)
        linear_attn.delta_rule_chunked(*args, scale=0.25, chunk=64)
    batch, _ = packed_rows()
    cfg = program_cfg()
    # traced anew under the patch: the gauge is set as a program is traced
    jax.jit(lambda *a: lm.score(*a, cfg))(params, batch["tokens"], batch["segment_ids"], SAMPLE_AT)
    assert not called and METRICS.gauge_value("kda.fused_layers") == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert linear_attn.fused_tile(cell, 64) == 256
    assert linear_attn.fused_tile((1, 3, 768, 128), 64) == 256
    assert linear_attn.fused_tile((1, 2, 384, 256), 64) == 128
    for shape, chunk in [((1, 2, 8192, 16), 64), ((1, 2, 8192, 8), 64), ((1, 2, 8192, 192), 64),
                         ((1, 2, 150, 128), 64), ((1, 2, 8192 + 64, 128), 64)] + [
                             (cell, c) for c in (1, 4, 16, 128)]:
        assert linear_attn.fused_tile(shape, chunk) is None, (shape, chunk)
    args = kernel_inputs(0, 256)
    linear_attn.delta_rule_chunked(*args, scale=0.25, chunk=64)
    assert len(called) == 1 and called[0][-1] == 256             # the tile


def test_a_layer_that_takes_the_kernel_is_the_layer_and_is_counted(monkeypatch):
    """``kda_mixer`` at the kernel's width with the dispatch answering as it
    would on a TPU and Pallas interpreting: the layer the plain form gives,
    and ``score`` counts the pattern's delta-rule layers as fused (two here,
    three until PR 38: a count of two is no more a flag's 1 than three is,
    and the interpreted kernel is compiled a layer)."""
    from jax.experimental.pallas import tpu as pltpu

    from tpu_tfrecord.metrics import METRICS

    wide = {**CFG, "num_hidden_layers": 3,
            "linear_attn_config": {"num_heads": 2, "head_dim": 128, "short_conv_kernel_size": 4}}
    cfg = lm.PatternLMConfig(**{**program_cfg(wide, kda_chunk=64, attn_block=32).__dict__,
                                "max_len": 128})
    params = init_params(jax.random.PRNGKey(5), cfg)
    rng = np.random.default_rng(6)
    tokens = jnp.asarray(rng.integers(1, 64, (1, 129)), jnp.int32)
    segs = jnp.asarray([[1] * 50 + [2] * 70 + [0] * 9], jnp.int32)
    at = jnp.zeros((1, 1), jnp.int32)

    def traced_anew():  # the dispatch and the gauge are read as a program is traced
        return jax.jit(lambda *a: lm.score(*a, cfg))(params, tokens, segs, at)["logprob"]

    plain = traced_anew()
    assert METRICS.gauge_value("kda.fused_layers") == 0 and METRICS.gauge_value("conv.kernel_layers") == 0
    monkeypatch.setattr(linear_attn, "fused_tile",
                        lambda shape, chunk, key_width=0: 128 if shape[-1] == 128 and chunk == 64 else None)
    with pltpu.force_tpu_interpret_mode():
        fused = traced_anew()
    # both layers' kernels prepared their own q, k and v from the projections
    assert METRICS.gauge_value("kda.fused_layers") == 2 and METRICS.gauge_value("conv.kernel_layers") == 2
    np.testing.assert_allclose(fused, plain, atol=2e-5)
    assert np.abs(np.asarray(plain)).max() > 1


def test_the_recurrent_oracle_is_the_references_scan(params):
    """models.linear_attn's oracle and the plain reference walk one recurrence."""
    cfg = lm.PatternLMConfig(**{**program_cfg().__dict__, "kda_chunk": 1})
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((1, 24, CFG["hidden_size"])), jnp.float32)
    got = jax.jit(lambda p, x: lm.kda_mixer(p, x, jnp.ones((1, 24), jnp.int32), cfg)[0])(
        params["layers"][1], x)
    np.testing.assert_allclose(got[0], reference_mixer("kda", params["layers"][1], x[0]), atol=2e-5)


dense_attention = jax.jit(lambda q, k, v, segs: attention_reference(q, k, v, causal=True, segments=segs))


@pytest.mark.parametrize("block", [64, 32, 48, 16, 256])
def test_blockwise_attention_is_the_dense_oracle(block):
    """Rows of 100 tokens (200 until PR 38): still one block and seven, blocks
    that divide the row and that do not, boundaries inside a block and a pad
    tail; the program of 16-token blocks unrolls 28 pairs where it unrolled 91."""
    r = np.random.default_rng(0)
    q = jnp.asarray(r.standard_normal((2, 100, 8, 16)), jnp.float32)
    k, v = (jnp.asarray(r.standard_normal((2, 100, 2, 16)), jnp.float32) for _ in range(2))
    segs = np.zeros((2, 100), np.int32)
    segs[0, :45], segs[0, 45:65], segs[0, 65:97], segs[1] = 1, 2, 3, 1
    want = dense_attention(q, k, v, jnp.asarray(segs))
    got = jax.jit(functools.partial(blockwise_attention, block=block))(q, k, v, jnp.asarray(segs))
    np.testing.assert_allclose(got, want, atol=2e-6)


@functools.partial(jax.jit, static_argnums=(4,))
def interpreted_kernel(q, k, v, segs, block):
    """What ``lm._attend`` runs on a TPU, ``flash_attention_widths`` as it calls it,
    interpreted: one program a shape."""
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        return flash_attention_widths(q, k, v, segs, q.shape[-1] ** -0.5, block, block)


plain_attention = jax.jit(lambda q, k, v, segs: jnp.swapaxes(blockwise_attention(
    jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), segs, block=64), 1, 2))


def held_to_the_plain_path(q, k, v, segs, block):
    """The kernel (interpreted here; called on a chip, outside pytest's conftest,
    which pins the CPU, ``lm._attend`` runs it as it is) against the plain path."""
    want = plain_attention(q, k, v, segs)
    got = (lm._attend(q, k, v, segs, block) if jax.default_backend() == "tpu"
           else interpreted_kernel(q, k, v, segs, block))
    real = np.asarray(segs[0] != 0)
    # on a chip both paths multiply float32 at the default precision (one bfloat16
    # pass): they agree to that rounding; a mask gone wrong moves the answer by 0.3 and more
    np.testing.assert_allclose(np.asarray(got)[:, :, real], np.asarray(want)[:, :, real],
                               atol=3e-2 if jax.default_backend() == "tpu" else 2e-5)


@pytest.mark.parametrize("block", [128, 256])
def test_the_kernel_a_tpu_runs_is_blockwise_attention(block):
    """``lm._attend`` runs the repo's Pallas kernel on a TPU, the full softmax
    layer's grouped heads on the K/V heads as they lie, and
    ``blockwise_attention`` elsewhere: the kernel against the plain path on a
    packed row: 4 query heads on 2 K/V heads, three documents and a pad tail."""
    r = np.random.default_rng(1)
    q = jnp.asarray(r.standard_normal((1, 4, 512, 128)), jnp.float32)      # [B, H, L, D]
    k, v = (jnp.asarray(r.standard_normal((1, 2, 512, 128)), jnp.float32) for _ in range(2))
    segs = np.zeros((1, 512), np.int32)
    segs[0, :100], segs[0, 100:130], segs[0, 130:400] = 1, 2, 3
    held_to_the_plain_path(q, k, v, jnp.asarray(segs), block)


def test_eight_query_heads_read_one_key_head_and_disjoint_blocks_are_skipped():
    """The grouping at its widest (8 query heads on ONE K/V head: a grid step's two
    heads read the same key block) on a row whose second half is a document of its
    own: the block pairs that hold the second document's queries and the first's
    keys share no document and are skipped (4 of the 10 at or under the diagonal
    in blocks of 128), and the answer is the plain path's."""
    r = np.random.default_rng(2)
    q = jnp.asarray(r.standard_normal((1, 8, 512, 128)), jnp.float32)
    k, v = (jnp.asarray(r.standard_normal((1, 1, 512, 128)), jnp.float32) for _ in range(2))
    rows = np.repeat(np.array([1, 1, 2, 2], np.int32), 128)[None]
    # query blocks 2 and 3 against key blocks 0 and 1: four pairs skipped; the second document's
    # one pair under the diagonal and the first's are plain; the four on the diagonal masked
    assert pair_kinds(rows, 128, 128) == (4, 2, 4)
    segs = jnp.asarray(rows)
    held_to_the_plain_path(q, k, v, segs, 128)
    # the skipping is at work: a pair computed and masked would weigh the first document's
    # values by 0, and 0 x NaN is NaN; a pair skipped reads none of them
    nan = jnp.full((1, 1, 256, 128), jnp.nan, jnp.float32)
    poisoned = interpreted_kernel(q, k.at[:, :, :256].set(nan), v.at[:, :, :256].set(nan), segs, 128)
    np.testing.assert_allclose(np.asarray(poisoned)[:, :, 256:],
                               np.asarray(plain_attention(q, k, v, segs))[:, :, 256:], atol=2e-5)


def equations(jaxpr):
    """The equations of ``jaxpr`` and of every jaxpr inside them."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


def test_a_tpu_hands_the_kernel_the_key_heads_as_they_lie(monkeypatch):
    """The full softmax layer as a TPU traces it (the backend stubbed): one Pallas
    call, handed K and V as the 2 heads the projections wrote under 8 query heads.
    No operation but the query's and the gate's projections, the gate and the
    kernel itself makes an array of the query heads' shape: no ``repeat`` of K or
    V (a ``broadcast_in_dim`` and a reshape), no copy a query head."""
    monkeypatch.setattr(lm.jax, "default_backend", lambda: "tpu")
    cfg = lm.PatternLMConfig(
        vocab_size=64, d_model=32, layer_pattern=("gqa",), ffn_pattern=("dense",), n_heads=8,
        n_kv_heads=2, head_dim=128, d_dense=16, max_len=256, attn_block=128, dtype=jnp.float32)
    p = jax.eval_shape(lambda: lm.pattern_init_params(jax.random.PRNGKey(0), cfg))["layers"][0]
    x, segs = jax.ShapeDtypeStruct((1, 256, 32), jnp.float32), jax.ShapeDtypeStruct((1, 256), jnp.int32)
    eqns = list(equations(jax.make_jaxpr(lambda p, x, s: lm.gqa_mixer(p, x, s, cfg))(p, x, segs).jaxpr))
    (kernel,) = [e for e in eqns if e.primitive.name == "pallas_call"]
    heads, held = (1, 8, 256, 128), (1, 2, 256, 128)
    assert [v.aval.shape for v in kernel.invars[-3:]] == [heads, held, held]
    # what is as large as the query heads (or a repeat's [1, 2, 4, 256, 128] on its way there): q
    # and the gate, each transposed from its product; the kernel's output and its jitted call
    # site; the gate's sigmoid and its product with the output
    wide = sorted(e.primitive.name for e in eqns for out in e.outvars
                  if getattr(out.aval, "shape", ()) in (heads, (1, 2, 4, 256, 128)))
    assert wide == ["jit", "logistic", "mul", "pallas_call", "transpose", "transpose"]
    # and K and V go from their projections to the call and nowhere else
    (site,) = [e for e in eqns if e.primitive.name == "jit" and e.outvars[0].aval.shape == heads]
    for operand in site.invars[1:3]:
        assert operand.aval.shape == held
        assert [e for e in eqns if operand in e.invars] == [site]
        assert [e.primitive.name for e in eqns if operand in e.outvars] == ["transpose"]


def moe_layer(seed=0, t=96, skew=0.0):
    cfg = {**CFG, "n_routed_experts_held": CFG["n_routed_experts"], "held_offset": 0,
           "num_hidden_layers": 1}                              # the one layer taken
    p = init_params(jax.random.PRNGKey(seed), program_cfg(cfg))["layers"][0]
    p["router"] = p["router"].at[:, 5].add(skew)
    x = np.random.default_rng(seed).standard_normal((t, CFG["hidden_size"]))
    # under a skew every token's score for expert 5 saturates: positive rows
    return cfg, p, jnp.asarray(np.abs(x) if skew else x, jnp.float32)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four chips of 4 experts each, the shared expert counted once, against
    the reference told that it holds all 16."""
    cfg, p, x = moe_layer()
    with jax.default_matmul_precision("highest"):
        whole, _, _ = ref.ref_moe(flat(p), x, cfg)
        shared = ref.ref_ffn(x, *(jnp.asarray(p["shared"][k]) for k in ("w_gate", "w_up", "w_down")))
    total, visits = -3 * shared, 0
    for first in range(0, 16, 4):
        share = {**p, **{k: p[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}}
        y, n, dropped, _ = held_experts(share, x, held_offset=first, top_k=4, tile=8)
        total, visits = total + y, visits + int(n.sum())
        assert int(dropped) == 0
    assert visits == x.shape[0] * 4
    np.testing.assert_allclose(total, whole, atol=2e-5)


@pytest.mark.parametrize("tile", [8, 32, 256])
def test_a_skewed_router_costs_time_and_never_a_visit(tile):
    """Every token picks expert 5: its run is many tiles long and none is lost."""
    cfg, p, x = moe_layer(seed=1, skew=4.0)
    share = {**p, **{k: p[k][4:8] for k in ("w_gate", "w_up", "w_down")}}
    y, n, dropped, _ = held_experts(share, x, held_offset=4, top_k=4, tile=tile)
    assert int(n[1]) == x.shape[0] and int(dropped) == 0
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.ref_moe(flat(share), x, {**cfg, "n_routed_experts_held": 4, "held_offset": 4})
    np.testing.assert_allclose(y, want, atol=2e-5)
    # the reference under a capacity (the control) does lose them, and says so
    _, lost, _ = ref.ref_moe(flat(share), x, {**cfg, "n_routed_experts_held": 4, "held_offset": 4},
                             capacity=24)
    assert int(lost) >= x.shape[0] - 24


#: visits to the one held expert under a unit of 8 rows and tiles of 32: none, one, around a
#: unit, around a tile, several tiles and a tail
RUNS = [0, 1, 7, 8, 9, 31, 32, 33, 55, 71, 96]


def one_run(visits, held, t=96):
    """A layer of 16 experts and tokens of which the first ``visits`` pick expert 5 and no
    other token does (its router column is raised or sunk), with the share of the experts
    that starts at expert 5: ``held`` of them."""
    cfg, p, x = moe_layer(seed=2, t=t)
    x = jnp.abs(x).at[visits:].multiply(-1.0)                # rows of one sign: a column decides
    p["router"] = p["router"].at[:, 5].set(4.0)
    share = {**p, **{k: p[k][5:5 + held] for k in ("w_gate", "w_up", "w_down")}}
    return {**cfg, "n_routed_experts_held": held, "held_offset": 5}, share, x


@pytest.fixture
def unit_of_8(monkeypatch):
    """The tail's unit at a size these layers fill: tiles of 16, 24 and 32 rows have tails."""
    monkeypatch.setattr(moe, "TAIL_UNIT", 8)
    return jax.jit(moe.held_experts_apply, static_argnames=(
        "held_offset", "top_k", "routed_scale", "tile", "n_group", "topk_group"))


@pytest.mark.parametrize("held", [2, 1], ids=["read_back", "added_as_computed"])
@pytest.mark.parametrize("visits", RUNS)
def test_a_run_of_any_length_goes_through_whole_tiles_and_tails(unit_of_8, visits, held):
    """Both forms (an eighth of the experts held: laid down and read back; a sixteenth:
    added as computed) against the reference, for runs that end before, on and after a
    unit's and a tile's edge; nothing dropped, the visits what the router chose."""
    cfg, share, x = one_run(visits, held)
    assert moe.adds_as_computed(held, 16) == (held == 1) and moe.row_unit(32) == 8
    y, n, dropped, (experts, _) = unit_of_8(share, x, held_offset=5, top_k=4, tile=32)
    assert int(n[0]) == visits == int((experts == 5).sum()) and int(dropped) == 0
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.ref_moe(flat(share), x, cfg)
    np.testing.assert_allclose(y, want, atol=2e-5)
    # and the tile that is the unit, or no multiple of it, is the one loop it was
    for tile in (8, 12):
        again, n_again, dropped, _ = unit_of_8(share, x, held_offset=5, top_k=4, tile=tile)
        assert moe.row_unit(tile) == tile and int(dropped) == 0
        np.testing.assert_array_equal(n_again, n)
        np.testing.assert_allclose(again, want, atol=2e-5)


@pytest.mark.parametrize("tile, held, rows", [
    (32, 1, [0, 8, 8, 8, 16, 32, 32, 40, 56, 72, 96]),       # added as computed: every tail through tail tiles
    (32, 2, [0, 8, 8, 8, 16, 32, 32, 40, 64, 72, 96]),       # read back: a tail of three units takes the whole tile
    (16, 2, [0, 8, 8, 8, 16, 32, 32, 40, 56, 72, 96]),       # two units a tile: the one tail there can be
    (24, 2, [0, 8, 8, 8, 16, 32, 32, 40, 56, 72, 96]),       # three: a tail of one or two
    (8, 2, [0, 8, 8, 8, 16, 32, 32, 40, 56, 72, 96]),        # the tile is the unit
    (12, 2, [0, 12, 12, 12, 12, 36, 36, 36, 60, 72, 96]),    # no multiple of the unit: whole tiles alone
])
def test_the_rows_the_two_loops_compute_are_what_the_gauge_counts(unit_of_8, monkeypatch, tile, held, rows):
    """``moe.region_units`` against the loops themselves: each tile's real rows are counted
    as it is computed (``done``), and a patched expert unit counts the rows it is handed."""
    from tpu_tfrecord.metrics import METRICS

    scattered = moe.adds_as_computed(held, 16)
    got = [int(moe.region_units(np.int64(v), tile, scattered)) * moe.row_unit(tile) for v in RUNS]
    assert got == rows
    handed = []
    unit = moe.expert_unit
    monkeypatch.setattr(moe, "expert_unit", lambda x, w, limit=None, e=None: (
        handed.append(x.shape[0]) if e is not None else None, unit(x, w, limit, e))[1])
    cfg, share, x = one_run(55, held)
    jaxpr = jax.make_jaxpr(lambda p, x: moe.held_experts_apply(
        p, x, held_offset=5, top_k=4, tile=tile))(share, x)
    loops = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "while"
             and "dot_general" in str(e.params["body_jaxpr"])]
    assert sorted(handed, reverse=True) == ([tile, 8] if moe.row_unit(tile) == 8 and tile > 8 else [tile])
    assert len(loops) == len(handed)
    # one layer's visits as a step's: the gauge is the real visits over the rows of the run of 55
    cfg_of = program_cfg({**CFG, "n_routed_experts_held": held}, expert_tile=tile)
    lm.record_moe_counters(np.array([[55] + [0] * (held - 1)]), np.array([0]), cfg_of)
    assert METRICS.gauge_value("moe.tile_fill") == round(55 / rows[RUNS.index(55)], 4)


#: visits to six held experts that sum to 96 tokens' 4 choices, so that no visit of the sorted
#: order is an absent expert's: none; runs that end on a tile's edge under every tiling here and
#: on a unit's edge alone (88); a ragged run; and a last run whose last tile reaches 3 places or
#: more past the end of the order, into its pad
TILED_VISITS = [0, 96, 96, 88, 91, 13]
TILINGS = [32, 16, 24, 8, 12]
FORMS = pytest.mark.parametrize("scattered", [False, True], ids=["read_back", "added_as_computed"])


def a_walk_of_the_runs(visits, tile, scattered):
    """Each loop's tiles as (expert, first place in the sorted order, first row of the buffer,
    real rows), run by run in plain Python: an expert's whole tiles, then its tails."""
    unit = moe.row_unit(tile)
    per = tile // unit
    loops, place, row = ([], []), 0, 0
    for e, v in enumerate(visits):
        units = -(-v // unit)
        if units % per > moe.tail_units(per, scattered):
            units += per - units % per
        for k in range(units // per):
            at = k * tile
            loops[0].append((e, place + at, row + at, min(v - at, tile)))
        for k in range(units % per):
            at = units // per * tile + k * unit
            loops[1].append((e, place + at, row + at, min(v - at, unit)))
        place, row = place + v, row + units * unit
    return list(loops[:1 if per == 1 else 2])


@FORMS
@pytest.mark.parametrize("tile", TILINGS)
def test_every_row_of_the_tables_is_the_tile_a_walk_of_the_runs_finds(unit_of_8, tile, scattered):
    """``moe.tile_tables`` against the arithmetic each tile did for itself: expert, first place,
    buffer row and real rows of every tile there is, in the loops' order, and no more tiles
    than the table has rows for."""
    unit = moe.row_unit(tile)
    rng = np.random.default_rng(tile)
    for visits in (TILED_VISITS, [0] * 6, [384, 0, 0, 0, 0, 0], *rng.multinomial(300, [1 / 6] * 6, size=4).tolist()):
        most = moe.buffer_units(384, 6, tile, scattered)
        first, region, loops = moe.tile_tables(jnp.asarray(visits, jnp.int32), tile, scattered, most)
        want = a_walk_of_the_runs(visits, tile, scattered)
        assert [rows for rows, _, _ in loops] == ([tile, unit] if len(want) == 2 else [tile])
        for (rows, tiles, table), tiles_of in zip(loops, want):
            assert int(tiles) == len(tiles_of) <= table.shape[0]
            got = np.asarray(table)[:len(tiles_of)] * [1, 1, unit, 1]
            np.testing.assert_array_equal(got.reshape(-1, 4), np.asarray(tiles_of).reshape(-1, 4))
        np.testing.assert_array_equal(first, np.cumsum(visits) - visits)
        units = moe.region_units(np.asarray(visits), tile, scattered)
        np.testing.assert_array_equal(region, np.cumsum(units) - units)


def a_layer_that_visits(visits, n_experts, seed=3):
    """96 tokens, ``n_experts`` experts of which numbers 5 to 10 are held, and a router under
    which token ``i`` picks exactly the held experts whose mark its row carries: a held
    expert's column sinks every row but the marked (rows are positive), so the held experts'
    visits are ``visits`` and, four marks a token, no visit is an absent expert's."""
    cfg, p, x = moe_layer(seed=seed)
    assert sum(visits) == 4 * x.shape[0] and visits[0] == 0
    left_out = np.repeat(np.arange(1, 6), [x.shape[0] - v for v in visits[1:]])   # a token omits one of five
    marks = np.ones((x.shape[0], 6), np.float32)
    marks[:, 0] = 0
    marks[np.arange(x.shape[0]), left_out] = 0
    np.testing.assert_array_equal(marks.sum(axis=0), visits)
    x = jnp.abs(x).at[:, :6].set(marks)
    router = np.random.default_rng(seed).standard_normal((x.shape[1], n_experts)).astype(np.float32)
    router[:, 5:11] = -1.0
    router[np.arange(6), 5 + np.arange(6)] = 60.0
    share = {**p, "router": jnp.asarray(router), **{k: p[k][5:11] for k in ("w_gate", "w_up", "w_down")}}
    return {**cfg, "n_routed_experts": n_experts, "n_routed_experts_held": 6, "held_offset": 5}, share, x


@FORMS
@pytest.mark.parametrize("tile", TILINGS)
def test_a_layer_reads_its_tiles_from_the_table_and_no_row_past_them(unit_of_8, monkeypatch, tile, scattered):
    """The layer under ``TILED_VISITS`` in both forms against the reference: an expert
    without a visit, runs that end on a unit's and on a tile's edge, a last tile whose slice
    reaches into the pad of the sorted order. The tables' rows past the tiles there are hold
    poison here: what no tile reads cannot show."""
    tables = moe.tile_tables

    def poisoned(*args):
        first, region, loops = tables(*args)
        return first, region, [(rows, tiles, jnp.where(
            jnp.arange(table.shape[0])[:, None] < tiles, table, 2 ** 30)) for rows, tiles, table in loops]

    monkeypatch.setattr(moe, "tile_tables", poisoned)
    cfg, share, x = a_layer_that_visits(TILED_VISITS, 56 if scattered else 16)
    assert moe.adds_as_computed(6, cfg["n_routed_experts"]) == scattered
    y, n, dropped, (experts, _) = unit_of_8(share, x, held_offset=5, top_k=4, tile=tile)
    np.testing.assert_array_equal(n, TILED_VISITS)
    assert int(dropped) == 0 and ((experts >= 5) & (experts < 11)).all()     # the order holds held visits alone
    reach = max(place + rows for tiles_of, rows in zip(a_walk_of_the_runs(TILED_VISITS, tile, scattered), (tile, 8))
                for _, place, _, _ in tiles_of)
    assert reach >= 4 * x.shape[0] + 3                          # a slice reaches into the pad
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.ref_moe(flat(share), x, cfg)
    np.testing.assert_allclose(y, want, atol=2e-5)


@pytest.mark.parametrize("tile, held", [(32, 2), (32, 1), (8, 2), (8, 1)],
                         ids=["two_loops_read_back", "two_loops_added", "one_loop_read_back", "one_loop_added"])
def test_a_loops_body_holds_no_search_and_no_gather_of_indices(unit_of_8, tile, held):
    """What a tile does beside its products: one read of its table's row, one slice of the
    padded order, ONE gather (its rows of x) and the write; where rows are added as computed
    also the gates' gather and the one scatter-add. No ``while`` inside the loop (a search),
    no ``sort``, no ``cumsum``: a tile works out nothing about where it is."""
    cfg, share, x = one_run(55, held)
    jaxpr = jax.make_jaxpr(lambda p, x: moe.held_experts_apply(
        p, x, held_offset=5, top_k=4, tile=tile))(share, x)
    loops = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "while"]
    assert len(loops) == (2 if tile == 32 else 1)                # and none outside them: the table is no search
    for loop, rows in zip(loops, (tile, 8)):
        body = list(equations(loop.params["body_jaxpr"].jaxpr))
        inside = [e.primitive.name for e in body]
        assert not {"while", "sort", "cumsum", "argsort"} & set(inside)
        scattered = moe.adds_as_computed(held, 16)
        assert inside.count("gather") == (2 if scattered else 1)
        assert inside.count("scatter-add") == (1 if scattered else 0)
        assert inside.count("dynamic_update_slice") == (0 if scattered else 1)
        slices = [e for e in body if e.primitive.name == "dynamic_slice"]
        shapes = [tuple(e.outvars[0].aval.shape) for e in slices]
        # the table's row, the tile's visits out of the padded order, and an expert's three matrices
        assert shapes.count((1, 4)) == shapes.count((rows,)) == 1 and len(shapes) == 5
        (visits,) = [e for e in slices if e.outvars[0].aval.shape == (rows,)]
        assert visits.invars[0].aval.shape == (x.shape[0] * 4 + tile,)


def test_the_moe_counters_sit_beside_pack_density():
    from tpu_tfrecord.metrics import METRICS

    before = METRICS.counter("moe.visits_dropped")
    uneven = lm.record_moe_counters(np.array([[10, 30], [20, 20]]), np.array([0, 0]))
    assert uneven == 1.5 and METRICS.gauge_value("moe.visits_max_over_mean") == 1.5
    assert METRICS.counter("moe.visits_dropped") == before


@pytest.mark.parametrize("tile, tail_unit, fill", [(8, 0, 0.8333), (512, 256, 0.0781), (1024, 256, 0.0781)])
def test_the_tilings_gauges_sit_beside_them(params, tile, tail_unit, fill):
    """``moe.tail_unit`` is set as the score program is traced (the rows of a tail tile, 0
    where one loop runs), and the two-argument call, the benchmark's, reckons
    ``moe.tile_fill`` by the program last traced: the emptiest layer's visits over its rows."""
    from tpu_tfrecord.metrics import METRICS

    cfg = program_cfg(expert_tile=tile)
    batch, _ = packed_rows()
    jax.make_jaxpr(lambda p, t, s, a: lm.score(p, t, s, a, cfg))(
        params, batch["tokens"], batch["segment_ids"], SAMPLE_AT)
    assert METRICS.gauge_value("moe.tail_unit") == tail_unit
    lm.record_moe_counters(np.array([[10, 30, 0, 0], [20, 20, 20, 20]]), np.array([0, 0]))
    assert METRICS.gauge_value("moe.tile_fill") == fill
    # a caller that names the program is not at the mercy of what was traced last
    lm.record_moe_counters(np.array([[8, 8, 8, 8]]), np.array([0]), program_cfg(expert_tile=8))
    assert METRICS.gauge_value("moe.tile_fill") == 1.0


@pytest.mark.parametrize("packing", ["best_fit", "first_fit"])
def test_the_packer_at_8192_gives_every_token_once(packing):
    rng = np.random.default_rng(11)
    lengths = np.clip(np.rint(np.exp(rng.normal(6.5, 1.2, 400))), 16, 8192).astype(int)
    docs = [rng.integers(1, 24576, size=n).astype(np.int32) for n in lengths]
    packer = TokenPacker(2, 8192, packing=packing)
    seen = []
    for at in range(0, len(docs), 16):
        packer.feed_docs(docs[at:at + 16])
        while (b := packer.pop()) is not None:
            seen += [d[:-1] for _, _, d in documents_of(b)]
            assert (b["tokens"][b["segment_ids"] == 0] == 0).all()
    packer.flush()
    while (b := packer.pop()) is not None:
        seen += [d[:-1] for _, _, d in documents_of(b)]
    key = lambda d: d.tobytes()  # noqa: E731
    assert sorted(map(key, seen)) == sorted(map(key, docs))
    assert 0.5 < packer.density() <= 1.0


def test_a_restored_packer_keeps_its_running_fill():
    a, b = TokenPacker(2, 32, packing="best_fit"), TokenPacker(2, 32, packing="best_fit")
    docs = [np.arange(1, n) for n in (9, 20, 5, 7, 30, 3)]
    a.feed_docs(docs[:3])
    b.restore(a.state())
    for p in (a, b):
        p.feed_docs(docs[3:])
        p.flush()
    while (x := a.pop()) is not None:
        y = b.pop()
        assert (x["tokens"] == y["tokens"]).all() and (x["segment_ids"] == y["segment_ids"]).all()
    assert b.pop() is None
