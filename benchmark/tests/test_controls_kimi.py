"""``kimi_vl_a3b_lm.score``'s controls at a size a test run can hold, judged
as a run is judged: each control's numbers go through ``window.judge``
against the mix's rehearsal limits, the limits the rehearsed program itself
is held to (``test_rehearsal_kimi.py``), and has to come out not
``correct``; the float32 program on the same documents comes out
``correct`` by the same limits. Two controls are left to the chip
(``controls_kimi.main``; PERF.md has the readings): ``bf16_rotary`` cannot
show on documents of 128 tokens, whose positions bfloat16 holds exactly,
and ``bf16_softmax`` shows in no end-to-end number at any size."""

import functools

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.loops import score_docs
from benchmark.models import kimi_vl_lm as model
from benchmark.tests import controls_docs, controls_kimi
from benchmark.tests.controls import load_cell

SEEDS = (11, 2 ** 31 + 12)


@functools.lru_cache(maxsize=None)
def small_cell():
    cfg, mix = load_cell(controls_kimi.CELL)
    sizes = {k: v for k, v in mix["rehearsal"].items() if k != "limits"}
    limits = {**mix["limits"], **mix["rehearsal"]["limits"]}
    return bench_run.at_rehearsal_size(cfg), {**mix, **sizes, "limits": limits}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", ["carried_positions", "no_router_bias", "bf16_router",
                                     "int8_weights", "dropped_visits"])
def test_the_control_is_not_correct(control, seed):
    cfg, mix = small_cell()
    numbers = controls_kimi.control_numbers(model, cfg, mix, seed, [control])[control]
    assert "scan_state_gap" not in numbers
    correct, outside = controls_docs.judged(numbers, mix["limits"])
    assert not correct and outside, f"{control} stayed inside every limit: {numbers}"
    must = {"no_router_bias": "router_gate_gap", "bf16_router": "router_gate_gap",
            "carried_positions": "boundary_median_gap", "dropped_visits": "moe_visits_dropped"}
    assert must.get(control, outside[0]) in outside, (control, outside, numbers)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_float32_program_is_correct_by_the_same_limits(seed):
    import jax
    import jax.numpy as jnp
    from tpu_tfrecord.models import lm
    from tpu_tfrecord.tpu.ingest import TokenPacker

    cfg, mix = small_cell()
    docs = controls_docs.step_documents(cfg, mix, seed, mix["batch"] * mix["row_tokens"])
    packer = TokenPacker(mix["batch"], mix["row_tokens"], packing=mix["packing"])
    packer.feed_docs([d[:-1] for d in docs])
    packer.flush()
    batch = packer.pop()
    pcfg = lm.PatternLMConfig(**{**model.program(cfg, mix).__dict__, "dtype": jnp.float32})
    params = jax.tree.map(lambda a: a.astype(jnp.float32), model.program_params(seed, cfg))
    at = score_docs.sample_positions(seed, mix["batch"], mix["row_tokens"], mix["logit_samples"])
    out = jax.tree.map(np.asarray, lm.score(
        params, batch["tokens"], batch["segment_ids"], jnp.asarray(at), pcfg, jnp.int32(1)))
    env = type("Env", (), {"expected": [d[:-1] for d in docs]})
    weights = model.reference_weights(seed, cfg)
    sound, strangers, n = score_docs.compare_steps(
        env, [{**out, **batch}], at,
        lambda docs, where: model.reference_score(cfg, docs, weights, where),
        lambda scans, routed: model.probe_numbers(cfg, seed, scans, routed))
    assert strangers == 0 and n > 0 and max(sound.values()) < 5e-3, sound
    correct, outside = controls_docs.judged(sound, mix["limits"])
    assert correct and not outside


def test_a_document_shifted_whole_reads_like_itself():
    """Why ``carried_positions`` shifts the keys alone: with queries and keys
    both counted from the document's start in its row, nothing moves but
    float32's rounding of larger angles."""
    import jax
    import jax.numpy as jnp

    cfg, _ = small_cell()
    p = model.part_weights(SEEDS[0], cfg, 1)
    u = jnp.asarray(np.random.default_rng(3).standard_normal((40, cfg["hidden_size"])), jnp.float32)
    with jax.default_matmul_precision("highest"):
        alone, one_sided = model.ref_mla(p, u, cfg), model.ref_mla(p, u, cfg, key_start=77)
        at = jnp.arange(40)
        turned = model.ref_rope(u[:, None, :8], at, 800000.0), model.ref_rope(u[:, None, :8], at + 77, 800000.0)
    scores = [np.einsum("qhd,khd->qk", t, t) for t in map(np.asarray, turned)]
    np.testing.assert_allclose(scores[0], scores[1], atol=2e-5)      # differences of positions only
    assert np.abs(np.asarray(alone) - np.asarray(one_sided)).max() > 1e-2


def test_bfloat16_angles_lose_the_positions_of_a_long_document():
    """``bf16_rotary`` at the cell's lengths: bfloat16 holds integers to 256."""
    import jax.numpy as jnp

    x = jnp.ones((2, 1, 64), jnp.float32)
    at = jnp.asarray([3, 5001])
    sound = np.asarray(model.ref_rope(x, at, 800000.0))
    low = np.asarray(model.ref_rope(x, at, 800000.0, jnp.bfloat16))
    assert np.abs(sound[0] - low[0]).max() < 0.1 < 1.0 < np.abs(sound[1] - low[1]).max()
