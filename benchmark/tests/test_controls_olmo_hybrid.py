"""``olmo_hybrid_7b_pp4.score``'s controls at a size a test run can hold,
judged as a run is judged: each control's numbers go through ``window.judge``
against the mix's rehearsal limits, the limits the rehearsed program itself is
held to (``test_rehearsal_olmo_hybrid.py``), and has to come out not
``correct``; the float32 program on the same documents comes out ``correct`` by
the same limits. On the chip: ``controls_olmo_hybrid.main``
(benchmark/TOKEN_DOCS_OLMO_HYBRID.md has the readings)."""

import functools

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.loops import score_docs, score_docs_dense
from benchmark.models import olmo_hybrid as model
from benchmark.tests import controls_docs, controls_olmo_hybrid
from benchmark.tests.controls import load_cell

SEEDS = (11, 2 ** 31 + 12)
#: the number that has to refuse a control, where one number is what it is there for
MUST = {"carried_state": "scan_state_gap", "carried_taps": "boundary_median_gap", "bf16_state": "scan_state_gap",
        "scale_by_dv": "scan_state_gap"}


@functools.lru_cache(maxsize=None)
def small_cell():
    cfg, mix = load_cell(controls_olmo_hybrid.CELL)
    sizes = {k: v for k, v in mix["rehearsal"].items() if k != "limits"}
    limits = {**mix["limits"], **mix["rehearsal"]["limits"]}
    return bench_run.at_rehearsal_size(cfg), {**mix, **sizes, "limits": limits}


def test_every_control_the_issue_names_is_run_here():
    assert set(controls_olmo_hybrid.CONTROLS) == {
        "pre_norm_gdn", "per_head_qk_norm", "no_qk_norm", "rotary_on_full", "sigmoid_gate", "beta_times_1",
        "scale_by_dv", "carried_state", "carried_taps", "bf16_state", "int8_weights"}
    assert set(MUST) < set(controls_olmo_hybrid.CONTROLS)
    _, mix = load_cell(controls_olmo_hybrid.CELL)
    assert "router_gate_gap" not in mix["limits"] and "moe_visits_dropped" not in mix["limits"]   # no router exists


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", controls_olmo_hybrid.CONTROLS)
def test_the_control_is_not_correct(control, seed):
    cfg, mix = small_cell()
    numbers = controls_olmo_hybrid.control_numbers(model, cfg, mix, seed, [control])[control]
    correct, outside = controls_docs.judged(numbers, mix["limits"])
    assert not correct and outside, f"{control} stayed inside every limit: {numbers}"
    if control in MUST:
        assert MUST[control] in outside, (control, outside, numbers)


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
        params, batch["tokens"], batch["segment_ids"], jnp.asarray(at), pcfg, jnp.int32(3)))
    score_docs_dense.without_experts(out)
    env = type("Env", (), {"expected": [d[:-1] for d in docs]})
    weights = model.reference_weights(seed, cfg)
    sound, strangers, n = score_docs_dense.compare_steps(
        env, [{**out, **batch}], at,
        lambda docs, where: model.reference_score(cfg, docs, weights, where),
        lambda scans, routed: model.probe_numbers(cfg, seed, scans, routed))
    assert strangers == 0 and n > 0 and max(sound.values()) < 5e-3, sound
    assert 0 < sound["scan_state_gap"] < 1e-5 and "router_gate_gap" not in sound
    correct, outside = controls_docs.judged(sound, mix["limits"])
    assert correct and not outside


def test_the_float64_walk_reads_the_recurrence_as_written():
    """``walk_head`` on a recurrence made by hand, keys of 3 under values of 5:
    the state [3 x 5] after two tokens with a beta above 1, and
    ``probe_numbers`` reading 0 on its own walk, the rounding of an output kept
    in bfloat16, and the other scale."""
    import ml_dtypes

    rng = np.random.default_rng(0)
    q, k, v = rng.standard_normal((5, 3)), rng.standard_normal((5, 3)), rng.standard_normal((5, 5))
    beta, g = rng.uniform(1.0, 2.0, 5), -rng.uniform(0.01, 2.0, 5)
    s1 = np.outer(k[0], beta[0] * v[0])
    s2 = np.exp(g[1]) * s1
    s2 = s2 + np.outer(k[1], beta[1] * (v[1] - k[1] @ s2))
    got = model.walk_head(q, k, v, g, beta, 3 ** -0.5)
    assert got.shape == (5, 5)
    np.testing.assert_allclose(got[:2], np.stack([q[0] @ s1, q[1] @ s2]) * 3 ** -0.5, rtol=1e-12)
    cfg = {"linear_key_head_dim": 3}
    scan = dict(q=q, k=k, v=v, log_decay=g, beta=beta, o=got)
    assert model.probe_numbers(cfg, 0, [scan], [{}]) == {"scan_state_gap": 0.0}
    scan["o"] = got.astype(ml_dtypes.bfloat16).astype(np.float64)
    assert 1e-4 < model.probe_numbers(cfg, 0, [scan], [{}])["scan_state_gap"] < 1e-2
    scan["o"] = got * (3 / 5) ** 0.5                      # q times dv^-1/2 where the model says dk^-1/2
    assert abs(model.probe_numbers(cfg, 0, [scan], [{}])["scan_state_gap"] - (1 - (3 / 5) ** 0.5)) < 1e-12
