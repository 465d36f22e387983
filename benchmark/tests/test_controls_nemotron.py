"""``nemotron_twotower_ep2.score``'s controls at a size a test run can hold,
judged as a run is judged: each control's numbers go through ``window.judge``
against the mix's rehearsal limits, the limits the rehearsed program itself is
held to (``test_rehearsal_nemotron.py``), and has to come out not ``correct``;
the float32 program on the same documents comes out ``correct`` by the same
limits. On the chip: ``controls_nemotron.main``
(benchmark/TOKEN_DOCS_NEMOTRON.md has the readings)."""

import functools

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.loops import score_docs
from benchmark.models import nemotron_h as model
from benchmark.tests import controls_docs, controls_nemotron
from benchmark.tests.controls import load_cell

SEEDS = (11, 2 ** 31 + 12)
#: the number that has to refuse a control, where one number is what it is there for
MUST = {"carried_state": "scan_state_gap", "bf16_state": "scan_state_gap",
        "bf16_router": "router_gate_gap", "dropped_visits": "moe_visits_dropped"}


@functools.lru_cache(maxsize=None)
def small_cell():
    cfg, mix = load_cell(controls_nemotron.CELL)
    sizes = {k: v for k, v in mix["rehearsal"].items() if k != "limits"}
    limits = {**mix["limits"], **mix["rehearsal"]["limits"]}
    return bench_run.at_rehearsal_size(cfg), {**mix, **sizes, "limits": limits}


def test_every_control_the_issue_names_is_run_here():
    assert len(controls_nemotron.CONTROLS) == 14 and set(MUST) < set(controls_nemotron.CONTROLS)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", controls_nemotron.CONTROLS)
def test_the_control_is_not_correct(control, seed):
    cfg, mix = small_cell()
    numbers = controls_nemotron.control_numbers(model, cfg, mix, seed, [control])[control]
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
    env = type("Env", (), {"expected": [d[:-1] for d in docs]})
    weights = model.reference_weights(seed, cfg)
    sound, strangers, n = score_docs.compare_steps(
        env, [{**out, **batch}], at,
        lambda docs, where: model.reference_score(cfg, docs, weights, where),
        lambda scans, routed: model.probe_numbers(cfg, seed, scans, routed))
    assert strangers == 0 and n > 0 and max(sound.values()) < 5e-3, sound
    assert 0 < sound["scan_state_gap"] < 1e-5
    correct, outside = controls_docs.judged(sound, mix["limits"])
    assert correct and not outside


def test_the_float64_walk_reads_the_recurrence_as_written():
    """``walk_head`` on a recurrence made by hand: the state after two tokens,
    and ``probe_numbers`` reading 0 on its own walk and the rounding of an
    output kept in bfloat16."""
    import ml_dtypes

    rng = np.random.default_rng(0)
    x, b, c = rng.standard_normal((5, 4)), rng.standard_normal((5, 3)), rng.standard_normal((5, 3))
    dt, g = rng.uniform(0.01, 0.1, 5), -rng.uniform(0.01, 2.0, 5)
    s1 = dt[0] * np.outer(x[0], b[0])
    s2 = np.exp(g[1]) * s1 + dt[1] * np.outer(x[1], b[1])
    got = model.walk_head(x, b, c, dt, g)
    np.testing.assert_allclose(got[:2], np.stack([s1 @ c[0], s2 @ c[1]]), rtol=1e-12)
    cfg = {**small_cell()[0], "hybrid_override_pattern": "M" * 13}   # no router to read
    scan = dict(x=x, b=b, c=c, dt=dt, log_decay=g, o=got)
    assert model.probe_numbers(cfg, 0, [scan], [])["scan_state_gap"] == 0.0
    scan["o"] = scan["o"].astype(ml_dtypes.bfloat16).astype(np.float64)
    assert 1e-4 < model.probe_numbers(cfg, 0, [scan], [])["scan_state_gap"] < 1e-2
