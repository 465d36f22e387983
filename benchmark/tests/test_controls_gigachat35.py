"""``gigachat35_ep16.score``'s controls at a size a test run can hold, judged
as a run is judged: each control's numbers go through ``window.judge`` against
the mix's rehearsal limits, the limits the rehearsed program itself is held to
(``test_rehearsal_gigachat35.py``), and has to come out not ``correct``; the
float32 program on the same documents comes out ``correct`` by the same
limits. Left to the chip (``controls_gigachat35.main``;
benchmark/TOKEN_DOCS_GIGACHAT35.md has the readings): ``bf16_rotary`` cannot
show on documents of 128 tokens, whose positions bfloat16 holds exactly."""

import functools

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.loops import score_docs
from benchmark.models import gigachat35 as model
from benchmark.tests import controls_docs, controls_gigachat35
from benchmark.tests.controls import load_cell

SEEDS = (11, 2 ** 31 + 12)
CHIP_ONLY = {"bf16_rotary": "positions under 256 are exact in bfloat16"}
#: the number that has to refuse a control, where one number is what it is there for
MUST = {"carried_state": "scan_state_gap", "bf16_state": "scan_state_gap",
        "decay_per_channel": "scan_state_gap", "bf16_router": "router_gate_gap",
        "dropped_visits": "moe_visits_dropped"}


@functools.lru_cache(maxsize=None)
def small_cell():
    cfg, mix = load_cell(controls_gigachat35.CELL)
    sizes = {k: v for k, v in mix["rehearsal"].items() if k != "limits"}
    limits = {**mix["limits"], **mix["rehearsal"]["limits"]}
    return bench_run.at_rehearsal_size(cfg), {**mix, **sizes, "limits": limits}


def test_every_control_is_run_here_or_named_with_its_reason():
    assert set(CHIP_ONLY) < set(controls_gigachat35.CONTROLS) and len(controls_gigachat35.CONTROLS) == 13


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", [c for c in controls_gigachat35.CONTROLS if c not in CHIP_ONLY])
def test_the_control_is_not_correct(control, seed):
    cfg, mix = small_cell()
    numbers = controls_gigachat35.control_numbers(model, cfg, mix, seed, [control])[control]
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


def test_the_float64_walk_reads_one_decay_a_token():
    """``walk_head`` on a recurrence made by hand: the state after two tokens
    under one decay a token, and ``probe_numbers`` reading 0 on its own walk
    and the rounding of an output kept in bfloat16."""
    import ml_dtypes

    rng = np.random.default_rng(0)
    q, k, v = (rng.standard_normal((5, 4)) for _ in range(3))
    g, b = -rng.uniform(0.01, 2.0, 5), rng.uniform(0.1, 0.9, 5)
    s1 = b[0] * np.outer(k[0], v[0])
    s2 = np.exp(g[1]) * s1 + b[1] * np.outer(k[1], v[1] - k[1] @ (np.exp(g[1]) * s1))
    got = model.walk_head(q, k, v, g, b, 0.5)
    np.testing.assert_allclose(got[:2], np.stack([q[0] @ s1, q[1] @ s2]) * 0.5, rtol=1e-12)
    cfg = {**small_cell()[0], "linear_key_head_dim": 16, "first_k_dense_replace": 5}   # no router to read
    scan = dict(q=q, k=k, v=v, log_decay=g, beta=b, o=model.walk_head(q, k, v, g, b, 0.25))
    assert model.probe_numbers(cfg, 0, [scan], [])["scan_state_gap"] == 0.0
    scan["o"] = scan["o"].astype(ml_dtypes.bfloat16).astype(np.float64)
    assert 1e-4 < model.probe_numbers(cfg, 0, [scan], [])["scan_state_gap"] < 1e-2
