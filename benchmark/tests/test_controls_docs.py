"""The token-document controls at a size a test run can hold, judged as a
run is judged: each control's numbers go through ``window.judge`` against
the mix's rehearsal limits, the limits the rehearsed program itself is held
to (``test_rehearsal_docs.py``), and has to come out not ``correct``. The
float32 program on the same documents comes out ``correct`` by the same
limits. On the chip the cell's own limits do that job
(``controls_docs.main``; PERF.md has the readings)."""

import functools

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.loops import score_docs
from benchmark.models import solar_open2 as model
from benchmark.tests import controls_docs
from benchmark.tests.controls import load_cell

SEEDS = (11, 2 ** 31 + 12)


@functools.lru_cache(maxsize=None)
def small_cell():
    cfg, mix = load_cell("solar_open2_ep8.score")
    sizes = {k: v for k, v in mix["rehearsal"].items() if k != "limits"}
    limits = {**mix["limits"], **mix["rehearsal"]["limits"]}
    return bench_run.at_rehearsal_size(cfg), {**mix, **sizes, "limits": limits}


@functools.lru_cache(maxsize=None)
def float32_program_reads(seed):
    """The loop's numbers for the program run in float32 on one packed batch."""
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
    import jax

    params = jax.tree.map(lambda a: a.astype(jnp.float32), model.program_params(seed, cfg))
    at = score_docs.sample_positions(seed, mix["batch"], mix["row_tokens"], mix["logit_samples"])
    out = jax.tree.map(np.asarray, lm.score(
        params, batch["tokens"], batch["segment_ids"], jnp.asarray(at), pcfg, jnp.int32(1)))
    env = type("Env", (), {"expected": [d[:-1] for d in docs]})
    weights = model.reference_weights(seed, cfg)
    numbers, strangers, n = score_docs.compare_steps(
        env, [{**out, **batch}], at,
        lambda docs, where: model.reference_score(cfg, docs, weights, where),
        lambda scans, routed: model.probe_numbers(cfg, seed, scans, routed))
    assert strangers == 0 and n > 0
    return numbers


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", ["int8_weights", "bf16_state", "bf16_router",
                                     "carried_state", "dropped_visits"])
def test_the_control_is_not_correct(control, seed):
    cfg, mix = small_cell()
    numbers = controls_docs.control_numbers(model, cfg, mix, seed, [control])[control]
    correct, outside = controls_docs.judged(numbers, mix["limits"])
    assert not correct and outside, f"{control} stayed inside every limit: {numbers}"
    must = {"bf16_state": "scan_state_gap", "bf16_router": "router_gate_gap",
            "carried_state": "scan_state_gap", "dropped_visits": "moe_visits_dropped"}
    assert must.get(control, outside[0]) in outside, (control, outside, numbers)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_float32_program_is_correct_by_the_same_limits(seed):
    cfg, mix = small_cell()
    sound = float32_program_reads(seed)
    assert max(sound.values()) < 5e-3, sound
    correct, outside = controls_docs.judged(sound, mix["limits"])
    assert correct and not outside


def test_the_reference_in_its_own_place_is_correct():
    """No departure at all: every number is exactly zero."""
    cfg, mix = small_cell()
    docs = controls_docs.step_documents(cfg, mix, SEEDS[0], 256)
    weights = model.reference_weights(SEEDS[0], cfg)
    a, b = (model.reference_score(cfg, docs, weights, [[0]] * len(docs)) for _ in range(2))
    numbers = score_docs.gaps(a["logprob"], b["logprob"], np.concatenate(a["logits"]),
                              np.concatenate(b["logits"]), [True] * len(docs))
    assert all(v == 0.0 for v in numbers.values()) and a["dropped"] == 0
