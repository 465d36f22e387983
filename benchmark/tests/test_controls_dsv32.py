"""``deepseek_v32_exp_ep16.score``'s controls at a size a test run can hold,
judged as a run is judged: each control's numbers go through
``window.judge`` against the mix's rehearsal limits, the limits the rehearsed
program itself is held to (``test_rehearsal_dsv32.py``), and has to come out
not ``correct``; the float32 program on the same documents comes out
``correct`` by the same limits. Left to the chip (``controls_dsv32.main``;
benchmark/TOKEN_DOCS_DSV32.md has the readings): ``bf16_rotary`` cannot show
on documents of 128 tokens, whose positions bfloat16 holds exactly, and
``no_yarn`` and ``int8_weights`` move the end-to-end numbers of a 64-wide
model by less than the room its rehearsal limits leave."""

import functools

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.loops import score_docs
from benchmark.models import deepseek_v32 as model
from benchmark.tests import controls_docs, controls_dsv32
from benchmark.tests.controls import load_cell

SEEDS = (11, 2 ** 31 + 12)
CHIP_ONLY = {"bf16_rotary": "positions under 256 are exact in bfloat16",
             "no_yarn": "at 128 tokens and 64 columns inside the rehearsal limits",
             "int8_weights": "at 64 columns inside the rehearsal limits"}


@functools.lru_cache(maxsize=None)
def small_cell():
    cfg, mix = load_cell(controls_dsv32.CELL)
    sizes = {k: v for k, v in mix["rehearsal"].items() if k != "limits"}
    limits = {**mix["limits"], **mix["rehearsal"]["limits"]}
    return bench_run.at_rehearsal_size(cfg), {**mix, **sizes, "limits": limits}


def test_every_control_is_run_here_or_named_with_its_reason():
    assert set(CHIP_ONLY) < set(controls_dsv32.CONTROLS) and len(controls_dsv32.CONTROLS) == 9


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", [c for c in controls_dsv32.CONTROLS if c not in CHIP_ONLY])
def test_the_control_is_not_correct(control, seed):
    cfg, mix = small_cell()
    numbers = controls_dsv32.control_numbers(model, cfg, mix, seed, [control])[control]
    assert "scan_state_gap" not in numbers
    correct, outside = controls_docs.judged(numbers, mix["limits"])
    assert not correct and outside, f"{control} stayed inside every limit: {numbers}"
    must = {"no_selection": "index_select_gap", "topk_half": "index_keys_short",
            "bf16_index_scores": "index_select_gap", "no_group_limit": "router_gate_gap",
            "bf16_router": "router_gate_gap", "dropped_visits": "moe_visits_dropped"}
    assert must[control] in outside, (control, outside, numbers)


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


def test_the_float64_probe_reads_the_selection_it_is_given():
    """``probe_numbers`` on a selection made by hand: exact, one key short,
    one stray key, and scores rounded to bfloat16."""
    rng = np.random.default_rng(0)
    cfg = {**small_cell()[0], "index_topk": 4, "num_hidden_layers": 1, "first_k_dense_replace": 1}
    keys = rng.standard_normal((12, 16)).astype(np.float32)
    q = rng.standard_normal((2, 8, 16)).astype(np.float32)
    w = rng.standard_normal((2, 8)).astype(np.float32)
    pos = np.array([9, 2])
    scores = [w[i].astype(np.float64) @ np.maximum(q[i].astype(np.float64) @ keys[:pos[i] + 1].T, 0)
              for i in range(2)]
    kept = np.zeros((2, 12), np.int8)
    kept[0, np.argsort(-scores[0])[:4]] = 1
    kept[1, :3] = 1

    def read(kept):
        routed = {"q_index": q[None], "w_index": w[None], "kept": kept[None],
                  "index_pos": pos[None], "index_start": np.zeros((1, 2), np.int32)}
        return model.probe_numbers(cfg, 0, [{"k_index": keys}], [routed])

    sound = read(kept)
    assert sound == {"router_gate_gap": 0.0, "index_select_gap": 0.0, "index_keys_short": 0.0}
    short = kept.copy()
    short[0, np.argsort(-scores[0])[3]] = 0
    assert read(short)["index_keys_short"] == 1.0
    stray = kept.copy()
    stray[1, 7] = 1
    assert read(stray)["index_keys_short"] == 1.0
    swapped = kept.copy()
    order = np.argsort(-scores[0])
    swapped[0, order[3]], swapped[0, order[5]] = 0, 1
    gap = read(swapped)["index_select_gap"]
    rms = np.sqrt(np.mean(scores[0] ** 2))
    assert gap == pytest.approx((scores[0][order[3]] - scores[0][order[5]]) / rms)
