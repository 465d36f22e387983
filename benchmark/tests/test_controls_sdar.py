"""``sdar_30b_a3b_pp8.score``'s controls at a size a test run can hold, judged as
a run is judged: each control's numbers go through ``window.judge`` against the
mix's rehearsal limits, the limits the rehearsed program itself is held to
(``test_rehearsal_sdar.py``), and has to come out not ``correct``; the float32
program on the same documents comes out ``correct`` by the same limits. Left to
the chip (``controls_sdar.main``; benchmark/TOKEN_DOCS_SDAR.md has the readings):
``bf16_softmax`` moves a 16-wide head's attention by less than the room the
rehearsal limit leaves the program's own bfloat16 probabilities."""

import functools

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.loops import score_docs, score_docs_bd
from benchmark.models import sdar_moe as model
from benchmark.tests import controls_docs, controls_sdar
from benchmark.tests.controls import load_cell

SEEDS = (11, 2 ** 31 + 12)
CHIP_ONLY = {"bf16_softmax": "a head of 16 columns: inside the rehearsal limit of the attention's probe"}
#: the number that has to refuse a control, where one number is what it is there for
MUST = {"causal_clean": "bda_keys_wrong", "own_clean_seen": "bda_keys_wrong", "blocks_from_row": "bda_keys_wrong",
        "sigmoid_router": "router_gate_gap", "bf16_router": "router_gate_gap",
        "dropped_visits": "moe_visits_dropped", "noise_ignores_t": "noise_off_law"}


@functools.lru_cache(maxsize=None)
def small_cell():
    cfg, mix = load_cell(controls_sdar.CELL)
    sizes = {k: v for k, v in mix["rehearsal"].items() if k != "limits"}
    limits = {**mix["limits"], **mix["rehearsal"]["limits"]}
    return bench_run.at_rehearsal_size(cfg), {**mix, **sizes, "limits": limits}


def test_every_control_is_run_here_or_named_with_its_reason():
    assert set(CHIP_ONLY) < set(controls_sdar.CONTROLS) and len(controls_sdar.CONTROLS) == 12
    assert set(MUST) <= set(controls_sdar.CONTROLS)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", [c for c in controls_sdar.CONTROLS if c not in CHIP_ONLY])
def test_the_control_is_not_correct(control, seed):
    cfg, mix = small_cell()
    numbers = controls_sdar.control_numbers(model, cfg, mix, seed, [control])[control]
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
    block, mask_id = mix["block_length"], mix["mask_id"]
    docs, _, _ = controls_sdar.step_documents(cfg, mix, seed, mix["batch"] * mix["row_tokens"])
    packer = TokenPacker(mix["batch"], mix["row_tokens"], packing=mix["packing"], noise=(block, mask_id, seed))
    packer.feed_docs([d[:-1] for d in docs])
    packer.flush()
    batch = packer.pop()
    pcfg = lm.PatternLMConfig(**{**model.program(cfg, mix).__dict__, "dtype": jnp.float32})
    params = jax.tree.map(lambda a: a.astype(jnp.float32), model.program_params(seed, cfg))
    at = score_docs.sample_positions(seed, mix["batch"], mix["row_tokens"], mix["logit_samples"])
    out = jax.tree.map(np.asarray, lm.score(params, batch["tokens"], batch["segment_ids"], jnp.asarray(at),
                                            pcfg, jnp.int32(1), batch["noised"]))
    env = type("Env", (), {"expected": [d[:-1] for d in docs], "mix": mix})
    weights = model.reference_weights(seed, cfg)
    sound, strangers, n = score_docs_bd.compare_steps(
        env, [{**out, **batch}], at,
        lambda docs, copies, where: model.reference_score(cfg, docs, weights, where, noised=copies,
                                                          block_length=block),
        lambda scans, routed: model.probe_numbers(cfg, seed, scans, routed, block))
    assert strangers == 0 and n > 0 and max(sound.values()) < 5e-3, sound
    correct, outside = controls_docs.judged(sound, mix["limits"])
    assert correct and not outside


def test_the_float64_probe_reads_the_mask_it_is_given():
    """``probe_numbers`` on one head's attention made by hand: M's own keys, each
    other mask's, and an output rounded to bfloat16."""
    import ml_dtypes

    rng = np.random.default_rng(0)
    cfg = {**small_cell()[0], "num_hidden_layers": 0}
    scan = {name: rng.standard_normal((23, 16)).astype(np.float32)
            for name in ("k_bda", "v_bda", "k_bda_noised", "v_bda_noised")}
    q = rng.standard_normal((2, 4, 16)).astype(np.float32)     # the noised stream's queries, the clean one's
    pos = np.array([0, 5, 13, 22])

    def read(rule, through=lambda a: a):
        att = [np.stack([through(model.attend(q[s, i].astype(np.float64),
                                              *model.seen_keys(scan, int(pos[i]), noised, 4, rule)))
                         for i in range(4)]) for s, noised in ((0, True), (1, False))]
        routed = {"q_bda": q[0][None], "att_bda": att[0][None], "q_bda_clean": q[1][None],
                  "att_bda_clean": att[1][None], "bda_pos": pos[None]}
        return model.probe_numbers(cfg, 0, [scan], [routed], 4)

    assert read("M") == {"router_gate_gap": 0.0, "bda_attn_gap": 0.0, "bda_keys_wrong": 0.0}
    # a noised query of a document's FIRST block sees no clean key under M, and its own block's clean copy if it leaks
    assert len(model.seen_keys(scan, 2, True, 4)[0]) == 4 and len(model.seen_keys(scan, 2, True, 4, "leak")[0]) == 8
    assert len(model.seen_keys(scan, 22, False, 4)[0]) == 23 and len(model.seen_keys(scan, 21, False, 4, "causal")[0]) == 22
    assert read("causal")["bda_keys_wrong"] >= 2 and read("leak")["bda_keys_wrong"] == 4
    assert read("origin")["bda_keys_wrong"] >= 4 and read("leak")["bda_attn_gap"] > 0.05
    rounded = read("M", lambda a: a.astype(ml_dtypes.bfloat16).astype(np.float64))
    assert rounded["bda_keys_wrong"] == 0.0 and 0 < rounded["bda_attn_gap"] < 5e-3
