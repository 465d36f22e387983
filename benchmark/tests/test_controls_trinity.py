"""``trinity_large_ep8.score``'s controls at a size a test run can hold, judged
as a run is judged: each control's numbers go through ``window.judge`` against
the mix's rehearsal limits, the limits the rehearsed program itself is held to
(``test_rehearsal_trinity.py``), and has to come out not ``correct``; the
float32 program on the same documents comes out ``correct`` by the same
limits. Left to the chip (``controls_trinity.main``;
benchmark/TOKEN_DOCS_TRINITY.md has the readings): ``bf16_rotary`` cannot show
on documents of 128 tokens, whose positions bfloat16 holds exactly, and
``int8_weights`` moves the end-to-end numbers of a 64-wide model by less than
the room its rehearsal limits leave."""

import functools

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.loops import score_docs
from benchmark.models import trinity_large as model
from benchmark.tests import controls_docs, controls_trinity
from benchmark.tests.controls import load_cell

SEEDS = (11, 2 ** 31 + 12)
CHIP_ONLY = {"bf16_rotary": "positions under 256 are exact in bfloat16",
             "int8_weights": "at 64 columns inside the rehearsal limits"}
#: the number that has to refuse a control, where one number is what it is there for
MUST = {"no_window": "window_keys_wrong", "window_4097": "window_keys_wrong",
        "bf16_router": "router_gate_gap", "dropped_visits": "moe_visits_dropped"}


@functools.lru_cache(maxsize=None)
def small_cell():
    cfg, mix = load_cell(controls_trinity.CELL)
    sizes = {k: v for k, v in mix["rehearsal"].items() if k != "limits"}
    limits = {**mix["limits"], **mix["rehearsal"]["limits"]}
    return bench_run.at_rehearsal_size(cfg), {**mix, **sizes, "limits": limits}


def test_every_control_is_run_here_or_named_with_its_reason():
    assert set(CHIP_ONLY) < set(controls_trinity.CONTROLS) and len(controls_trinity.CONTROLS) == 11


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("control", [c for c in controls_trinity.CONTROLS if c not in CHIP_ONLY])
def test_the_control_is_not_correct(control, seed):
    cfg, mix = small_cell()
    numbers = controls_trinity.control_numbers(model, cfg, mix, seed, [control])[control]
    assert "scan_state_gap" not in numbers
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


def test_the_float64_probe_reads_the_window_it_is_given():
    """``probe_numbers`` on one head's attention made by hand: the window's own
    keys, one key more, one fewer, every key, and an output rounded to bfloat16."""
    import ml_dtypes

    rng = np.random.default_rng(0)
    cfg = {**small_cell()[0], "sliding_window": 6, "num_hidden_layers": 1, "num_dense_layers": 1}
    keys, values = (rng.standard_normal((40, 16)).astype(np.float32) for _ in range(2))
    q = rng.standard_normal((3, 16)).astype(np.float32)
    pos = np.array([30, 3, 12])

    def read(count, through=lambda a: a):
        att = np.stack([through(model.attend_keys(q[i].astype(np.float64), keys.astype(np.float64),
                                                  values.astype(np.float64), int(pos[i]), count))
                        for i in range(3)])
        routed = {"q_swa": q[None], "att_swa": att[None], "swa_pos": pos[None]}
        return model.probe_numbers(cfg, 0, [{"k_swa": keys, "v_swa": values}], [routed])

    assert read(6) == {"router_gate_gap": 0.0, "window_attn_gap": 0.0, "window_keys_wrong": 0.0}
    # position 3 has four keys behind it whatever the window: two queries can tell
    assert read(7)["window_keys_wrong"] == read(5)["window_keys_wrong"] == 2.0
    assert read(40)["window_keys_wrong"] == 2.0 and read(7)["window_attn_gap"] > 0.01
    rounded = read(6, lambda a: a.astype(ml_dtypes.bfloat16).astype(np.float64))
    assert rounded["window_keys_wrong"] == 0.0 and 0 < rounded["window_attn_gap"] < 5e-3
