"""CPU rehearsals of ``nemotron_twotower_ep2.score`` (``--rehearse``: the
widths, the vocabulary and the rows cut to what a CPU walks in seconds, ragged
documents several a row, 4 heads a group): the result line; ``correct``
turning false when what this configuration added is broken underneath (a state
and taps that cross a boundary, a gate on the softmax layer, a gated unit's
square left out, a skip dropped); and the ``.ssm`` metrics firing in this cell
and in no other."""

import json
import os

from benchmark import run as bench_run
from benchmark.tests.test_rehearsal import rehearse
from benchmark.tests.test_rehearsal_docs import compared

import pytest

CELL = "nemotron_twotower_ep2.score"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_result_line(capsys, trace):
    rc, result, earlier = rehearse(capsys, CELL, trace)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["metrics"] == {} and result["rehearsal"] is True
    numbers = compared(earlier)
    assert {"logprob_median_gap", "logit_rms_gap", "boundary_median_gap", "repeat_gap", "docs_missing",
            "router_gate_gap", "scan_state_gap", "moe_visits_dropped"} <= set(numbers)
    assert all(c["ok"] for c in numbers.values()) and numbers["scan_state_gap"]["value"] > 0
    packed = next(json.loads(x.split(" ", 1)[1]) for x in earlier if x.startswith("[packed]"))
    assert packed["documents"] > 2 * result["attempted"]        # several documents a row
    for said in (x for x in earlier if x.startswith("[placement]")):   # a process places a seed once
        assert [layer["layer"] for layer in json.loads(said.split(" ", 1)[1])["layers"]] == [1, 3, 6, 8, 10]
    if trace == "1":
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def broken(capsys, monkeypatch, target, name, replacement):
    monkeypatch.setattr(target, name, replacement)
    _, result, earlier = rehearse(capsys, CELL)
    assert result["correct"] is False
    return compared(earlier)


def test_a_state_and_taps_that_cross_a_boundary_are_not_correct(capsys, monkeypatch):
    import jax.numpy as jnp
    from tpu_tfrecord.models import lm

    sound = lm.ssm_mixer
    monkeypatch.setitem(lm._RECURRENT, "ssm", lambda p, x, segs, cfg, probe_head=None: sound(
        p, x, jnp.ones_like(segs), cfg, probe_head))
    _, result, earlier = rehearse(capsys, CELL)
    numbers = compared(earlier)
    assert result["correct"] is False
    assert not numbers["boundary_median_gap"]["ok"] and not numbers["scan_state_gap"]["ok"]


def test_a_gate_on_the_softmax_layer_is_not_correct(capsys, monkeypatch):
    """The layer as the configurations before this one have it: a sigmoid gate
    on the attention's output (its matrix here the query's, for want of one)."""
    import dataclasses

    from tpu_tfrecord.models import lm

    sound = lm.gqa_mixer
    numbers = broken(capsys, monkeypatch, lm, "gqa_mixer", lambda p, x, segs, cfg, sliding=False: sound(
        {**p, "wg": p["wq"]}, x, segs, dataclasses.replace(cfg, gqa_gate=True), sliding))
    assert not all(numbers[k]["ok"] for k in ("logprob_median_gap", "logprob_p90_gap", "logit_rms_gap"))


def test_a_unit_without_its_square_is_not_correct(capsys, monkeypatch):
    import jax
    import jax.numpy as jnp
    from tpu_tfrecord.models import moe

    numbers = broken(capsys, monkeypatch, moe, "relu2_ffn", lambda x, w_up, w_down: jnp.dot(
        jax.nn.relu(jnp.dot(x, w_up, preferred_element_type=jnp.float32)).astype(x.dtype), w_down,
        preferred_element_type=jnp.float32))
    assert not all(numbers[k]["ok"] for k in ("logprob_median_gap", "logprob_p90_gap", "logit_rms_gap"))


def test_a_pre_norm_left_out_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import lm

    numbers = broken(capsys, monkeypatch, lm, "_norm", lambda x, weight, cfg: (x * weight).astype(x.dtype))
    assert not numbers["logprob_median_gap"]["ok"]


def test_the_ssm_metrics_fire_in_this_cell_and_in_no_other():
    """A ``.ssm`` metric names this cell's mix alone, and an older metric that
    names its own mix does not fire here: ``run.per_layer`` reads ``mixes``."""
    here = os.path.join(bench_run.HERE, "layer_metrics")
    fires = {}
    for fname in sorted(os.listdir(here)):
        spec = bench_run.load_json("layer_metrics", fname)
        fires[fname[:-len(".json")]] = spec.get("mixes")
    mine = {name for name, mixes in fires.items() if mixes == ["score_docs_ssm"]}
    assert mine == {"step_ms.ssm", "roofline_pct.ssm_scan", "kernel_layers.ssm", "step_ms.full_attn.ssm",
                    "step_ms.moe_route.ssm", "step_ms.moe_experts.ssm", "step_ms.lm_head.ssm",
                    "step_ms.all_once.ssm", "roofline_pct.gqa.ssm", "roofline_pct.moe_experts.ssm",
                    "pack_tokens_busy_pct.ssm", "decode_blocked_pct.docs.ssm", "pack_blocked_pct.docs.ssm"}
    for name, mixes in fires.items():
        if name not in mine:
            assert mixes is None or "score_docs_ssm" not in mixes, name
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in mine) and len(bench["per_layer"]) == 103
    reported = bench_run.reports(bench, "per_layer", CELL)
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert mine | {"step_unscoped_pct", "h2d_blocked_pct", "h2d_ms"} | unlisted == reported
    for older in ("solar_open2_ep8.score", "kimi_vl_a3b_lm.score", "deepseek_v32_exp_ep16.score",
                  "trinity_large_ep8.score", "gigachat35_ep16.score", "criteo_mlperf.score"):
        assert not mine & bench_run.reports(bench, "per_layer", older)
    # the mix offers Solar's traffic: score_docs.json key for key but for what a configuration owns
    base, mix = (bench_run.load_json("traffic", name + ".json") for name in ("score_docs", "score_docs_ssm"))
    assert {"verify_batches", "limits", "limit_reasons"} <= {k for k in base if base[k] != mix[k]} <= {
        "verify_batches", "limits", "limit_reasons", "rehearsal"} and set(base) == set(mix)
    assert {k for k in base["rehearsal"] if base["rehearsal"][k] != mix["rehearsal"][k]} <= {"limits"}
