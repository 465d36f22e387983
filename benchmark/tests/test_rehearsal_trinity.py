"""CPU rehearsals of ``trinity_large_ep8.score`` (``--rehearse``: the widths, the
vocabulary and the rows cut to what a CPU walks in seconds, ragged documents
several a row, 16 keys a window): the result line; ``correct`` turning false
when what this configuration added is broken underneath (a window one key too
long, rotary turns on the full layers too, no branch norms, an embedding left
unscaled); and the ``.swa`` metrics firing in this cell and in no other."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.tests.test_rehearsal import rehearse
from benchmark.tests.test_rehearsal_docs import compared

CELL = "trinity_large_ep8.score"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_result_line(capsys, trace):
    rc, result, earlier = rehearse(capsys, CELL, trace)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["metrics"] == {} and result["rehearsal"] is True
    numbers = compared(earlier)
    assert {"logprob_median_gap", "logit_rms_gap", "boundary_median_gap", "repeat_gap",
            "docs_missing", "router_gate_gap", "window_attn_gap", "window_keys_wrong",
            "moe_visits_dropped"} <= set(numbers)
    assert "scan_state_gap" not in numbers and all(c["ok"] for c in numbers.values())
    packed = next(json.loads(x.split(" ", 1)[1]) for x in earlier if x.startswith("[packed]"))
    assert packed["documents"] > 2 * result["attempted"]        # several documents a row
    for said in (x for x in earlier if x.startswith("[placement]")):   # a process places a seed once
        assert [layer["layer"] for layer in json.loads(said.split(" ", 1)[1])["layers"]] == [1, 2, 3, 4]
    if trace == "1":
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def broken(capsys, monkeypatch, target, name, replacement):
    monkeypatch.setattr(target, name, replacement)
    _, result, earlier = rehearse(capsys, CELL)
    assert result["correct"] is False
    return compared(earlier)


def test_a_window_one_key_too_long_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import lm

    sound = lm._attend
    numbers = broken(capsys, monkeypatch, lm, "_attend",
                     lambda q, k, v, segs, block, scale=None, keep=None, window=None: sound(
                         q, k, v, segs, block, scale, keep, window and window + 1))
    assert not numbers["window_keys_wrong"]["ok"]


def test_rotary_turns_on_the_full_layers_are_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import lm

    import dataclasses

    sound = lm.gqa_mixer  # a full layer run as a sliding one whose window is the whole row
    numbers = broken(capsys, monkeypatch, lm, "gqa_mixer",
                     lambda p, x, segs, cfg, sliding=False: sound(
                         p, x, segs, cfg if sliding else dataclasses.replace(cfg, window=cfg.max_len),
                         True))
    assert not all(numbers[k]["ok"] for k in ("logprob_median_gap", "logprob_p90_gap",
                                              "logit_rms_gap"))


def test_a_branch_joined_without_its_norm_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import lm

    numbers = broken(capsys, monkeypatch, lm, "_joined", lambda x, y, weight, cfg, scope: x + y)
    assert not numbers["logprob_median_gap"]["ok"]


def test_an_embedding_left_unscaled_is_not_correct(capsys, monkeypatch):
    from benchmark.models import trinity_large as model

    sound = model.program
    numbers = broken(capsys, monkeypatch, model, "program", lambda cfg, mix: sound(
        {**cfg, "mup_enabled": False}, mix))
    assert not numbers["logprob_median_gap"]["ok"]


def test_the_swa_metrics_fire_in_this_cell_and_in_no_other():
    """A ``.swa`` metric names this cell's mix alone, and an older metric that
    names its own mix does not fire here: ``run.per_layer`` reads ``mixes``."""
    here = os.path.join(bench_run.HERE, "layer_metrics")
    fires = {}
    for fname in sorted(os.listdir(here)):
        spec = bench_run.load_json("layer_metrics", fname)
        fires[fname[:-len(".json")]] = spec.get("mixes")
    mine = {name for name, mixes in fires.items() if mixes == ["score_docs_swa"]}
    assert mine == {"step_ms.swa", "roofline_pct.swa_attn", "kernel_layers.swa",
                    "step_ms.full_attn.swa", "step_ms.dense_ffn.swa", "step_ms.moe_route.swa",
                    "step_ms.moe_experts.swa", "step_ms.lm_head.swa", "step_ms.all_once.swa",
                    "roofline_pct.gqa.swa", "roofline_pct.moe_experts.swa",
                    "pack_tokens_busy_pct.swa", "decode_blocked_pct.docs.swa",
                    "pack_blocked_pct.docs.swa"}
    for name, mixes in fires.items():
        if name not in mine:
            assert mixes is None or "score_docs_swa" not in mixes, name
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in mine)
    reported = bench_run.reports(bench, "per_layer", CELL)
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert mine | {"step_unscoped_pct", "h2d_blocked_pct", "h2d_ms"} | unlisted == reported
    for older in ("solar_open2_ep8.score", "kimi_vl_a3b_lm.score", "deepseek_v32_exp_ep16.score",
                  "criteo_mlperf.score"):
        assert not mine & bench_run.reports(bench, "per_layer", older)
