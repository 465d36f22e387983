"""CPU rehearsals of ``olmo_hybrid_7b_pp4.score`` (``--rehearse``: the widths,
the vocabulary and the rows cut to what a CPU walks in seconds, ragged
documents several a row, keys of 8 under values of 16) through the
``score_docs_dense`` loop: the result line; ``correct`` turning false when
what this configuration added is broken underneath (a state and taps that
cross a boundary, a beta held under 1, a sigmoid gate, a pre-norm, a Q/K norm
a head); the loop's handling of a pattern's empty ``visits``; and the ``.hyb``
metrics firing in this cell and in no other, as entries and new files and no
edited one."""

import json
import os
import subprocess

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.loops import score_docs_dense
from benchmark.tests.test_rehearsal import rehearse
from benchmark.tests.test_rehearsal_docs import compared

CELL = "olmo_hybrid_7b_pp4.score"
HYB = {"step_ms.gdn.hyb", "roofline_pct.gdn_scan.hyb", "kernel_layers.gdn.hyb", "lane_fill_pct.gdn_scan.hyb",
       "step_ms.full_attn.hyb", "roofline_pct.gqa.hyb", "step_ms.dense_ffn.hyb", "step_ms.lm_head.hyb",
       "step_ms.all_once.hyb", "pack_tokens_busy_pct.hyb", "decode_blocked_pct.docs.hyb",
       "pack_blocked_pct.docs.hyb"}


@pytest.mark.parametrize("trace", ["0", "1"])
def test_result_line(capsys, trace):
    rc, result, earlier = rehearse(capsys, CELL, trace)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["metrics"] == {} and result["rehearsal"] is True
    numbers = compared(earlier)
    assert set(numbers) == {"boundary_median_gap", "logprob_median_gap", "logprob_p90_gap", "logprob_rms_gap",
                            "doc_score_gap", "logit_rms_gap", "scan_state_gap", "repeat_gap", "tokens_altered",
                            "docs_missing", "docs_doubled", "segments_wrong", "steps_not_finite"}
    assert all(c["ok"] for c in numbers.values()) and numbers["scan_state_gap"]["value"] > 0
    packed = next(json.loads(x.split(" ", 1)[1]) for x in earlier if x.startswith("[packed]"))
    assert packed["documents"] > 2 * result["attempted"]        # several documents a row
    assert set(packed["a_step"]) == {"tokens", "triangle"}      # no visits: nothing to tell needs() of
    assert not any(x.startswith("[placement]") for x in earlier)
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

    sound = lm.gdn_mixer
    monkeypatch.setitem(lm._RECURRENT, "gdn", lambda p, x, segs, cfg, probe_head=None: sound(
        p, x, jnp.ones_like(segs), cfg, probe_head))
    _, result, earlier = rehearse(capsys, CELL)
    numbers = compared(earlier)
    assert result["correct"] is False
    assert not numbers["boundary_median_gap"]["ok"] and not numbers["scan_state_gap"]["ok"]


@pytest.mark.parametrize("field, other", [("gdn_neg_eigval", False), ("gdn_gate", "sigmoid2"), ("pre_norms", True),
                                          ("qk_norm_whole", False)],
                         ids=["beta_under_1", "sigmoid_gate", "pre_norms", "qk_norm_a_head"])
def test_the_other_reading_of_a_field_is_not_correct(capsys, monkeypatch, field, other):
    """The program built with the OTHER value of one of the fields this
    configuration added (the parameters made to fit: a pre-norm's weight is the
    branch's own, a head's Q/K weight the projection's first 16)."""
    import dataclasses

    from benchmark.models import olmo_hybrid as model

    sound_program, sound_params = model.program, model.program_params

    def program(cfg, mix):
        changed = {field: other, **({"qk_norm": True} if field == "qk_norm_whole" else {})}
        return dataclasses.replace(sound_program(cfg, mix), **changed)

    def params(seed, cfg):
        tree = sound_params(seed, cfg)
        for layer in tree["layers"]:
            if field == "pre_norms":
                layer.update(attn_norm=layer["post_attn_norm"], ffn_norm=layer["post_ffn_norm"])
            if field == "qk_norm_whole" and "q_norm" in layer:
                layer.update(q_norm=layer["q_norm"][:16], k_norm=layer["k_norm"][:16])
        return tree

    monkeypatch.setattr(model, "program_params", params)
    numbers = broken(capsys, monkeypatch, model, "program", program)
    assert not all(numbers[k]["ok"] for k in ("logprob_median_gap", "logprob_p90_gap", "logit_rms_gap"))


def test_the_loop_takes_a_pattern_without_experts_and_no_other():
    """What ``lm.score`` returns for a pattern without an expert layer goes
    through; a program that did route (visits to look at, a router probed) is
    refused, since this loop would read none of it."""
    dense = {"visits": np.zeros((0, 16), np.int32), "dropped": np.zeros((0,), np.int32), "probes": {"scan": {}}}
    score_docs_dense.without_experts(dense)
    for routed in ({**dense, "visits": np.ones((2, 16), np.int32), "dropped": np.zeros((2,), np.int32)},
                   {**dense, "probes": {"scan": {}, "router": {"u": np.zeros((1, 2, 4, 8))}}}):
        with pytest.raises(ValueError, match="without expert layers"):
            score_docs_dense.without_experts(routed)
    # what the older loop does with the same output: the two lines ISSUE 49 names
    with pytest.raises(ValueError):
        (dense["visits"].max(axis=1) / dense["visits"].mean(axis=1)).max()
    with pytest.raises(KeyError):
        dense["probes"]["router"]


def test_the_hyb_metrics_fire_in_this_cell_and_in_no_other():
    """A ``.hyb`` metric names this cell's mix alone, and an older metric that
    names its own mix does not fire here: ``run.per_layer`` reads ``mixes``."""
    fires = {}
    for fname in sorted(os.listdir(os.path.join(bench_run.HERE, "layer_metrics"))):
        fires[fname[:-len(".json")]] = bench_run.load_json("layer_metrics", fname).get("mixes")
    mine = {name for name, mixes in fires.items() if mixes == ["score_docs_dense"]}
    assert mine == HYB
    for name, mixes in fires.items():
        if name not in mine:
            assert mixes is None or "score_docs_dense" not in mixes, name
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in mine) and len(bench["per_layer"]) == 115
    assert [m["name"] for m in bench["per_layer"][-12:]] == [
        "step_ms.gdn.hyb", "roofline_pct.gdn_scan.hyb", "kernel_layers.gdn.hyb", "lane_fill_pct.gdn_scan.hyb",
        "step_ms.full_attn.hyb", "roofline_pct.gqa.hyb", "step_ms.dense_ffn.hyb", "step_ms.lm_head.hyb",
        "step_ms.all_once.hyb", "pack_tokens_busy_pct.hyb", "decode_blocked_pct.docs.hyb",
        "pack_blocked_pct.docs.hyb"]
    assert len(bench["workloads"]) == 9 and bench["workloads"][-1]["name"] == CELL
    reported = bench_run.reports(bench, "per_layer", CELL)
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert mine | {"step_unscoped_pct", "h2d_blocked_pct", "h2d_ms"} | unlisted == reported
    for older in ("solar_open2_ep8.score", "kimi_vl_a3b_lm.score", "deepseek_v32_exp_ep16.score",
                  "trinity_large_ep8.score", "gigachat35_ep16.score", "nemotron_twotower_ep2.score",
                  "criteo_mlperf.score"):
        assert not mine & bench_run.reports(bench, "per_layer", older)
    # the mix offers Solar's traffic: score_docs.json key for key but for what a configuration owns
    base, mix = (bench_run.load_json("traffic", name + ".json") for name in ("score_docs", "score_docs_dense"))
    assert {"loop", "limits", "limit_reasons"} <= {k for k in base if base[k] != mix[k]} <= {
        "loop", "verify_batches", "limits", "limit_reasons", "rehearsal"} and set(base) == set(mix)
    assert {k for k in base["rehearsal"] if base["rehearsal"][k] != mix["rehearsal"][k]} <= {"limits"}
    assert set(base["limits"]) - set(mix["limits"]) == {"router_gate_gap", "moe_visits_dropped"}
    assert not set(mix["limits"]) - set(base["limits"])


def test_the_lane_fill_reader_is_silent_on_a_program_without_the_gauge():
    """``readers/program_gauge_pct``: a share times 100, and None where the
    program never set the gauge (the parent of this PR under these files)."""
    from benchmark.readers import program_gauge_pct
    from tpu_tfrecord.metrics import METRICS

    assert program_gauge_pct.read({}, "gdn.no_such_gauge") is None
    METRICS.gauge("gdn.lane_fill", 0.75)
    assert program_gauge_pct.read({}, "gdn.lane_fill") == 75.0


def test_nothing_the_benchmark_had_is_edited():
    """Against the parent commit, where git can say: under ``benchmark/`` this PR
    adds files and changes none; in BENCHMARK.json it appends."""
    root = bench_run.ROOT
    try:
        changed = subprocess.run(["git", "diff", "--name-status", "HEAD", "--", "benchmark"], cwd=root,
                                 capture_output=True, text=True, check=True).stdout.split("\n")
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("no git history here")
    assert not [line for line in changed if line and not line.startswith("A")], changed
