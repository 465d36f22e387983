"""CPU rehearsals of ``gigachat35_ep16.score`` (``--rehearse``: the widths, the
vocabulary and the rows cut to what a CPU walks in seconds, ragged documents
several a row, 2 key heads under 4 value heads): the result line; ``correct``
turning false when what this configuration added is broken underneath (a
state and taps that cross a boundary, latent attention left ungated, a branch
joined without its norm, a norm's gain read without its gate); and the
``.gdn`` metrics firing in this cell and in no other."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.tests.test_rehearsal import rehearse
from benchmark.tests.test_rehearsal_docs import compared

CELL = "gigachat35_ep16.score"


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
        assert [layer["layer"] for layer in json.loads(said.split(" ", 1)[1])["layers"]] == [1, 2, 3, 4]
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
    numbers = broken(capsys, monkeypatch, lm, "gdn_mixer",
                     lambda p, x, segs, cfg, probe_head=None: sound(p, x, jnp.ones_like(segs), cfg, probe_head))
    assert not numbers["boundary_median_gap"]["ok"] and not numbers["scan_state_gap"]["ok"]


def test_latent_attention_left_ungated_is_not_correct(capsys, monkeypatch):
    from benchmark.models import gigachat35 as model

    sound = model.program
    numbers = broken(capsys, monkeypatch, model, "program", lambda cfg, mix: sound(
        {**cfg, "gated_attention": False}, mix))
    assert not all(numbers[k]["ok"] for k in ("logprob_median_gap", "logprob_p90_gap", "logit_rms_gap"))


def test_a_branch_joined_without_its_norm_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import lm

    numbers = broken(capsys, monkeypatch, lm, "_joined", lambda x, y, weight, cfg, scope: x + y)
    assert not numbers["logprob_median_gap"]["ok"]


def test_a_gain_read_without_its_gate_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import lm

    numbers = broken(capsys, monkeypatch, lm, "_norm", lambda x, weight, cfg: lm.weighted_rms_norm(
        x, 1.0 + weight, cfg.norm_eps))
    assert not all(numbers[k]["ok"] for k in ("logprob_median_gap", "logprob_p90_gap", "logit_rms_gap"))


def test_the_gdn_metrics_fire_in_this_cell_and_in_no_other():
    """A ``.gdn`` metric names this cell's mix alone, and an older metric that
    names its own mix does not fire here: ``run.per_layer`` reads ``mixes``."""
    here = os.path.join(bench_run.HERE, "layer_metrics")
    fires = {}
    for fname in sorted(os.listdir(here)):
        spec = bench_run.load_json("layer_metrics", fname)
        fires[fname[:-len(".json")]] = spec.get("mixes")
    mine = {name for name, mixes in fires.items() if mixes == ["score_docs_gdn"]}
    assert mine == {"step_ms.gdn", "roofline_pct.gdn_scan", "kernel_layers.gdn", "step_ms.mla.gdn",
                    "step_ms.dense_ffn.gdn", "step_ms.moe_route.gdn", "step_ms.moe_experts.gdn",
                    "step_ms.lm_head.gdn", "step_ms.all_once.gdn", "roofline_pct.mla_attn.gdn",
                    "roofline_pct.moe_experts.gdn", "pack_tokens_busy_pct.gdn",
                    "decode_blocked_pct.docs.gdn", "pack_blocked_pct.docs.gdn"}
    for name, mixes in fires.items():
        if name not in mine:
            assert mixes is None or "score_docs_gdn" not in mixes, name
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in mine)
    reported = bench_run.reports(bench, "per_layer", CELL)
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert mine | {"step_unscoped_pct", "h2d_blocked_pct", "h2d_ms"} | unlisted == reported
    for older in ("solar_open2_ep8.score", "kimi_vl_a3b_lm.score", "deepseek_v32_exp_ep16.score",
                  "trinity_large_ep8.score", "criteo_mlperf.score"):
        assert not mine & bench_run.reports(bench, "per_layer", older)
