"""CPU rehearsals of ``deepseek_v32_exp_ep16.score`` (``--rehearse``: the
widths, the vocabulary and the rows cut to what a CPU walks in seconds, ragged
documents several a row, 16 keys a query): the result line; ``correct``
turning false when what this configuration added is broken underneath (a
selection that keeps too few keys, index scores summed without their
weights, a router that forgets its groups, plain rotary frequencies); and no
``.dsa`` metric firing on the three older mixes."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.tests.test_rehearsal import rehearse
from benchmark.tests.test_rehearsal_docs import compared

CELL = "deepseek_v32_exp_ep16.score"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_result_line(capsys, trace):
    rc, result, earlier = rehearse(capsys, CELL, trace)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["metrics"] == {} and result["rehearsal"] is True
    numbers = compared(earlier)
    assert {"logprob_median_gap", "logit_rms_gap", "boundary_median_gap", "repeat_gap",
            "docs_missing", "router_gate_gap", "index_select_gap", "index_keys_short",
            "moe_visits_dropped"} <= set(numbers)
    assert "scan_state_gap" not in numbers and all(c["ok"] for c in numbers.values())
    packed = next(json.loads(x.split(" ", 1)[1]) for x in earlier if x.startswith("[packed]"))
    assert packed["documents"] > 2 * result["attempted"]        # several documents a row
    if trace == "1":
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def broken(capsys, monkeypatch, target, name, replacement):
    monkeypatch.setattr(target, name, replacement)
    _, result, earlier = rehearse(capsys, CELL)
    assert result["correct"] is False
    return compared(earlier)


def test_a_selection_that_keeps_half_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import sparse_attn

    sound = sparse_attn.select_keys
    numbers = broken(capsys, monkeypatch, sparse_attn, "select_keys",
                     lambda q, k, w, segs, topk, block=1024: sound(q, k, w, segs, topk // 2, block))
    assert not numbers["index_keys_short"]["ok"] and not numbers["index_select_gap"]["ok"]


def test_index_scores_without_their_weights_are_not_correct(capsys, monkeypatch):
    import jax.numpy as jnp
    from tpu_tfrecord.models import sparse_attn

    sound = sparse_attn.select_keys
    numbers = broken(capsys, monkeypatch, sparse_attn, "select_keys",
                     lambda q, k, w, segs, topk, block=1024: sound(q, k, jnp.ones_like(w), segs, topk, block))
    assert not numbers["index_select_gap"]["ok"] and numbers["index_keys_short"]["ok"]


def test_a_router_that_forgets_its_groups_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import moe

    sound = moe.route_top_k
    numbers = broken(
        capsys, monkeypatch, moe, "route_top_k",
        lambda x, router, top_k, scale=1.0, bias=None, **grouped: sound(x, router, top_k, scale, bias))
    assert not numbers["router_gate_gap"]["ok"]


def test_plain_rotary_frequencies_are_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import lm

    sound = lm.rotary
    numbers = broken(capsys, monkeypatch, lm, "rotary",
                     lambda x, positions, theta, scaling=(): sound(x, positions, theta))
    assert not all(numbers[k]["ok"] for k in ("logprob_median_gap", "logprob_p90_gap",
                                              "logit_rms_gap", "index_select_gap"))


def test_no_dsa_metric_fires_on_the_older_mixes():
    """A ``.dsa`` twin names this cell's mix alone, and an older metric that
    names its own mix does not fire here: ``run.per_layer`` reads ``mixes``."""
    here = os.path.join(bench_run.HERE, "layer_metrics")
    fires = {}
    for fname in sorted(os.listdir(here)):
        spec = bench_run.load_json("layer_metrics", fname)
        fires[fname[:-len(".json")]] = spec.get("mixes")
    mine = {name for name, mixes in fires.items() if mixes == ["score_docs_dsa"]}
    assert mine == {"step_ms.dsa", "roofline_pct.dsa_index", "step_ms.mla.dsa",
                    "roofline_pct.mla_attn.dsa", "step_ms.dense_ffn.dsa", "step_ms.moe_route.dsa",
                    "step_ms.moe_experts.dsa", "step_ms.lm_head.dsa", "step_ms.all_once.dsa",
                    "roofline_pct.moe_experts.dsa", "pack_tokens_busy_pct.dsa",
                    "decode_blocked_pct.docs.dsa", "pack_blocked_pct.docs.dsa"}
    for name, mixes in fires.items():
        if name not in mine:
            assert mixes is None or "score_docs_dsa" not in mixes, name
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in mine)
    reported = bench_run.reports(bench, "per_layer", CELL)
    assert mine | {"step_unscoped_pct", "h2d_blocked_pct", "h2d_ms", "device_idle_pct",
                   "step_device_ms", "step_roofline_pct", "input_wait_ms",
                   "host_cpu_s_per_Mex"} == reported
    for older in ("solar_open2_ep8.score", "kimi_vl_a3b_lm.score", "criteo_mlperf.score"):
        assert not mine & bench_run.reports(bench, "per_layer", older)
