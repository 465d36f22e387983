"""CPU rehearsals of ``sdar_30b_a3b_pp8.score`` (``--rehearse``: the widths, the
vocabulary and the rows cut to what a CPU walks in seconds, ragged documents
several a row, the feed's four columns): the result line; ``correct`` turning
false when what this configuration added is broken underneath (a block mask that
leaks a block's clean copy, a clean stream that is merely causal, a sigmoid
router, a target shifted by one, a feed whose noise ignores its level); and the
``.bd`` metrics firing in this cell and in no other."""

import json
import os

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.tests.test_rehearsal import rehearse
from benchmark.tests.test_rehearsal_docs import compared

CELL = "sdar_30b_a3b_pp8.score"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_result_line(capsys, trace):
    rc, result, earlier = rehearse(capsys, CELL, trace)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["metrics"] == {} and result["rehearsal"] is True
    numbers = compared(earlier)
    assert {"logprob_median_gap", "logit_rms_gap", "boundary_median_gap", "doc_score_gap", "repeat_gap",
            "docs_missing", "tokens_altered", "router_gate_gap", "bda_attn_gap", "bda_keys_wrong",
            "noise_off_law", "moe_visits_dropped"} <= set(numbers)
    assert "scan_state_gap" not in numbers and all(c["ok"] for c in numbers.values())
    packed = next(json.loads(x.split(" ", 1)[1]) for x in earlier if x.startswith("[packed]"))
    assert packed["documents"] > 2 * result["attempted"]        # several documents a row
    assert 0.4 < packed["masked_share"] < 0.6                   # t ~ U(0, 1]: half the tokens
    # both streams visit: twice the row's real tokens, 3 experts each
    assert abs(packed["a_step"]["visits"] - 2 * 3 * packed["a_step"]["tokens"]) < 1e-6
    ingest = next(json.loads(x.split(" ", 1)[1]) for x in earlier if x.startswith("[ingest]"))
    assert ingest["docs_read"] == ingest["docs_written"] == 192
    if trace == "1":
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def broken(capsys, monkeypatch, target, name, replacement):
    monkeypatch.setattr(target, name, replacement)
    _, result, earlier = rehearse(capsys, CELL)
    assert result["correct"] is False
    return compared(earlier)


def test_a_noised_query_that_sees_its_blocks_clean_copy_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import attention

    sound = attention._blockwise_two_streams

    def leak(q, k, v, segments, scale, block, numbers, length):
        half = numbers.shape[1] // 2
        later = numbers.at[:, half:].add(1)          # a noised query counts itself one block on: "before" lets its own through
        return sound(q, k, v, segments, scale, block, later, length)

    numbers = broken(capsys, monkeypatch, attention, "_blockwise_two_streams", leak)
    assert not numbers["bda_keys_wrong"]["ok"]


def test_a_clean_stream_that_is_merely_causal_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import lm

    sound = lm._attend

    def causal(q, k, v, segs, block, scale=None, keep=None, window=None, diffusion=None):
        return sound(q, k, v, segs, block, scale, diffusion=1)  # blocks of one token: a clean query sees nothing after it

    numbers = broken(capsys, monkeypatch, lm, "_attend", causal)
    assert not numbers["bda_keys_wrong"]["ok"]


def test_a_sigmoid_router_is_not_correct(capsys, monkeypatch):
    from benchmark.models import sdar_moe as model
    import dataclasses

    sound = model.program
    numbers = broken(capsys, monkeypatch, model, "program",
                     lambda cfg, mix: dataclasses.replace(sound(cfg, mix), router_scoring="sigmoid"))
    assert not numbers["router_gate_gap"]["ok"]


def test_a_target_shifted_by_one_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import head, lm

    sound = head.logprob  # position i scored against token i + 1, as a causal model's head would have it
    numbers = broken(capsys, monkeypatch, head, "logprob", lambda flat, w, targets, block: sound(
        flat, w, lm.jnp.roll(targets, -1), block))
    assert not numbers["logprob_median_gap"]["ok"]


def test_a_feed_whose_noise_ignores_its_level_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.tpu import ingest

    sound = ingest.TokenPacker._noised

    def flat(self, toks, lies):
        out = sound(self, toks, lies)
        rng = np.random.default_rng(int(toks.sum()) % (2 ** 32))
        hit = (rng.random(toks.shape) < 0.5) & (out["noise_level"] > 0)
        return {**out, "noised": np.where(hit, self.noise[1], toks).astype(np.int32)}

    numbers = broken(capsys, monkeypatch, ingest.TokenPacker, "_noised", flat)
    assert not numbers["noise_off_law"]["ok"] and numbers["tokens_altered"]["ok"]


def test_the_bd_metrics_fire_in_this_cell_and_in_no_other():
    """A ``.bd`` metric names this cell's mix alone, and an older metric that
    names its own mix does not fire here: ``run.per_layer`` reads ``mixes``."""
    here = os.path.join(bench_run.HERE, "layer_metrics")
    fires = {}
    for fname in sorted(os.listdir(here)):
        spec = bench_run.load_json("layer_metrics", fname)
        fires[fname[:-len(".json")]] = spec.get("mixes")
    mine = {name for name, mixes in fires.items() if mixes == ["score_docs_bd"]}
    # four, not the thirteen ISSUE 53 listed: BENCHMARK.json may hold 128 per-layer metrics and had 124
    assert mine == {"step_ms.bda.bd", "roofline_pct.bda_attn.bd", "step_ms.moe_experts.bd", "roofline_pct.moe_experts.bd"}
    for name, mixes in fires.items():
        if name not in mine:
            assert mixes is None or "score_docs_bd" not in mixes, name
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m.get("workloads") for m in bench["per_layer"]}
    assert all(listed[name] == [CELL] for name in mine)
    reported = bench_run.reports(bench, "per_layer", CELL)
    unlisted = {m["name"] for m in bench["per_layer"] if "workloads" not in m}
    assert mine | {"step_unscoped_pct", "h2d_blocked_pct", "h2d_ms"} | unlisted == reported
    for older in ("solar_open2_ep8.score", "kimi_vl_a3b_lm.score", "trinity_large_ep8.score",
                  "olmo_hybrid_7b_pp4.score", "criteo_mlperf.score"):
        assert not mine & bench_run.reports(bench, "per_layer", older)
