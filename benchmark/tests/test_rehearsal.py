"""CPU rehearsals of a whole run (``--rehearse``: rows 2^8, B 256, 2 shards):
the result line's keys, ``correct`` turning false when the timed path is
broken underneath, a cell added as one ``workloads`` entry, and the refusal
of anything that is not the chip."""

import json
import os

import numpy as np
import pytest

from benchmark import run as bench_run

CELLS = ["criteo_mlperf.train", "criteo_mlperf.score"]
SEED = str(2 ** 31 + 17)


def rehearse(capsys, cell, trace="0", **kw):
    rc = bench_run.main(
        ["--workload", cell, "--seed", SEED, "--seconds", "1", "--trace", trace, "--rehearse"],
        **kw,
    )
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]), lines[:-1]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", ["0", "1"])
def test_result_line(capsys, cell, trace):
    rc, result, earlier = rehearse(capsys, cell, trace)
    assert rc == 0
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(result)
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    # a rehearsal is no measurement: no number under any metric's name
    assert result["metrics"] == {} and result["rehearsal"] is True
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    if trace == "1":
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    # every number compared is printed beside its limit
    compared = [json.loads(x.split(" ", 1)[1]) for x in earlier if x.startswith("[compare]")]
    assert compared and all({"number", "value", "limit", "ok"} <= set(c) for c in compared)


@pytest.mark.parametrize("cell", CELLS)
def test_a_corrupted_batch_is_not_correct(capsys, monkeypatch, cell):
    import tpu_tfrecord.tpu as tpu

    sound, calls = tpu.pack_mixed, []

    def corrupting(arr, keep, bits):
        calls.append(1)
        if len(calls) == 3:
            arr = arr.copy()
            arr[5, keep + 2] ^= 1  # one index of one row of the third batch
        return sound(arr, keep, bits)

    monkeypatch.setattr(tpu, "pack_mixed", corrupting)
    _, result, earlier = rehearse(capsys, cell)
    assert result["correct"] is False
    assert any('"number": "rows_altered"' in x and '"value": 1.0' in x for x in earlier)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    import tpu_tfrecord.models as models

    def lazy(params, opt_state, batch, cfg, tx, **kw):
        dense = {k: v for k, v in params.items() if k != "embeddings"}
        rows = params["embeddings"][np.arange(cfg.num_categorical)[None, :], batch["cat"]]
        return params, opt_state, models.dlrm.loss_fn(dense, batch, cfg, emb=rows)

    monkeypatch.setattr(models, "sparse_train_step", lazy)
    _, result, earlier = rehearse(capsys, "criteo_mlperf.train")
    assert result["correct"] is False
    assert any('"number": "change_norm_gap"' in x and '"ok": false' in x for x in earlier)


def test_half_a_batch_left_out_is_not_correct(capsys, monkeypatch):
    """The step trains on the first half of every batch, twice over: the
    loss is the mean over half the rows, and the losses say so."""
    import jax.numpy as jnp

    from benchmark.harness import criteo_io

    sound = criteo_io.split_wire

    def halved(gb, vocab):
        return {k: jnp.concatenate([v[: v.shape[0] // 2]] * 2) for k, v in sound(gb, vocab).items()}

    monkeypatch.setattr(criteo_io, "split_wire", halved)
    _, result, earlier = rehearse(capsys, "criteo_mlperf.train")
    assert result["correct"] is False
    assert any('"number": "loss_gap_step1"' in x and '"ok": false' in x for x in earlier)


def test_a_table_held_in_bfloat16_is_not_correct(capsys, monkeypatch):
    import jax.numpy as jnp
    import tpu_tfrecord.models as models

    sound = models.sparse_train_step

    def through_bf16(params, opt_state, batch, **kw):
        params, opt_state, loss = sound(params, opt_state, batch, **kw)
        params["embeddings"] = params["embeddings"].astype(jnp.bfloat16).astype(jnp.float32)
        return params, opt_state, loss

    monkeypatch.setattr(models, "sparse_train_step", through_bf16)
    _, result, earlier = rehearse(capsys, "criteo_mlperf.train")
    assert result["correct"] is False
    assert any('"number": "untouched_gap"' in x and '"ok": false' in x for x in earlier)


def test_an_altered_answer_is_not_correct(capsys, monkeypatch):
    import tpu_tfrecord.models as models

    sound = models.forward

    def altered(params, batch, cfg, **kw):
        return sound(params, batch, cfg, **kw).at[0].add(0.05)

    monkeypatch.setattr(models, "forward", altered)
    _, result, earlier = rehearse(capsys, "criteo_mlperf.score")
    assert result["correct"] is False
    assert any('"number": "logit_gap"' in x and '"ok": false' in x for x in earlier)


def test_a_cell_is_entries_and_no_other_file(capsys, tmp_path):
    """criteo_kaggle.train: the configuration's file and the mix are there;
    it takes an entry in ``configs`` for the file and one in ``workloads``."""
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "criteo_kaggle", "source": "facebookresearch/dlrm bench/dlrm_s_criteo_kaggle.sh",
        "file": "benchmark/configs/criteo_kaggle.json", "reduced": ["rows_per_table", "dataset"],
        "why": "the step at other widths",
    })
    bench["workloads"].append({
        "name": "criteo_kaggle.train", "config": "criteo_kaggle", "traffic": "train",
        "chips": 1, "why": "the step at other widths",
    })
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    rc, result, _ = rehearse(capsys, "criteo_kaggle.train", bench_path=str(path))
    assert rc == 0 and result["correct"] is True


def test_nothing_but_the_chip_is_measured(capsys):
    peaks = bench_run.load_json("harness", "peaks.json")
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    bench_run.require_chip(tpu, 1, peaks)
    for found, chips in (
        (dict(tpu, platform="cpu", kind="cpu"), 1),
        (dict(tpu, kind="TPU v9"), 1),
        (tpu, 4),
    ):
        with pytest.raises(bench_run.Refused):
            bench_run.require_chip(found, chips, peaks)
    # and a run without --rehearse on this CPU prints no result line
    with pytest.raises(bench_run.Refused):
        bench_run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert '"correct"' not in capsys.readouterr().out
