"""The door for a configuration of another model and another data shape: a
probe that holds none of Criteo's keys. Its configuration and its mix are
files in ``tmp_path`` (named by absolute path, so nothing is added to
``benchmark/``); its data module, model and loop are registered under
``benchmark.data.probe``, ``benchmark.models.probe`` and
``benchmark.loops.probe``, where ``run.py`` finds modules by name. The data
are TFRecord shards of ``tf.Example``s that each hold one variable-length
int64 list ``tokens``; the loop reads them back through ``TFRecordDataset``
and sums each document on the device. No file of ``benchmark/`` is edited."""

import json
import shutil
import sys
import types

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.readers import roofline
from benchmark.tests.test_rehearsal import rehearse

CONFIG = {"model": "probe", "data": "probe", "vocab": 50000, "row_tokens": 4096,
          "precision": "int32, exact", "rehearsal": {"row_tokens": 64}}
MIX = {"loop": "probe", "batch": 512, "shards": 8, "docs_per_shard": 4096, "in_flight": 2,
       "trace_seconds": 2.0, "limits": {"sum_gap": 0},
       "rehearsal": {"batch": 16, "shards": 2, "docs_per_shard": 64, "trace_seconds": 0.3}}


def schema():
    from tpu_tfrecord.schema import ArrayType, LongType, StructField, StructType

    return StructType([StructField("tokens", ArrayType(LongType()))])


def write(data_dir, seed, cfg, mix):
    """Documents of 1 to ``row_tokens`` tokens; an append job a shard, the
    shard's number as its task, so that sorted names are the order written."""
    from tpu_tfrecord.io.writer import DatasetWriter
    from tpu_tfrecord.options import TFRecordOptions

    shutil.rmtree(data_dir, ignore_errors=True)
    rng = np.random.default_rng(seed)
    docs = []
    for shard in range(mix["shards"]):
        lengths = rng.integers(1, cfg["row_tokens"] + 1, size=mix["docs_per_shard"])
        new = [rng.integers(0, cfg["vocab"], size=n).tolist() for n in lengths]
        DatasetWriter(data_dir, schema(), TFRecordOptions.from_map(), mode="append").write_rows(
            [[d] for d in new], task_id=shard)
        docs += new
    return docs


def describe(expected, cfg, mix):
    return {"tokens": sum(map(len, expected)), "longest": max(map(len, expected))}


def program(tokens):
    return tokens.sum(axis=1)


def needs(cfg, batch, loop):
    return {"flops": float(batch * cfg["row_tokens"]), "bytes": 4.0 * batch * cfg["row_tokens"]}


def run(env):
    import jax
    import jax.numpy as jnp
    from tpu_tfrecord.io.dataset import TFRecordDataset

    from benchmark.harness import window

    batch, width = env.mix["batch"], env.cfg["row_tokens"]
    ds = TFRecordDataset(env.data_dir, batch_size=batch, schema=schema(), num_epochs=None)
    sums = []

    def rows():
        for cb in ds.batches():
            col, out = cb["tokens"], np.zeros((batch, width), np.int32)
            for i in range(batch):
                doc = col.values[col.offsets[i]:col.offsets[i + 1]]
                out[i, :doc.shape[0]] = doc
            yield jnp.asarray(out)

    step = jax.jit(env.model.program)
    loop = window.StepLoop(rows(), step, lambda x: sums.append(np.asarray(x)), env.spans,
                           env.mix["in_flight"])
    for _ in range(3):
        loop.step()
    loop.drain()
    measured = env.measure(loop)
    want = np.array([sum(d) for d in env.expected]).reshape(-1, batch)  # [batches an epoch, B]
    gap = float(max(np.abs(got - want[k % want.shape[0]]).max() for k, got in enumerate(sums)))
    measured.update(rows=measured["steps"] * batch, attempted=measured["steps"], failed=0)
    measured["correct"] = window.judge(env, {"sum_gap": gap}, env.mix["limits"])
    return measured


def probe_bench(tmp_path, monkeypatch, program=program):
    for kind, fns in (("data", {"write": write, "describe": describe}),
                      ("models", {"needs": needs, "program": program}),
                      ("loops", {"run": run})):
        module = types.ModuleType(f"benchmark.{kind}.probe")
        module.__dict__.update(fns)
        monkeypatch.setitem(sys.modules, module.__name__, module)
    (tmp_path / "probe.json").write_text(json.dumps(CONFIG))
    (tmp_path / "probe_mix.json").write_text(json.dumps(MIX))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "probe", "file": str(tmp_path / "probe.json")}],
        "workloads": [{"name": "probe.sum", "config": "probe", "chips": 1,
                       "traffic": str(tmp_path / "probe_mix")}],
        "end_to_end": [], "per_layer": [],
    }))
    return str(tmp_path / "BENCHMARK.json")


@pytest.mark.parametrize("trace", ["0", "1"])
def test_a_configuration_that_is_not_criteo(capsys, tmp_path, monkeypatch, trace):
    rc, result, earlier = rehearse(capsys, "probe.sum", trace, bench_path=probe_bench(tmp_path, monkeypatch))
    assert rc == 0 and result["correct"] is True and result["attempted"] > 0
    assert result["compared"] == {"sum_gap": [0.0, 0]}
    data = [json.loads(x.split(" ", 1)[1]) for x in earlier if x.startswith("[data]")]
    assert data[0]["rows"] == 128 and data[0]["tokens"] > 0 and "distinct_key_share" not in data[0]


def test_its_comparison_is_not_empty(capsys, tmp_path, monkeypatch):
    """A program that leaves each document's first token out is not correct."""
    path = probe_bench(tmp_path, monkeypatch, program=lambda tokens: tokens[:, 1:].sum(axis=1))
    _, result, _ = rehearse(capsys, "probe.sum", bench_path=path)
    assert result["correct"] is False and result["compared"]["sum_gap"][0] > 0


def test_the_roofline_asks_a_model_for_needs_and_nothing_else(capsys):
    """``step_roofline_pct`` for the probe, from a step of 1 ms: a rehearsal
    has no peaks, so the reader is called as ``run.per_layer`` calls it."""
    env = bench_run.Env(model=types.SimpleNamespace(needs=needs), cfg=CONFIG, mix=MIX)
    peaks = bench_run.load_json("harness", "peaks.json")["TPU v5 lite"]
    trace = {"events": 10, "steps": 10, "op_s": 0.01}
    share = roofline.read({"env": env, "trace": trace, "peaks": peaks})
    least_s = 4.0 * 512 * 4096 / peaks["bytes_per_s"]
    assert share == 100.0 * least_s / 1e-3 and '"bound": "bytes"' in capsys.readouterr().out
