"""BENCHMARK.json against the files it names, and the files against it. No
JAX: every entry finds its file, every file its module, and no file of
``layer_metrics/`` is left without an entry."""

import json
import os

import pytest

from benchmark import run as bench_run

HERE = bench_run.HERE
with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = {w["name"] for w in BENCH["workloads"]}
METRIC_FILES = sorted(f[:-len(".json")] for f in os.listdir(os.path.join(HERE, "layer_metrics"))
                      if f.endswith(".json"))


def module_exists(kind: str, name: str) -> bool:
    return os.path.isfile(os.path.join(HERE, kind, name + ".py"))


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configuration_names_a_model_and_a_data_shape_that_exist(entry):
    with open(os.path.join(bench_run.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert module_exists("models", cfg["model"]) and module_exists("data", cfg["data"])
    assert isinstance(cfg.get("rehearsal", {}), dict)
    assert entry["name"] in {w["config"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_a_cell_names_a_configuration_and_a_mix_whose_loop_exists(cell):
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = bench_run.load_json("traffic", cell["traffic"] + ".json")
    assert module_exists("loops", mix["loop"]) and isinstance(mix.get("rehearsal", {}), dict)
    # a rehearsal overrides what the mix has, and invents no key
    assert set(mix.get("rehearsal", {})) <= set(mix)
    assert set(mix.get("rehearsal", {}).get("limits", {})) <= set(mix["limits"])


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_a_per_layer_metric_has_its_file_and_its_reader(metric):
    spec = bench_run.load_json("layer_metrics", metric["name"] + ".json")
    assert module_exists("readers", spec["reader"]["kind"])
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        metric["layer"], metric["unit"], metric["moves"])


@pytest.mark.parametrize("name", METRIC_FILES)
def test_no_metric_file_is_an_orphan(name):
    assert name in {m["name"] for m in BENCH["per_layer"]}


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_a_metric_lists_cells_that_exist_and_moves_a_metric_that_exists(metric):
    assert set(metric.get("workloads", [])) <= CELLS
    if "moves" in metric:
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
