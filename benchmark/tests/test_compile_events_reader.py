"""``readers/compile_events.py`` and ``readers/program_gauge.py`` on a
hand-made log: records before, inside and after a window, a trace inside a
trace, a read inside its compile; a program that never started the log, and
one that has none."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.readers import compile_events, program_gauge
from tpu_tfrecord import compile_cache
from tpu_tfrecord.metrics import METRICS

WINDOW = (100.0, 120.0)
CTX = {"measured": {"windows": [WINDOW, (121.0, 130.0)]}}


@pytest.fixture
def log(monkeypatch):
    """Set-up 0-100 s, the window, the reference after it."""
    made = compile_cache._Log(64)
    for phase, fun, begin, end, cache in [
        ("trace", "sin", 10.5, 10.75, None),            # inside score's trace
        ("kernel_trace", "mla_attn", 11.0, 12.0, None),  # likewise
        ("trace", "score", 10.0, 14.0, None),
        ("lower", "score", 14.0, 16.0, None),
        ("cache_read", "score", 16.25, 16.5, None),      # inside the compile's span
        ("backend", "score", 16.0, 17.0, "hit"),
        ("trace", "placement", 30.0, 30.5, None),
        ("lower", "placement", 30.5, 31.0, None),
        ("backend", "placement", 31.0, 33.0, "miss"),
        ("backend", "in_the_window", 105.0, 106.0, "miss"),
        ("trace", "reference", 125.0, 127.0, None),
        ("kernel_trace", "mla_attn", 125.5, 126.0, None),
        ("backend", "reference", 127.0, 150.0, "miss"),
    ]:
        made.append(compile_cache.Event(phase, fun, begin, end, 1, cache), 0.0)
    monkeypatch.setattr(compile_cache, "_LOG", made)
    return made


@pytest.mark.parametrize("phase, what, want", [
    ("all", "seconds", 7.0 + 3.0),          # 10-17 and 30-33, each moment once
    ("trace", "seconds", 4.0 + 0.5),        # the outermost only
    ("lower", "seconds", 2.0 + 0.5),
    ("backend", "seconds", 1.0 + 2.0),
    ("cache_read", "seconds", 0.25),
    ("kernel_trace", "seconds", 1.0),
    ("backend", "misses", 1),
    ("backend", "count", 2),
    ("kernel_trace", "count", 1),
    ("trace", "count", 3),
])
def test_the_reader_reads_what_ended_before_the_first_window(log, phase, what, want):
    assert compile_events.read(CTX, phase, what) == pytest.approx(want)


def test_the_parts_cover_the_whole(log):
    whole = compile_events.read(CTX, "all", "seconds")
    parts = sum(compile_events.read(CTX, p, "seconds") for p in ("trace", "lower", "backend", "kernel_trace"))
    assert whole <= parts


def test_a_compile_in_the_window_is_named_by_the_programs_own_accessor(log):
    assert [r.fun for r in compile_cache.events(since=WINDOW[0], until=WINDOW[1])] == ["in_the_window"]


def test_a_program_that_never_started_its_log_reports_nothing(monkeypatch):
    monkeypatch.setattr(compile_cache, "_LOG", None)
    assert compile_events.read(CTX, "all", "seconds") is None
    assert compile_events.read(CTX, "backend", "misses") is None


def test_a_program_without_a_log_reports_nothing(monkeypatch):
    monkeypatch.delattr(compile_cache, "events")
    assert compile_events.read(CTX, "all", "seconds") is None


def test_an_unknown_quantity_is_an_error(log):
    with pytest.raises(ValueError):
        compile_events.read(CTX, "all", "bytes")


def test_a_gauge_is_read_as_the_program_left_it():
    METRICS.reset()
    assert program_gauge.read({}, "kda.fused_layers") is None
    METRICS.gauge("kda.fused_layers", 3)
    assert program_gauge.read({}, "kda.fused_layers") == 3.0
    METRICS.reset()


SETUP_METRICS = ("setup_compile_s", "setup_trace_s", "setup_lower_s", "setup_backend_s",
                 "setup_cache_misses", "setup_kernel_traces")
GAUGE_METRICS = {"kernel_layers.kda": "solar_open2_ep8.score",
                 "kernel_layers.dsa": "deepseek_v32_exp_ep16.score"}


@pytest.mark.parametrize("name", SETUP_METRICS + tuple(GAUGE_METRICS))
def test_a_new_metrics_file_fires_in_the_cells_that_report_it_and_no_other(name):
    """The set-up layer's six in every cell, a gauge's metric in its one cell:
    ``run.per_layer`` goes by the file's ``mixes``, the driver by
    ``BENCHMARK.json``'s ``workloads``, and the two agree cell by cell.
    (``test_rehearsal_dsv32.py`` pins the long-document cell's reported set to
    PR 33's 21 names, so any metric added to that cell fails it: PERF.md §7.)"""
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mixes = bench_run.load_json("layer_metrics", name + ".json")["mixes"]
    for cell in bench["workloads"]:
        fires = mixes is None or cell["traffic"] in mixes
        assert fires == (name in bench_run.reports(bench, "per_layer", cell["name"])), cell["name"]
        assert fires == (name in SETUP_METRICS or GAUGE_METRICS[name] == cell["name"])
