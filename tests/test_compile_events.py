"""The compile log (``tpu_tfrecord.compile_cache``): every trace, lowering,
compile and cache read by program name, folded into ``METRICS``, cut at a
window by ``events`` and added up by ``summary``; and ``kernel_trace`` around
the four Pallas call sites. CPU, a cache directory under ``tmp_path``."""

import json
import os
import sys
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import compilation_cache as jax_cache

from tpu_tfrecord import compile_cache, telemetry, tracing, vocabulary
from tpu_tfrecord.metrics import METRICS

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "examples"))
import _harness  # noqa: E402

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"

STAGES = ("compile.trace", "compile.lower", "compile.backend", "compile.cache_read",
          "kernel.trace.mla_attn", "kernel.trace.kda_scan", "kernel.trace.dsa_index",
          "kernel.trace.interaction")
COUNTERS = ("compile.cache_hits", "compile.cache_misses")
CONFIG = ("jax_compilation_cache_dir", "jax_compilation_cache_include_metadata_in_key",
          "jax_persistent_cache_min_compile_time_secs", "jax_persistent_cache_min_entry_size_bytes")


def ours(listeners) -> int:
    return sum(1 for fn in listeners if getattr(fn, "__module__", "") == compile_cache.__name__)


@pytest.fixture
def log(tmp_path, monkeypatch):
    """``enable()`` with the cache under ``tmp_path`` and every compile kept;
    afterwards the process is as the other tests expect it: no listener, no
    cache directory."""
    from jax._src import monitoring

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", str(tmp_path / "cache"))
    before = {name: getattr(jax.config, name) for name in CONFIG}
    jax_cache.reset_cache()
    assert compile_cache.enable() == str(tmp_path / "cache")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    METRICS.reset()
    yield compile_cache._LOG
    monitoring.unregister_event_time_span_listener(compile_cache._on_span)
    monitoring.unregister_event_duration_listener(compile_cache._on_duration)
    monitoring.unregister_event_listener(compile_cache._on_event)
    compile_cache._LOG = None
    tracing.unwatch_host()  # enable() started it
    for name, value in before.items():
        jax.config.update(name, value)
    jax_cache.reset_cache()
    telemetry.disable()
    METRICS.reset()


def span(log, event, begin, end, fun="f"):
    """One of JAX's time spans, ``begin`` / ``end`` on the log's clock."""
    jax.monitoring.record_event_time_span(event, begin - log.offset, end - log.offset, fun_name=fun)


def test_nothing_is_logged_before_enable():
    assert compile_cache._LOG is None
    assert compile_cache.events() is None and compile_cache.summary() is None
    with compile_cache.kernel_trace("kernel.trace.interaction"):
        pass  # the stage is timed, the log is not there to take it
    assert compile_cache.events() is None


def test_two_enables_register_one_set_of_listeners(log):
    from jax._src import monitoring

    compile_cache.enable()
    assert compile_cache._LOG is log
    assert ours(monitoring.get_event_time_span_listeners()) == 1
    assert ours(monitoring.get_event_duration_listeners()) == 1
    assert ours(monitoring.get_event_listeners()) == 1


def test_a_jit_that_calls_a_jit_is_counted_once(log):
    @jax.jit
    def inner(x):
        return jnp.sin(x) @ x

    @jax.jit
    def outer(x):
        return inner(x) + inner(2 * x)

    outer(jnp.ones((16, 16))).block_until_ready()
    traces = [r for r in compile_cache.events() if r.phase == "trace"]
    whole = next(r for r in traces if r.fun == "outer")
    inside = [r for r in traces if r is not whole and whole.begin <= r.begin and r.end <= whole.end]
    assert {"inner", "sin"} <= {r.fun for r in inside}
    found = compile_cache.summary()
    each_once = found["seconds"]["trace"]
    assert each_once < sum(r.end - r.begin for r in traces)
    assert METRICS.stage("compile.trace").seconds == pytest.approx(each_once, rel=1e-6)
    assert METRICS.stage("compile.trace").batches == len(traces)
    # a function traced inside another is part of that program's row
    assert "inner" not in {row["fun"] for row in found["programs"]}
    row = next(row for row in found["programs"] if row["fun"] == "outer")
    assert row["trace_s"] == pytest.approx(whole.end - whole.begin)
    assert row["lower_s"] > 0 and row["backend_s"] > 0
    assert found["seconds"]["all"] <= sum(found["seconds"][p] for p in ("trace", "lower", "backend"))


def test_a_program_jax_leaves_unnamed_takes_its_traces_name(log):
    import functools

    def scaled(scale, x):
        return jnp.tanh(x) * scale

    jax.jit(functools.partial(scaled, 2.0))(jnp.ones((4, 4))).block_until_ready()  # no __name__: "<unknown>"
    found = [(r.phase, r.fun) for r in compile_cache.events() if r.phase in ("lower", "backend")]
    assert found[-2:] == [("lower", "scaled"), ("backend", "scaled")]
    row = next(row for row in compile_cache.summary()["programs"] if row["fun"] == "scaled")
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["backend_s"] > 0
    assert "<unknown>" not in {row["fun"] for row in compile_cache.summary()["programs"]}


def test_the_same_program_comes_back_as_a_hit(log):
    @jax.jit
    def program(x):
        return jnp.cos(x) * 3

    x = jnp.ones((8, 8))

    between = []
    for _ in range(2):  # one call site: the callers' lines are metadata, and so in the key
        jax.clear_caches()
        program(x).block_until_ready()
        between.append(time.perf_counter())
    t_between = between[0]
    first, second = [r for r in compile_cache.events() if r.phase == "backend" and r.fun == "program"]
    assert (first.cache, second.cache) == ("miss", "hit")
    reads = [r for r in compile_cache.events() if r.phase == "cache_read"]
    assert [r.fun for r in reads] == ["program"]
    assert second.begin <= reads[0].begin and reads[0].end <= second.end
    assert METRICS.counter("compile.cache_hits") == 1
    assert METRICS.counter("compile.cache_misses") >= 1
    assert METRICS.stage("compile.cache_read").batches == 1
    cold, warm = compile_cache.summary(until=t_between), compile_cache.summary(since=t_between)
    assert (cold["hits"], warm["hits"], warm["misses"]) == (0, 1, 0)
    assert [row["cache"] for row in warm["programs"] if row["fun"] == "program"] == ["hit"]
    # the read lies inside the compile's span: together they count each moment once
    assert warm["seconds"]["all"] < sum(warm["seconds"].values()) - warm["seconds"]["all"]


def test_events_cut_by_end_time_and_summary_follows(log):
    base = time.perf_counter() + 100.0
    span(log, TRACE, base + 0.0, base + 1.0)
    span(log, LOWER, base + 1.0, base + 3.0, fun="jit(f)")
    span(log, BACKEND, base + 3.0, base + 4.0, fun="jit(f)")
    span(log, "/jax/core/some/other_duration", base, base + 9.0)
    ends = lambda found: [round(r.end - base, 6) for r in found]  # noqa: E731
    assert ends(compile_cache.events()) == [1.0, 3.0, 4.0]
    lowered = compile_cache.events()[1].end  # [since, until): a record belongs to the interval it ends in
    assert ends(compile_cache.events(until=lowered)) == [1.0]
    assert ends(compile_cache.events(since=lowered)) == [3.0, 4.0]
    assert ends(compile_cache.events(since=base + 1.5, until=base + 3.5)) == [3.0]
    assert compile_cache.events(since=base + 5.0) == []
    last = compile_cache.events()[-1]
    assert (last.phase, last.fun, last.cache) == ("backend", "f", "off")  # no cache was asked
    found = compile_cache.summary(until=base + 3.5)
    assert found["seconds"] == pytest.approx(
        {"trace": 1.0, "lower": 2.0, "backend": 0.0, "cache_read": 0.0, "kernel_trace": 0.0, "all": 3.0})
    assert found["programs"] == [pytest.approx(
        {"fun": "f", "trace_s": 1.0, "lower_s": 2.0, "backend_s": 0.0, "cache": None})]
    assert compile_cache.summary(since=base + 9.0)["programs"] == []


def test_a_second_program_of_one_name_has_a_row_of_its_own(log):
    base = time.perf_counter() + 100.0
    for at, backend in ((0.0, 2.0), (10.0, 0.5)):
        span(log, TRACE, base + at, base + at + 1.0, fun="step")
        span(log, LOWER, base + at + 1.0, base + at + 2.0, fun="jit(step)")
        jax.monitoring.record_event("/jax/compilation_cache/compile_requests_use_cache")
        if at:
            jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        span(log, BACKEND, base + at + 2.0, base + at + 2.0 + backend, fun="jit(step)")
    rows = compile_cache.summary()["programs"]
    assert [(row["fun"], row["backend_s"], row["cache"]) for row in rows] == [
        ("step", pytest.approx(2.0), "miss"), ("step", pytest.approx(0.5), "hit")]


def test_the_log_is_bounded_and_counts_what_it_drops(log):
    assert log.records.maxlen == compile_cache.LOG_CAPACITY == 16384
    log.records = deque(maxlen=4)
    base = time.perf_counter()
    for i in range(7):
        span(log, TRACE, base + i, base + i + 0.5, fun=f"f{i}")
    assert [r.fun for r in compile_cache.events()] == ["f3", "f4", "f5", "f6"]
    assert compile_cache.summary()["dropped"] == log.dropped == 3
    assert METRICS.stage("compile.trace").batches == 7  # the totals lose nothing


def test_the_flight_recorder_shows_set_up_when_it_is_on(log):
    base = time.perf_counter()
    span(log, TRACE, base, base + 0.25, fun="quiet")
    telemetry.enable()
    telemetry.RECORDER.clear()
    span(log, LOWER, base + 1.0, base + 1.5, fun="jit(loud)")
    with compile_cache.kernel_trace("kernel.trace.mla_attn"):
        pass
    got = [(name, t0, dur, attrs) for name, t0, dur, _, attrs, _ in telemetry.RECORDER.spans()]
    assert [(name, attrs) for name, _, _, attrs in got] == [
        ("compile.lower", {"fun": "loud"}), ("kernel.trace.mla_attn", {"fun": "mla_attn"})]
    assert got[0][1] == pytest.approx((base + 1.0) * 1e9, abs=1e3) and got[0][2] == pytest.approx(5e8, abs=1e3)


def test_the_examples_loop_says_after_its_first_step_what_set_up_cost(log, capsys):
    it = iter([1.0, 2.0, 3.0])
    step = jax.jit(lambda total, x: (total + x, total))
    _harness.run_train_loop(it, jnp.float32, lambda state, gb: step(state, gb), jnp.float32(0), log_every=100)
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("setup ")]
    assert len(lines) == 1  # once, after the first step: not a line a step
    said = json.loads(lines[0][len("setup "):])
    assert said["event"] == "setup" and said["hits"] == 0 and said["misses"] >= 1
    assert set(said["seconds"]) == {"trace", "lower", "backend", "cache_read", "kernel_trace", "all"}
    assert 1 <= len(said["largest"]) <= 3 and "<lambda>" in {row["fun"] for row in said["largest"]}


def test_the_examples_loop_is_silent_where_nothing_enabled_the_log(capsys):
    assert _harness.log_setup_summary() is None
    assert capsys.readouterr().out == ""


def kernel_programs():
    """{kernel: (function, arguments)}: each of the program's Pallas call
    sites at a small shape it takes; tracing needs no TPU."""
    from tpu_tfrecord.models import attention, interaction, linear_attn, sparse_attn

    f32, ones = jnp.float32, jnp.ones
    segs = ones((1, 256), jnp.int32)
    heads = ones((1, 2, 256, 128), f32)
    return {
        "mla_attn": (lambda q, k, v, q_rope, k_rope: attention.flash_attention_widths(
            q, k, v, segs, 0.1, 128, 128, q_rope=q_rope, k_rope=k_rope),
                     (heads, heads, heads, ones((1, 2, 256, 64), f32), ones((1, 1, 256, 64), f32))),
        "kda_scan": (lambda q, g, beta: linear_attn._delta_rule_fused(q, q, q, g, beta, segs, 0.1, 128),
                     (heads, -heads, ones((1, 2, 256), f32))),
        "dsa_index": (lambda q, k, w: sparse_attn._select_fused(q, k, w, segs, 16, (64, 128)),
                      (heads, ones((1, 256, 128), f32), ones((1, 256, 2), f32))),
        "interaction": (interaction.dot_interaction_pallas, (ones((8, 4, 16), f32),)),
    }


@pytest.mark.parametrize("kernel", ["mla_attn", "kda_scan", "dsa_index", "interaction"])
def test_a_pallas_call_site_adds_one_kernel_trace_a_program(log, kernel):
    fn, args = kernel_programs()[kernel]
    jax.jit(fn).trace(*args)
    found = [r for r in compile_cache.events() if r.phase == "kernel_trace"]
    assert [r.fun for r in found] == [kernel]
    stage = METRICS.stage("kernel.trace." + kernel)
    assert stage.batches == 1 and stage.seconds == pytest.approx(found[0].end - found[0].begin, abs=1e-3)
    # inside the program's own trace, and in the summary's seconds
    outer = [r for r in compile_cache.events() if r.phase == "trace"][-1]
    assert outer.begin <= found[0].begin and found[0].end <= outer.end
    assert compile_cache.summary()["seconds"]["kernel_trace"] == pytest.approx(found[0].end - found[0].begin)
    # a second program around the kernel builds it again
    jax.jit(lambda *a: fn(*a)).trace(*[np.asarray(a) for a in args])
    again = [r for r in compile_cache.events() if r.phase == "kernel_trace"]
    assert len(again) == (1 if kernel == "mla_attn" else 2)  # the jitted call site is traced once a shape


@pytest.mark.parametrize("name", STAGES + COUNTERS)
def test_every_name_of_the_compile_log_is_in_the_vocabulary(name):
    kind = "counter" if name in COUNTERS else "stage"
    assert vocabulary.is_registered(name, kind)
    if kind == "stage":
        assert vocabulary.is_registered(name, "span")  # record_span takes the stage's name
    assert f"| `{name}` |" in vocabulary.vocabulary_markdown()
