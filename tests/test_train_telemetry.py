"""Training flight recorder tests (ISSUE 13): step-phase decomposition,
the training verdict, in-jit model diagnostics (MoE counts/drops/entropy
pinned against the routing oracle, measured pipeline bubble vs the
analytic), trainer spooling + mixed-role fleet aggregation, the
``tfrecord_doctor train`` subcommand, and the ``--json`` document mode.

Unit tests drive private Metrics/TelemetrySpool instances; the
integration tests run the real ``examples/train_lm.py`` trainer and the
doctor CLI as subprocesses.
"""

import functools
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_tfrecord import fleet, telemetry
from tpu_tfrecord.fleet import TelemetryAggregator, TelemetrySpool
from tpu_tfrecord.metrics import METRICS, Metrics
from tpu_tfrecord.models import lm, moe, pipeline
from tpu_tfrecord.telemetry import TraceContext, training_verdict
from tpu_tfrecord.tpu import create_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCTOR = os.path.join(REPO, "tools", "tfrecord_doctor.py")
TRAIN_LM = os.path.join(REPO, "examples", "train_lm.py")

sys.path.insert(0, os.path.join(REPO, "examples"))
import _harness  # noqa: E402

from hlo_util import compiled_memory_bytes  # noqa: E402
from test_pipeline_parallel import pipelined, sequential  # noqa: E402 - each one program
from tools.graftlint import hlo_contracts  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_registry():
    METRICS.reset()
    yield
    METRICS.reset()
    telemetry.disable()


# ---------------------------------------------------------------------------
# Training verdict
# ---------------------------------------------------------------------------


class TestTrainingVerdict:
    def test_thresholds(self):
        assert training_verdict(None) == "unknown"
        assert training_verdict({}) == "unknown"
        assert training_verdict({"compute": 0.0}) == "unknown"
        assert training_verdict({"compute": 1.0}) == "compute_bound"
        # input = data_wait + h2d
        assert (
            training_verdict({"data_wait": 0.3, "h2d": 0.25, "compute": 0.45})
            == "input_bound"
        )
        assert (
            training_verdict({"data_wait": 0.3, "h2d": 0.1, "compute": 0.6})
            == "compute_bound"
        )
        # ckpt wins even when input is also heavy: different fix
        assert (
            training_verdict({"data_wait": 0.5, "ckpt": 0.3, "compute": 0.2})
            == "ckpt_bound"
        )
        assert (
            training_verdict({"ckpt": 0.25, "compute": 0.75}) == "ckpt_bound"
        )
        assert (
            training_verdict({"ckpt": 0.24, "compute": 0.76})
            == "compute_bound"
        )


# ---------------------------------------------------------------------------
# StepPhases: the harness-side recorder
# ---------------------------------------------------------------------------


class _FakeDeviceIt:
    def __init__(self):
        self.transfer_seconds = 0.0


class TestStepPhases:
    def test_phases_land_as_train_stages_with_histograms(self):
        m = Metrics()
        rec = _harness.StepPhases(window=2, metrics=m)
        for _ in range(2):
            with rec.phase("data_wait"):
                pass
            with rec.phase("compute"):
                time.sleep(0.01)
            rec.end_step()
        snap = m.snapshot()
        assert snap["train.compute"]["records"] == 2
        assert snap["train.compute"]["seconds"] >= 0.02
        assert snap["train.compute"]["hist_count"] == 2  # latency histogram
        assert m.counter("train.steps") == 2
        assert snap["train.step"]["hist_count"] == 2
        # window completed: share gauges published
        assert m.gauge_value("train.share.compute") > 0.9
        assert m.gauge_value("train.share.data_wait") is not None
        assert rec.verdict() == "compute_bound"

    def test_inline_transfer_reattributed_from_wait_to_h2d(self):
        m = Metrics()
        rec = _harness.StepPhases(metrics=m)
        it = _FakeDeviceIt()
        with rec.phase("data_wait", iterator=it):
            it.transfer_seconds += 0.05
            time.sleep(0.06)
        rec.end_step()
        # exactly the iterator's transfer delta lands in h2d...
        assert m.stage("train.h2d").seconds == pytest.approx(0.05)
        # ...and data_wait keeps only the remainder of the wall
        assert m.stage("train.data_wait").seconds >= 0.005
        assert m.stage("train.data_wait").seconds < 0.06

    def test_transfer_delta_capped_at_observed_wall(self):
        # a transfer THREAD can progress more than this wait's wall time;
        # attribution must never go negative or exceed the wall
        m = Metrics()
        rec = _harness.StepPhases(metrics=m)
        it = _FakeDeviceIt()
        with rec.phase("data_wait", iterator=it):
            it.transfer_seconds += 10.0
            time.sleep(0.01)
        rec.end_step()
        assert m.stage("train.data_wait").seconds == 0.0
        assert m.stage("train.h2d").seconds < 1.0

    def test_aborted_discovery_iteration_records_nothing(self):
        # the loop's final next(it) that only DISCOVERS exhaustion can
        # block on the drained pipeline: abort_step must drop it so
        # stage records, shares, and spans agree with train.steps
        m = Metrics()
        rec = _harness.StepPhases(window=1, metrics=m)
        with rec.phase("compute"):
            time.sleep(0.005)
        rec.end_step()
        with rec.phase("data_wait"):
            time.sleep(0.05)  # the exhaustion probe's long wait
        rec.abort_step()
        rec.flush()
        assert rec.steps == 1
        assert m.counter("train.steps") == 1
        assert m.stage("train.data_wait").records == 0
        assert m.stage("train.data_wait").seconds == 0.0
        # the verdict stays compute_bound: the probe wait never voted
        assert rec.verdict() == "compute_bound"
        assert m.gauge_value("train.share.data_wait") == 0.0

    def test_exhausted_loop_spans_match_step_count(self):
        # drive run_train_loop to EXHAUSTION (max_steps=None): exactly
        # one train.step span per counted step, none for the discovery
        # iteration
        telemetry.RECORDER.clear()
        telemetry.enable()
        try:
            rec = _harness.StepPhases(metrics=Metrics())
            it = iter([1, 2, 3])
            state, steps, _ = _harness.run_train_loop(
                it, produce=lambda cb: cb,
                step_fn=lambda s, gb: (s, None),
                state=(), phases=rec, log_every=1000,
            )
            assert steps == 3 and rec.steps == 3
            spans = [
                s for s in telemetry.RECORDER.spans()
                if s[0] == "train.step" and s[5] == "X"
            ]
            assert len(spans) == 3
        finally:
            telemetry.disable()
            telemetry.RECORDER.clear()

    def test_flush_publishes_partial_window(self):
        m = Metrics()
        rec = _harness.StepPhases(window=100, metrics=m)
        with rec.phase("compute"):
            time.sleep(0.002)
        rec.end_step()
        assert m.gauge_value("train.share.compute") is None
        rec.flush()
        assert m.gauge_value("train.share.compute") == pytest.approx(
            1.0, abs=0.01
        )

    def test_input_bound_verdict_from_wait_heavy_steps(self):
        m = Metrics()
        rec = _harness.StepPhases(window=2, metrics=m)
        for _ in range(2):
            with rec.phase("data_wait"):
                time.sleep(0.02)
            with rec.phase("compute"):
                time.sleep(0.002)
            rec.end_step()
        assert rec.verdict() == "input_bound"

    def test_window_validation(self):
        with pytest.raises(ValueError):
            _harness.StepPhases(window=0)


# ---------------------------------------------------------------------------
# MoE in-jit diagnostics vs the routing oracle
# ---------------------------------------------------------------------------


def _moe_setup(top_k, capacity_factor=1.0, seed=0):
    cfg = moe.MoEConfig(
        d_model=8, d_ff=16, n_experts=4, top_k=top_k,
        capacity_factor=capacity_factor,
    )
    params = moe.init_params(jax.random.key(seed), cfg)
    x = jnp.asarray(
        np.random.default_rng(seed).normal(size=(16, 8)), jnp.float32
    )
    return cfg, params, x


def _same_counts(diag, rdiag):
    """The in-jit counters against the routing oracle's."""
    np.testing.assert_allclose(
        np.asarray(diag["expert_tokens"]), rdiag["expert_tokens"]
    )
    np.testing.assert_allclose(
        np.asarray(diag["expert_kept"]), rdiag["expert_kept"]
    )
    assert float(diag["dropped_fraction"]) == pytest.approx(
        rdiag["dropped_fraction"], abs=1e-6
    )
    assert float(diag["gate_entropy"]) == pytest.approx(
        rdiag["gate_entropy"], abs=1e-4
    )


class TestMoEDiagnostics:
    @pytest.mark.parametrize("top_k", [1, 2])
    def test_dense_counts_pin_against_oracle(self, top_k):
        cfg, params, x = _moe_setup(top_k)
        y, aux, diag = jax.jit(
            lambda p, x: moe.moe_apply(p, x, cfg, diagnostics=True)
        )(params, x)
        ref, rdiag = moe.moe_reference(params, x, cfg, return_diag=True)
        _same_counts(diag, rdiag)
        # routed assignments always sum to tokens * top_k
        assert float(diag["expert_tokens"].sum()) == 16 * top_k
        # the output itself is unchanged by the flag (different compiled
        # program -> float-association noise only)
        y2, aux2 = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(params, x)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y2), atol=1e-6
        )

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_ep_shard_map_counts_pin_against_sharded_oracle(self, top_k):
        cfg, params, x = _moe_setup(top_k)
        mesh = create_mesh({"expert": 4, "data": 2})
        y, aux, diag = jax.jit(
            lambda p, x: moe.moe_apply_ep(p, x, cfg, mesh, diagnostics=True)
        )(params, x)
        ref, rdiag = moe.moe_reference(
            params, x, cfg, shards=4, return_diag=True
        )
        np.testing.assert_allclose(np.asarray(y), ref, atol=1e-5)
        # psum'd GLOBAL counts == the oracle's cross-block tallies
        _same_counts(diag, rdiag)
        assert float(diag["expert_tokens"].sum()) == 16 * top_k

    def test_valid_mask_excludes_padding_from_counts(self):
        cfg, params, x = _moe_setup(2)
        valid = jnp.asarray([True] * 10 + [False] * 6)
        y, aux, diag = jax.jit(lambda p, x, valid: moe.moe_apply(
            p, x, cfg, valid=valid, diagnostics=True
        ))(params, x, valid)
        ref, rdiag = moe.moe_reference(
            params, x, cfg, valid=np.asarray(valid), return_diag=True
        )
        np.testing.assert_allclose(
            np.asarray(diag["expert_tokens"]), rdiag["expert_tokens"]
        )
        assert float(diag["expert_tokens"].sum()) == 10 * 2
        assert float(diag["gate_entropy"]) == pytest.approx(
            rdiag["gate_entropy"], abs=1e-4
        )

    def test_drops_show_up_at_tight_capacity(self):
        # capacity_factor far below balanced: drops are guaranteed
        cfg = moe.MoEConfig(
            d_model=8, d_ff=16, n_experts=4, top_k=2, capacity_factor=0.3
        )
        params = moe.init_params(jax.random.key(0), cfg)
        x = jnp.asarray(
            np.random.default_rng(1).normal(size=(32, 8)), jnp.float32
        )
        _, _, diag = jax.jit(
            lambda p, x: moe.moe_apply(p, x, cfg, diagnostics=True)
        )(params, x)
        _, rdiag = moe.moe_reference(params, x, cfg, return_diag=True)
        assert float(diag["dropped_fraction"]) > 0
        assert float(diag["dropped_fraction"]) == pytest.approx(
            rdiag["dropped_fraction"], abs=1e-6
        )

    def test_ep_diagnostics_hlo_keeps_all_to_all_no_gather(self):
        # the comms contract survives the flag: diagnostics add [E]-sized
        # psums, never a gather of tokens or weights — pin + construction
        # live in the shared manifest
        hlo_contracts.verify("moe_apply_ep_diagnostics")

    def test_grads_unperturbed_by_diagnostics(self):
        cfg, params, x = _moe_setup(2)

        def loss_plain(p):
            y, aux = moe.moe_apply(p, x, cfg)
            return jnp.sum(y**2) + aux

        def loss_diag(p):
            y, aux, diag = moe.moe_apply(p, x, cfg, diagnostics=True)
            return jnp.sum(y**2) + aux

        g1 = jax.jit(jax.grad(loss_plain))(params)
        g2 = jax.jit(jax.grad(loss_diag))(params)
        for k in g1:
            np.testing.assert_allclose(
                np.asarray(g1[k]), np.asarray(g2[k]), atol=1e-6
            )


# ---------------------------------------------------------------------------
# Pipeline measured bubble vs the analytic
# ---------------------------------------------------------------------------


def _pipe_setup(n_stages, seed=0):
    mesh = create_mesh({"pipe": n_stages, "data": 8 // n_stages})
    params = {
        "w": jnp.asarray(
            np.random.default_rng(seed).normal(size=(n_stages, 8, 8)) * 0.1,
            jnp.float32,
        )
    }

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    return mesh, params, stage_fn


class TestPipelineBubble:
    @pytest.mark.parametrize("n_stages", [2, 4, 8])
    @pytest.mark.parametrize("m_per_stage", [1, 2, 3])
    def test_measured_bubble_matches_analytic(self, n_stages, m_per_stage):
        mesh, params, stage_fn = _pipe_setup(n_stages)
        m = m_per_stage * n_stages
        xs = jnp.asarray(
            np.random.default_rng(1).normal(size=(m, 4, 8)), jnp.float32
        )
        out, diag = pipelined(stage_fn, params, xs, mesh, diagnostics=True)
        ref = sequential(stage_fn, params, xs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        analytic = (n_stages - 1) / (m + n_stages - 1)
        assert float(diag["bubble_fraction"]) == pytest.approx(
            analytic, abs=1e-6
        )
        assert float(diag["useful_ticks"]) == m
        assert float(diag["total_ticks"]) == m + n_stages - 1

    def test_ragged_stream_bubble_over_real_microbatches(self):
        mesh, params, stage_fn = _pipe_setup(4)
        xs = jnp.asarray(
            np.random.default_rng(2).normal(size=(7, 4, 8)), jnp.float32
        )
        out, diag = pipelined(stage_fn, params, xs, mesh, diagnostics=True)
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(sequential(stage_fn, params, xs)),
            atol=1e-5,
        )
        # n_micro=7, S=4: analytic over the REAL stream
        assert float(diag["bubble_fraction"]) == pytest.approx(
            3 / 10, abs=1e-6
        )

    def test_diagnostics_hlo_stays_gather_free(self):
        # pin + construction live in the shared manifest
        hlo_contracts.verify("pipeline_diagnostics")

    def test_off_path_output_unchanged(self):
        mesh, params, stage_fn = _pipe_setup(4)
        xs = jnp.asarray(
            np.random.default_rng(3).normal(size=(8, 4, 8)), jnp.float32
        )
        on, _ = pipelined(stage_fn, params, xs, mesh, diagnostics=True)
        off = pipelined(stage_fn, params, xs, mesh)
        np.testing.assert_allclose(
            np.asarray(on), np.asarray(off), atol=1e-6
        )

    def test_grads_flow_through_diagnostics(self):
        mesh, params, stage_fn = _pipe_setup(4)
        xs = jnp.asarray(
            np.random.default_rng(4).normal(size=(8, 4, 8)), jnp.float32
        )

        def loss(p):
            out, diag = pipeline.pipeline_apply(
                stage_fn, p, xs, mesh, diagnostics=True
            )
            return jnp.sum(out**2)

        g = jax.jit(jax.grad(loss))(params)
        assert np.isfinite(np.asarray(g["w"])).all()
        assert np.abs(np.asarray(g["w"])).sum() > 0


# ---------------------------------------------------------------------------
# LM train_step diagnostics + fold into gauges
# ---------------------------------------------------------------------------


def _lm_setup(mesh_axes, batch, **cfg_kw):
    """``lm.train_step`` on a tiny model and mesh, and its arguments."""
    import optax

    mesh = create_mesh(mesh_axes)
    cfg = lm.LMConfig(
        vocab_size=64, d_model=16, n_heads=2, max_len=16, **cfg_kw
    )
    params = lm.init_params(jax.random.key(0), cfg)
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    toks = jnp.asarray(lm.make_synthetic_tokens(cfg, batch, seed=0))
    step = functools.partial(
        lm.train_step, cfg=cfg, tx=tx, mesh=mesh, data_axis="data",
        pipe_axis="pipe" if "pipe" in mesh_axes else None,
    )
    return step, params, opt, toks


def _lm_step(mesh_axes, batch, **cfg_kw):
    """One step with diagnostics, jitted as ``examples/`` jit it (called bare
    the step runs primitive by primitive: a thousand compiles where this is
    one). Returns (the step's outputs, :func:`_lm_setup`'s)."""
    step, *args = _lm_setup(mesh_axes, batch, **cfg_kw)
    return jax.jit(functools.partial(step, diagnostics=True))(*args), (step, *args)


class TestLMDiagnostics:
    def test_moe_lm_step_returns_diag_and_folds(self):
        (p2, o2, loss, diag), (step, params, opt, toks) = _lm_step(
            {"data": 8}, 8, n_layers=2, moe_experts=4, moe_top_k=2
        )
        # counts sum to n_layers * tokens * top_k (every layer routes the
        # full stream)
        t = 8 * 16
        assert float(diag["expert_tokens"].sum()) == 2 * t * 2
        m = Metrics()
        folded = _harness.fold_model_diagnostics(diag, metrics=m)
        assert m.gauge_value("moe.expert_imbalance") >= 1.0
        assert 0.0 <= m.gauge_value("moe.dropped_fraction") <= 1.0
        assert m.gauge_value("moe.gate_entropy") > 0
        assert set(folded) == {
            "moe.expert_imbalance", "moe.dropped_fraction", "moe.gate_entropy"
        }
        # loss identical to the plain step
        _, _, loss_plain = jax.jit(step)(params, opt, toks)
        assert float(loss) == pytest.approx(float(loss_plain), abs=1e-6)

    def test_pipeline_lm_step_reports_bubble(self):
        (_, _, loss, diag), _ = _lm_step(
            {"pipe": 4, "data": 2}, 16, n_layers=4, n_micro=8
        )
        # M=8, S=4 -> (S-1)/(M+S-1) = 3/11
        assert float(diag["bubble_fraction"]) == pytest.approx(
            3 / 11, abs=1e-6
        )
        m = Metrics()
        _harness.fold_model_diagnostics(diag, metrics=m)
        assert m.gauge_value("pipeline.bubble_fraction") == pytest.approx(
            3 / 11, abs=1e-4
        )

    def test_interleaved_lm_step_reports_v_bubble_and_folds(self):
        """V>1 diag carries virtual_stages and folds the interleaved
        number under its own gauge (pipeline.bubble_fraction_v) next to
        the shared pipeline.bubble_fraction."""
        (_, _, _, diag), _ = _lm_step(
            {"pipe": 2, "data": 4}, 32, n_layers=4, n_micro=8, n_virtual=2
        )
        # M=8, S=2, V=2 -> (S-1)/(V·M+S-1) = 1/17, below 1F1B's 1/9
        assert float(diag["bubble_fraction"]) == pytest.approx(
            1 / 17, abs=1e-6
        )
        assert float(diag["virtual_stages"]) == 2
        m = Metrics()
        folded = _harness.fold_model_diagnostics(diag, metrics=m)
        assert m.gauge_value("pipeline.bubble_fraction_v") == pytest.approx(
            1 / 17, abs=1e-4
        )
        assert m.gauge_value("pipeline.bubble_fraction") == pytest.approx(
            1 / 17, abs=1e-4
        )
        assert "pipeline.bubble_fraction_v" in folded

    def test_fold_none_and_empty_are_noops(self):
        m = Metrics()
        assert _harness.fold_model_diagnostics(None, metrics=m) == {}
        assert _harness.fold_model_diagnostics({}, metrics=m) == {}
        assert m.gauges() == {}

    def test_dimensionless_hists_never_render_as_milliseconds(self):
        # the folded diagnostics are FRACTIONS: quantiles_ms (the one
        # ms-renderer every pulse/doctor line goes through) must
        # skip them — a dropped fraction of 0.02 printed as "20ms of
        # latency" on the fleet page would lie
        m = Metrics()
        m.observe("moe.dropped_fraction", 0.02)
        m.observe("pipeline.bubble_fraction", 0.18)
        m.observe("decode", 0.01)
        ms = telemetry.quantiles_ms(m.quantiles())
        assert "decode" in ms
        assert "moe.dropped_fraction" not in ms
        assert "pipeline.bubble_fraction" not in ms
        # ...and the federated latency summary excludes them too
        assert not telemetry.is_latency_hist("moe.gate_entropy")
        assert telemetry.is_latency_hist("train.step")

    def test_lm_compiled_memory_fields(self):
        # per-device compiled-memory bytes
        # from the same compiled handle as the HLO pins, backend-labeled
        fn, params, opt, toks = _lm_setup({"data": 8}, 8, n_layers=2)
        mem = compiled_memory_bytes(fn, params, opt, toks)
        assert mem["backend"] == "cpu"
        assert mem["argument_bytes"] > 0
        assert "temp_bytes" in mem


# ---------------------------------------------------------------------------
# Mixed-role aggregation: a trainer spool next to reader spools
# ---------------------------------------------------------------------------


def _write_trainer_spool(spool_dir, pid=101, steps=40, clock=lambda: 100.0):
    m = Metrics()
    for _ in range(steps):
        m.add("train.data_wait", records=1, seconds=0.001, latency=0.001)
        m.add("train.h2d", records=1, seconds=0.001, latency=0.001)
        m.add("train.compute", records=1, seconds=0.018, latency=0.018)
        m.add("train.step", records=1, seconds=0.02, latency=0.02)
        m.count("train.steps")
    m.gauge("train.share.data_wait", 0.05)
    m.gauge("train.share.h2d", 0.05)
    m.gauge("train.share.compute", 0.9)
    m.gauge("train.share.ckpt", 0.0)
    m.gauge("moe.expert_imbalance", 1.25)
    m.gauge("moe.dropped_fraction", 0.02)
    m.gauge("moe.gate_entropy", 1.1)
    import dataclasses

    ctx = dataclasses.replace(TraceContext.new(role="trainer"), pid=pid)
    sp = TelemetrySpool(
        str(spool_dir), metrics=m, context=ctx, clock=clock
    )
    sp.tick()
    return m, ctx


def _write_reader_spool(spool_dir, pid, decode_records, trace_id=None,
                        clock=lambda: 100.0):
    m = Metrics()
    m.add("decode", records=decode_records, nbytes=decode_records * 10,
          seconds=0.5, latency=0.01)
    m.gauge(telemetry.OCCUPANCY_GAUGE, 0.2)
    import dataclasses

    ctx = dataclasses.replace(TraceContext.new(role="reader"), pid=pid)
    if trace_id is not None:
        ctx = dataclasses.replace(ctx, trace_id=trace_id)
    sp = TelemetrySpool(str(spool_dir), metrics=m, context=ctx, clock=clock)
    sp.tick()
    return m, ctx


class TestMixedRoleAggregation:
    def test_trainer_aggregated_alongside_readers_exact_sums(self, tmp_path):
        spool = tmp_path / "spool"
        tm, tctx = _write_trainer_spool(spool, pid=101, steps=40)
        _write_reader_spool(spool, pid=102, decode_records=300,
                            trace_id=tctx.trace_id)
        _write_reader_spool(spool, pid=103, decode_records=500,
                            trace_id=tctx.trace_id)
        agg = TelemetryAggregator(str(spool), clock=lambda: 100.5)
        snap = agg.aggregate()
        assert len(snap.processes) == 3
        assert {p.role for p in snap.processes} == {"trainer", "reader"}
        # exact sums across roles
        assert snap.counters["train.steps"] == 40
        assert snap.stages["decode"][0] == 800
        assert snap.stages["train.compute"][0] == 40
        # role filter scopes exactly
        trainer_only = agg.aggregate(roles=["trainer"])
        assert len(trainer_only.processes) == 1
        assert trainer_only.counters["train.steps"] == 40
        assert "decode" not in trainer_only.stages
        readers_only = agg.aggregate(roles=["reader"])
        assert readers_only.stages["decode"][0] == 800
        assert "train.steps" not in readers_only.counters

    def test_role_labels_on_federated_page(self, tmp_path):
        spool = tmp_path / "spool"
        _, tctx = _write_trainer_spool(spool, pid=101)
        _write_reader_spool(spool, pid=102, decode_records=10,
                            trace_id=tctx.trace_id)
        agg = TelemetryAggregator(str(spool), clock=lambda: 100.5)
        page = agg.prometheus_text()
        assert 'role="trainer"' in page
        assert 'role="reader"' in page
        assert 'stage="train.compute"' in page

    def test_train_phase_shares_prefers_window_gauges(self, tmp_path):
        spool = tmp_path / "spool"
        _write_trainer_spool(spool, pid=101)
        snap = TelemetryAggregator(
            str(spool), clock=lambda: 100.5
        ).processes()[0]
        shares = fleet.train_phase_shares(snap)
        assert shares["compute"] == 0.9  # the gauge, not the stage ratio
        assert telemetry.training_verdict(shares) == "compute_bound"

    def test_train_phase_shares_falls_back_to_stage_seconds(self, tmp_path):
        spool = tmp_path / "spool"
        m = Metrics()
        m.add("train.data_wait", records=1, seconds=0.6, latency=0.6)
        m.add("train.compute", records=1, seconds=0.4, latency=0.4)
        sp = TelemetrySpool(
            str(spool), metrics=m, context=TraceContext.new(role="trainer"),
            clock=lambda: 1.0,
        )
        sp.tick()
        snap = TelemetryAggregator(
            str(spool), clock=lambda: 1.5
        ).processes()[0]
        shares = fleet.train_phase_shares(snap)
        assert shares["data_wait"] == pytest.approx(0.6)
        assert telemetry.training_verdict(shares) == "input_bound"

    def test_reader_snapshot_has_no_train_shares(self, tmp_path):
        spool = tmp_path / "spool"
        _write_reader_spool(spool, pid=102, decode_records=10)
        snap = TelemetryAggregator(
            str(spool), clock=lambda: 100.5
        ).processes()[0]
        assert fleet.train_phase_shares(snap) is None

    def test_doctor_fleet_shows_both_roles_and_trainer_verdict(self, tmp_path):
        spool = tmp_path / "spool"
        _, tctx = _write_trainer_spool(spool, pid=101)
        _write_reader_spool(spool, pid=102, decode_records=10,
                            trace_id=tctx.trace_id)
        res = subprocess.run(
            [sys.executable, DOCTOR, "fleet", str(spool),
             "--stale-after", "1e18"],
            capture_output=True, text=True,
        )
        assert res.returncode == 0, (res.stdout, res.stderr)
        lines = [json.loads(l) for l in res.stdout.splitlines() if l.strip()]
        procs = {l["role"]: l for l in lines if l["event"] == "proc"}
        assert set(procs) == {"trainer", "reader"}
        # the trainer's verdict is the TRAINING one, the reader's the
        # occupancy one
        assert procs["trainer"]["verdict"] == "compute_bound"
        assert procs["reader"]["verdict"] == "producer_bound"


# ---------------------------------------------------------------------------
# tfrecord_doctor train
# ---------------------------------------------------------------------------


class TestDoctorTrain:
    def _lines(self, res):
        return [json.loads(l) for l in res.stdout.splitlines() if l.strip()]

    def test_report_fields_and_exit_zero(self, tmp_path):
        spool = tmp_path / "spool"
        _, tctx = _write_trainer_spool(spool, pid=101, steps=40)
        _write_reader_spool(spool, pid=102, decode_records=10,
                            trace_id=tctx.trace_id)
        res = subprocess.run(
            [sys.executable, DOCTOR, "train", str(spool),
             "--stale-after", "1e18"],
            capture_output=True, text=True,
        )
        assert res.returncode == 0, (res.stdout, res.stderr)
        lines = self._lines(res)
        trainers = [l for l in lines if l["event"] == "trainer"]
        assert len(trainers) == 1  # the reader is not a trainer
        t = trainers[0]
        assert t["steps"] == 40
        assert t["verdict"] == "compute_bound"
        assert t["phase_shares"]["compute"] == 0.9
        assert t["phase_seconds"]["compute"] > 0
        assert t["step_p50_ms"] > 0 and t["step_p99_ms"] >= t["step_p50_ms"]
        assert t["moe"]["expert_imbalance"] == 1.25
        summary = [l for l in lines if l["event"] == "train"][0]
        assert summary["trainers"] == 1
        assert summary["steps"] == 40
        assert summary["verdict"] == "compute_bound"
        assert summary["phase_shares"]["compute"] > 0.8

    def test_no_trainers_exits_two(self, tmp_path):
        spool = tmp_path / "spool"
        _write_reader_spool(spool, pid=102, decode_records=10)
        res = subprocess.run(
            [sys.executable, DOCTOR, "train", str(spool),
             "--stale-after", "1e18"],
            capture_output=True, text=True,
        )
        assert res.returncode == 2
        err = self._lines(res)[0]
        assert err["event"] == "error"
        assert "no trainer spools" in err["error"]
        assert "reader" in err["error"]

    def test_empty_dir_exits_two(self, tmp_path):
        spool = tmp_path / "empty"
        spool.mkdir()
        res = subprocess.run(
            [sys.executable, DOCTOR, "train", str(spool)],
            capture_output=True, text=True,
        )
        assert res.returncode == 2
        assert "no spool files" in self._lines(res)[0]["error"]

    def test_custom_role_still_reported_via_train_stages(self, tmp_path):
        # a harness user with a custom telemetry_role still qualifies:
        # the train.* stages are the marker, not the label
        spool = tmp_path / "spool"
        m = Metrics()
        m.add("train.compute", records=1, seconds=1.0, latency=1.0)
        m.count("train.steps")
        TelemetrySpool(
            str(spool), metrics=m,
            context=TraceContext.new(role="my_custom_job"),
            clock=lambda: 1.0,
        ).tick()
        res = subprocess.run(
            [sys.executable, DOCTOR, "train", str(spool),
             "--stale-after", "1e18"],
            capture_output=True, text=True,
        )
        assert res.returncode == 0, (res.stdout, res.stderr)
        trainers = [
            l for l in self._lines(res) if l["event"] == "trainer"
        ]
        assert trainers and trainers[0]["role"] == "my_custom_job"


# ---------------------------------------------------------------------------
# --json document mode: one doc mirroring the text lines
# ---------------------------------------------------------------------------


def _strip_volatile(obj):
    """Remove wall-clock-derived fields (heartbeat age changes between two
    doctor invocations) so text-lines vs --json-doc compare equal."""
    if isinstance(obj, dict):
        return {
            k: _strip_volatile(v)
            for k, v in obj.items()
            if k != "heartbeat_age_s"
        }
    if isinstance(obj, list):
        return [_strip_volatile(v) for v in obj]
    return obj


class TestDoctorJson:
    def _roundtrip(self, argv):
        text = subprocess.run(
            [sys.executable, DOCTOR, *argv], capture_output=True, text=True
        )
        doc = subprocess.run(
            [sys.executable, DOCTOR, *argv, "--json"],
            capture_output=True, text=True,
        )
        assert doc.returncode == text.returncode, (doc.stdout, doc.stderr)
        lines = [
            json.loads(l) for l in text.stdout.splitlines() if l.strip()
        ]
        parsed = json.loads(doc.stdout)
        assert set(parsed) == {"events"}
        assert _strip_volatile(parsed["events"]) == _strip_volatile(lines)
        return text.returncode, parsed["events"]

    def test_fleet_roundtrip(self, tmp_path):
        spool = tmp_path / "spool"
        _, tctx = _write_trainer_spool(spool, pid=101)
        _write_reader_spool(spool, pid=102, decode_records=10,
                            trace_id=tctx.trace_id)
        rc, events = self._roundtrip(
            ["fleet", str(spool), "--stale-after", "1e18"]
        )
        assert rc == 0
        assert events[-1]["event"] == "fleet"

    def test_train_roundtrip(self, tmp_path):
        spool = tmp_path / "spool"
        _write_trainer_spool(spool, pid=101)
        rc, events = self._roundtrip(
            ["train", str(spool), "--stale-after", "1e18"]
        )
        assert rc == 0
        assert events[-1]["event"] == "train"

    def test_train_error_path_roundtrip_exit_two(self, tmp_path):
        spool = tmp_path / "empty"
        spool.mkdir()
        rc, events = self._roundtrip(["train", str(spool)])
        assert rc == 2
        assert events[0]["event"] == "error"

    def test_serve_status_roundtrip(self):
        from tpu_tfrecord import service

        d = service.ServiceDispatcher(lease_ttl_s=5.0).start()
        try:
            rc, events = self._roundtrip(["serve-status", d.addr])
            assert rc == 0
            assert events[-1]["event"] == "service"
        finally:
            d.stop()

    def test_serve_status_unreachable_roundtrip_exit_two(self):
        rc, events = self._roundtrip(
            ["serve-status", "127.0.0.1:1", "--timeout", "0.5"]
        )
        assert rc == 2
        assert events[0]["event"] == "error"


# ---------------------------------------------------------------------------
# Subprocess E2E: train_lm --spool lands final:true + doctor train reads it
# ---------------------------------------------------------------------------


class TestTrainLMSpoolE2E:
    def test_spooling_trainer_emits_final_and_doctor_reads_it(self, tmp_path):
        spool = tmp_path / "spool"
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        }
        res = subprocess.run(
            [sys.executable, TRAIN_LM, "--mesh", "dp", "--steps", "4",
             "--epochs", "1", "--save-every", "2",
             "--data-dir", str(tmp_path / "data"),
             "--ckpt-dir", str(tmp_path / "ckpt"),
             "--spool", str(spool), "--spool-interval", "0.2"],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-2000:])
        files = [
            n for n in os.listdir(spool) if n.endswith(fleet.SPOOL_SUFFIX)
        ]
        assert len(files) == 1
        snap = fleet.read_spool(str(spool / files[0]))
        assert snap is not None
        assert snap.final, "clean exit must land the final:true snapshot"
        assert snap.role == "trainer"
        assert snap.counters.get("train.steps", 0) >= 1
        assert "train.compute" in snap.stages
        # the doctor reads the same spool: exit 0 with a verdict
        doc = subprocess.run(
            [sys.executable, DOCTOR, "train", str(spool),
             "--stale-after", "1e18"],
            capture_output=True, text=True,
        )
        assert doc.returncode == 0, (doc.stdout, doc.stderr)
        lines = [
            json.loads(l) for l in doc.stdout.splitlines() if l.strip()
        ]
        summary = [l for l in lines if l["event"] == "train"][0]
        assert summary["verdict"] in (
            "input_bound", "compute_bound", "ckpt_bound"
        )
        trainer = [l for l in lines if l["event"] == "trainer"][0]
        assert trainer["finished"] is True
        assert trainer["alive"] is True
