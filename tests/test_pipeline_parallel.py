"""GPipe-style pipeline parallelism vs the sequential oracle, plus the
scale-shape pins: sharded input stream, O(mb) collectives, no gathers —
and the INTERLEAVED virtual-stage schedule (stage weights [S, V, ...],
bubble shrinking toward (S-1)/(V·M+S-1), measured per tick)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from hlo_util import per_device_argument_bytes
from tools.graftlint import hlo_contracts
from tpu_tfrecord.models import moe, pipeline
from tpu_tfrecord.tpu import create_mesh


def make_stages(n_stages=4, d=8, seed=0, n_virtual=1):
    rng = np.random.default_rng(seed)
    lead = (n_stages, n_virtual) if n_virtual > 1 else (n_stages,)
    params = {
        "w": jnp.asarray(
            rng.normal(size=lead + (d, d)) * 0.5, jnp.float32
        ),
        "b": jnp.asarray(rng.normal(size=lead + (d,)) * 0.1, jnp.float32),
    }

    def stage_fn(p, x):
        return jax.nn.gelu(x @ p["w"] + p["b"])

    return params, stage_fn


def sharded_args(mesh, params, xs, pipe_axis="pipe"):
    """Place params and the microbatch stream in their pipeline layout:
    stage-sharded weights, pipe-sharded stream (the scale-shape input
    contract — no device holds the full [M, mb, ...] tensor). ndim is
    inferred from the stream array itself."""
    p_sh = jax.device_put(params, NamedSharding(mesh, P(pipe_axis)))
    xs_sh = jax.device_put(
        xs, pipeline.microbatch_sharding(mesh, pipe_axis, ndim=xs)
    )
    return p_sh, xs_sh


def interleaved_bubble(n_stages, n_virtual, m):
    """The interleaved schedule's analytic bubble over the REAL stream
    (ragged M included): useful = M·V chunk ticks out of u_last + S."""
    r_last, i_last = (m - 1) // n_stages, (m - 1) % n_stages
    u_last = (
        r_last * n_virtual * n_stages + (n_virtual - 1) * n_stages + i_last
    )
    return 1.0 - m * n_virtual / (u_last + n_stages)


def sequential(stage_fn, params, xs, n_virtual=1):
    """The sequential oracle as one program (bare, ``vmap`` runs it primitive
    by primitive, each primitive a compile)."""
    return jax.jit(
        lambda p, xs: pipeline.pipeline_reference(stage_fn, p, xs, n_virtual)
    )(params, xs)


def pipelined(stage_fn, params, xs, mesh, **kw):
    """``pipeline_apply`` as one program, as its callers run it."""
    return jax.jit(
        lambda p, xs: pipeline.pipeline_apply(stage_fn, p, xs, mesh, **kw)
    )(params, xs)


class TestPipeline:
    def test_matches_sequential_oracle(self):
        mesh = create_mesh({"pipe": 4}, jax.devices()[:4])
        params, stage_fn = make_stages()
        xs = jnp.asarray(
            np.random.default_rng(1).normal(size=(6, 2, 8)), jnp.float32
        )
        want = sequential(stage_fn, params, xs)
        got = pipelined(stage_fn, params, xs, mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_eight_stages_single_microbatch_edge(self):
        """M=1 (pure bubble) and M > S both reduce to the same math."""
        mesh = create_mesh({"pipe": 8})
        params, stage_fn = make_stages(n_stages=8)
        for m in (1, 12):
            xs = jnp.asarray(
                np.random.default_rng(m).normal(size=(m, 3, 8)), jnp.float32
            )
            want = sequential(stage_fn, params, xs)
            got = pipelined(stage_fn, params, xs, mesh)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
            )

    def test_grads_match_sequential(self):
        mesh = create_mesh({"pipe": 4}, jax.devices()[:4])
        params, stage_fn = make_stages()
        xs = jnp.asarray(
            np.random.default_rng(2).normal(size=(5, 2, 8)), jnp.float32
        )

        def loss_p(p, xs):
            return (pipeline.pipeline_apply(stage_fn, p, xs, mesh) ** 2).sum()

        def loss_r(p, xs):
            return (pipeline.pipeline_reference(stage_fn, p, xs) ** 2).sum()

        g = jax.jit(jax.grad(loss_p))(params, xs)
        g_ref = jax.jit(jax.grad(loss_r))(params, xs)
        for k in g:
            np.testing.assert_allclose(
                np.asarray(g[k]), np.asarray(g_ref[k]), rtol=1e-4, atol=1e-5
            )

    def test_stage_count_mismatch_rejected(self):
        mesh = create_mesh({"pipe": 4}, jax.devices()[:4])
        params, stage_fn = make_stages(n_stages=3)  # != axis size 4
        xs = jnp.zeros((2, 2, 8), jnp.float32)
        with pytest.raises(ValueError, match="stack 4 stages"):
            pipeline.pipeline_apply(stage_fn, params, xs, mesh)


class TestScaleShape:
    """The GSPMD contract the rebuild exists for: per-device memory and
    communication scale with the SHARD of the microbatch stream, never the
    global [M, mb, ...] tensor (the old construction replicated it to
    every stage and psum-broadcast the output)."""

    def _jitted(self, mesh, stage_fn):
        return jax.jit(
            lambda p, xs: pipeline.pipeline_apply(stage_fn, p, xs, mesh)
        )

    def test_hlo_collective_permute_no_gather_no_reduce(self):
        """Activation/feed/output movement must be neighbor permutes of ONE
        microbatch slice: no all-gather of the stream, and no all-reduce —
        the old full-[M, mb, ...] psum broadcast is gone. The pin (required
        and forbidden collectives AND the canonical construction) lives in
        the shared manifest — this test is its tier-1 driver."""
        hlo_contracts.verify("pipeline_feed_ring")

    def test_per_device_input_flat_as_pipeline_grows(self):
        """Weak scaling — the scale shape itself: grow the machine (S) and
        the stream with it (M = 2S, fixed microbatches per stage) and ONE
        device's compiled argument bytes stay FLAT. The old replicated
        layout grew linearly in M even at fixed per-stage load."""
        sizes = []
        for s in (2, 4, 8):
            mesh = create_mesh({"pipe": s}, jax.devices()[:s])
            params, stage_fn = make_stages(n_stages=s)
            xs = jnp.zeros((2 * s, 2, 8), jnp.float32)
            p_sh, xs_sh = sharded_args(mesh, params, xs)
            sizes.append(
                per_device_argument_bytes(
                    self._jitted(mesh, stage_fn), p_sh, xs_sh
                )
            )
        assert sizes[0] == sizes[1] == sizes[2], sizes

    def test_per_device_input_is_the_shard(self):
        """Fixed S: growing M adds exactly mb_bytes/S per microbatch to one
        device (the 1/S shard slope; the old replicated input's slope was
        the full mb_bytes)."""
        s = 4
        mesh = create_mesh({"pipe": s}, jax.devices()[:s])
        params, stage_fn = make_stages(n_stages=s)
        mb_bytes = 2 * 8 * 4  # [2, 8] f32 slice
        got = {}
        for m in (8, 16):
            xs = jnp.zeros((m, 2, 8), jnp.float32)
            p_sh, xs_sh = sharded_args(mesh, params, xs)
            got[m] = per_device_argument_bytes(
                self._jitted(mesh, stage_fn), p_sh, xs_sh
            )
        assert got[16] - got[8] == (16 - 8) * mb_bytes // s, got

    def test_microbatch_sharding_is_block_layout(self):
        """Device d holds microbatches [d*R, (d+1)*R) and nothing else."""
        mesh = create_mesh({"pipe": 4}, jax.devices()[:4])
        xs = jnp.arange(8 * 2 * 8, dtype=jnp.float32).reshape(8, 2, 8)
        xs_sh = jax.device_put(
            xs, pipeline.microbatch_sharding(mesh, ndim=xs)
        )
        for d, shard in enumerate(xs_sh.addressable_shards):
            assert shard.data.shape == (2, 2, 8)
            np.testing.assert_array_equal(
                np.asarray(shard.data), np.asarray(xs[2 * d : 2 * d + 2])
            )

    def test_non_divisible_microbatch_count_pads_invisibly(self):
        """M % S != 0 pads internally; the caller-visible result is exact."""
        mesh = create_mesh({"pipe": 4}, jax.devices()[:4])
        params, stage_fn = make_stages()
        xs = jnp.asarray(
            np.random.default_rng(7).normal(size=(7, 2, 8)), jnp.float32
        )
        got = pipelined(stage_fn, params, xs, mesh)
        want = sequential(stage_fn, params, xs)
        assert got.shape == (7, 2, 8)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )


class TestDpPpComposition:
    """batch_spec shards the PER-MICROBATCH dims over further axes: the
    dp×pp composed mesh ROADMAP #4a names."""

    def test_matches_oracle_on_composed_mesh(self):
        mesh = create_mesh({"pipe": 4, "data": 2})
        params, stage_fn = make_stages()
        xs = jnp.asarray(
            np.random.default_rng(3).normal(size=(8, 4, 8)), jnp.float32
        )
        want = sequential(stage_fn, params, xs)
        p_sh = jax.device_put(params, NamedSharding(mesh, P("pipe")))
        xs_sh = jax.device_put(
            xs,
            pipeline.microbatch_sharding(
                mesh, ndim=xs.ndim, batch_spec=P("data")
            ),
        )
        got = pipelined(stage_fn, p_sh, xs_sh, mesh, batch_spec=P("data"))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )

    def test_composed_grads_match_sequential(self):
        mesh = create_mesh({"pipe": 4, "data": 2})
        params, stage_fn = make_stages()
        xs = jnp.asarray(
            np.random.default_rng(4).normal(size=(4, 4, 8)), jnp.float32
        )

        def loss_p(p, xs):
            return (
                pipeline.pipeline_apply(
                    stage_fn, p, xs, mesh, batch_spec=P("data")
                )
                ** 2
            ).sum()

        def loss_r(p, xs):
            return (pipeline.pipeline_reference(stage_fn, p, xs) ** 2).sum()

        g = jax.jit(jax.grad(loss_p))(params, xs)
        g_ref = jax.jit(jax.grad(loss_r))(params, xs)
        for k in g:
            np.testing.assert_allclose(
                np.asarray(g[k]), np.asarray(g_ref[k]), rtol=1e-4, atol=1e-5
            )

    def test_composed_hlo_still_gather_free(self):
        """dp×pp composition pin, from the shared manifest."""
        hlo_contracts.verify("pipeline_feed_ring_dp")


class TestInterleaved:
    """GSPMD-style interleaved virtual stages (ROADMAP #2): stage weights
    [S, V, ...], device d owning the V round-robin chunks d, d+S, …; the
    schedule must stay oracle-exact while the measured bubble (the
    per-tick occupancy counter, not a closed form) shrinks toward
    (S-1)/(V·M+S-1)."""

    @pytest.mark.parametrize("n_stages", [2, 4])
    @pytest.mark.parametrize("n_virtual", [2, 4])
    @pytest.mark.parametrize("m_kind", ["eq", "2x", "ragged", "one"])
    def test_matches_sequential_oracle_sxvxm(
        self, n_stages, n_virtual, m_kind
    ):
        m = {
            "eq": n_stages,          # one round
            "2x": 2 * n_stages,      # two full rounds
            "ragged": 2 * n_stages + 3,  # non-dividing: padded internally
            "one": 1,                # pure bubble
        }[m_kind]
        mesh = create_mesh({"pipe": n_stages}, jax.devices()[:n_stages])
        params, stage_fn = make_stages(
            n_stages, seed=n_stages + n_virtual, n_virtual=n_virtual
        )
        xs = jnp.asarray(
            np.random.default_rng(m).normal(size=(m, 2, 8)), jnp.float32
        )
        want = sequential(stage_fn, params, xs, n_virtual)
        if m % n_stages == 0:
            p_sh, xs_sh = sharded_args(mesh, params, xs)
        else:
            # a ragged stream arrives unsharded; pipeline_apply pads it
            # into the block layout internally
            p_sh, xs_sh = params, xs
        got = pipelined(stage_fn, p_sh, xs_sh, mesh, n_virtual=n_virtual)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
        )

    def test_grads_unperturbed_vs_sequential(self):
        """Reverse mode through the interleaved fori_loop (per-tick
        dynamic chunk indexing included) == the sequential composition's
        gradients."""
        mesh = create_mesh({"pipe": 4}, jax.devices()[:4])
        params, stage_fn = make_stages(n_virtual=2)
        xs = jnp.asarray(
            np.random.default_rng(2).normal(size=(6, 2, 8)), jnp.float32
        )

        def loss_p(p, xs):
            return (
                pipeline.pipeline_apply(
                    stage_fn, p, xs, mesh, n_virtual=2
                ) ** 2
            ).sum()

        def loss_r(p, xs):
            return (
                pipeline.pipeline_reference(stage_fn, p, xs, n_virtual=2)
                ** 2
            ).sum()

        g = jax.jit(jax.grad(loss_p))(params, xs)
        g_ref = jax.jit(jax.grad(loss_r))(params, xs)
        for k in g:
            np.testing.assert_allclose(
                np.asarray(g[k]), np.asarray(g_ref[k]), rtol=1e-4, atol=1e-5
            )

    def test_bubble_shrinks_monotonically_in_v(self):
        """Fixed S and M: the MEASURED bubble (the PR 13 per-tick counter
        reading the interleaved schedule's own occupancy predicate) falls
        strictly as V grows, matching the interleaved analytic within
        1e-6 at every V — the acceptance number."""
        s, m = 4, 8
        mesh = create_mesh({"pipe": s}, jax.devices()[:s])
        measured = {}
        for v in (1, 2, 4):
            params, stage_fn = make_stages(s, seed=v, n_virtual=v)
            xs = jnp.asarray(
                np.random.default_rng(0).normal(size=(m, 2, 8)), jnp.float32
            )
            out, diag = pipelined(
                stage_fn, params, xs, mesh, n_virtual=v, diagnostics=True
            )
            np.testing.assert_allclose(
                np.asarray(out),
                np.asarray(sequential(stage_fn, params, xs, v)),
                rtol=1e-5, atol=1e-6,
            )
            measured[v] = float(diag["bubble_fraction"])
            assert measured[v] == pytest.approx(
                interleaved_bubble(s, v, m), abs=1e-6
            )
            assert measured[v] == pytest.approx(
                (s - 1) / (v * m + s - 1), abs=1e-6
            )
        assert measured[1] > measured[2] > measured[4], measured

    def test_ragged_m_bubble_over_real_microbatches(self):
        """Non-dividing M: padding never counts as useful work — the
        counter reports the bubble of the REAL stream."""
        s, v, m = 4, 2, 11
        mesh = create_mesh({"pipe": s}, jax.devices()[:s])
        params, stage_fn = make_stages(s, n_virtual=v)
        xs = jnp.asarray(
            np.random.default_rng(3).normal(size=(m, 2, 8)), jnp.float32
        )
        _, diag = pipelined(
            stage_fn, params, xs, mesh, n_virtual=v, diagnostics=True
        )
        assert float(diag["bubble_fraction"]) == pytest.approx(
            interleaved_bubble(s, v, m), abs=1e-6
        )
        assert float(diag["useful_ticks"]) == m * v
        assert float(diag["virtual_stages"]) == v

    def test_stage_stack_shape_mismatch_rejected(self):
        mesh = create_mesh({"pipe": 4}, jax.devices()[:4])
        params, stage_fn = make_stages(n_virtual=2)  # [S, 2, ...]
        xs = jnp.zeros((4, 2, 8), jnp.float32)
        with pytest.raises(ValueError, match=r"\[S, V, \.\.\.\]"):
            pipeline.pipeline_apply(
                stage_fn, params, xs, mesh, n_virtual=4
            )

    def test_hlo_collective_permute_only(self):
        """Interleaving may not re-introduce a gather or broadcast of the
        stream; pin + construction live in the shared manifest."""
        hlo_contracts.verify("pipeline_interleaved")

    def test_per_device_input_still_the_shard(self):
        """The scale shape survives interleaving: one device's compiled
        argument bytes are identical at V=1 and V=4 for the same S, M
        (stage weights aside — the stream shard and the in-flight slice
        do not grow with V)."""
        s, m, d = 4, 8, 8
        mesh = create_mesh({"pipe": s}, jax.devices()[:s])
        got = {}
        for v in (1, 4):
            params, stage_fn = make_stages(s, d=d, n_virtual=v)
            xs = jnp.zeros((m, 2, d), jnp.float32)
            p_sh, xs_sh = sharded_args(mesh, params, xs)
            fn = jax.jit(
                lambda p, x, _v=v: pipeline.pipeline_apply(
                    stage_fn, p, x, mesh, n_virtual=_v
                )
            )
            # subtract this V's stage-weight bytes: what remains is the
            # stream shard + loop slices, which must not grow with V
            w_bytes = sum(
                a.size * a.dtype.itemsize for a in jax.tree.leaves(params)
            ) // s
            got[v] = per_device_argument_bytes(fn, p_sh, xs_sh) - w_bytes
        assert got[1] == got[4], got


class TestMicrobatchShardingNdim:
    def test_ndim_inferred_from_stream_array(self):
        """Passing the stream itself (anything with .ndim) matches the
        explicit-int spelling — call sites stop hand-threading
        ndim=xs.ndim."""
        mesh = create_mesh({"pipe": 4}, jax.devices()[:4])
        xs = jnp.zeros((8, 2, 8), jnp.float32)
        by_int = pipeline.microbatch_sharding(mesh, ndim=xs.ndim)
        by_arr = pipeline.microbatch_sharding(mesh, ndim=xs)
        assert by_int == by_arr
        np_arr = np.zeros((8, 2, 8), np.float32)
        assert pipeline.microbatch_sharding(mesh, ndim=np_arr) == by_int

    def test_explicit_int_still_works(self):
        mesh = create_mesh({"pipe": 4}, jax.devices()[:4])
        sh = pipeline.microbatch_sharding(mesh, ndim=2)
        assert sh.spec == P("pipe", None)


class TestEpUnderV:
    """EP composed under V (ISSUE 15): `moe.moe_ep_body` as an interleaved
    virtual-stage chunk inside the pipeline's pipe×expert shard_map — the
    all-to-all dispatch runs INSIDE the schedule, expert weights sharded
    via ``param_spec``, tokens via ``batch_spec``."""

    def _build(self):
        cfg = moe.MoEConfig(
            d_model=16, d_ff=32, n_experts=4, top_k=2, capacity_factor=2.0
        )
        s, v = 2, 2
        keys = jax.random.split(jax.random.key(0), s * v)
        init = jax.jit(moe.init_params, static_argnums=1)
        layers = [init(k, cfg) for k in keys]
        # chunk order k = v·S + s -> stacked[s][v]
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs)
            .reshape((v, s) + xs[0].shape)
            .transpose((1, 0) + tuple(range(2, 2 + xs[0].ndim))),
            *layers,
        )

        def stage_fn(p_chunk, x):  # x [mb_local, T_local, D]
            y, _aux = moe.moe_ep_body(p_chunk, x, cfg, "expert")
            return x + y

        return cfg, layers, stacked, stage_fn

    def test_matches_sequential_ep_layers(self):
        """pipeline(pipe=2, V=2) of 4 MoE chunks == the same 4
        `moe_apply_ep` layers applied sequentially (capacity factor
        leaves headroom, so the differing shard budgets never bind)."""
        cfg, layers, stacked, stage_fn = self._build()
        mesh = create_mesh({"pipe": 2, "expert": 4})
        m, mb, t = 4, 2, 16
        xs = jnp.asarray(
            np.random.default_rng(0).normal(size=(m, mb, t, 16)),
            jnp.float32,
        )
        param_spec = {
            "router": P("pipe", None),
            "w_in": P("pipe", None, "expert", None, None),
            "w_out": P("pipe", None, "expert", None, None),
        }
        got = pipelined(
            stage_fn, stacked, xs, mesh, batch_spec=P(None, "expert"),
            n_virtual=2, param_spec=param_spec,
        )
        mesh_e = create_mesh({"expert": 4}, jax.devices()[:4])
        layer = jax.jit(  # one program serves the four layers
            lambda p, flat: flat + moe.moe_apply_ep(p, flat, cfg, mesh_e)[0]
        )
        want = xs.reshape(m * mb, t, 16)
        for k in range(4):
            want = layer(layers[k], want)
        want = want.reshape(m, mb, t, 16)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
        )

    def test_param_spec_must_lead_with_pipe_axis(self):
        """A param_spec leaf not leading with the pipe axis would hand
        every device the full stage stack (silently running stage 0's
        weights everywhere) — rejected loudly instead."""
        cfg, _, stacked, stage_fn = self._build()
        mesh = create_mesh({"pipe": 2, "expert": 4})
        xs = jnp.zeros((4, 2, 16, 16), jnp.float32)
        bad = {
            "router": P(),  # replicated: does not shard the stage dim
            "w_in": P("pipe", None, "expert", None, None),
            "w_out": P("pipe", None, "expert", None, None),
        }
        with pytest.raises(ValueError, match="lead with the pipe axis"):
            pipeline.pipeline_apply(
                stage_fn, stacked, xs, mesh,
                batch_spec=P(None, "expert"), n_virtual=2, param_spec=bad,
            )
        # a None leaf means "replicated" to shard_map and is DROPPED by a
        # naive tree flatten — it must hit the same loud rejection
        bad_none = dict(bad, router=None)
        with pytest.raises(ValueError, match="lead with the pipe axis"):
            pipeline.pipeline_apply(
                stage_fn, stacked, xs, mesh,
                batch_spec=P(None, "expert"), n_virtual=2,
                param_spec=bad_none,
            )

    def test_hlo_all_to_all_inside_schedule_no_gather(self):
        """The composed program carries BOTH contracts at once: the
        pipeline's collective-permute rings and EP's all-to-all dispatch,
        with no all-gather of tokens, stream, or expert weights."""
        cfg, _, stacked, stage_fn = self._build()
        mesh = create_mesh({"pipe": 2, "expert": 4})
        xs = jnp.zeros((4, 2, 16, 16), jnp.float32)
        param_spec = {
            "router": P("pipe", None),
            "w_in": P("pipe", None, "expert", None, None),
            "w_out": P("pipe", None, "expert", None, None),
        }
        p_sh = jax.device_put(
            stacked,
            {
                k: NamedSharding(mesh, param_spec[k])
                for k in ("router", "w_in", "w_out")
            },
        )
        xs_sh = jax.device_put(
            xs,
            pipeline.microbatch_sharding(
                mesh, ndim=xs, batch_spec=P(None, "expert")
            ),
        )
        fn = jax.jit(
            lambda p, x: pipeline.pipeline_apply(
                stage_fn, p, x, mesh, batch_spec=P(None, "expert"),
                n_virtual=2, param_spec=param_spec,
            )
        )
        import hlo_util

        hlo_util.assert_hlo(
            fn, (p_sh, xs_sh),
            contains=("collective-permute", "all-to-all"),
            absent=("all-gather",),
        )
