"""MoE layer with expert parallelism vs the per-token oracle: top-1 and
top-2 routing, capacity semantics, and the pinned all-to-all EP dispatch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tools.graftlint import hlo_contracts
from tpu_tfrecord.models import moe
from tpu_tfrecord.tpu import create_mesh

CFG = moe.MoEConfig(d_model=16, d_ff=32, n_experts=4, capacity_factor=1.25)


def setup(b=4, t=20, seed=0, cfg=CFG):
    params = moe.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, t, cfg.d_model)), dtype=jnp.float32)
    return params, x


class TestMoE:
    def test_matches_per_token_oracle(self):
        params, x = setup()
        y, aux = jax.jit(lambda p, x: moe.moe_apply(p, x, CFG))(params, x)
        want = moe.moe_reference(params, x, CFG)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
        assert float(aux) > 0  # load-balance loss is positive by construction

    def test_valid_mask_excludes_padding_everywhere(self):
        """Masked (padding) tokens must not route, consume capacity, or
        feed the aux loss — outputs and aux depend only on valid content.
        Oracle implements the skip independently."""
        cfg = moe.MoEConfig(d_model=16, d_ff=32, n_experts=4, capacity_factor=0.5)
        params, x = setup(cfg=cfg)
        rng = np.random.default_rng(9)
        valid = jnp.asarray(rng.random(x.shape[:-1]) < 0.6)
        y, aux = jax.jit(
            lambda p, x, v: moe.moe_apply(p, x, cfg, valid=v)
        )(params, x, valid)
        want = moe.moe_reference(params, x, cfg, valid=valid)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
        # invalid rows are exactly zero
        assert np.abs(np.asarray(y)[~np.asarray(valid)]).max() == 0.0
        # poisoning ONLY the masked positions changes nothing
        x2 = jnp.where(valid[..., None], x, 1e3)
        y2, aux2 = jax.jit(
            lambda p, x, v: moe.moe_apply(p, x, cfg, valid=v)
        )(params, x2, valid)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y), rtol=1e-5)
        np.testing.assert_allclose(float(aux2), float(aux), rtol=1e-6)

    def test_capacity_drops_tokens_in_arrival_order(self):
        """With capacity_factor tiny, late tokens routed to a full expert
        contribute ZERO (they ride the residual outside the layer) — the
        oracle implements the drop rule independently."""
        cfg = moe.MoEConfig(d_model=16, d_ff=32, n_experts=4, capacity_factor=0.3)
        params, x = setup(cfg=cfg)
        y, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(params, x)
        want = moe.moe_reference(params, x, cfg)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
        # some tokens must actually have been dropped for this test to bite
        flat = np.asarray(y).reshape(-1, cfg.d_model)
        assert (np.abs(flat).sum(axis=-1) == 0).any()

    def test_expert_parallel_sharding_matches(self):
        """Experts sharded over the 'model' axis (EP): same numbers, expert
        weights never replicated."""
        mesh = create_mesh({"data": 2, "model": 4})
        params, x = setup()
        want = moe.moe_reference(params, x, CFG)
        sh = moe.param_shardings(mesh, expert_axis="model")
        p_sh = {k: jax.device_put(v, sh[k]) for k, v in params.items()}
        x_sh = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        y, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, CFG))(p_sh, x_sh)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
        # the expert dim of the weights is genuinely partitioned: each
        # device holds E / axis_size experts, not all E (a regression to
        # replicated would show the full expert dim per shard)
        assert p_sh["w_in"].sharding.spec[0] == "model"
        shard = p_sh["w_in"].addressable_shards[0].data
        assert shard.shape[0] == CFG.n_experts // mesh.shape["model"]

    def test_grads_flow_and_match_shardings(self):
        mesh = create_mesh({"data": 2, "model": 4})
        params, x = setup()
        sh = moe.param_shardings(mesh, expert_axis="model")
        p_sh = {k: jax.device_put(v, sh[k]) for k, v in params.items()}

        def loss(p, x):
            y, aux = moe.moe_apply(p, x, CFG)
            return (y**2).sum() + 0.01 * aux

        g = jax.jit(jax.grad(loss))(p_sh, x)
        g_ref = jax.jit(jax.grad(loss))(params, x)  # the unsharded program
        for k in g:
            np.testing.assert_allclose(
                np.asarray(g[k]), np.asarray(g_ref[k]), rtol=1e-4, atol=1e-5
            )

    def test_bf16_compute(self):
        cfg = moe.MoEConfig(d_model=16, d_ff=32, n_experts=4, dtype=jnp.bfloat16)
        params, x = setup(cfg=cfg)
        y, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(params, x)
        assert y.dtype == x.dtype  # output in the input dtype
        want = moe.moe_reference(params, x, cfg)
        np.testing.assert_allclose(np.asarray(y), want, rtol=5e-2, atol=5e-2)


class TestTop2:
    """Top-2 routing against the capacity-semantics oracle: rank-major
    arrival (every first choice queues before any second choice), raw-prob
    gates, capacity-dropped assignments contribute zero."""

    def test_matches_oracle_on_randomized_batches(self):
        cfg = moe.MoEConfig(
            d_model=16, d_ff=32, n_experts=4, capacity_factor=1.25, top_k=2
        )
        for seed in range(5):
            params, x = setup(b=3, t=24, seed=seed, cfg=cfg)
            y, aux = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg))(params, x)
            want = moe.moe_reference(params, x, cfg)
            np.testing.assert_allclose(
                np.asarray(y), want, rtol=1e-4, atol=1e-5, err_msg=f"seed={seed}"
            )
            assert float(aux) > 0

    def test_tight_capacity_drops_second_choices_first(self):
        """Rank-major arrival means a flood of first choices can push
        second choices past capacity but never vice versa: with factor
        small enough to drop SOME assignments, every surviving slot must
        match the oracle, and top-2 output must dominate top-1 (each token
        keeps at least its first-choice contribution)."""
        cfg2 = moe.MoEConfig(
            d_model=16, d_ff=32, n_experts=4, capacity_factor=0.5, top_k=2
        )
        cfg1 = moe.MoEConfig(
            d_model=16, d_ff=32, n_experts=4, capacity_factor=0.5, top_k=1
        )
        params, x = setup(b=4, t=20, seed=11, cfg=cfg2)
        y2, _ = jax.jit(lambda p, x: moe.moe_apply(p, x, cfg2))(params, x)
        want2 = moe.moe_reference(params, x, cfg2)
        np.testing.assert_allclose(np.asarray(y2), want2, rtol=1e-4, atol=1e-5)
        # capacity budget scales with top_k, so the RANK-0 dispatch under
        # top_k=2 is a superset of top_k=1's: oracle pins both exactly
        want1 = moe.moe_reference(params, x, cfg1)
        assert not np.allclose(want1, want2)  # second choices contributed

    def test_valid_mask_composes_with_top2(self):
        cfg = moe.MoEConfig(
            d_model=16, d_ff=32, n_experts=4, capacity_factor=0.75, top_k=2
        )
        params, x = setup(cfg=cfg)
        rng = np.random.default_rng(3)
        valid = jnp.asarray(rng.random(x.shape[:-1]) < 0.6)
        y, aux = jax.jit(
            lambda p, x, v: moe.moe_apply(p, x, cfg, valid=v)
        )(params, x, valid)
        want = moe.moe_reference(params, x, cfg, valid=valid)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
        assert np.abs(np.asarray(y)[~np.asarray(valid)]).max() == 0.0
        # poisoning ONLY the masked positions changes nothing
        x2 = jnp.where(valid[..., None], x, 1e3)
        y2, aux2 = jax.jit(
            lambda p, x, v: moe.moe_apply(p, x, cfg, valid=v)
        )(params, x2, valid)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y), rtol=1e-5)
        np.testing.assert_allclose(float(aux2), float(aux), rtol=1e-6)

    def test_bad_top_k_rejected(self):
        cfg = moe.MoEConfig(d_model=16, d_ff=32, n_experts=4, top_k=5)
        params, x = setup(cfg=cfg)
        with pytest.raises(ValueError, match="top_k"):
            moe.moe_apply(params, x, cfg)


class TestExplicitEP:
    """moe_apply_ep: the comms-PINNED flavor — tokens and experts sharded
    on the expert axis, dispatch via lax.all_to_all, per-shard capacity."""

    def _sharded(self, mesh, params, x, cfg, expert_axis="expert",
                 x_spec=P(None, "expert", None)):
        sh = moe.param_shardings(mesh, expert_axis=expert_axis)
        p_sh = {k: jax.device_put(v, sh[k]) for k, v in params.items()}
        x_sh = jax.device_put(x, NamedSharding(mesh, x_spec))
        return p_sh, x_sh

    def test_matches_per_shard_oracle(self):
        """EP semantics = the oracle run with shards=P: each token shard
        applies its own capacity budget. The stream is 2-D [T, D] so one
        device's shard IS one contiguous oracle block. Randomized batches,
        both top_k."""
        mesh = create_mesh({"expert": 4}, jax.devices()[:4])
        for top_k in (1, 2):
            cfg = moe.MoEConfig(
                d_model=16, d_ff=32, n_experts=4, capacity_factor=0.75,
                top_k=top_k,
            )
            for seed in range(3):
                params, x3 = setup(b=2, t=16, seed=seed, cfg=cfg)
                x = x3.reshape(-1, cfg.d_model)                 # [32, D]
                p_sh, x_sh = self._sharded(
                    mesh, params, x, cfg, x_spec=P("expert", None)
                )
                y, aux = jax.jit(
                    lambda p, x: moe.moe_apply_ep(p, x, cfg, mesh)
                )(p_sh, x_sh)
                want = moe.moe_reference(params, x, cfg, shards=4)
                np.testing.assert_allclose(
                    np.asarray(y), want, rtol=1e-4, atol=1e-5,
                    err_msg=f"top_k={top_k} seed={seed}",
                )
                assert np.isfinite(float(aux))

    def test_hlo_all_to_all_no_all_gather(self):
        """THE pin moe.py's docstring used to claim without asserting: EP
        dispatch lowers to all-to-all; neither tokens nor expert weights
        are ever gathered. Contract + construction live in the shared
        manifest — this test is its tier-1 driver."""
        hlo_contracts.verify("moe_apply_ep")

    def test_expert_weights_stay_partitioned(self):
        mesh = create_mesh({"expert": 4}, jax.devices()[:4])
        cfg = moe.MoEConfig(d_model=16, d_ff=32, n_experts=4)
        params, x = setup(cfg=cfg)
        p_sh, x_sh = self._sharded(mesh, params, x, cfg)
        y, _ = jax.jit(lambda p, x: moe.moe_apply_ep(p, x, cfg, mesh))(
            p_sh, x_sh
        )
        shard = p_sh["w_in"].addressable_shards[0].data
        assert shard.shape[0] == cfg.n_experts // mesh.shape["expert"]

    def test_composes_with_data_axis(self):
        mesh = create_mesh({"data": 2, "expert": 4})
        cfg = moe.MoEConfig(d_model=16, d_ff=32, n_experts=4, top_k=2)
        # B == data-axis size: each device's shard (one batch row × one
        # T/4 chunk) is one contiguous block of the global flat stream, so
        # oracle shards=8 models the partition exactly
        params, x = setup(b=2, t=16, cfg=cfg)
        p_sh, x_sh = self._sharded(
            mesh, params, x, cfg, x_spec=P("data", "expert", None)
        )
        y, _ = jax.jit(
            lambda p, x: moe.moe_apply_ep(p, x, cfg, mesh, data_axis="data")
        )(p_sh, x_sh)
        want = moe.moe_reference(params, x, cfg, shards=8)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)

    def test_grads_flow_through_all_to_all(self):
        mesh = create_mesh({"expert": 4}, jax.devices()[:4])
        cfg = moe.MoEConfig(d_model=16, d_ff=32, n_experts=4, top_k=2)
        params, x = setup(b=2, t=16, cfg=cfg)
        p_sh, x_sh = self._sharded(mesh, params, x, cfg)

        def loss(p, x):
            y, aux = moe.moe_apply_ep(p, x, cfg, mesh)
            return (y**2).sum() + 0.01 * aux

        g = jax.jit(jax.grad(loss))(p_sh, x_sh)
        for k in g:
            assert np.isfinite(np.asarray(g[k])).all(), k
        # router grads must be nonzero (gates differentiate through probs)
        assert np.abs(np.asarray(g["router"])).max() > 0

    def test_indivisible_shapes_rejected(self):
        mesh = create_mesh({"expert": 4}, jax.devices()[:4])
        params, x = setup(b=2, t=15, cfg=CFG)  # 30 % 4 != 0 on the token dim
        with pytest.raises(ValueError, match="token dim"):
            moe.moe_apply_ep(params, x, CFG, mesh)
        cfg = moe.MoEConfig(d_model=16, d_ff=32, n_experts=6)
        params6, x16 = setup(b=2, t=16, cfg=cfg)
        with pytest.raises(ValueError, match="n_experts"):
            moe.moe_apply_ep(params6, x16, cfg, mesh)
