"""Long-document classifier: ring attention inside a full sharded train
step, fed by SequenceExample ingestion (8-device CPU mesh)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpu_tfrecord.io as tfio
from tpu_tfrecord.models import long_doc
from tpu_tfrecord.tpu.mesh import create_mesh

CFG = long_doc.LongDocConfig(
    seq_dim=8, d_model=16, n_heads=2, n_layers=2, n_classes=2, max_len=16,
    dtype=jnp.float32,
)


#: the dense reference and the loss, one program a configuration and mesh
#: (bare, they run primitive by primitive, each primitive a compile)
forward = jax.jit(
    long_doc.forward, static_argnums=(2, 3), static_argnames=("data_axis", "with_aux")
)
loss_fn = jax.jit(long_doc.loss_fn, static_argnums=(2, 3), static_argnames="data_axis")


def _mesh(data=2, seq=4):
    return create_mesh({"data": data, "seq": seq}, jax.devices()[: data * seq])


class TestForward:
    def test_ring_matches_dense_reference(self):
        """forward(mesh) (ring attention, SP-sharded) must equal
        forward(None) (dense oracle) on identical weights and batch."""
        mesh = _mesh()
        params = long_doc.init_params(jax.random.key(0), CFG)
        hb = long_doc.make_synthetic_batch(CFG, 8, seed=1)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        want = forward(params, batch, CFG)  # dense reference
        sh = long_doc.batch_shardings(mesh, hb)
        sharded = {
            k: jax.device_put(v, sh[k]) for k, v in batch.items()
        }
        got = forward(params, sharded, CFG, mesh, data_axis="data")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_padding_is_inert_moe_flavor(self):
        """The MoE FFN must keep the dense flavor's contract: logits (and
        the aux loss) depend ONLY on valid positions — padding content must
        neither route through experts nor consume their capacity."""
        import dataclasses

        cfg = dataclasses.replace(CFG, moe_experts=4, moe_capacity_factor=0.5)
        params = long_doc.init_params(jax.random.key(0), cfg)
        hb = long_doc.make_synthetic_batch(cfg, 8, seed=5)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        base, aux_base = forward(params, batch, cfg, with_aux=True)
        frames = np.asarray(batch["frames"]).copy()
        lengths = np.asarray(batch["frames_len"])
        for i, n in enumerate(lengths):
            frames[i, n:] = 1e3  # garbage in every padded position
        poisoned = dict(batch, frames=jnp.asarray(frames))
        got, aux_got = forward(params, poisoned, cfg, with_aux=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-5)
        np.testing.assert_allclose(float(aux_got), float(aux_base), rtol=1e-6)

    def test_padding_is_inert(self):
        """Changing bytes past frames_len must not change the logits."""
        params = long_doc.init_params(jax.random.key(0), CFG)
        hb = long_doc.make_synthetic_batch(CFG, 4, seed=2)
        hb["frames_len"] = np.minimum(hb["frames_len"], CFG.max_len // 2)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        base = forward(params, batch, CFG)
        hb2 = dict(hb)
        frames2 = hb["frames"].copy()
        frames2[:, CFG.max_len // 2 :] = 99.0  # garbage in the padding
        hb2["frames"] = frames2
        batch2 = {k: jnp.asarray(v) for k, v in hb2.items()}
        out2 = forward(params, batch2, CFG)
        np.testing.assert_allclose(np.asarray(base), np.asarray(out2), rtol=1e-5)


class TestTraining:
    def test_sharded_training_decreases_loss(self):
        import optax

        mesh = _mesh()
        params = long_doc.init_params(jax.random.key(0), CFG)
        tx = optax.adam(3e-3)
        opt_state = tx.init(params)
        p_sh = long_doc.param_shardings(mesh, params)
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(
            opt_state, jax.tree.map(lambda _: p_sh["pos"], opt_state)
        )
        hb = long_doc.make_synthetic_batch(CFG, 16, seed=3)
        b_sh = long_doc.batch_shardings(mesh, hb)
        batch = {k: jax.device_put(jnp.asarray(v), b_sh[k]) for k, v in hb.items()}
        step = jax.jit(
            functools.partial(
                long_doc.train_step, cfg=CFG, tx=tx, mesh=mesh, data_axis="data"
            ),
            donate_argnums=(0, 1),
        )
        first = float(
            loss_fn(
                jax.device_put(long_doc.init_params(jax.random.key(0), CFG), p_sh),
                batch, CFG, mesh, data_axis="data",
            )
        )
        for _ in range(25):
            params, opt_state, loss = step(params, opt_state, batch)
        assert float(loss) < first

    def test_end_to_end_from_sequence_example_files(self, sandbox, tmp_path):
        """The full long-context path: ragged SequenceExample shards ->
        TFRecordDataset -> pad/bucket -> seq-sharded global batch -> one
        ring-attention train step."""
        import optax

        from tpu_tfrecord.io.dataset import TFRecordDataset
        from tpu_tfrecord.schema import (
            ArrayType,
            FloatType,
            LongType,
            StructField,
            StructType,
        )
        from tpu_tfrecord.tpu.ingest import host_batch_from_columnar

        schema = StructType(
            [
                StructField("label", LongType(), nullable=False),
                StructField("frames", ArrayType(ArrayType(FloatType()))),
            ]
        )
        rng = np.random.default_rng(5)
        rows = []
        for _ in range(16):
            n = int(rng.integers(1, CFG.max_len + 1))
            frames = [[float(x) for x in rng.normal(size=CFG.seq_dim)] for _ in range(n)]
            rows.append([int(rng.integers(0, CFG.n_classes)), frames])
        out = str(sandbox / "docs")
        tfio.write(rows, schema, out, mode="overwrite", recordType="SequenceExample")

        mesh = _mesh()
        ds = TFRecordDataset(out, batch_size=16, schema=schema,
                             recordType="SequenceExample")
        with ds.batches() as it:
            cb = next(it)
        hb = host_batch_from_columnar(
            cb, ds.schema, pad_to={"frames": (CFG.max_len, CFG.seq_dim)}
        )
        hb.pop("frames_inner_len")
        b_sh = long_doc.batch_shardings(mesh, hb)
        batch = {
            k: jax.make_array_from_process_local_data(b_sh[k], v)
            for k, v in hb.items()
        }
        params = long_doc.init_params(jax.random.key(1), CFG)
        tx = optax.sgd(1e-2)
        opt_state = tx.init(params)
        step = jax.jit(
            functools.partial(
                long_doc.train_step, cfg=CFG, tx=tx, mesh=mesh, data_axis="data"
            )
        )
        params, opt_state, loss = step(params, opt_state, batch)
        assert np.isfinite(float(loss))

    def test_remat_grads_match_non_remat(self):
        """jax.checkpoint changes memory, never math: gradients with
        remat=True must equal the plain backward."""
        import dataclasses

        params = long_doc.init_params(jax.random.key(0), CFG)
        hb = long_doc.make_synthetic_batch(CFG, 8, seed=4)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        cfg_r = dataclasses.replace(CFG, remat=True)
        g_plain, g_remat = (
            jax.jit(jax.grad(lambda p: long_doc.loss_fn(p, batch, cfg)))(params) for cfg in (CFG, cfg_r))
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            g_plain,
            g_remat,
        )

    def test_remat_grads_match_on_ring_attention_mesh(self):
        """remat=True is FOR the long-context SP path: jax.checkpoint must
        compile and differentiate through the shard_map + ppermute ring and
        produce the same gradients as the non-remat sharded backward."""
        import dataclasses

        mesh = _mesh()
        params = long_doc.init_params(jax.random.key(0), CFG)
        hb = long_doc.make_synthetic_batch(CFG, 8, seed=5)
        sh = long_doc.batch_shardings(mesh, hb)
        batch = {k: jax.device_put(jnp.asarray(v), sh[k]) for k, v in hb.items()}
        cfg_r = dataclasses.replace(CFG, remat=True)
        g_plain, g_remat = (
            jax.jit(jax.grad(lambda p: long_doc.loss_fn(p, batch, cfg, mesh, data_axis="data")))(params)
            for cfg in (CFG, cfg_r))
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
            ),
            g_plain,
            g_remat,
        )

    def test_ring_hlo_has_collective_permute_no_allgather(self):
        """The SP path must ride ICI neighbor hops, not gather the sequence."""
        mesh = _mesh()
        params = long_doc.init_params(jax.random.key(0), CFG)
        hb = long_doc.make_synthetic_batch(CFG, 8, seed=1)
        b_sh = long_doc.batch_shardings(mesh, hb)
        batch = {k: jax.device_put(jnp.asarray(v), b_sh[k]) for k, v in hb.items()}
        from hlo_util import assert_hlo

        fn = jax.jit(
            functools.partial(
                long_doc.forward, cfg=CFG, mesh=mesh, data_axis="data"
            )
        )
        assert_hlo(
            fn, (params, batch),
            contains=["collective-permute"], absent=["all-gather"],
        )


class TestUlyssesFlavor:
    def test_ulysses_matches_dense_reference_end_to_end(self):
        """cfg.sp_attention='ulysses' routes the blocks through the
        all-to-all SP attention; logits must equal the dense oracle (and
        therefore the ring flavor) on identical weights and batch. n_heads=2
        covers the 2-way seq axis."""
        import dataclasses

        cfg = dataclasses.replace(CFG, sp_attention="ulysses")
        mesh = _mesh(data=2, seq=2)
        params = long_doc.init_params(jax.random.key(0), cfg)
        hb = long_doc.make_synthetic_batch(cfg, 8, seed=1)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        want = forward(params, batch, cfg)  # dense reference
        sh = long_doc.batch_shardings(mesh, hb)
        sharded = {k: jax.device_put(v, sh[k]) for k, v in batch.items()}
        got = forward(params, sharded, cfg, mesh, data_axis="data")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_bad_flavor_rejected(self):
        import dataclasses

        cfg = dataclasses.replace(CFG, sp_attention="flash")
        with pytest.raises(ValueError, match="sp_attention"):
            long_doc.init_params(jax.random.key(0), cfg)
        # a config mutated AFTER init_params must fail in forward too, not
        # silently run the ring flavor (code-review r5 finding)
        params = long_doc.init_params(jax.random.key(0), CFG)
        hb = long_doc.make_synthetic_batch(CFG, 4, seed=0)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        with pytest.raises(ValueError, match="sp_attention"):
            long_doc.forward(params, batch, cfg, mesh=_mesh(data=2, seq=2))


class TestMoEFlavor:
    """moe_experts > 0 swaps the blocks' FFN for the Switch MoE layer
    (models.moe) — SP attention and EP FFN compose in one model."""

    def _cfg(self, **kw):
        import dataclasses

        return dataclasses.replace(
            CFG, moe_experts=4, moe_aux_weight=0.01, **kw
        )

    def test_aux_loss_flows(self):
        cfg = self._cfg()
        params = long_doc.init_params(jax.random.key(0), cfg)
        assert "moe" in params["layers"][0] and "mlp_in" not in params["layers"][0]
        hb = long_doc.make_synthetic_batch(cfg, 8, seed=1)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        logits, aux = forward(params, batch, cfg, with_aux=True)
        assert logits.shape == (8, cfg.n_classes)
        assert float(aux) > 0  # load-balance loss accumulated across layers
        # dense flavor reports exactly zero aux
        dp = long_doc.init_params(jax.random.key(0), CFG)
        _, aux0 = forward(dp, batch, CFG, with_aux=True)
        assert float(aux0) == 0.0

    def test_ep_sharded_params_match_replicated(self):
        from tpu_tfrecord.models import moe as moe_mod

        cfg = self._cfg()
        mesh = create_mesh({"data": 2, "seq": 2, "expert": 2})
        params = long_doc.init_params(jax.random.key(0), cfg)
        hb = long_doc.make_synthetic_batch(cfg, 8, seed=2)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        want = forward(params, batch, cfg)
        sh = moe_mod.param_shardings(mesh, expert_axis="expert")
        p_sh = dict(params)
        p_sh["layers"] = [
            {**layer, "moe": {k: jax.device_put(v, sh[k]) for k, v in layer["moe"].items()}}
            for layer in params["layers"]
        ]
        got = forward(p_sh, batch, cfg)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_moe_longdoc_trains_on_sp_mesh(self):
        """Full composition: SP attention (mesh 'seq' axis) + EP-SHARDED
        experts (mesh 'expert' axis) + aux loss in ONE jit train step;
        loss must decrease and the experts must stay partitioned."""
        import optax

        from tpu_tfrecord.models import moe as moe_mod

        cfg = self._cfg()
        mesh = create_mesh({"data": 2, "seq": 2, "expert": 2})
        params = long_doc.init_params(jax.random.key(0), cfg)
        esh = moe_mod.param_shardings(mesh, expert_axis="expert")
        params["layers"] = [
            {**ly, "moe": {k: jax.device_put(v, esh[k]) for k, v in ly["moe"].items()}}
            for ly in params["layers"]
        ]
        tx = optax.adam(3e-3)
        opt = tx.init(params)
        hb = long_doc.make_synthetic_batch(cfg, 16, seed=3)
        batch = {k: jnp.asarray(v) for k, v in hb.items()}
        step = jax.jit(
            functools.partial(
                long_doc.train_step, cfg=cfg, tx=tx, mesh=mesh, data_axis="data"
            )
        )
        first = None
        for _ in range(30):
            params, opt, loss = step(params, opt, batch)
            first = first if first is not None else float(loss)
        assert float(loss) < first, (first, float(loss))
        # the updated expert weights are still EP-partitioned, not gathered
        w = params["layers"][0]["moe"]["w_in"]
        assert w.addressable_shards[0].data.shape[0] == cfg.moe_experts // 2


class TestGQAFlavor:
    def test_gqa_mesh_matches_dense_reference(self):
        """n_kv_heads < n_heads: the SP mesh path must equal the dense
        reference on identical weights/batch (both flavors)."""
        import dataclasses

        # ring takes any Hkv (MQA Hkv=1 here); ulysses also needs
        # Hkv % seq-axis == 0, so it runs GQA with Hkv=2 over 4 q heads
        for flavor, heads, kv in (("ring", 2, 1), ("ulysses", 4, 2)):
            cfg = dataclasses.replace(
                CFG, n_heads=heads, n_kv_heads=kv, sp_attention=flavor
            )
            mesh = _mesh(data=2, seq=2)
            params = long_doc.init_params(jax.random.key(0), cfg)
            hb = long_doc.make_synthetic_batch(cfg, 8, seed=4)
            batch = {k: jnp.asarray(v) for k, v in hb.items()}
            want = forward(params, batch, cfg)
            sh = long_doc.batch_shardings(mesh, hb)
            sharded = {k: jax.device_put(v, sh[k]) for k, v in batch.items()}
            got = forward(params, sharded, cfg, mesh, data_axis="data")
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
            )

    def test_kv_heads_shrink_qkv_projection(self):
        import dataclasses

        cfg = dataclasses.replace(CFG, n_kv_heads=1)
        params = long_doc.init_params(jax.random.key(0), cfg)
        dh = cfg.d_model // cfg.n_heads
        assert params["layers"][0]["qkv"]["w"].shape[-1] == (cfg.n_heads + 2) * dh

    def test_indivisible_kv_heads_rejected(self):
        import dataclasses

        cfg = dataclasses.replace(CFG, n_heads=2, n_kv_heads=0)  # fine
        long_doc.init_params(jax.random.key(0), cfg)
        bad = dataclasses.replace(CFG, n_heads=4, n_kv_heads=3)
        with pytest.raises(ValueError, match="n_kv_heads"):
            long_doc.init_params(jax.random.key(0), bad)
