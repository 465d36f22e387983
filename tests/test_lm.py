"""Causal LM: the end-to-end consumer of zigzag ring attention, the
scale-shaped pipeline, and the all-to-all MoE — every parallel mode must
reproduce the dense reference on the same params and data (the dp×pp
composition test ROADMAP #4a names), and the packed-batch feed must
checkpoint/resume byte-identically."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tools.graftlint import hlo_contracts
from tpu_tfrecord.models import lm
from tpu_tfrecord.tpu import TokenPacker, create_mesh

CFG = lm.LMConfig(vocab_size=64, d_model=16, n_heads=2, n_layers=2, max_len=16)

#: the dense reference, one program a configuration (bare, it runs primitive
#: by primitive, each primitive a compile)
dense_forward = jax.jit(lm.forward, static_argnums=2)


def sharded_forward(cfg, mesh, **axes):
    return jax.jit(functools.partial(lm.forward, cfg=cfg, mesh=mesh, **axes))


def batch(cfg=CFG, b=8, seed=0):
    return jnp.asarray(lm.make_synthetic_tokens(cfg, b, seed=seed))


class TestForwardParity:
    def test_zigzag_sp_matches_dense_reference(self):
        """mesh(dp×sp) + zigzag causal ring == the dense forward on the
        same params and tokens — the repo's most intricate code finally
        sits behind an end-to-end parity pin."""
        mesh = create_mesh({"data": 2, "seq": 4})
        params = lm.init_params(jax.random.key(0), CFG)
        toks = batch()
        want, _ = dense_forward(params, toks, CFG)
        sh = lm.batch_shardings(mesh)
        toks_sh = jax.device_put(toks, sh["tokens"])
        got, _ = sharded_forward(CFG, mesh, data_axis="data", seq_axis="seq")(params, toks_sh)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_pipeline_matches_dense_reference(self):
        """mesh(dp×pp): blocks as pipeline stages == the dense forward."""
        cfg = lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=4, max_len=16,
            n_micro=4,
        )
        mesh = create_mesh({"pipe": 4, "data": 2})
        params = lm.init_params(jax.random.key(0), cfg)
        toks = batch(cfg)
        want, _ = dense_forward(params, toks, cfg)
        p_sh = jax.device_put(
            params, lm.param_shardings(mesh, params, pipe_axis="pipe")
        )
        got, _ = sharded_forward(cfg, mesh, data_axis="data", pipe_axis="pipe")(p_sh, toks)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )

    def test_interleaved_pipeline_matches_dense_reference(self):
        """mesh(dp×pp) with n_virtual=2: each device owns 2 round-robin
        layer chunks; the interleaved schedule must still equal the dense
        forward, and the measured bubble must beat the 1F1B analytic."""
        cfg = lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=4, max_len=16,
            n_micro=4, n_virtual=2,
        )
        mesh = create_mesh({"pipe": 2, "data": 2}, jax.devices()[:4])
        params = lm.init_params(jax.random.key(0), cfg)
        toks = batch(cfg)
        want, _ = dense_forward(params, toks, cfg)
        p_sh = jax.device_put(
            params, lm.param_shardings(mesh, params, pipe_axis="pipe")
        )
        got, _, diag = sharded_forward(cfg, mesh, data_axis="data", pipe_axis="pipe", diagnostics=True)(p_sh, toks)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )
        # S=2, V=2, M=4: (S-1)/(V·M+S-1) = 1/9, below 1F1B's 1/5
        assert float(diag["bubble_fraction"]) == pytest.approx(
            1 / 9, abs=1e-6
        )

    def test_interleaved_layer_count_mismatch_rejected(self):
        cfg = lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=2, max_len=16,
            n_virtual=2,
        )
        mesh = create_mesh({"pipe": 4, "data": 2})
        params = lm.init_params(jax.random.key(0), cfg)
        with pytest.raises(ValueError, match="n_virtual"):
            lm.forward(
                params, batch(cfg), cfg, mesh, data_axis="data",
                pipe_axis="pipe",
            )

    def test_moe_ep_matches_unsharded_moe(self):
        """expert_axis routes the FFN through the pinned all-to-all EP;
        per-shard capacity means parity holds vs moe_apply when the
        factor leaves headroom (no cross-shard drops at this scale)."""
        cfg = lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=2, max_len=16,
            moe_experts=4, moe_top_k=2, moe_capacity_factor=4.0,
        )
        mesh = create_mesh({"data": 2, "expert": 4})
        params = lm.init_params(jax.random.key(0), cfg)
        toks = batch(cfg)
        want, aux_want = dense_forward(params, toks, cfg)
        p_sh = jax.device_put(
            params, lm.param_shardings(mesh, params, expert_axis="expert")
        )
        got, aux = sharded_forward(cfg, mesh, data_axis="data", expert_axis="expert")(p_sh, toks)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
        )
        assert float(aux) > 0

    def test_mode_conflicts_rejected(self):
        mesh = create_mesh({"pipe": 4, "seq": 2})
        params = lm.init_params(jax.random.key(0), CFG)
        toks = batch()
        with pytest.raises(ValueError, match="mutually exclusive"):
            lm.forward(
                params, toks, CFG, mesh, seq_axis="seq", pipe_axis="pipe"
            )
        cfg = lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=2, max_len=16,
            moe_experts=4,
        )
        with pytest.raises(ValueError, match="pipeline"):
            lm.forward(
                lm.init_params(jax.random.key(0), cfg), toks, cfg, mesh,
                pipe_axis="pipe",
            )


class TestComposition:
    """Same params + same data => same loss trajectory as pure dp — the
    missing dp×pp composition test."""

    def _trajectory(self, cfg, mesh=None, steps=6, **axes):
        params = lm.init_params(jax.random.key(0), cfg)
        if mesh is not None and axes.get("pipe_axis"):
            params = jax.device_put(
                params,
                lm.param_shardings(mesh, params, pipe_axis=axes["pipe_axis"]),
            )
        tx = optax.adam(3e-3)
        opt = tx.init(params)
        step = jax.jit(
            functools.partial(lm.train_step, cfg=cfg, tx=tx, mesh=mesh, **axes)
        )
        losses = []
        for i in range(steps):
            toks = batch(cfg, b=8, seed=100 + i)
            params, opt, loss = step(params, opt, toks)
            losses.append(float(loss))
        return losses

    def test_dp_pp_trajectory_matches_pure_dp(self):
        cfg = lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=4, max_len=16,
            n_micro=4,
        )
        ref = self._trajectory(cfg)
        mesh = create_mesh({"pipe": 4, "data": 2})
        got = self._trajectory(
            cfg, mesh=mesh, data_axis="data", pipe_axis="pipe"
        )
        np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)

    def test_dp_sp_trajectory_matches_pure_dp(self):
        ref = self._trajectory(CFG)
        mesh = create_mesh({"data": 2, "seq": 4})
        got = self._trajectory(
            CFG, mesh=mesh, data_axis="data", seq_axis="seq"
        )
        np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)

    def test_interleaved_dp_pp_trajectory_matches_pure_dp(self):
        """Grads unperturbed by interleaving: same params + same data =>
        same loss trajectory as pure dp, V=2."""
        cfg = lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=4, max_len=16,
            n_micro=2, n_virtual=2,
        )
        ref = self._trajectory(cfg)
        mesh = create_mesh({"pipe": 2, "data": 4})
        got = self._trajectory(
            cfg, mesh=mesh, data_axis="data", pipe_axis="pipe"
        )
        np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


class TestLMStream:
    """The serving flavor: streamed logits == the batch path bitwise, and
    both match the dense reference."""

    def _cfg(self):
        return lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=4, max_len=16,
            n_micro=4, n_virtual=2,
        )

    def test_streamed_logits_bitwise_equal_batch_path(self):
        cfg = self._cfg()
        mesh = create_mesh({"pipe": 2}, jax.devices()[:2])
        params = lm.init_params(jax.random.key(0), cfg)
        stream = lm.LMStream(params, cfg, mesh)
        reqs = [lm.make_synthetic_tokens(cfg, 4, seed=i) for i in range(6)]
        outs = []
        for r in reqs:
            outs.extend(stream.submit(r))
        outs.extend(stream.flush())
        assert len(outs) == len(reqs)
        ref = stream.batch_reference(reqs)
        for got, want in zip(outs, ref):
            np.testing.assert_array_equal(got, want)
        dense_cfg = lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=4, max_len=16
        )
        for got, r in zip(outs, reqs):
            want, _ = dense_forward(params, jnp.asarray(r), dense_cfg)
            np.testing.assert_allclose(
                got, np.asarray(want), rtol=2e-4, atol=2e-4
            )

    def test_moe_rejected(self):
        cfg = lm.LMConfig(
            vocab_size=64, d_model=16, n_heads=2, n_layers=4, max_len=16,
            moe_experts=4,
        )
        mesh = create_mesh({"pipe": 2}, jax.devices()[:2])
        params = lm.init_params(jax.random.key(0), cfg)
        with pytest.raises(ValueError, match="pipeline"):
            lm.LMStream(params, cfg, mesh)


class TestTraining:
    def test_zigzag_sp_loss_decreases(self):
        """The headline dryrun shape at test scale: zigzag causal ring
        attention inside a jitted train step, loss falls on the bigram
        language."""
        mesh = create_mesh({"data": 4, "seq": 2})
        params = lm.init_params(jax.random.key(0), CFG)
        tx = optax.adam(3e-3)
        opt = tx.init(params)
        step = jax.jit(
            functools.partial(
                lm.train_step, cfg=CFG, tx=tx, mesh=mesh, data_axis="data",
                seq_axis="seq",
            )
        )
        first = None
        for i in range(30):
            toks = batch(b=16, seed=i)
            params, opt, loss = step(params, opt, toks)
            first = first if first is not None else float(loss)
        assert float(loss) < first, (first, float(loss))

    def test_pipeline_hlo_no_gather_of_microbatch_stream(self):
        """The acceptance pin, at the TRAIN-STEP level: the compiled dp×pp
        step moves activations by collective-permute and never all-gathers
        the microbatch stream (grads over 'data' still all-reduce — that
        is dp's collective, not the pipeline's). Pin + construction live
        in the shared manifest."""
        hlo_contracts.verify("lm_train_step")


class TestTokenPacker:
    def test_packs_stream_exactly(self):
        pk = TokenPacker(batch_size=2, seq_len=4, eos_id=0)
        docs = [np.arange(1, 8), np.arange(10, 13), np.arange(20, 31)]
        pk.feed_docs(docs)
        stream = []
        for d in docs:
            stream.extend(d.tolist())
            stream.append(0)
        got = []
        while (b := pk.pop()) is not None:
            assert b.shape == (2, 5) and b.dtype == np.int32
            got.extend(b.reshape(-1).tolist())
        assert got == stream[: len(got)]
        assert len(stream) - len(got) < 2 * 5  # only the tail remains

    def test_state_resume_is_byte_identical(self):
        """Checkpoint mid-stream, feed the SAME remaining docs to a fresh
        packer restored from the state: the packed batches match the
        uninterrupted run exactly."""
        rng = np.random.default_rng(0)
        docs = [
            rng.integers(1, 50, size=rng.integers(3, 20)) for _ in range(40)
        ]
        a = TokenPacker(batch_size=2, seq_len=8)
        full = []
        for d in docs:
            a.feed_docs([d])
            while (b := a.pop()) is not None:
                full.append(b)
        # interrupted at doc 17 — with batches still pending in the carry
        b1 = TokenPacker(batch_size=2, seq_len=8)
        early = []
        for d in docs[:17]:
            b1.feed_docs([d])
        while len(early) < 3 and (bt := b1.pop()) is not None:
            early.append(bt)
        state = b1.state()
        b2 = TokenPacker(batch_size=2, seq_len=8)
        b2.restore(state)
        resumed = list(early)
        while (bt := b2.pop()) is not None:
            resumed.append(bt)
        for d in docs[17:]:
            b2.feed_docs([d])
            while (bt := b2.pop()) is not None:
                resumed.append(bt)
        assert len(resumed) == len(full)
        for x, y in zip(resumed, full):
            np.testing.assert_array_equal(x, y)

    def test_feed_column_matches_feed_docs(self):
        from tpu_tfrecord.columnar import Column
        from tpu_tfrecord.schema import LongType

        rng = np.random.default_rng(1)
        docs = [rng.integers(0, 9, size=n) for n in (3, 7, 2, 9)]
        values = np.concatenate(docs).astype(np.int64)
        offsets = np.cumsum([0] + [len(d) for d in docs]).astype(np.int64)
        a = TokenPacker(2, 3)
        a.feed_docs(docs)
        b = TokenPacker(2, 3)
        b.feed_column(
            Column("tokens", LongType(), values=values, offsets=offsets)
        )
        while (x := a.pop()) is not None:
            np.testing.assert_array_equal(x, b.pop())
        assert b.pop() is None

    def test_bad_sizes_rejected(self):
        with pytest.raises(ValueError, match="batch_size"):
            TokenPacker(0, 4)
