"""Pallas dot-interaction kernel vs the XLA reference (interpret mode on
CPU; the real-TPU compile/run is chip_smoke.py's comparison phase and
tests/test_tpu_compile.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_tfrecord.models.interaction import (
    dot_interaction,
    dot_interaction_pallas,
    dot_interaction_reference,
)


def make_emb(b=32, f=27, d=16, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(b, f, d)), dtype=dtype)


class TestDotInteraction:
    @pytest.mark.parametrize("b,f,d", [(32, 27, 16), (16, 4, 8), (64, 13, 32)])
    def test_kernel_matches_reference(self, b, f, d):
        emb = make_emb(b, f, d)
        want = dot_interaction_reference(emb)
        got = dot_interaction_pallas(emb, block_b=16, interpret=True)
        assert got.shape == (b, f * (f - 1) // 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)

    def test_bf16(self):
        emb = make_emb(dtype=jnp.bfloat16)
        want = dot_interaction_reference(emb.astype(jnp.float32))
        got = dot_interaction_pallas(emb, block_b=32, interpret=True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32), np.asarray(want), rtol=3e-2, atol=3e-1
        )

    def test_non_divisible_batch_falls_back_to_gcd_tile(self):
        emb = make_emb(b=48)
        got = dot_interaction_pallas(emb, block_b=32, interpret=True)  # tile=gcd(48,32)=16
        want = dot_interaction_reference(emb)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)

    def test_sub_sublane_tile_rejected_loudly(self):
        with pytest.raises(ValueError, match="pad the batch"):
            dot_interaction_pallas(make_emb(b=31), block_b=16, interpret=True)

    def test_gradient_through_pallas_branch(self):
        emb = make_emb(b=8, f=6, d=4)

        def loss_pallas(e):
            return (dot_interaction(e, True, 8, True) ** 2).sum()

        def loss_ref(e):
            return (dot_interaction_reference(e) ** 2).sum()

        g_p = jax.grad(loss_pallas)(emb)
        g_ref = jax.grad(loss_ref)(emb)
        np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_ref), rtol=1e-4, atol=1e-5)

    def test_gradient_matches_reference(self):
        emb = make_emb(b=8, f=6, d=4)

        def loss_k(e):
            return (dot_interaction(e, False) ** 2).sum()

        def loss_ref(e):
            return (dot_interaction_reference(e) ** 2).sum()

        g_k = jax.grad(loss_k)(emb)
        g_ref = jax.grad(loss_ref)(emb)
        np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_ref), rtol=1e-5)

    def test_dispatcher_cpu_uses_reference(self):
        emb = make_emb(b=8, f=5, d=4)
        got = dot_interaction(emb, None)  # cpu backend -> XLA reference
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(dot_interaction_reference(emb)), rtol=1e-6
        )

    def test_large_f_tiles_pair_dim(self):
        """P-tiled grid: F=64 gives P=2016 pairs, forcing multiple pair
        tiles (and padding) under a small block_p — results must still
        match the reference exactly (the pre-tiling kernel OOM'd VMEM
        here on real hardware)."""
        emb = make_emb(b=16, f=64, d=8)
        got = dot_interaction_pallas(emb, block_b=8, block_p=512, interpret=True)
        want = dot_interaction_reference(emb)
        assert got.shape == (16, 64 * 63 // 2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)

    def test_auto_block_b_shrink_preserves_divisibility(self):
        """b=20 with a huge D forces the VMEM-budget shrink; the shrink must
        land on a divisor of b or trailing rows silently vanish from the
        grid (regression: 20 -> 8 left rows 16-19 garbage)."""
        emb = make_emb(b=20, f=8, d=1024)
        got = dot_interaction_pallas(emb, block_b=20, interpret=True)
        want = dot_interaction_reference(emb)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-2
        )

    def test_auto_block_p_budgeted(self):
        # auto-sizing must pick a lane-multiple tile and still be exact
        emb = make_emb(b=16, f=40, d=32)
        got = dot_interaction_pallas(emb, block_b=8, interpret=True)
        want = dot_interaction_reference(emb)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


class TestDLRMDotInteraction:
    def test_training_decreases_loss(self):
        import functools
        import optax
        from tpu_tfrecord.models import DLRMConfig, init_params, loss_fn, make_synthetic_batch, train_step

        cfg = DLRMConfig(num_dense=4, num_categorical=3, vocab_size=16, embed_dim=4,
                         bottom_mlp=(8, 4), top_mlp=(8, 1), interaction="dot")
        params = init_params(jax.random.key(0), cfg)
        batch = {k: jnp.asarray(v) for k, v in make_synthetic_batch(cfg, 32).items()}
        import jax as _jax
        tx = optax.adam(1e-2)
        opt_state = tx.init(params)
        step = _jax.jit(functools.partial(train_step, cfg=cfg, tx=tx))
        first = float(_jax.jit(loss_fn, static_argnums=2)(params, batch, cfg))
        for _ in range(15):
            params, opt_state, loss = step(params, opt_state, batch)
        assert float(loss) < first

    def test_mismatched_dims_rejected(self):
        from tpu_tfrecord.models import DLRMConfig, init_params

        cfg = DLRMConfig(num_dense=4, num_categorical=3, vocab_size=16, embed_dim=8,
                         bottom_mlp=(8, 4), top_mlp=(8, 1), interaction="dot")
        with pytest.raises(ValueError, match="bottom_mlp"):
            init_params(jax.random.key(0), cfg)


class TestDeviceTimeHarness:
    def test_measurement_harness_runs_and_loops_execute(self, monkeypatch):
        """tools/pallas_device_time.py smoke: the fori_loop carry makes K
        data-dependent applications that cannot collapse — the looped
        accumulator must equal K times one application's mean."""
        import sys, os
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
        from tools.pallas_device_time import _looped
        from tpu_tfrecord.models.interaction import dot_interaction_reference

        rng = np.random.default_rng(0)
        emb = jnp.asarray(rng.normal(size=(16, 8, 4)), dtype=jnp.float32)
        one = float(dot_interaction_reference(emb).mean())
        for k in (1, 3, 7):
            acc = float(_looped(dot_interaction_reference, k)(emb))
            # eps=1e-12 feedback leaves values numerically unchanged in f32
            assert acc == pytest.approx(k * one, rel=1e-5), k
