"""Ring attention vs the dense oracle on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hlo_util import assert_hlo
from tpu_tfrecord.models.attention import attention_reference, ring_attention
from tpu_tfrecord.tpu import create_mesh


def make_qkv(b=2, l=32, h=2, d=8, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, l, h, d)), dtype=dtype)
    return mk(), mk(), mk()


#: ``ring_attention`` as one program a mesh, mode and shape (bare, it runs
#: primitive by primitive, each primitive a compile)
ring = jax.jit(ring_attention, static_argnums=3, static_argnames=("data_axis", "causal", "zigzag"))


class TestRingAttention:
    def test_matches_dense_oracle_8way(self):
        mesh = create_mesh({"seq": 8})
        q, k, v = make_qkv()
        want = attention_reference(q, k, v)
        got = ring(q, k, v, mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_matches_with_data_and_seq_axes(self):
        mesh = create_mesh({"data": 2, "seq": 4})
        q, k, v = make_qkv(b=4, l=16)
        want = attention_reference(q, k, v)
        # batch on 'data', sequence on 'seq'
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(mesh, P("data", "seq", None, None))
        q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
        got = ring(q, k, v, mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_single_device_axis_degenerates(self):
        mesh = create_mesh({"seq": 1, "data": 8})
        q, k, v = make_qkv(l=8)
        want = attention_reference(q, k, v)
        got = ring(q, k, v, mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_bf16_inputs(self):
        mesh = create_mesh({"seq": 4, "data": 2})
        q, k, v = make_qkv(l=16, dtype=jnp.bfloat16)
        got = ring(q, k, v, mesh)
        assert got.dtype == jnp.bfloat16
        want = attention_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                                   v.astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32), np.asarray(want), rtol=3e-2, atol=3e-2
        )

    def test_grad_flows(self):
        mesh = create_mesh({"seq": 8})
        q, k, v = make_qkv(l=16)

        def loss(q, k, v):
            return ring_attention(q, k, v, mesh).sum()

        g = jax.jit(jax.grad(loss))(q, k, v)
        assert np.isfinite(np.asarray(g)).all()
        # oracle gradient agreement
        g_ref = jax.jit(jax.grad(lambda q, k, v: attention_reference(q, k, v).sum()))(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4, atol=1e-5)


class TestRingAttentionMaskAndSharding:
    def test_padding_mask_matches_oracle(self):
        mesh = create_mesh({"seq": 8})
        q, k, v = make_qkv(b=3, l=32)
        lengths = jnp.asarray([32, 10, 1], dtype=jnp.int32)
        want = attention_reference(q, k, v, lengths=lengths)
        got = ring(q, k, v, mesh, lengths=lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_mask_actually_excludes_pad_keys(self):
        mesh = create_mesh({"seq": 4}, jax.devices()[:4])
        q, k, v = make_qkv(b=1, l=16)
        lengths = jnp.asarray([5], dtype=jnp.int32)
        base = ring(q, k, v, mesh, lengths=lengths)
        # garbage in the padded K/V region must not change the output
        k2 = k.at[:, 5:].set(999.0)
        v2 = v.at[:, 5:].set(-999.0)
        got = ring(q, k2, v2, mesh, lengths=lengths)
        np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-6)

    def test_data_axis_keeps_batch_sharded(self):
        mesh = create_mesh({"data": 2, "seq": 4})
        q, k, v = make_qkv(b=4, l=16)
        want = attention_reference(q, k, v)
        fn = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh, data_axis="data")
        )
        got = fn(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)
        # batch dim must be sharded on 'data' in the compiled output, and the
        # HLO must not all-gather the batch
        assert got.sharding.spec[0] == "data"
        assert_hlo(fn, (q, k, v), absent=["all-gather"])


class TestUlyssesAttention:
    """All-to-all (DeepSpeed-Ulysses) sequence parallelism: same contract
    as ring_attention, collective profile = 2 all_to_alls instead of p-1
    K/V rotations (SURVEY.md: 'ring attention OR all-to-all')."""

    def test_matches_dense_oracle_8way(self):
        from tpu_tfrecord.models.attention import ulysses_attention

        mesh = create_mesh({"seq": 8})
        q, k, v = make_qkv(h=8)
        want = attention_reference(q, k, v)
        got = jax.jit(lambda q, k, v: ulysses_attention(q, k, v, mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_matches_ring_and_oracle_with_mask(self):
        from tpu_tfrecord.models.attention import ulysses_attention

        mesh = create_mesh({"seq": 4, "data": 2})
        q, k, v = make_qkv(b=3, l=16, h=4)
        lengths = jnp.asarray([16, 9, 2], dtype=jnp.int32)
        want = attention_reference(q, k, v, lengths=lengths)
        got_u = jax.jit(
            lambda q, k, v, le: ulysses_attention(q, k, v, mesh, lengths=le)
        )(q, k, v, lengths)
        got_r = ring(q, k, v, mesh, lengths=lengths)
        np.testing.assert_allclose(np.asarray(got_u), np.asarray(want), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(got_u), np.asarray(got_r), rtol=2e-5, atol=2e-6)

    def test_grad_matches_oracle(self):
        from tpu_tfrecord.models.attention import ulysses_attention

        mesh = create_mesh({"seq": 8})
        q, k, v = make_qkv(l=16, h=8)
        g = jax.jit(
            jax.grad(lambda q, k, v: ulysses_attention(q, k, v, mesh).sum())
        )(q, k, v)
        g_ref = jax.jit(jax.grad(lambda q, k, v: attention_reference(q, k, v).sum()))(q, k, v)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4, atol=1e-5)

    def test_heads_must_cover_axis(self):
        from tpu_tfrecord.models.attention import ulysses_attention

        mesh = create_mesh({"seq": 8})
        q, k, v = make_qkv(h=2)  # 2 heads cannot split 8 ways
        with pytest.raises(ValueError, match="num_heads"):
            ulysses_attention(q, k, v, mesh)

    def test_hlo_all_to_all_no_all_gather(self):
        from tpu_tfrecord.models.attention import ulysses_attention

        mesh = create_mesh({"data": 2, "seq": 4})
        q, k, v = make_qkv(b=4, l=16, h=4)
        fn = jax.jit(
            lambda q, k, v: ulysses_attention(q, k, v, mesh, data_axis="data")
        )
        got = fn(q, k, v)
        assert got.sharding.spec[0] == "data"
        assert_hlo(fn, (q, k, v), contains=["all-to-all"], absent=["all-gather"])

    def test_bf16_inputs(self):
        from tpu_tfrecord.models.attention import ulysses_attention

        mesh = create_mesh({"seq": 4, "data": 2})
        q, k, v = make_qkv(l=16, h=4, dtype=jnp.bfloat16)
        got = jax.jit(lambda q, k, v: ulysses_attention(q, k, v, mesh))(q, k, v)
        assert got.dtype == jnp.bfloat16
        want = attention_reference(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
        )
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32), np.asarray(want), rtol=3e-2, atol=3e-2
        )


class TestGQA:
    """Grouped-query attention: k/v carry Hkv < H heads; each K/V head
    serves H/Hkv query heads. Both SP flavors stay comm-optimal (only the
    Hkv heads rotate/exchange; the repeat happens locally)."""

    @staticmethod
    def make_gqa(b=2, l=32, h=8, hkv=2, d=8, seed=0, dtype=jnp.float32):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(b, l, h, d)), dtype=dtype)
        k = jnp.asarray(rng.normal(size=(b, l, hkv, d)), dtype=dtype)
        v = jnp.asarray(rng.normal(size=(b, l, hkv, d)), dtype=dtype)
        return q, k, v

    def oracle(self, q, k, v, lengths=None):
        """Independent GQA oracle: explicit repeat to H heads + dense MHA
        (differentiable — the grad test traces through it)."""
        g = q.shape[2] // k.shape[2]
        kx = jnp.repeat(k, g, axis=2)
        vx = jnp.repeat(v, g, axis=2)
        return attention_reference(q, kx, vx, lengths=lengths)

    def test_ring_gqa_matches_oracle(self):
        mesh = create_mesh({"seq": 8})
        q, k, v = self.make_gqa()
        want = self.oracle(q, k, v)
        got = ring(q, k, v, mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_ulysses_gqa_matches_oracle_and_ring(self):
        from tpu_tfrecord.models.attention import ulysses_attention

        mesh = create_mesh({"seq": 2, "data": 4})
        q, k, v = self.make_gqa(b=4, l=16, h=4, hkv=2)
        lengths = jnp.asarray([16, 9, 4, 1], dtype=jnp.int32)
        want = self.oracle(q, k, v, lengths=lengths)
        got_u = jax.jit(
            lambda q, k, v, le: ulysses_attention(q, k, v, mesh, lengths=le)
        )(q, k, v, lengths)
        got_r = ring(q, k, v, mesh, lengths=lengths)
        np.testing.assert_allclose(np.asarray(got_u), np.asarray(want), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(got_r), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_gqa_grads_match_oracle(self):
        mesh = create_mesh({"seq": 4}, jax.devices()[:4])
        q, k, v = self.make_gqa(l=16, h=4, hkv=2)
        g = jax.jit(
            jax.grad(lambda q, k, v: ring_attention(q, k, v, mesh).sum(), argnums=(0, 1, 2))
        )(q, k, v)
        g_ref = jax.jit(jax.grad(
            lambda q, k, v: self.oracle(q, k, v).sum(), argnums=(0, 1, 2)
        ))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)

    def test_mqa_single_kv_head(self):
        """MQA (Hkv=1): ring rotates a single K/V head."""
        mesh = create_mesh({"seq": 4}, jax.devices()[:4])
        q, k, v = self.make_gqa(h=4, hkv=1, l=16)
        want = self.oracle(q, k, v)
        got = ring(q, k, v, mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_indivisible_heads_rejected(self):
        mesh = create_mesh({"seq": 4}, jax.devices()[:4])
        q, k, v = self.make_gqa(h=4, hkv=3, l=16)
        with pytest.raises(ValueError, match="num_kv_heads"):
            ring_attention(q, k, v, mesh)


class TestCausal:
    """Decoder/LM masking: keys after the query position get no mass.
    The ring must mask by GLOBAL positions across rotated blocks; ulysses
    inherits the mask locally after the exchange."""

    def test_ring_causal_matches_oracle(self):
        mesh = create_mesh({"seq": 8})
        q, k, v = make_qkv()
        want = attention_reference(q, k, v, causal=True)
        got = ring(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_ulysses_causal_matches_oracle(self):
        from tpu_tfrecord.models.attention import ulysses_attention

        mesh = create_mesh({"seq": 4, "data": 2})
        q, k, v = make_qkv(l=16, h=4)
        want = attention_reference(q, k, v, causal=True)
        got = jax.jit(
            lambda q, k, v: ulysses_attention(q, k, v, mesh, causal=True)
        )(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_causal_composes_with_lengths_and_gqa(self):
        mesh = create_mesh({"seq": 4}, jax.devices()[:4])
        q = jnp.asarray(np.random.default_rng(0).normal(size=(3, 16, 4, 8)), jnp.float32)
        kv = [jnp.asarray(np.random.default_rng(i).normal(size=(3, 16, 2, 8)), jnp.float32) for i in (1, 2)]
        lengths = jnp.asarray([16, 7, 2], dtype=jnp.int32)
        g = 2
        want = attention_reference(
            q, jnp.repeat(kv[0], g, axis=2), jnp.repeat(kv[1], g, axis=2),
            lengths=lengths, causal=True,
        )
        got = ring(q, kv[0], kv[1], mesh, lengths=lengths, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_future_keys_are_inert(self):
        """Garbage in strictly-future K/V positions must not change any
        query's output (the operational meaning of causal)."""
        mesh = create_mesh({"seq": 4}, jax.devices()[:4])
        q, k, v = make_qkv(b=1, l=16)
        base = ring(q, k, v, mesh, causal=True)
        # poison the second half; queries in the FIRST half must not move
        k2 = k.at[:, 8:].set(777.0)
        v2 = v.at[:, 8:].set(-777.0)
        got = ring(q, k2, v2, mesh, causal=True)
        np.testing.assert_allclose(
            np.asarray(got)[:, :8], np.asarray(base)[:, :8], rtol=1e-6
        )

    def test_causal_grads_match_oracle(self):
        mesh = create_mesh({"seq": 8})
        q, k, v = make_qkv(l=16)
        g = jax.jit(
            jax.grad(lambda q, k, v: ring_attention(q, k, v, mesh, causal=True).sum(),
                     argnums=(0, 1, 2))
        )(q, k, v)
        g_ref = jax.jit(jax.grad(
            lambda q, k, v: attention_reference(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2),
        ))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)


class TestZigzagCausal:
    """Balanced causal ring: internal strip re-striping, contiguous
    contract preserved, identical math."""

    def test_matches_contiguous_and_oracle(self):
        mesh = create_mesh({"seq": 8})
        q, k, v = make_qkv()
        want = attention_reference(q, k, v, causal=True)
        plain = ring(q, k, v, mesh, causal=True)
        zz = ring(q, k, v, mesh, causal=True, zigzag=True)
        np.testing.assert_allclose(np.asarray(zz), np.asarray(want), rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(np.asarray(zz), np.asarray(plain), rtol=2e-5, atol=2e-6)

    def test_composes_with_lengths_gqa_and_data_axis(self):
        mesh = create_mesh({"data": 2, "seq": 4})
        q = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16, 4, 8)), jnp.float32)
        k = jnp.asarray(np.random.default_rng(1).normal(size=(4, 16, 2, 8)), jnp.float32)
        v = jnp.asarray(np.random.default_rng(2).normal(size=(4, 16, 2, 8)), jnp.float32)
        lengths = jnp.asarray([16, 9, 4, 1], dtype=jnp.int32)
        want = attention_reference(
            q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
            lengths=lengths, causal=True,
        )
        got = ring(q, k, v, mesh, data_axis="data", lengths=lengths, causal=True, zigzag=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_grads_match_oracle(self):
        mesh = create_mesh({"seq": 8})
        q, k, v = make_qkv(l=16)
        g = jax.jit(
            jax.grad(
                lambda q, k, v: ring_attention(
                    q, k, v, mesh, causal=True, zigzag=True
                ).sum(),
                argnums=(0, 1, 2),
            )
        )(q, k, v)
        g_ref = jax.jit(jax.grad(
            lambda q, k, v: attention_reference(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2),
        ))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)

    def test_work_is_balanced(self):
        """The schedule's justification: with the half-swap striping
        (device j owns strip 2j and its mirror 2p-1-2j) every device holds
        exactly the same number of unmasked causal (q, k) pairs — which is
        why the kernel's static half-block program (one [Lc, s] or [s, Lk]
        einsum per non-diagonal step, identical on every device) loses
        nothing. The contiguous layout is maximally imbalanced. Computed
        from the same position arithmetic the kernel uses."""
        p, lc = 8, 8  # 8 devices, Lc=8 (strips of 4), L=64
        s = lc // 2

        def dev_pos(dev, zigzag):
            if zigzag:
                half = np.arange(s)
                return np.concatenate(
                    [2 * dev * s + half, (2 * p - 1 - 2 * dev) * s + half]
                )
            return dev * lc + np.arange(lc)

        def unmasked(dev, zigzag):
            qp = dev_pos(dev, zigzag)
            total = 0
            for src in range(p):
                kp = dev_pos(src, zigzag)
                total += int((kp[None, :] <= qp[:, None]).sum())
            return total

        zz = [unmasked(d, True) for d in range(p)]
        plain = [unmasked(d, False) for d in range(p)]
        assert len(set(zz)) == 1, zz                    # perfectly equal
        assert max(plain) > 1.8 * min(plain), plain     # contiguous is not

    def test_zigzag_hlo_collective_permute_no_all_gather(self):
        """The re-stripe must be the in-kernel ppermute half-swap (finding
        r5: a host-level permute of the sharded seq axis could lower to an
        all-gather and break the L/p memory bound)."""
        mesh = create_mesh({"data": 2, "seq": 4})
        q, k, v = make_qkv(b=4, l=16)
        fn = jax.jit(
            lambda q, k, v: ring_attention(
                q, k, v, mesh, data_axis="data", causal=True, zigzag=True
            )
        )
        got = fn(q, k, v)
        assert got.sharding.spec[0] == "data"
        assert_hlo(
            fn, (q, k, v), contains=["collective-permute"], absent=["all-gather"]
        )

    def test_single_device_axis_self_swap(self):
        """p=1: the swap involution is a self-edge; must degenerate to
        plain causal attention."""
        mesh = create_mesh({"seq": 1, "data": 8})
        q, k, v = make_qkv(l=8)
        want = attention_reference(q, k, v, causal=True)
        got = ring(q, k, v, mesh, causal=True, zigzag=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-6)

    def test_zigzag_requires_causal_and_divisibility(self):
        mesh = create_mesh({"seq": 8})
        q, k, v = make_qkv()
        with pytest.raises(ValueError, match="causal"):
            ring_attention(q, k, v, mesh, zigzag=True)
        q2, k2, v2 = make_qkv(l=24)  # 24 % 16 != 0
        with pytest.raises(ValueError, match="zigzag needs"):
            ring_attention(q2, k2, v2, mesh, causal=True, zigzag=True)
