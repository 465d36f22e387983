"""Transfer bit-packing: host pack / device unpack round-trip.

No reference analog (the JVM rows never crossed a device link); this pins
the TPU-first transfer-packing layer the Criteo feed uses: hashed
bucket indices packed to their significant bits on the host, unpacked
bit-exactly inside the consumer's jit.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_tfrecord.tpu.bitpack import pack_bits, pack_mixed, packed_width, unpack_bits


@pytest.mark.parametrize("bits", [1, 3, 7, 13, 20, 24, 31, 32])
@pytest.mark.parametrize("n_cols", [1, 2, 26, 40])
def test_round_trip_random(bits, n_cols):
    rng = np.random.default_rng(bits * 100 + n_cols)
    vals = rng.integers(0, 1 << bits, size=(64, n_cols)).astype(np.int64)
    packed = pack_bits(vals, bits)
    assert packed.shape == (64, packed_width(n_cols, bits))
    assert packed.dtype == np.int32
    out = np.asarray(jax.jit(unpack_bits, static_argnums=(1, 2))(packed, n_cols, bits))
    np.testing.assert_array_equal(out, vals.astype(np.int32))


@pytest.mark.parametrize("bits", [5, 20, 27])
def test_all_ones_straddle(bits):
    # max values exercise every bit lane including cross-lane straddles
    vals = np.full((8, 33), (1 << bits) - 1, dtype=np.int64)
    out = np.asarray(unpack_bits(pack_bits(vals, bits), 33, bits))
    np.testing.assert_array_equal(out, vals.astype(np.int32))


def test_width_savings():
    # the motivating case: 26 cats at 20 bits -> 17 lanes instead of 26
    assert packed_width(26, 20) == 17
    assert packed_width(26, 32) == 26


def test_rejects_negative_and_bad_shape():
    with pytest.raises(ValueError, match="non-negative"):
        pack_bits(np.array([[-1, 2]], dtype=np.int64), 20)
    with pytest.raises(ValueError, match=r"\[B, C\]"):
        pack_bits(np.zeros(5, dtype=np.int32), 20)
    with pytest.raises(ValueError, match="bits"):
        packed_width(4, 0)


def test_bits32_passthrough_values():
    vals = np.array([[0, 1, (1 << 31) - 1]], dtype=np.int64)
    packed = pack_bits(vals, 32)
    np.testing.assert_array_equal(packed, vals.astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(unpack_bits(jnp.asarray(packed), 3, 32)), vals.astype(np.int32)
    )
    # [2**31, 2**32): bit pattern preserved, read back as int32 reinterpretation
    big = np.array([[3_000_000_000]], dtype=np.int64)
    out = pack_bits(big, 32)
    assert out[0, 0] == np.uint32(3_000_000_000).view(np.int32)
    # negatives rejected at every width, including 32
    with pytest.raises(ValueError, match="non-negative"):
        pack_bits(np.array([[-5]], dtype=np.int64), 32)


def test_unpack_under_sharding():
    """Unpack composes with the data-sharded global batch on the 8-dev mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(0)
    vals = rng.integers(0, 1 << 20, size=(32, 26)).astype(np.int64)
    packed = pack_bits(vals, 20)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    gb = jax.device_put(packed, NamedSharding(mesh, P("data", None)))
    out = jax.jit(lambda p: unpack_bits(p, 26, 20))(gb)
    np.testing.assert_array_equal(np.asarray(out), vals.astype(np.int32))


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("bits", [1, 7, 20, 31, 32])
@pytest.mark.parametrize("keep,c", [(0, 26), (14, 26), (3, 1), (5, 0)])
def test_pack_mixed_equals_reference(dtype, bits, keep, c):
    """pack_mixed == concat + pack_bits; int32 input takes the native
    kernel (when built), int64 the numpy fallback — both bit-identical."""
    rng = np.random.default_rng(bits + keep)
    arr = np.concatenate(
        [
            rng.integers(0, 1 << 31, size=(37, keep)),
            rng.integers(0, min(1 << bits, 1 << 31), size=(37, c)),
        ],
        axis=1,
    ).astype(dtype)
    got = pack_mixed(arr, keep, bits)
    ref = np.concatenate(
        [arr[:, :keep].astype(np.int32), pack_bits(arr[:, keep:].astype(np.int64), bits)],
        axis=1,
    )
    np.testing.assert_array_equal(got, ref)
    # and the round trip through the device-side unpack
    if c:
        out = np.asarray(unpack_bits(got[:, keep:], c, bits))
        np.testing.assert_array_equal(
            out, (arr[:, keep:].astype(np.int64) & ((1 << bits) - 1)).astype(np.int32)
        )


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_pack_mixed_rejects_bad_args(dtype):
    arr = np.zeros((4, 6), dtype=dtype)
    with pytest.raises(ValueError, match="keep"):
        pack_mixed(arr, 7, 20)
    with pytest.raises(ValueError, match="non-negative"):
        # negative in a PACKED column — caught by the kernel's packing pass
        # (int32/native) or the fallback's scan (int64/numpy)
        bad = np.zeros((2, 3), dtype=dtype)
        bad[1, 2] = -1
        pack_mixed(bad, 1, 20)
    with pytest.raises(ValueError, match=r"\[B, C\]"):
        pack_mixed(np.zeros(3, dtype=np.int32), 0, 20)
    with pytest.raises(ValueError, match="bits"):
        pack_mixed(arr, 1, 0)  # validated before native dispatch
    with pytest.raises(ValueError, match="bits"):
        pack_mixed(arr, 1, 33)
    # negative values in KEEP lanes are fine (verbatim int32 transfer lanes)
    ok = np.full((2, 3), -7, dtype=dtype)
    out = pack_mixed(ok, 3, 20)
    np.testing.assert_array_equal(out, ok.astype(np.int32))


def test_bench_style_mixed_layout():
    """label+dense stay 32-bit, cats pack to 20 — the Criteo wire's [B,31] layout."""
    rng = np.random.default_rng(1)
    full = np.concatenate(
        [
            rng.integers(0, 2, size=(128, 1)),
            rng.integers(0, 1 << 31, size=(128, 13)),
            rng.integers(0, 1 << 20, size=(128, 26)),
        ],
        axis=1,
    ).astype(np.int64)
    wire_mat = np.concatenate(
        [full[:, :14].astype(np.int32), pack_bits(full[:, 14:], 20)], axis=1
    )
    assert wire_mat.shape == (128, 31)

    @jax.jit
    def consume(m):
        label = m[:, 0]
        dense = m[:, 1:14]
        cats = unpack_bits(m[:, 14:], 26, 20)
        return label, dense, cats

    label, dense, cats = consume(wire_mat)
    np.testing.assert_array_equal(np.asarray(label), full[:, 0].astype(np.int32))
    np.testing.assert_array_equal(np.asarray(dense), full[:, 1:14].astype(np.int32))
    np.testing.assert_array_equal(np.asarray(cats), full[:, 14:].astype(np.int32))
