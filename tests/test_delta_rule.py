"""The gated delta rule (``models.linear_attn``) at sizes a CPU walks in
seconds: the chunked recurrence against the token-by-token one, the Pallas
kernel a TPU runs, interpreted, against both, a boundary at every edge the
kernel has, keys that all but repeat, decays that overflow a naive chunk, and
the short convolution's taps at a boundary. The model these layers sit in is
tests/test_pattern_lm.py's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.models import solar_open2 as ref
from tpu_tfrecord.models import linear_attn

#: One program a shape for the process: cases that differ in their data find
#: it built (called bare, a scan or a kernel is compiled anew at every call).
recurrent = jax.jit(linear_attn.delta_rule_recurrent, static_argnames="scale")
chunked = jax.jit(linear_attn.delta_rule_chunked, static_argnames=("scale", "chunk"))


def test_taps_stop_at_a_boundary():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((1, 2, 20, 3)), jnp.float32)       # [B, H, L, D]
    taps = jnp.asarray(rng.standard_normal((4, 2, 3)), jnp.float32)
    segs = jnp.asarray([[1] * 7 + [2] * 9 + [0] * 4], jnp.int32)
    flat_x = jnp.moveaxis(x[0], 1, 0).reshape(20, 6)                       # [L, H * D]
    rows = lambda y: jnp.moveaxis(y[0], 1, 0).reshape(20, 6)  # noqa: E731
    got = rows(linear_attn.short_conv(x, taps, segs))
    np.testing.assert_allclose(got[:7], ref.ref_conv(flat_x[:7], taps.reshape(4, 6)), atol=1e-6)
    np.testing.assert_allclose(got[7:16], ref.ref_conv(flat_x[7:16], taps.reshape(4, 6)), atol=1e-6)
    # and without the boundary the first tokens of the second document differ
    whole = rows(linear_attn.short_conv(x, taps, jnp.ones((1, 20), jnp.int32)))
    assert np.abs(whole[7:10] - got[7:10]).max() > 1e-2


def delta_rule_inputs(seed, length, near_parallel_keys=False, b=2, h=3, d=16):
    r = np.random.default_rng(seed)
    q, k, v = (r.standard_normal((b, h, length, d)) for _ in range(3))
    if near_parallel_keys:
        k = k * 0.1 + r.standard_normal((b, h, 1, d))
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    log_decay = -np.exp(r.uniform(-6, 0.3, (b, h, length, d)))
    beta = 2 / (1 + np.exp(-2 * r.standard_normal((b, h, length))))
    segs = np.zeros((b, length), np.int32)
    for row in range(b):
        cuts = np.sort(r.choice(np.arange(1, length - 8), 4, replace=False))
        for s, (a, z) in enumerate(zip([0, *cuts], [*cuts, length - 5])):
            segs[row, a:z] = s + 1
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, log_decay, beta)] + [jnp.asarray(segs)]


@functools.partial(jax.jit, static_argnums=(6, 7))
def interpreted_kernel(q, k, v, log_decay, beta, segs, scale, tile=128):
    """The Pallas kernel a TPU runs for chunks of 64 at width 128, interpreted."""
    return linear_attn._delta_rule_fused(q, k, v, log_decay, beta, segs, scale, tile, interpret=True)


def kernel_inputs(seed, length, **kw):
    """:func:`delta_rule_inputs` at the width the kernel takes, two heads of one row."""
    return delta_rule_inputs(seed, length, b=1, h=2, d=128, **kw)


@pytest.mark.parametrize("chunk", [1, 4, 16, 64, 128])
@pytest.mark.parametrize("length", [150, 64, 37])
def test_the_chunked_recurrence_is_the_token_by_token_one(chunk, length):
    args = delta_rule_inputs(chunk + length, length)
    want = recurrent(*args, scale=0.25)
    got = chunked(*args, scale=0.25, chunk=chunk)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=5e-6)


@pytest.mark.parametrize("operands", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("length,tile,heads", [(128, 128, 2), (256, 128, 2), (256, 256, 2), (512, 256, 2),
                                               (768, 256, 3)])
def test_the_kernel_is_the_token_by_token_recurrence_and_the_plain_form(length, tile, heads, operands):
    """Four boundaries a row at random places and a pad tail of segment 0:
    documents start and end inside a chunk, inside a pair of chunks and
    inside a grid step, and the state crosses from one grid step to the next.
    An even number of heads goes two to a grid step, an odd number one. Under
    this decay a channel too q, k and v may come in bfloat16, as a convolution
    writes them (the decay stays float32): the kernel widens a tile at a time."""
    q, k, v, *rest = delta_rule_inputs(length + tile, length, b=1, h=heads, d=128)
    args = [a.astype(operands) for a in (q, k, v)] + rest
    segs = np.asarray(args[-1])
    assert (segs[:, -1] == 0).all() and (np.diff(segs) != 0).sum() >= 4
    want = recurrent(*args, scale=0.25)
    plain = chunked(*args, scale=0.25, chunk=64)
    got = interpreted_kernel(*args, 0.25, tile)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=5e-6)
    np.testing.assert_allclose(got, plain, atol=5e-6)


def test_a_boundary_at_every_edge_the_kernel_has():
    """Documents that end exactly where a 16-token block, a chunk, a pair of
    chunks and a grid step end, one token long, and one across three steps."""
    q, k, v, log_decay, beta, _ = kernel_inputs(8, 768)
    segs = np.zeros((1, 768), np.int32)
    for s, (a, z) in enumerate([(0, 16), (16, 64), (64, 128), (128, 129), (129, 256), (256, 700)]):
        segs[0, a:z] = s + 1
    segs = jnp.asarray(segs)
    want = recurrent(q, k, v, log_decay, beta, segs, scale=0.25)
    for tile in (128, 256):
        got = interpreted_kernel(q, k, v, log_decay, beta, segs, 0.25, tile)
        np.testing.assert_allclose(got, want, atol=5e-6)


@pytest.mark.parametrize("form", ["plain", "kernel"])
@pytest.mark.parametrize("one_key", [False, True])
def test_near_parallel_keys_do_not_blow_the_chunk_up(one_key, form):
    """beta near 2 on keys that all but repeat, or do repeat (a document that
    says one token over and over): the triangle's inverse by forward
    substitution (the plain form) or by doubling from single rows (the
    kernel) stays exact where a product of a block's powers cancelled terms
    of 1e6 against each other (it was held to 1e-3 here)."""
    q, k, v, log_decay, beta, segs = (
        delta_rule_inputs(1, 150, near_parallel_keys=True) if form == "plain"
        else kernel_inputs(1, 256, near_parallel_keys=True))
    if one_key:
        k, beta = jnp.broadcast_to(k[:, :, :1], k.shape), jnp.full_like(beta, 1.98)
    want = recurrent(q, k, v, log_decay, beta, segs, scale=0.25)
    if form == "plain":
        got = chunked(q, k, v, log_decay, beta, segs, scale=0.25, chunk=64)
    else:
        got = interpreted_kernel(q, k, v, log_decay, beta, segs, 0.25)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("rate", [3.0, 5.0, 9.0])
@pytest.mark.parametrize("chunk", [16, 64, 128, "kernel"])
def test_fast_decays_do_not_overflow_the_chunk(rate, chunk):
    """Channels that forget at ``rate`` a token, real tokens and the pads of
    a row's tail alike: exp(+-sum of log-decay) around one reference point
    for a whole chunk of 64 overflowed float32 at 2.5 a token, and a NaN
    behind a zero of the triangle's inverse reached the document before.
    ``kernel``: the interpreted kernel (chunks of 64) at its width."""
    q, k, v, log_decay, beta, segs = delta_rule_inputs(3, 150) if chunk != "kernel" else kernel_inputs(3, 256)
    fast = np.random.default_rng(4).random(log_decay.shape) < 0.3
    log_decay = jnp.where(fast, -rate, log_decay)
    segs = segs.at[:, 120:].set(0)
    want = recurrent(q, k, v, log_decay, beta, segs, scale=0.25)
    if chunk == "kernel":
        got = interpreted_kernel(q, k, v, log_decay, beta, segs, 0.25)
    else:
        got = chunked(q, k, v, log_decay, beta, segs, scale=0.25, chunk=chunk)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-5)


# ---------------------------------------------------------------------------
# One decay a head and token, key heads shared by their value heads
# ---------------------------------------------------------------------------


def grouped_scalar_inputs(seed, length, h=4, group=2, d=16, b=2, rates=None):
    """:func:`delta_rule_inputs` with q and k at ``h / group`` heads and one
    log-decay a head and token ([B, H, L]); ``rates``: the decays a token, drawn
    from these where given (near 0: exp(-9); near 1: exp(-1e-4))."""
    q, k, v, log_decay, beta, segs = delta_rule_inputs(seed, length, b=b, h=h, d=d)
    scalar = log_decay[..., 0]
    if rates is not None:
        picks = np.random.default_rng(seed).integers(len(rates), size=scalar.shape)
        scalar = -jnp.asarray(np.asarray(rates, np.float32)[picks])
    return q[:, : h // group], k[:, : h // group], v, scalar, beta, segs


def short_documents(length, seed=0):
    """A row of documents of 1 to 40 tokens (shorter than a chunk of 64, so
    that boundaries fall inside every chunk), then a pad tail."""
    rng, segs, at, s = np.random.default_rng(seed), np.zeros((1, length), np.int32), 0, 1
    while at < length - 9:
        n = int(rng.integers(1, 41))
        segs[0, at:at + n] = s
        at, s = min(at + n, length - 9), s + 1
    segs[0, length - 9:] = 0
    return jnp.asarray(segs)


@pytest.mark.parametrize("chunk", [4, 16, 64])
@pytest.mark.parametrize("h,group", [(4, 2), (6, 3), (4, 1), (32, 2)])
def test_one_decay_a_token_and_shared_key_heads_are_the_recurrence_and_the_broadcast(h, group, chunk):
    """The plain form handed a decay [B, H, L] and q, k at H / group heads: the
    token-by-token recurrence, and what the per-channel form gives when both
    broadcasts are written out for it."""
    q, k, v, scalar, beta, segs = grouped_scalar_inputs(h + chunk, 150, h=h, group=group)
    want = recurrent(q, k, v, scalar, beta, segs, scale=0.25)
    got = chunked(q, k, v, scalar, beta, segs, scale=0.25, chunk=chunk)
    assert got.shape == v.shape
    np.testing.assert_allclose(got, want, atol=5e-6)
    spread = chunked(jnp.repeat(q, group, axis=1), jnp.repeat(k, group, axis=1), v,
                     jnp.broadcast_to(scalar[..., None], v.shape), beta, segs, scale=0.25, chunk=chunk)
    np.testing.assert_allclose(got, spread, atol=5e-6)


@pytest.mark.parametrize("length,tile,h,group", [(256, 128, 2, 2), (512, 256, 4, 2), (256, 128, 3, 1),
                                                 (256, 256, 4, 4), (384, 128, 2, 1)])
def test_the_kernel_under_one_decay_a_token_is_the_recurrence_and_the_plain_form(length, tile, h, group):
    """The interpreted kernel in its other form: the decay laid out as beta
    is, a grid step's two value heads reading one key head (or each its own,
    or four one), q, k and v handed over in bfloat16 as a convolution writes
    them."""
    q, k, v, scalar, beta, segs = grouped_scalar_inputs(length + h, length, h=h, group=group, d=128, b=1)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    want = recurrent(q, k, v, scalar, beta, segs, scale=0.25)
    plain = chunked(q, k, v, scalar, beta, segs, scale=0.25, chunk=64)
    got = interpreted_kernel(q, k, v, scalar, beta, segs, 0.25, tile)
    assert got.shape == v.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=5e-6)
    np.testing.assert_allclose(got, plain, atol=5e-6)


@pytest.mark.parametrize("form", ["plain", "kernel"])
@pytest.mark.parametrize("rates", [(9.0, 1e-4), (1e-4,), (9.0, 3.0)], ids=["both", "near_one", "near_zero"])
def test_short_documents_under_decays_near_0_and_near_1(rates, form):
    """Documents shorter than a chunk (a boundary inside every chunk, several
    inside most), tokens that forget everything (a decay of e^-9) beside
    tokens that forget nothing (e^-0.0001): one exponent a pair, never positive."""
    d, b = (16, 2) if form == "plain" else (128, 1)
    q, k, v, scalar, beta, _ = grouped_scalar_inputs(9, 256, h=4, group=2, d=d, b=b, rates=rates)
    segs = jnp.concatenate([short_documents(256, seed=s) for s in range(b)])
    assert (np.diff(np.asarray(segs)) != 0).sum() >= 8 * b
    want = recurrent(q, k, v, scalar, beta, segs, scale=0.25)
    got = (chunked(q, k, v, scalar, beta, segs, scale=0.25, chunk=64) if form == "plain"
           else interpreted_kernel(q, k, v, scalar, beta, segs, 0.25, 256))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=1e-5)


# ---------------------------------------------------------------------------
# The kernel handed the projections and their taps: it prepares its own q, k and v
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(7, 8))
def from_projections(q, k, v, taps, log_decay, beta, segs, scale, tile):
    """The kernel a TPU runs for a layer, interpreted: the projections in, o and what it prepared out."""
    return linear_attn._delta_rule_fused(q, k, v, log_decay, beta, segs, scale, tile, interpret=True, taps=taps,
                                         handed=True)


@jax.jit
def plainly_prepared(q, k, v, taps, segs):
    """The plain preparation, whole arrays at a time: what the kernel's has to equal."""
    return tuple(linear_attn.prepared(x, t, segs, unit) for x, t, unit in zip((q, k, v), taps, (True, True, False)))


def projections(seed, length, h, group, scalar, dtype=jnp.bfloat16):
    """Projections as a layer's three products write them (not normed, a few units wide), taps of the
    model's size, and the rest of :func:`grouped_scalar_inputs` at the kernel's width, one row."""
    _, _, _, log_decay, beta, segs = delta_rule_inputs(seed, length, b=1, h=h, d=128)
    r = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(1.5 * r.standard_normal((1, n, length, 128)), dtype) for n in (h // group, h // group, h))
    taps = tuple(jnp.asarray(0.5 * r.standard_normal((4, n * 128)), dtype) for n in (h // group, h // group, h))
    return q, k, v, taps, (log_decay[..., 0] if scalar else log_decay), beta, segs


def bits(a):
    return np.asarray(a).view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def held_to_the_plain_forms(q, k, v, taps, log_decay, beta, segs, tile, exact=True):
    """The interpreted kernel, its prologue included, against the plain preparation (bit for bit
    where the operands are bfloat16: both round the same float32 numbers twice), the plain chunked
    form and the token-by-token recurrence over the plainly prepared q, k and v."""
    o, *handed = from_projections(q, k, v, taps, log_decay, beta, segs, 0.25, tile)
    want = plainly_prepared(q, k, v, taps, segs)
    for name, got, plain in zip("qkv", handed, want):
        assert got.shape == plain.shape and got.dtype == plain.dtype == q.dtype, name
        if exact:
            assert np.array_equal(bits(got), bits(plain)), (name, int((bits(got) != bits(plain)).sum()))
        else:  # float32 operands are never rounded: the last place follows how a compiler joins a product and a sum
            np.testing.assert_allclose(got, plain, atol=2e-6, err_msg=name)
    assert o.shape == v.shape and o.dtype == jnp.float32
    np.testing.assert_allclose(o, recurrent(*want, log_decay, beta, segs, scale=0.25), atol=5e-6)
    np.testing.assert_allclose(o, chunked(*want, log_decay, beta, segs, scale=0.25, chunk=64), atol=5e-6)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(handed[0], np.float32), axis=-1), 1, atol=1e-2)
    return o, handed


@pytest.mark.parametrize("length,tile,h,group,scalar", [
    (256, 128, 2, 1, False), (256, 128, 2, 2, True), (256, 256, 4, 4, True), (384, 128, 3, 1, True)],
    ids=["a_channel", "a_token_two_share", "a_token_four_share", "a_token_own_keys_odd_heads"])
def test_the_kernel_that_prepares_its_own_is_the_plain_preparation_and_the_recurrence(length, tile, h, group, scalar):
    """Both forms of the decay, a key head a value head or shared by two or four (prepared once a KEY
    head: where four share, two grid steps prepare it and write the same block), two heads a grid
    step or one, tiles of 256 and 128, one tile a row and several: the recurrence runs a grid step
    behind the preparation and reads it from VMEM."""
    held_to_the_plain_forms(*projections(length + h, length, h, group, scalar), tile)


def test_the_kernel_prepares_float32_projections_too():
    held_to_the_plain_forms(*projections(5, 256, 2, 1, False, jnp.float32), 128, exact=False)


@pytest.mark.parametrize("tile", [128, 256])
def test_the_taps_stop_at_a_boundary_wherever_it_falls_in_a_tile(tile):
    """Documents that start at rows 0, 1, 2 and 3 of a tile of 128 (the tap three places back reads
    the tile before, or must not), one that ends inside the three rows before a tile and one that
    lies wholly in them, a boundary at a tile's last row and at a strip's, a row's first tile (nothing
    before it: the first three tokens have fewer taps), pads at the end. The kernel's preparation is
    the plain one to the bit, so a tap that crossed a boundary or missed the rows before a tile shows."""
    length = 768
    q, k, v, taps, log_decay, beta, _ = projections(11, length, 2, 1, False)
    segs = np.zeros((1, length), np.int32)
    cuts = [0, 126, 128, 193, 257, 386, 515, 640, 700]   # 128 + 0, 256 + 1, 384 + 2, 512 + 3; 640 = 5 x 128
    for s, (a, z) in enumerate(zip(cuts, cuts[1:])):
        segs[0, a:z] = s + 1
    segs = jnp.asarray(segs)
    _, (qp, _, vp) = held_to_the_plain_forms(q, k, v, taps, log_decay, beta, segs, tile)
    # and it matters: told that the row is one document, the tokens after a boundary come out otherwise
    whole = plainly_prepared(q, k, v, taps, jnp.ones_like(segs))
    for start in cuts[1:-1]:
        assert np.abs(np.asarray(whole[2][0, :, start:start + 3] - vp[0, :, start:start + 3], np.float32)).max() > 1e-2
    # a row's first token has one tap: v there is SiLU of the rounded x * taps[0]
    first = (v[0, :, 0].astype(jnp.float32) * taps[2][0].reshape(2, 128).astype(jnp.float32)).astype(v.dtype)
    np.testing.assert_array_equal(bits(vp[0, :, 0]), bits(jax.nn.silu(first.astype(jnp.float32)).astype(v.dtype)))
