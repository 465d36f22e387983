"""The scoring head (``models.head``) at sizes a CPU walks in seconds: the Pallas
kernel a TPU runs, interpreted, against the plain form's blocks of float32
logits, over shapes that cross every edge the kernel has (a vocabulary of whole
tiles and a ragged one, the head as it lies row-major and column-major, a model
width in one product and cut in two, targets on a tile's first and last column
and in the ragged tile, a maximum that arrives in the last tile), what the
shape function takes and declines, and ``lm.score``'s masking of pads and
document boundaries on top of the kernel. The model around the head is
tests/test_pattern_lm.py's; the kernel compiled for the chip,
tests/test_tpu_compile.py."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.models import head, lm

from test_pattern_lm import CFG, SAMPLE_AT, init_params, packed_rows, program_cfg

#: One program a shape, a tiling and a layout for the process (``_head_fused`` is
#: jitted where it stands): cases that differ in their data find it built.
interpreted = functools.partial(head._head_fused, interpret=True)
plain = jax.jit(head.logprob_blocks, static_argnums=3)

#: (tokens, model width, vocabulary, (token tile, vocabulary tile, channels a product))
SHAPES = {
    "whole_tiles": (64, 128, 384, (32, 128, 128)),
    "ragged": (64, 128, 400, (32, 128, 128)),
    "ragged_wide_tile": (64, 128, 400, (64, 256, 128)),
    "cut_in_two": (64, 256, 384, (32, 128, 128)),
    "cut_in_two_ragged": (64, 256, 400, (32, 128, 128)),
    "one_token_tile": (32, 128, 1000, (32, 256, 128)),
}


def operands(seed, t, d, v, dtype=jnp.bfloat16):
    """flat [t, d], head [d, v] and targets [t] that sit on every edge: token 0's
    on the vocabulary's first column, 1's on its last (in the ragged tile where
    there is one), 2's and 3's on the first tile's last column and the second's
    first (of tiles of 128 and of 256). Token 4's largest logit by far is the
    LAST column's, so its maximum arrives in the last tile and every sum before
    it is rescaled; token 5's is the first column's, so no later tile moves it."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((t, d)).astype(np.float32)
    w = (rng.standard_normal((d, v)) * d ** -0.5).astype(np.float32)
    w[:, -1] = 6.0 * x[4] / np.linalg.norm(x[4])
    w[:, 0] = 6.0 * x[5] / np.linalg.norm(x[5])
    targets = rng.integers(0, v, t).astype(np.int32)
    targets[:8] = [0, v - 1, 127, 128, v - 1, 0, 255, 256]
    return jnp.asarray(x, dtype), jnp.asarray(w, dtype), jnp.asarray(targets)


@pytest.mark.parametrize("by_column", [False, True], ids=["row_major", "column_major"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_interpreted_kernel_is_the_plain_form(shape, by_column):
    """bfloat16 operands, float32 logits, maximum, sum and pick in both: the two
    differ by the order of a float32 sum. The interpreter fills what a ragged
    block holds past the vocabulary with NaN: it must not matter."""
    t, d, v, tile = SHAPES[shape]
    x, w, targets = operands(len(shape), t, d, v)
    want = plain(x, w, targets, 32)
    got = interpreted(x, w, targets, tile, by_column)
    assert got.shape == (t,) and got.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=1e-5)
    logits = np.asarray(x, np.float64) @ np.asarray(w, np.float64)
    assert logits[4].argmax() == v - 1 and logits[4, -1] > logits[4, :-1].max() + 8   # the rescaling had work
    assert logits[5].argmax() == 0
    exact = logits[np.arange(t), np.asarray(targets)] - np.log(np.exp(logits - logits.max(1, keepdims=True)).sum(1)) \
        - logits.max(1)
    np.testing.assert_allclose(got, exact, atol=2e-5)
    assert float(np.abs(exact).max()) > 5


def test_float32_operands_through_the_kernel_are_the_plain_form_too():
    """The chip's kernel is asked for bfloat16 alone (``head_tile``), the body is
    not: a float32 program's head through it, as a test of ``score`` steers it."""
    t, d, v, tile = SHAPES["cut_in_two_ragged"]
    x, w, targets = operands(3, t, d, v, jnp.float32)
    np.testing.assert_allclose(interpreted(x, w, targets, tile), plain(x, w, targets, 16), atol=1e-5)


def test_a_tiling_the_shapes_do_not_fill_is_refused():
    x, w, targets = operands(0, 64, 128, 400)
    for tile in [(48, 128, 128), (32, 128, 96), (32, 512, 128), (32, 192, 128)]:
        with pytest.raises(ValueError, match="whole"):
            interpreted(x, w, targets, tile)


#: the seven token cells' heads: tokens a step, model width, vocabulary held (BENCHMARK.json's configurations)
CELLS = {
    "solar_open2_ep8": (16384, 4096, 24576), "kimi_vl_a3b_lm": (16384, 2048, 163840),
    "deepseek_v32_exp_ep16": (16384, 7168, 16160), "trinity_large_ep8": (32768, 3072, 25024),
    "gigachat35_ep16": (16384, 7168, 16032), "nemotron_twotower_ep2": (16384, 2688, 65536),
    "olmo_hybrid_7b_pp4": (16384, 3840, 100352),
}


def test_off_a_tpu_the_plain_form_runs(monkeypatch):
    """The dispatch reads the backend and the shape, nothing else: here (the CPU)
    every shape takes the plain form's blocks."""
    called = []
    monkeypatch.setattr(head, "_head_fused", lambda *a, **k: called.append(a))
    assert jax.default_backend() != "tpu"
    assert all(head.head_tile(*shape, jnp.bfloat16) is None for shape in CELLS.values())
    x, w, targets = operands(1, 64, 128, 400)
    got, fused = head.logprob(x, w, targets, 32)
    np.testing.assert_array_equal(got, plain(x, w, targets, 32))
    assert not fused
    assert not called


@pytest.mark.parametrize("cell", list(CELLS))
def test_on_a_tpu_every_cells_head_has_a_tiling(monkeypatch, cell):
    """Every token cell's head shape tiles, and the kernel runs where the step's
    float32 logits are ``_MIN_LOGITS`` bytes or more: of the seven cells in
    Kimi-VL's alone (10.7 GB; Olmo's 6.6 GB is the next), the one cell whose
    step was measured around the kernel (PERF.md section 6, PR 50). The other six keep
    the parent's program until theirs are."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, d, v = CELLS[cell]
    assert (head.head_tile(t, d, v, jnp.bfloat16) is not None) == (cell == "kimi_vl_a3b_lm")
    monkeypatch.setattr(head, "_MIN_LOGITS", 0)
    rows, columns, depth = head.head_tile(t, d, v, jnp.bfloat16)
    assert t % rows == 0 and rows >= 256 and columns % 128 == 0 and v >= columns and d % depth == 0
    # the token tile and the head's tile, twice each, leave half of a v5e's 128 MiB of VMEM free
    assert 4 * (rows + columns) * d <= 64 * 2 ** 20


def test_what_the_shape_function_declines(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, d, v = CELLS["kimi_vl_a3b_lm"]
    assert head.head_tile(t, d, v, jnp.bfloat16) is not None
    assert head.head_tile(t, d, v, jnp.float32) is None           # the float32 program keeps the plain form
    assert head.head_tile(t, d + 64, v, jnp.bfloat16) is None     # a width that is not whole lane blocks
    assert head.head_tile(t + 8, d, v, jnp.bfloat16) is None      # tokens that are not whole tiles
    assert head.head_tile(t, d, 256, jnp.bfloat16) is None        # a vocabulary under one tile
    assert head.head_tile(t // 2, d, v, jnp.bfloat16) is None     # float32 logits under _MIN_LOGITS bytes a step
    monkeypatch.setattr(head, "_MIN_LOGITS", 0)
    assert head.head_tile(512, d, v, jnp.bfloat16)[0] == 512      # the largest token tile that divides the batch
    called = []
    monkeypatch.setattr(head, "_head_fused", lambda *a, **k: called.append(a) or jnp.zeros((1024,), jnp.float32))
    x, w, targets = operands(2, 1024, 128, 1000)
    assert head.logprob(x, w, targets, 256)[1]
    assert len(called) == 1 and called[0][3] == head.head_tile(1024, 128, 1000, jnp.bfloat16)
    # operands of two types: the plain form, and the answer says so (``lm.score``'s gauge reads it)
    assert not head.logprob(x, w.astype(jnp.float32), targets, 256)[1]
    assert len(called) == 1


@pytest.fixture(scope="module")
def wide_vocabulary():
    """test_pattern_lm's tiny model under a vocabulary of 400: three tiles of 128 and a ragged one."""
    cfg = program_cfg({**CFG, "vocab_size": 400})
    return cfg, init_params(jax.random.PRNGKey(7), cfg)


def test_score_masks_pads_and_boundaries_on_top_of_the_kernel(monkeypatch, wide_vocabulary):
    """``lm.score`` with the dispatch answering as it would on a TPU and Pallas
    interpreting: the log-probabilities the plain form gives, zero where the
    next token is a pad or another document's, the sampled logits untouched,
    and the gauge ``head.fused`` says which of the two ran."""
    cfg, params = wide_vocabulary
    batch, _ = packed_rows()
    rng = np.random.default_rng(8)
    tokens = jnp.asarray(rng.integers(1, 400, batch["tokens"].shape), jnp.int32)
    segs = jnp.asarray(batch["segment_ids"])

    def traced_anew():  # the dispatch and the gauge are read as a program is traced
        return jax.jit(lambda *a: lm.score(*a, cfg))(params, tokens, segs, SAMPLE_AT)

    want = traced_anew()
    assert METRICS.gauge_value("head.fused") == 0
    t = tokens.shape[0] * (tokens.shape[1] - 1)
    monkeypatch.setattr(head, "head_tile", lambda *shape: (t // 3, 128, cfg.d_model))
    monkeypatch.setattr(head, "_head_fused", interpreted)
    got = traced_anew()
    assert METRICS.gauge_value("head.fused") == 1
    np.testing.assert_allclose(got["logprob"], want["logprob"], atol=1e-5)
    np.testing.assert_array_equal(got["logits"], want["logits"])
    scored = np.asarray((segs[:, 1:] == segs[:, :-1]) & (segs[:, :-1] != 0))
    assert (np.asarray(got["logprob"])[~scored] == 0).all() and (~scored).sum() >= 8
    assert (np.asarray(got["logprob"])[scored] < -1).all()
