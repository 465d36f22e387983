"""DLRM dot-interaction: pairwise feature dots, as a Pallas TPU kernel.

The signature compute op of the DLRM family this framework feeds: given
per-feature embeddings E [B, F, D], emit every pairwise dot <E_i, E_j> for
i > j as a packed [B, F*(F-1)/2] tensor that is concatenated into the top
MLP input.

TPU shaping:
- the Gram matrix G = E @ E^T per sample is a batched matmul -> MXU;
- the kernel fuses the triangle extraction with the matmul while G is still
  in VMEM, so the [B, F, F] intermediate never round-trips through HBM
  (XLA materializes it between the batched-dot and the gather);
- the batch dim is tiled by the grid; F and D are small (tens), so a
  [TB, F, D] block sits comfortably in VMEM.

Gradients flow via a custom VJP whose backward is plain XLA (dE = (dG +
dG^T) @ E with dG scattered from the packed pairs) — simple, and backward is
not the hot path for inference-heavy recommenders.

`dot_interaction` picks the Pallas kernel on TPU backends and the XLA
reference elsewhere (or under `interpret=True` for CPU tests).

RETIRED from auto-dispatch (round 4): dispatch-free DEVICE-TIME
measurement on a real v5e chip (``tools/pallas_device_time.py``, fori_loop
with a data-dependency carry, two-length delta, completion forced by a
scalar fetch; full table in PARITY.md "Pallas kernel") shows XLA's
einsum+gather is faster at EVERY F — Pallas/XLA device-time ratios at
B=8192, D=32, bf16: F=8 0.27x, F=16 0.98x, F=27 0.89x, F=32 0.70x,
F=64 0.46x. The selection-matmul formulation's ~F/2 x FLOP overhead (two
[F,P] one-hot contractions vs one [F,F] Gram) costs more than the
avoided [B,F,F] HBM round-trip saves at these sizes. ``dot_interaction``
therefore defaults to the XLA path EVERYWHERE; the kernel remains as the
in-repo TEMPLATE for fusion kernels (P-tiled grid, matmul-instead-of-
gather, custom VJP) and is reachable only via ``use_pallas=True``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_tfrecord.compile_cache import kernel_trace


def _tril_indices(f: int):
    rows, cols = np.tril_indices(f, k=-1)
    return rows.astype(np.int32), cols.astype(np.int32)


def dot_interaction_reference(emb: jax.Array) -> jax.Array:
    """XLA reference: [B, F, D] -> [B, F*(F-1)/2] packed lower triangle."""
    gram = jnp.einsum("bfd,bgd->bfg", emb, emb)
    rows, cols = _tril_indices(emb.shape[1])
    return gram[:, rows, cols]


def _interaction_kernel(sel_rows_ref, sel_cols_ref, emb_ref, out_ref):
    emb = emb_ref[:].astype(jnp.float32)          # [TB, F, D]
    # Gathers and unaligned reshapes don't lower to the MXU/VPU; one-hot
    # selection MATMULS do. R[tb,d,p] = E[tb, rows[p], d], same for C, then
    # the packed pairwise dots are an elementwise product reduced over D.
    contract = (((1,), (0,)), ((), ()))            # contract the F dim
    # a selection has to be EXACT. bf16 input survives the MXU's default
    # single bf16 pass unchanged; float32 input would be rounded to bf16
    # by it (3.2e-3 of scale on the v5e), so it takes the multi-pass f32
    # contraction
    precision = (
        jax.lax.Precision.HIGHEST
        if emb_ref.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT
    )
    r = jax.lax.dot_general(
        emb, sel_rows_ref[:], dimension_numbers=contract,
        precision=precision, preferred_element_type=jnp.float32,
    )                                              # [TB, D, P]
    c = jax.lax.dot_general(
        emb, sel_cols_ref[:], dimension_numbers=contract,
        precision=precision, preferred_element_type=jnp.float32,
    )
    out_ref[:] = jnp.sum(r * c, axis=1).astype(out_ref.dtype)


def dot_interaction_pallas(
    emb: jax.Array,
    block_b: int = 128,
    block_p: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Pallas kernel: [B, F, D] -> [B, P] with P = F*(F-1)/2.

    B must be divisible by ``block_b`` (pad the batch otherwise — the ingest
    layer produces fixed batch sizes, so callers control this statically).
    The pair dimension P is tiled too (``block_p``, auto-sized to a VMEM
    budget): the dominant allocations are the two [TB, D, TP] f32 selection
    products, so large F (P grows as F^2) scales by shrinking TP/TB instead
    of spilling — the [B, F, F] Gram tensor still never exists in HBM.
    """
    import math

    b, f, d = emb.shape
    block_b = min(block_b, b)
    if b % block_b:
        block_b = math.gcd(b, block_b)  # largest compatible tile
    if block_b < 8 and b >= 8:
        # refuse to degrade to sub-sublane tiles silently (e.g. a prime
        # batch would run b grid steps of [1, F, D]) — pad the batch instead
        raise ValueError(
            f"batch {b} only tiles at block_b={block_b} (<8); pad the batch "
            "to a multiple of 8 or pass a compatible block_b"
        )
    rows, cols = _tril_indices(f)
    p = len(rows)
    if block_p is None:
        # budget for the two [TB, D, TP] f32 intermediates; shrink TB first
        # so TP stays a full lane multiple
        budget = 6 << 20

        def tp_for(tb: int) -> int:
            return (budget // (2 * tb * d * 4) // 128) * 128

        while block_b > 8 and tp_for(block_b) < 128:
            # shrink along DIVISORS of b only — a non-divisor tile would
            # floor-drop trailing batch rows from the grid (silent garbage)
            cands = [k for k in range(8, block_b) if b % k == 0]
            if not cands:
                break
            block_b = max(cands)
        # the 128 floor may exceed the budget for extreme D*P at this
        # block_b; results stay correct and real hardware fails loudly at
        # compile rather than silently
        block_p = max(128, tp_for(block_b))
    p_pad = -(-p // block_p) * block_p
    # one-hot selection matrices [F, P_pad]: column k picks feature rows[k]
    # (resp. cols[k]); padded columns are all-zero -> zero dots, sliced off
    sel_rows = np.zeros((f, p_pad), dtype=np.float32)
    sel_rows[rows, np.arange(p)] = 1.0
    sel_cols = np.zeros((f, p_pad), dtype=np.float32)
    sel_cols[cols, np.arange(p)] = 1.0
    call = pl.pallas_call(
        _interaction_kernel,
        out_shape=jax.ShapeDtypeStruct((b, p_pad), emb.dtype),
        grid=(b // block_b, p_pad // block_p),
        in_specs=[
            pl.BlockSpec((f, block_p), lambda i, j: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((f, block_p), lambda i, j: (0, j), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (block_b, f, d), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_b, block_p), lambda i, j: (i, j), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )
    with kernel_trace("kernel.trace.interaction"):  # the body's trace, as a program is traced
        out = call(jnp.asarray(sel_rows), jnp.asarray(sel_cols), emb)
    return out[:, :p] if p_pad != p else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def dot_interaction(emb: jax.Array, use_pallas: Optional[bool] = None,
                    block_b: int = 128, interpret: bool = False) -> jax.Array:
    """Packed pairwise dots with autodiff.

    Auto-dispatch (use_pallas=None) resolves to the XLA path everywhere:
    measured device time on a real v5e shows XLA faster at every F (module
    docstring / PARITY.md). The Pallas kernel is opt-in (use_pallas=True)
    as a template; callers inside a shard_map pass it per-device shapes.
    """
    return _forward(emb, use_pallas, block_b, interpret)


def _forward(emb, use_pallas, block_b, interpret):
    if use_pallas is None:
        # Retired from auto-dispatch: v5e device-time table (PARITY.md)
        # shows XLA's einsum+gather faster at every F measured.
        use_pallas = False
    if use_pallas:
        return dot_interaction_pallas(emb, block_b=block_b, interpret=interpret)
    return dot_interaction_reference(emb)


def _fwd(emb, use_pallas, block_b, interpret):
    return _forward(emb, use_pallas, block_b, interpret), emb


def _bwd(use_pallas, block_b, interpret, emb, g):
    # out[b, p] = sum_d E[b, rows[p], d] * E[b, cols[p], d]
    # dE = (dG + dG^T) @ E with dG scattered from the packed pairs.
    b, f, d = emb.shape
    rows, cols = _tril_indices(f)
    dgram = jnp.zeros((b, f, f), dtype=jnp.float32)
    dgram = dgram.at[:, rows, cols].set(g.astype(jnp.float32))
    sym = dgram + jnp.swapaxes(dgram, 1, 2)
    demb = jnp.einsum("bfg,bgd->bfd", sym, emb.astype(jnp.float32))
    return (demb.astype(emb.dtype),)


dot_interaction.defvjp(_fwd, _bwd)
