#!/usr/bin/env python
"""Device-time comparison: Pallas dot-interaction vs XLA reference.

Wall-clock per call is dominated by dispatch latency, and naive
K-iteration Python loops let XLA hoist or CSE the repeated op
(PARITY.md: earlier isolation attempts "collapse
under XLA's loop optimizations"). This tool measures honestly:

- K applications run inside ONE jit via ``lax.fori_loop``;
- each iteration's input depends on the previous output through a scalar
  carry (``emb * (1 + eps * out.mean())``), so iterations can neither be
  hoisted, CSE'd, nor reordered — the loop body must execute K times;
- per-iteration overhead of the carry is one reduction + one broadcast
  multiply, identical for both implementations, so it cancels in the
  ratio;
- the measured quantity is (t_loop(K2) - t_loop(K1)) / (K2 - K1):
  subtracting two loop lengths cancels dispatch AND warmup entirely.

Run on a real TPU: ``python tools/pallas_device_time.py``. Prints a
markdown table (for PARITY.md) plus one JSON line per shape.

Off a TPU it refuses to run: timing the interpreter measures nothing.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from tpu_tfrecord.models.interaction import (
    dot_interaction_pallas,
    dot_interaction_reference,
)

K1 = int(os.environ.get("TFR_PALLAS_K1", 20))
K2 = int(os.environ.get("TFR_PALLAS_K2", 120))
REPEATS = int(os.environ.get("TFR_PALLAS_REPEATS", 5))


def _looped(fn, k: int):
    """K data-dependent applications of fn inside one jit."""

    @jax.jit
    def run(emb):
        def body(_, carry):
            emb, acc = carry
            out = fn(emb)
            m = out.astype(jnp.float32).mean()
            # scalar feedback: next input depends on this output, so the
            # loop body cannot be hoisted or collapsed; eps keeps values
            # numerically unchanged in bf16
            emb = emb * (1 + 1e-12 * m).astype(emb.dtype)
            return emb, acc + m

        _, acc = jax.lax.fori_loop(0, k, body, (emb, jnp.float32(0)))
        return acc

    return run


def _time_loop(run, emb) -> float:
    # Completion is forced with a scalar fetch of the loop's f32
    # accumulator. The fetch's round-trip latency is a constant per timing,
    # so the two-length delta cancels it exactly like dispatch.
    float(run(emb))  # compile + warm
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        float(run(emb))
        best = min(best, time.perf_counter() - t0)
    return best


def measure(fn, emb) -> float:
    """Per-application device time in seconds via the two-length delta.
    Raises on a non-monotonic measurement (t_K2 <= t_K1): that means noise
    swamped the op — exactly the bogus number this tool must never emit.
    Raise K2 (TFR_PALLAS_K2) until the delta is stable."""
    t1 = _time_loop(_looped(fn, K1), emb)
    t2 = _time_loop(_looped(fn, K2), emb)
    if t2 <= t1:
        raise RuntimeError(
            f"non-monotonic timing: t(K={K2})={t2:.6f}s <= t(K={K1})={t1:.6f}s"
            " — noise exceeds the op cost; raise TFR_PALLAS_K2/REPEATS"
        )
    return (t2 - t1) / (K2 - K1)


def main() -> None:
    backend = jax.default_backend()
    if backend != "tpu":
        # a device-time tool that times the interpreter measures nothing
        raise SystemExit(
            f"pallas_device_time needs a TPU; JAX found backend={backend!r}"
        )
    b = int(os.environ.get("TFR_PALLAS_B", 8192))
    d = int(os.environ.get("TFR_PALLAS_D", 32))
    shapes = [int(f) for f in os.environ.get(
        "TFR_PALLAS_FS", "8,16,27,32,64").split(",")]
    rng = np.random.default_rng(0)
    print(f"| F | P | XLA µs | Pallas µs | Pallas speedup | (B={b}, D={d}, "
          f"bf16, {backend}) |")
    print("|---|---|--------|-----------|----------------|---|")
    for f in shapes:
        emb = jnp.asarray(rng.normal(size=(b, f, d)), dtype=jnp.bfloat16)
        t_xla = measure(dot_interaction_reference, emb)
        t_pallas = measure(
            dot_interaction_pallas, emb
        )
        ratio = t_xla / t_pallas
        p = f * (f - 1) // 2
        print(f"| {f} | {p} | {t_xla * 1e6:.1f} | {t_pallas * 1e6:.1f} "
              f"| {ratio:.2f}x | |")
        print(json.dumps({
            "metric": "dot_interaction_device_time",
            "backend": backend, "B": b, "F": f, "D": d,
            "xla_us": round(t_xla * 1e6, 2),
            "pallas_us": round(t_pallas * 1e6, 2),
            "pallas_speedup": round(ratio, 3),
        }), file=sys.stderr)


if __name__ == "__main__":
    main()
