"""Shared HLO-pin helpers: compile a function and assert which collectives
the backend actually emitted.

The model-parallel layer's contracts are COMMS contracts — "activations hop
by collective-permute", "EP dispatch is an all-to-all", "nothing gathers
the sharded stream" — and the only place those are real is the compiled
HLO. Every pin goes through `assert_hlo` so the idiom (lower -> compile ->
as_text -> grep) lives once, and through `per_device_argument_bytes` for
the memory-shape pins (what one device actually holds of the inputs).
"""

from __future__ import annotations

from typing import Iterable, Sequence

import jax


def compiled(fn, *args, **kwargs):
    """The compiled executable of ``fn(*args)`` — the ONE handle both the
    HLO-text pins and the memory-shape pins read from. ``fn`` may already
    be jitted; sharded example args pin their layouts."""
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    return fn.lower(*args, **kwargs).compile()


def compiled_hlo(fn, *args, **kwargs) -> str:
    """Compiled (post-SPMD-partitioning) HLO text of ``fn(*args)``."""
    return compiled(fn, *args, **kwargs).as_text()


def assert_hlo(
    fn,
    args: Sequence,
    contains: Iterable[str] = (),
    absent: Iterable[str] = (),
) -> str:
    """Compile ``fn(*args)`` and assert substrings of the HLO text.

    ``contains``: ops that MUST appear (e.g. "collective-permute",
    "all-to-all"); ``absent``: ops that must NOT (e.g. "all-gather").
    Returns the HLO text for any further custom checks.
    """
    hlo = compiled_hlo(fn, *args)
    for op in contains:
        assert op in hlo, f"expected {op!r} in compiled HLO, not found"
    for op in absent:
        assert op not in hlo, f"forbidden {op!r} present in compiled HLO"
    return hlo


def per_device_argument_bytes(fn, *args) -> int:
    """Per-device bytes of ``fn``'s compiled arguments — what ONE device
    holds of the inputs (shards, not global tensors). This is the number
    the scale-shape pins compare as meshes and microbatch counts grow."""
    ma = compiled(fn, *args).memory_analysis()
    assert ma is not None, "backend reports no memory analysis"
    return int(ma.argument_size_in_bytes)


def compiled_memory_bytes(fn, *args) -> dict:
    """Per-device compiled-memory byte sizes from ``memory_analysis()``,
    labeled with the backend that compiled them — so a CPU-mesh number
    and a real-device one land in the SAME fields. Returns {} when the backend reports no
    memory analysis (some PJRT plugins)."""
    ma = compiled(fn, *args).memory_analysis()
    if ma is None:
        return {}
    out = {"backend": jax.default_backend()}
    for field in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, field, None)
        if v is not None:
            out[field.replace("_size_in_bytes", "_bytes")] = int(v)
    return out
