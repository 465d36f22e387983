#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the system's own job once, through the entry points a user calls, on
ONE TPU chip: TFRecord shards -> native decode -> fused hash -> pack -> H2D
-> DLRM sparse train step at the smoke's full width (26 tables x 2^20 rows x
32 f32, B = 16,384), with resume; then the LM trainer and the serving
replica as the processes they are. It checks what comes out by the repo's
own references and fails loudly — non-zero exit, no result line — when JAX
finds no TPU, when the native extension does not build, or when any check
fails.

    python chip_smoke.py             # one chip (what the driver runs)
    python chip_smoke.py --chips 4   # the sharded paths only, on four chips

One process holds a chip at a time. This parent never imports jax: it runs
each phase as a child, one after the other, and every child refuses any
platform but ``tpu`` before it does anything else (no option turns that
off). The phase functions below are plain functions taking sizes, so
tests/test_chip_smoke.py calls them tiny on the CPU mesh.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
with the device as JAX reported it to the child that ran the main phase.
Everything else (compile seconds, steps/s, peak bytes, tolerances measured)
is printed on earlier lines, labelled with platform and device kind; none
of it is a metric yet.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "examples"))

import numpy as np  # noqa: E402

import tpu_tfrecord  # noqa: E402,F401  (no jax: the parent holds no chip)
from criteo import (  # noqa: E402  (examples/criteo.py: no jax at import either)
    CAT_BITS, HASH_BUCKETS, NUM_CAT, NUM_DENSE, criteo_dlrm_config,
    criteo_read_schema, criteo_reader_spec, split_wire, write_dataset,
)

#: Device kinds this smoke has been run on. A kind that is not here is an
#: error, not a default: nothing is assumed about a chip nobody has seen.
KNOWN_DEVICE_KINDS = ("TPU v5 lite",)

#: The full-width run: 16 shards x 32,768 rows =
#: 32 batches of 16,384 in ONE epoch, so rows consumed == rows written.
FULL = dict(
    shards=16, rows_per_shard=32768, batch=16384, vocab=1 << 20, steps=32,
    cmp_vocab=1 << 12,
)

#: the whole run, compilation included, must end inside 1200 s; past this
#: the parent kills whatever child holds the chip and fails
DEADLINE_S = 1100

#: sparse_train_step vs the dense reference, float32 config, at the
#: device's DEFAULT matmul precision: every compared quantity must satisfy
#: max|got - want| <= this x max|want|. Measured on the v5e: 3.5e-7 on the
#: touched rows, 1.2e-7 on the accumulators, loss and MLP leaves bit-equal
#: (see phase_compare's docstring for why).
SPARSE_VS_DENSE_TOL = 1e-5

#: __graft_entry__._dryrun_body's agreement checks on real chips, (rtol,
#: atol) per matmul precision; what is not named keeps the CPU's float32
#: bound (__graft_entry__.DRYRUN_TOL). 'default' is what users run: one
#: bf16 pass rounds each operand to 2^-9, and two algorithms that sum in
#: different orders then differ by a few 2^-8 of their O(1) values (on four
#: v5e chips: ring vs ulysses 3.6e-3, MoE vs its float64 oracle 8.9e-3) —
#: held to 2^-6, while a wrong route, hop or shard is O(0.1) or more.
#: pipeline_apply vs the sequential composition is one algorithm at one
#: precision (0.0 apart on the chips) and keeps the CPU bound. Under
#: 'highest' only the MoE atol moves: its oracle is float64 numpy, and the
#: TPU's exp/tanh (router softmax, gelu) are good to ~1e-5 absolute
#: (1.5e-5 measured).
MULTICHIP_TOL = {
    "default": {
        "ulysses_vs_ring": (2 ** -6, 2 ** -6),
        "moe_vs_oracle": (2 ** -6, 2 ** -6),
    },
    "highest": {"moe_vs_oracle": (1e-4, 5e-5)},
}

#: Pallas vs XLA dot interaction, (rtol, atol) per input dtype:
#: tests/test_interaction.py's. phase_compare also holds every output
#: column to rtol of that column's own scale.
INTERACTION_TOL = {"bfloat16": (3e-2, 3e-1), "float32": (1e-4, 1e-4)}


class SmokeFailure(Exception):
    """A phase's check failed; the message says which."""


class Checks:
    """Collects named checks so ONE run reports every failure, then raises."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failed: list = []

    def check(self, name: str, ok, detail: str = "") -> bool:
        ok = bool(ok)
        print(f"[{self.phase}] {'ok  ' if ok else 'FAIL'} {name} {detail}".rstrip(),
              flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def finish(self) -> None:
        if self.failed:
            raise SmokeFailure(f"{self.phase}: failed checks: {self.failed}")


def info(phase: str, **fields) -> None:
    """One informational line (never the last line of stdout)."""
    print(f"[{phase}] " + json.dumps(fields, sort_keys=True), flush=True)


# ---------------------------------------------------------------------------
# Device identity
# ---------------------------------------------------------------------------


def device_line() -> dict:
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def require_tpu(chips: int) -> dict:
    """Refuse anything but ``chips`` TPU devices of a known kind."""
    dev = device_line()
    if dev["platform"] != "tpu":
        raise SmokeFailure(
            f"chip_smoke needs a TPU; JAX found platform={dev['platform']!r}"
        )
    if dev["kind"] not in KNOWN_DEVICE_KINDS:
        raise SmokeFailure(
            f"unknown device_kind {dev['kind']!r} (known: {KNOWN_DEVICE_KINDS})"
        )
    if dev["count"] != chips:
        raise SmokeFailure(f"asked for {chips} chip(s), JAX sees {dev['count']}")
    return dev


def _on_devices(tree, devices) -> bool:
    import jax

    allowed = set(devices)
    return all(leaf.devices() <= allowed for leaf in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# Phase: build
# ---------------------------------------------------------------------------


def phase_build(clean: bool = True) -> dict:
    """Build the native extension from csrc and require it. ``clean``
    removes ``tpu_tfrecord/_lib/`` first (it is git-ignored, copied as it
    lies on disk, and trusted by mtime): `_native.load()` swallows a failed
    build and serves pure Python, which no exit code would show."""
    from tpu_tfrecord import _native

    if clean:
        shutil.rmtree(os.path.dirname(_native._LIB_PATH), ignore_errors=True)
    t0 = time.perf_counter()
    if not _native.available():
        raise SmokeFailure(f"native extension unavailable: {_native.load_error()}")
    secs = time.perf_counter() - t0
    info("build", native_build_s=round(secs, 2), lib=_native._LIB_PATH)
    return {"native_build_s": secs}


# ---------------------------------------------------------------------------
# The Criteo reader (examples/criteo.py's schema and spec)
# ---------------------------------------------------------------------------


def _criteo_dataset(data_dir: str, batch: int, **kw):
    """The Criteo reader (fused hash to 2^20 buckets, [B, 40] pack) at this
    batch size."""
    from tpu_tfrecord.io.dataset import TFRecordDataset

    hash_buckets, pack = criteo_reader_spec()
    ds = TFRecordDataset(
        data_dir, batch_size=batch, schema=criteo_read_schema(), prefetch=4,
        hash_buckets=hash_buckets, pack=pack, **kw,
    )
    return ds, hash_buckets, pack


def _host_packed(ds, cb, hash_buckets, pack) -> np.ndarray:
    from tpu_tfrecord.tpu import host_batch_from_columnar

    return host_batch_from_columnar(
        cb, ds.schema, hash_buckets=hash_buckets, pack=pack
    )["packed"]


def _timed_compile(name: str, jitted, *args):
    """AOT-compile ``jitted`` for ``args``; prints compile seconds (a warm
    persistent cache shows here) and returns (compiled, seconds)."""
    lowered = jitted.lower(*args)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    secs = time.perf_counter() - t0
    info("compile", program=name, compile_s=round(secs, 3), **device_line())
    return compiled, secs


# ---------------------------------------------------------------------------
# Phase: ingest + train at full width
# ---------------------------------------------------------------------------


def phase_ingest_train(
    data_dir: str, rows_written: int, batch: int, vocab: int, steps: int,
    seed: int = 0,
) -> dict:
    """TFRecordDataset -> host_batch_from_columnar -> pack_mixed ->
    DeviceIterator -> split/unpack_bits -> sparse_train_step, one epoch."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_tfrecord.models import init_params, sparse_opt_init, sparse_train_step
    from tpu_tfrecord.tpu import (
        DeviceIterator, HostPrefetcher, create_mesh, pack_mixed,
    )

    c = Checks("ingest_train")
    dev = device_line()
    mesh = create_mesh()  # every device on the 'data' axis
    devices = list(mesh.devices.flat)
    repl = NamedSharding(mesh, P())
    cfg = criteo_dlrm_config(vocab)
    tx = optax.sgd(1e-3)
    params = jax.device_put(init_params(jax.random.key(seed), cfg), repl)
    opt_state = jax.device_put(sparse_opt_init(params, cfg, tx), repl)
    # a slab of rows kept from before training: afterwards each must have
    # changed iff some batch touched it
    slab_rows = min(64, vocab)
    slab0 = np.asarray(params["embeddings"][:, :slab_rows, :])

    ds, hash_buckets, pack = _criteo_dataset(data_dir, batch, num_epochs=1)
    it = ds.batches()
    touched = np.zeros((NUM_CAT, vocab), bool)
    seen = {"rows": 0, "first_wire": None}
    f_ix = np.arange(NUM_CAT)[None, :]

    def host_batches():
        for cb in it:
            packed = _host_packed(ds, cb, hash_buckets, pack)
            seen["rows"] += packed.shape[0]
            touched[f_ix, packed[:, 1 + NUM_DENSE:] % vocab] = True
            wire = pack_mixed(packed, 1 + NUM_DENSE, CAT_BITS)
            if seen["first_wire"] is None:
                seen["first_wire"] = wire.copy()
            yield {"wire": wire}

    split_j = jax.jit(functools.partial(split_wire, vocab=vocab))
    step_j = jax.jit(
        functools.partial(sparse_train_step, cfg=cfg, tx=tx),
        donate_argnums=(0, 1), out_shardings=(repl, repl, repl),
    )
    prefetcher = dev_it = None
    losses = []
    try:
        prefetcher = HostPrefetcher(host_batches())
        dev_it = DeviceIterator(prefetcher, mesh, transfer_thread=True)
        gb = next(dev_it)
        c.check("device batch lives on the mesh devices",
                _on_devices(gb, devices), str(sorted(map(str, gb["wire"].devices()))))
        c.check("first device batch, fetched back, is bit-equal to the host batch",
                np.array_equal(np.asarray(gb["wire"]), seen["first_wire"]))
        split_c, _ = _timed_compile("split_wire", split_j, gb)
        b0 = split_c(gb)
        step_c, step_compile_s = _timed_compile(
            "sparse_train_step", step_j, params, opt_state, b0
        )
        mem = step_c.memory_analysis()
        est = None
        if mem is not None:
            est = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "alias_bytes": int(mem.alias_size_in_bytes),
            }
        t0 = time.perf_counter()
        n = 0
        while True:
            b = split_c(gb)
            params, opt_state, loss = step_c(params, opt_state, b)
            losses.append(loss)
            n += 1
            if n == 1:
                c.check("split/step outputs live on the mesh devices",
                        _on_devices((b, params, opt_state, loss), devices))
            try:
                gb = next(dev_it)
            except StopIteration:
                break
        jax.block_until_ready((params, opt_state, losses))
        wall = time.perf_counter() - t0
    finally:
        if dev_it is not None:
            dev_it.close()
        if prefetcher is not None:
            prefetcher.close()
        it.close()

    losses = [float(x) for x in losses]
    c.check(f"ran >= {steps} steps", n >= steps, f"steps={n}")
    c.check("rows consumed in one epoch == rows written",
            seen["rows"] == rows_written, f"{seen['rows']} vs {rows_written}")
    c.check("losses finite", np.isfinite(losses).all(),
            f"first={losses[0]:.4f} last={losses[-1]:.4f}")
    slab1 = np.asarray(params["embeddings"][:, :slab_rows, :])
    changed = (slab1 != slab0).any(axis=-1)
    t_slab = touched[:, :slab_rows]
    c.check("the slab holds touched and untouched rows",
            t_slab.any() and not t_slab.all())
    c.check("a row no batch touched is unchanged", not changed[~t_slab].any(),
            f"untouched rows in slab: {int((~t_slab).sum())}")
    c.check("a touched row is changed", changed[t_slab].all(),
            f"touched rows in slab: {int(t_slab.sum())}")
    stats = devices[0].memory_stats() or {}
    # information for whoever reads the rates below: the step alone, on a
    # batch already resident (block_until_ready waits — transport probe)
    alone = []
    for _ in range(5):
        t0 = time.perf_counter()
        params, opt_state, loss = step_c(params, opt_state, b)
        jax.block_until_ready(loss)
        alone.append(time.perf_counter() - t0)
    info(
        "ingest_train", steps=n, batch=batch, vocab=vocab,
        step_alone_s_median=float(np.median(alone)),
        steps_per_s=round(n / wall, 2), examples_per_s=round(n * batch / wall, 1),
        peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"),
        bytes_limit=stats.get("bytes_limit", "not reported"),
        compiler_estimate=est, note="information, not a metric", **dev,
    )
    c.finish()
    return {"steps": n, "step_compile_s": step_compile_s,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


# ---------------------------------------------------------------------------
# Phase: what it is compared with
# ---------------------------------------------------------------------------


def _err(got, want) -> dict:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    diff = np.abs(got - want)
    return {
        "max_abs": float(diff.max()),
        "max_abs_over_scale": float(diff.max() / max(np.abs(want).max(), 1e-30)),
    }


def phase_compare(
    data_dir: str, batch: int, cmp_vocab: int, interpret: bool = False,
    seed: int = 0,
) -> None:
    """One REAL ingested batch through sparse_train_step and through the
    plain reference of the same semantics (full dense table gradient +
    row-wise AdaGrad applied densely, models.dlrm), at the smoke's feature
    widths and B with indices folded to ``cmp_vocab`` so the dense gradient
    fits; then dot interaction XLA vs the compiled Pallas kernel.

    Tolerance. Both programs run the float32 config at the device's
    DEFAULT matmul precision — what the system trains at. On the chip that
    is bf16-operand MXU passes, but each program rounds the same operands
    the same way, so they do NOT drift by bf16's 4e-3: the forward and the
    MLP gradients came out bit-equal on the v5e, and what is left is f32
    summation order in the row gradients (scatter-add vs sort +
    segment-sum): 3.5e-7 of scale. So the CPU test's 1e-5 holds on the chip
    too, stated scale-relative (SPARSE_VS_DENSE_TOL) because an elementwise
    rtol is meaningless on rows whose entries cross zero.

    The Pallas kernel is held to tests/test_interaction.py's tolerance for
    each input dtype (INTERACTION_TOL), and to the same rtol taken against
    each output COLUMN's own scale with no absolute slack: embeddings start
    at 0.05·N(0,1), so 325 of the 351 pair columns hold values of 0.01–0.1
    and an `atol` alone would pass them as all zeros.
    ``interpret`` exists for the CPU rehearsal only; main() never sets it.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from tpu_tfrecord.models import (
        dlrm, init_params, sparse_opt_init, sparse_train_step,
    )
    from tpu_tfrecord.models.interaction import (
        dot_interaction_pallas, dot_interaction_reference,
    )
    from tpu_tfrecord.tpu import pack_mixed

    c = Checks("compare")
    ds, hash_buckets, pack = _criteo_dataset(data_dir, batch, num_epochs=1)
    with ds.batches() as it:
        packed = _host_packed(ds, next(it), hash_buckets, pack)
    wire = jnp.asarray(pack_mixed(packed, 1 + NUM_DENSE, CAT_BITS))
    b = jax.jit(functools.partial(split_wire, vocab=cmp_vocab))({"wire": wire})

    cfg = criteo_dlrm_config(cmp_vocab, dtype=jnp.float32)
    tx = optax.sgd(1e-3)
    params = init_params(jax.random.key(seed), cfg)
    opt0 = sparse_opt_init(params, cfg, tx)
    got_p, got_s, got_l = jax.jit(
        functools.partial(sparse_train_step, cfg=cfg, tx=tx)
    )(params, opt0, b)
    want_p, want_s, want_l = jax.jit(
        functools.partial(dlrm.dense_rowwise_adagrad_reference, cfg=cfg, tx=tx)
    )(params, opt0, b)
    pairs = {
        "loss": (got_l, want_l),
        "embeddings": (got_p["embeddings"], want_p["embeddings"]),
        "accum": (got_s.accum, want_s.accum),
    }
    for name in ("bottom", "top"):
        for i, (gl, wl) in enumerate(zip(got_p[name], want_p[name])):
            pairs[f"{name}[{i}].w"] = (gl["w"], wl["w"])
            pairs[f"{name}[{i}].b"] = (gl["b"], wl["b"])
    for name, (g, w) in pairs.items():
        err = _err(g, w)
        c.check(f"sparse == dense reference: {name}",
                err["max_abs_over_scale"] <= SPARSE_VS_DENSE_TOL, json.dumps(err))
    moved = np.asarray(got_p["embeddings"]) != np.asarray(params["embeddings"])
    c.check("the step moved embedding rows", moved.any())

    # dot interaction: XLA vs the compiled Pallas kernel on this batch's
    # own [B, 27, 32] stack (bottom output + gathered rows)
    def stack_of(p, batch_, dt):
        bottom = dlrm._mlp(p["bottom"], batch_["dense"].astype(dt), dt)
        rows = p["embeddings"][jnp.arange(NUM_CAT)[None, :], batch_["cat"]]
        return jnp.concatenate([bottom[:, None, :], rows.astype(dt)], axis=1)

    kernel = jax.jit(functools.partial(dot_interaction_pallas, interpret=interpret))
    with jax.default_matmul_precision("highest"):
        stack32 = jax.jit(functools.partial(stack_of, dt=jnp.float32))(params, b)
        want32 = jax.jit(dot_interaction_reference)(stack32)
    hlo = kernel.lower(stack32).compile().as_text()
    if not interpret:
        c.check("Pallas kernel compiled to a tpu_custom_call",
                "tpu_custom_call" in hlo)
    want32 = np.asarray(want32)
    col_scale = np.abs(want32).max(axis=0)
    for name, (rtol, atol) in INTERACTION_TOL.items():
        got = kernel(stack32.astype(name))
        got32 = np.asarray(got, np.float32)
        err = _err(got32, want32)
        err["max_over_columns_of_abs_over_column_scale"] = float(
            (np.abs(got32 - want32).max(axis=0) / col_scale).max()
        )
        err["min_column_scale"] = float(col_scale.min())
        c.check(f"pallas == XLA ({name} in, rtol={rtol} atol={atol}, "
                f"every column to {rtol} of its own scale)",
                got.dtype == jnp.dtype(name)
                and np.allclose(got32, want32, rtol=rtol, atol=atol)
                and err["max_over_columns_of_abs_over_column_scale"] <= rtol,
                json.dumps(err))
    c.finish()


# ---------------------------------------------------------------------------
# Phase: resume
# ---------------------------------------------------------------------------


def phase_resume(data_dir: str, batch: int, ckpt_dir: str, after: int = 3) -> None:
    """``it.state()`` mid-epoch -> save_state/load_state on disk -> a fresh
    iterator from that state yields the byte-identical next batch."""
    from tpu_tfrecord import checkpoint

    c = Checks("resume")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    ds, hash_buckets, pack = _criteo_dataset(data_dir, batch, num_epochs=1)
    with ds.batches() as it:
        for _ in range(after):
            next(it)
        checkpoint.save_state(ckpt_dir, it.state())
        want = _host_packed(ds, next(it), hash_buckets, pack).copy()
    state = checkpoint.load_state(ckpt_dir)
    c.check("state round-trips through disk", state is not None, str(state))
    ds2, _, _ = _criteo_dataset(data_dir, batch, num_epochs=1)
    with ds2.batches(state) as it2:
        got = _host_packed(ds2, next(it2), hash_buckets, pack)
    c.check("resumed iterator yields the byte-identical next batch",
            got.tobytes() == want.tobytes())
    c.finish()


# ---------------------------------------------------------------------------
# Phase: transport probe (information for ROADMAP D10; asserts nothing)
# ---------------------------------------------------------------------------


def phase_transport_probe(n: int = 4096, chain: int = 20, h2d_mb: int = 256) -> dict:
    """Does ``block_until_ready`` wait for the device, and does a
    host-to-device copy return at dispatch? Times a chain of matmuls ended
    by block_until_ready vs by a scalar fetch, and device_put dispatch vs
    completion. Medians of 5; information only."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        for _ in range(chain):
            x = (x @ x) * (1.0 / n)
        return x

    x = jnp.ones((n, n), jnp.bfloat16)
    float(f(x)[0, 0])  # compile + warm
    disp, block, fetch = [], [], []
    for _ in range(5):
        t0 = time.perf_counter()
        y = f(x)
        t1 = time.perf_counter()
        y.block_until_ready()
        t2 = time.perf_counter()
        disp.append(t1 - t0)
        block.append(t2 - t0)
        t0 = time.perf_counter()
        float(f(x)[0, 0])
        fetch.append(time.perf_counter() - t0)
    host = np.ones((h2d_mb << 20) // 4, np.int32)
    jax.device_put(host).block_until_ready()  # warm
    h_disp, h_done = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        d = jax.device_put(host)
        t1 = time.perf_counter()
        d.block_until_ready()
        h_disp.append(t1 - t0)
        h_done.append(time.perf_counter() - t0)
    med = lambda v: float(np.median(v))  # noqa: E731
    out = {
        "matmul_chain": f"{chain} x [{n},{n}] bf16",
        "dispatch_s": med(disp), "block_until_ready_s": med(block),
        "scalar_fetch_s": med(fetch),
        "h2d_mb": h2d_mb, "h2d_dispatch_s": med(h_disp),
        "h2d_complete_s": med(h_done),
    }
    info("transport_probe", **out, **device_line())
    return out


# ---------------------------------------------------------------------------
# Phase: four chips (behind --chips 4; the driver never runs it)
# ---------------------------------------------------------------------------


def _placement(a) -> tuple:
    """(distinct devices, distinct shard index ranges) of a jax.Array."""
    shards = a.addressable_shards
    return (len({s.device for s in shards}), len({str(s.index) for s in shards}))


def phase_multichip(n: int = 4, steps: int = 4) -> None:
    """The sharded paths on ``n`` real devices, in one process:
    ``__graft_entry__._dryrun_body(n)`` inline (data 1 x model 2 x seq 2 at
    n=4: real-file ingest -> sharded step, ring attention, pipeline_apply
    vs pipeline_reference), then ``lm.train_step`` on pipe 2 x data 1 x
    fsdp n/2 against the pure-dp loss trajectory on the same data
    (tests/test_lm_fsdp.py pins it on CPU). Every agreement check runs
    twice: at the device's DEFAULT matmul precision, which is what users
    run, to MULTICHIP_TOL's bf16-level bounds, and under
    ``default_matmul_precision('highest')`` to the CPU's float32 bounds."""
    import jax
    import jax.numpy as jnp
    import optax

    import __graft_entry__ as ge
    from tpu_tfrecord.models import lm
    from tpu_tfrecord.tpu import create_mesh

    c = Checks("multichip")
    for precision, tol in MULTICHIP_TOL.items():
        with jax.default_matmul_precision(precision):
            out = ge._dryrun_body(n, tol=tol)
        info("multichip", matmul_precision=precision, tol=tol,
             dryrun_max_abs_diff=out["max_abs_diff"], **device_line())
        for name, (a, parts) in out["sharded"].items():
            c.check(f"dryrun ({precision}): {name} has shards on {n} distinct "
                    f"devices, {parts} distinct parts",
                    _placement(a) == (n, parts), str(_placement(a)))

    cfg = lm.LMConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=4, max_len=16, n_micro=4
    )
    tx = optax.adam(3e-3)

    def trajectory(mesh=None, **axes):
        params = lm.init_params(jax.random.key(0), cfg)
        if mesh is not None:
            place = {k: v for k, v in axes.items() if k != "data_axis"}
            params = jax.device_put(
                params, lm.param_shardings(mesh, params, **place)
            )
            leaf = params["blocks"]["qkv"]["w"]
            want = mesh.shape["pipe"] * mesh.shape["fsdp"]
            c.check(f"lm: block weights sharded pipe x fsdp over {n} devices",
                    _placement(leaf) == (n, want), str(_placement(leaf)))
        opt = tx.init(params)
        step = jax.jit(functools.partial(
            lm.train_step, cfg=cfg, tx=tx, mesh=mesh, **axes))
        out = []
        for i in range(steps):
            toks = jnp.asarray(lm.make_synthetic_tokens(cfg, 8, seed=100 + i))
            params, opt, loss = step(params, opt, toks)
            out.append(float(loss))
        return out

    mesh = create_mesh({"pipe": 2, "data": 1, "fsdp": n // 2}, jax.devices()[:n])
    axes = dict(data_axis="data", pipe_axis="pipe", fsdp_axis="fsdp")
    # the CPU test's tolerance at either precision: it is already under one
    # bf16 ulp of the loss (2^-8 x 4.2), and both programs round the same
    # operands the same way (4.8e-7 apart on four v5e chips at 'default')
    for precision in MULTICHIP_TOL:
        with jax.default_matmul_precision(precision):
            ref = trajectory()
            got = trajectory(mesh, **axes)
        c.check(f"lm ({precision}): dp x fsdp x pp loss trajectory == pure dp "
                "(rtol=1e-3 atol=1e-4)",
                np.allclose(got, ref, rtol=1e-3, atol=1e-4),
                json.dumps({"got": got, "ref": ref, "max_abs_diff": float(
                    np.abs(np.array(got) - np.array(ref)).max())}))
    c.finish()


# ---------------------------------------------------------------------------
# Children: one process per phase group, each refusing non-TPU first
# ---------------------------------------------------------------------------


def child_dlrm(args) -> dict:
    dev = require_tpu(1)
    from tpu_tfrecord import compile_cache

    info("cache", dir=compile_cache.enable(), placed_by_env=bool(
        os.environ.get(compile_cache.ENV_VAR)))
    phase_build(clean=True)
    data_dir = os.path.join(args.workdir, "criteo")
    t0 = time.perf_counter()
    try:
        rows = write_dataset(
            data_dir, args.seed, FULL["shards"], FULL["rows_per_shard"]
        )
    except RuntimeError as e:
        raise SmokeFailure(str(e)) from e
    info("dataset", rows=rows, shards=FULL["shards"],
         write_s=round(time.perf_counter() - t0, 2))
    phase_transport_probe()
    phase_compare(data_dir, FULL["batch"], FULL["cmp_vocab"], seed=args.seed)
    phase_ingest_train(
        data_dir, rows, FULL["batch"], FULL["vocab"], FULL["steps"], seed=args.seed
    )
    phase_resume(data_dir, FULL["batch"], os.path.join(args.workdir, "input_state"))
    return dev


def phase_serve_reference(workdir: str) -> None:
    """The serving reference, computed in THIS process (on the chip, after
    the replica gave it back): same seed -> same synthetic params -> the
    byte-exact tokens the replica must have answered."""
    from tpu_tfrecord import serving

    with open(os.path.join(workdir, "serve_requests.json")) as fh:
        doc = json.load(fh)
    ns = argparse.Namespace(**doc["model"])
    params, cfg, mesh = serving._build_synthetic(ns)
    want = serving.sequential_reference(
        params, cfg, mesh, [(w, doc["n_new"]) for w in doc["windows"]], ns.mb
    )
    c = Checks("serving")
    c.check("4 ServeClient.generate answers byte-equal sequential_reference",
            want == doc["answers"], json.dumps({"got": doc["answers"], "want": want}))
    c.finish()


def child_serve_ref(args) -> dict:
    dev = require_tpu(1)
    phase_serve_reference(args.workdir)
    return dev


def child_multichip(args) -> dict:
    dev = require_tpu(4)
    from tpu_tfrecord import compile_cache

    compile_cache.enable()
    phase_multichip(4)
    return dev


CHILDREN = {
    "dlrm": child_dlrm, "serve-ref": child_serve_ref, "multichip": child_multichip,
}


def run_child(args) -> int:
    try:
        dev = CHILDREN[args.phase](args)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr, flush=True)
        return 1
    with open(args.result, "w") as fh:
        json.dump(dev, fh)
    return 0


# ---------------------------------------------------------------------------
# Parent: sequential children, never a backend of its own
# ---------------------------------------------------------------------------

SERVE_MODEL = dict(
    vocab=96, d_model=32, heads=2, layers=4, max_len=16, mb=4, virtual=1,
    stages=1, seed=0,
)


def _spawn(cmd, timeout, **kw) -> int:
    """Run one child in its own process group; kill the group on timeout."""
    print(f"[parent] $ {' '.join(cmd)}", flush=True)
    p = subprocess.Popen(cmd, cwd=HERE, start_new_session=True, **kw)
    try:
        return p.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"{cmd[:3]} exceeded {timeout}s")
    finally:  # a timeout or the parent's own deadline: leave nothing running
        if p.poll() is None:
            os.killpg(p.pid, signal.SIGKILL)
            p.wait()


def _phase_child(phase: str, args, timeout: float) -> dict:
    result = os.path.join(args.workdir, f"result-{phase}.json")
    if os.path.exists(result):
        os.remove(result)
    rc = _spawn(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         "--workdir", args.workdir, "--seed", str(args.seed), "--result", result],
        timeout,
    )
    if rc != 0 or not os.path.exists(result):
        raise SmokeFailure(f"phase {phase!r} failed (rc={rc})")
    with open(result) as fh:
        return json.load(fh)


def parent_train_lm(workdir: str) -> None:
    """examples/train_lm.py --mesh dp --steps 8 leaves a checkpoint."""
    data = os.path.join(workdir, "lm_data")
    ckpt = os.path.join(workdir, "lm_ckpt")
    shutil.rmtree(ckpt, ignore_errors=True)
    log = os.path.join(workdir, "train_lm.log")
    with open(log, "w") as fh:
        rc = _spawn(
            [sys.executable, os.path.join(HERE, "examples", "train_lm.py"),
             "--mesh", "dp", "--steps", "8", "--data-dir", data, "--ckpt-dir", ckpt],
            600, stdout=fh, stderr=subprocess.STDOUT,
        )
    with open(log) as fh:
        out = fh.read()
    print(out[-3000:], flush=True)
    c = Checks("train_lm")
    c.check("examples/train_lm.py --mesh dp --steps 8 exits 0", rc == 0, f"rc={rc}")
    c.check("it ran on platform=tpu", "platform=tpu" in out)
    manifest = os.path.join(ckpt, "gen-00000008", "MANIFEST.json")
    c.check("it left a checkpoint", os.path.exists(manifest), manifest)
    c.finish()


def parent_serving(workdir: str, seed: int = 0, platform: str = "tpu") -> None:
    """``python -m tpu_tfrecord.serving --stages 1`` answers 4 requests,
    then drains on SIGTERM with exit 0; the answers land in
    ``serve_requests.json`` for `phase_serve_reference`, which runs once
    the replica has given the chip back."""
    from tpu_tfrecord.serving import ServeClient

    m = SERVE_MODEL
    cmd = [sys.executable, "-m", "tpu_tfrecord.serving"]
    for k in ("vocab", "d_model", "heads", "layers", "max_len", "mb",
              "virtual", "stages", "seed"):
        cmd += [f"--{k.replace('_', '-')}", str(m[k])]
    print(f"[parent] $ {' '.join(cmd)}", flush=True)
    env = dict(os.environ, PYTHONPATH=HERE + os.pathsep + os.environ.get("PYTHONPATH", ""))
    err_log = open(os.path.join(workdir, "serving.log"), "w")
    p = subprocess.Popen(
        cmd, cwd=HERE, env=env, stdout=subprocess.PIPE, stderr=err_log,
        text=True, start_new_session=True,
    )
    c = Checks("serving")
    try:
        ready = json.loads(p.stdout.readline() or "{}")
        c.check(f"replica announced itself on platform={platform}",
                ready.get("platform") == platform and "addr" in ready,
                json.dumps(ready))
        c.finish()
        rng = np.random.default_rng(seed)
        windows = [
            rng.integers(1, m["vocab"], size=m["max_len"]).tolist() for _ in range(4)
        ]
        client = ServeClient([ready["addr"]])
        try:
            answers = [client.generate(w, 4, deadline_s=300.0) for w in windows]
        finally:
            client.close()
        p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=120)
        c.check("replica drained on SIGTERM with exit 0", rc == 0, f"rc={rc}")
    finally:
        if p.poll() is None:
            os.killpg(p.pid, signal.SIGKILL)
            p.wait()
        err_log.close()
    c.finish()
    with open(os.path.join(workdir, "serve_requests.json"), "w") as fh:
        json.dump({"model": m, "n_new": 4, "windows": windows, "answers": answers}, fh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workdir", default=os.path.join(HERE, ".chip_smoke_work"),
                    help="scratch inside the checkout (git-ignored)")
    ap.add_argument("--phase", choices=sorted(CHILDREN), help=argparse.SUPPRESS)
    ap.add_argument("--result", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return run_child(args)
    os.makedirs(args.workdir, exist_ok=True)

    def _deadline(signum, frame):
        raise SmokeFailure(f"chip_smoke exceeded its {DEADLINE_S}s deadline")

    signal.signal(signal.SIGALRM, _deadline)
    signal.alarm(DEADLINE_S)
    try:
        if args.chips == 4:
            dev = _phase_child("multichip", args, 1000)
        else:
            dev = _phase_child("dlrm", args, 900)
            parent_train_lm(args.workdir)
            parent_serving(args.workdir, args.seed)
            _phase_child("serve-ref", args, 300)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
