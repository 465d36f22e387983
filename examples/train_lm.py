#!/usr/bin/env python
"""End-to-end example: train the causal LM on packed token SequenceExamples.

The trainer that proves the model-parallel layer (ISSUE 10 / ROADMAP #4):
  1. generate token documents (a sparse-bigram synthetic language) as
     SequenceExamples through the io layer
  2. stream them with TFRecordDataset; pack the ragged docs into dense
     [B, L+1] causal batches with TokenPacker (no padding, no masks)
  3. feed the mesh through the double-buffered DeviceIterator
  4. jit train steps whose attention is ZIGZAG CAUSAL RING over the 'seq'
     axis (--mesh dp_sp, default), or whose blocks run as PIPELINE stages
     over the 'pipe' axis (--mesh dp_pp: the dp×pp composed mesh with the
     scale-shaped O(mb) microbatch stream), or plain dp (--mesh dp), or
     with GSPMD WEIGHT SHARDING over the 'fsdp' axis (--mesh dp_fsdp /
     dp_fsdp_pp: params + optimizer state live sharded, gather on use —
     per-device at-rest bytes shrink ~linearly in the fsdp extent)
  5. checkpoint params + optimizer + IteratorState + packer carry in ONE
     atomic file every --save-every steps; kill -9 and rerun to resume —
     the packed-batch stream and the loss curve continue byte-identically
     (tools/verify.sh pins this)
  6. fly the training flight recorder (ISSUE 13): every step decomposes
     into train.data_wait/h2d/compute/ckpt phases with a windowed
     input/compute/ckpt-bound verdict; --spool SPOOL_DIR joins the fleet
     under the trainer role (read it with `tfrecord_doctor train`),
     --trace-out saves a step-marked Chrome trace, and --diagnostics
     folds the in-jit MoE/pipeline diagnostics (expert counts, dropped
     fraction, gate entropy, measured bubble) into gauges each step

Run on any JAX backend; for a local simulation:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_lm.py
"""

import argparse
import functools
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from tpu_tfrecord import compile_cache

compile_cache.enable()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache

import numpy as np
import optax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _harness

from tpu_tfrecord import checkpoint
from tpu_tfrecord.io.dataset import IteratorState, TFRecordDataset
from tpu_tfrecord.io.writer import DatasetWriter
from tpu_tfrecord.models import lm
from tpu_tfrecord.options import TFRecordOptions
from tpu_tfrecord.schema import ArrayType, LongType, StructField, StructType
from tpu_tfrecord.tpu import DeviceIterator, TokenPacker, create_mesh

VOCAB = 256
SEQ_LEN = 64
BATCH = 32


def make_schema() -> StructType:
    return StructType([StructField("tokens", ArrayType(LongType()))])


def generate(data_dir: str, shards: int = 4, docs: int = 256) -> None:
    """Token documents from the shared sparse-bigram language, written as
    SequenceExamples in ONE job (sharded via max_records_per_file) so
    _SUCCESS can never cover a partial dataset."""
    if os.path.exists(os.path.join(data_dir, "_SUCCESS")):
        return
    rng = np.random.default_rng(0)
    table = lm.bigram_table(VOCAB, 4)
    rows = []
    for _ in range(shards * docs):
        n = int(rng.integers(16, 97))
        t = int(rng.integers(VOCAB))
        doc = np.empty(n, np.int64)
        for j in range(n):
            doc[j] = t
            t = int(table[t, rng.integers(4)])
        rows.append([doc.tolist()])
    DatasetWriter(
        data_dir,
        make_schema(),
        TFRecordOptions.from_map(recordType="SequenceExample"),
        mode="overwrite",
        max_records_per_file=docs,
    ).write_rows(rows)


def pick_mesh(kind: str, virtual: int = 1):
    """(mesh, cfg axes, n_layers) for the requested parallelism on the
    devices that exist. A mesh whose extents do not divide the device count
    RAISES — it never quietly becomes ``dp`` under a banner that still
    names what was asked for. ``virtual`` > 1 picks the interleaved dp_pp
    shape: 2 stages × V round-robin chunks of the same 4 layers, cutting
    the bubble toward (S-1)/(V·M+S-1). The fsdp kinds add GSPMD weight
    sharding: params live sharded over the 'fsdp' axis and gather on use
    (models.lm), so per-device at-rest bytes for params + optimizer state
    shrink ~linearly in the fsdp extent."""
    n_dev = len(jax.devices())

    def need(multiple: int) -> None:
        if n_dev % multiple:
            raise SystemExit(
                f"--mesh {kind} needs a device count divisible by "
                f"{multiple}, have {n_dev}; ask for a mesh that fits "
                "(dp runs on any count)"
            )

    if kind == "dp":
        return create_mesh({"data": n_dev}), {"data_axis": "data"}, 2
    if kind == "dp_sp":
        need(2)
        mesh = create_mesh({"data": n_dev // 2, "seq": 2})
        return mesh, {"data_axis": "data", "seq_axis": "seq"}, 2
    if kind == "dp_fsdp":
        need(2)
        mesh = create_mesh({"data": 2, "fsdp": n_dev // 2})
        return mesh, {"data_axis": "data", "fsdp_axis": "fsdp"}, 2
    if kind == "dp_fsdp_pp":
        need(4)
        data = 2 if n_dev % 8 == 0 else 1
        mesh = create_mesh(
            {"pipe": 2, "data": data, "fsdp": n_dev // (2 * data)}
        )
        return mesh, {
            "data_axis": "data", "pipe_axis": "pipe", "fsdp_axis": "fsdp",
        }, 4
    if kind == "dp_pp":
        need(2)
        pipe = 4 if virtual == 1 and n_dev % 4 == 0 else 2
        mesh = create_mesh({"pipe": pipe, "data": n_dev // pipe})
        return mesh, {"data_axis": "data", "pipe_axis": "pipe"}, 4
    raise SystemExit(f"unknown --mesh {kind!r}")


class LMCheckpoint:
    """Params + optimizer + input position + packer carry, saved together.

    Now the async npz-shard twin (ISSUE 16): a thin wrapper over
    ``checkpoint.AsyncCheckpointer``, so the caller's thread only pays
    for the device snapshot while the stage+fsync+rename commit and the
    manifest-last generation layout run on the background commit thread.
    A kill -9 at any point resumes from the newest COMPLETE generation —
    the same pairing guarantee the old single-file ``os.replace`` gave,
    plus durability (fsync) and an off-step-path disk. ``sync=True`` is
    the measurement twin: identical bytes, commit inline on the caller's
    thread (what verify.sh's throttle legs compare).
    Still numpy+stdlib on the persistence side — orbax stays optional.
    """

    def __init__(self, directory: str, *, sync: bool = False):
        self.directory = directory
        self._ck = checkpoint.AsyncCheckpointer(
            directory, keep=2, process_index=0, process_count=1, sync=sync,
        )

    def save(self, step: int, state, payload: dict) -> None:
        self._ck.save(step, state, payload)

    def load(self, template):
        """(step, state, payload) or (None, template, None)."""
        return self._ck.restore(template)

    def latest_step(self):
        return self._ck.latest_step()

    def clear(self) -> None:
        """Drop every generation (the epoch-budget-exhausted path)."""
        self._ck.clear()

    def wait(self) -> None:
        self._ck.wait()

    def close(self) -> None:
        self._ck.close()


def packed_stream(it, packer: TokenPacker, snaps: dict):
    """Columnar batches -> packed host batches; records, for packed batch
    n, the (IteratorState, packer carry, digest) snapshot that resumes the
    stream at batch n+1. The DeviceIterator runs this at most one batch
    ahead, so ``snaps`` stays small (pruned to the last 16)."""
    n = 0
    while True:
        b = packer.pop()
        while b is None:
            cb = next(it, None)
            if cb is None:
                return
            packer.feed_column(cb["tokens"])
            b = packer.pop()
        snaps[n] = {
            "input": it.state().to_json(),
            "packer": packer.state(),
            "digest": hashlib.sha256(
                np.ascontiguousarray(b).tobytes()
            ).hexdigest()[:16],
        }
        for old in [k for k in snaps if k < n - 16]:
            del snaps[old]
        yield {"tokens": b}
        n += 1


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mesh", default=os.environ.get("LM_MESH", "dp_sp"),
                    choices=("dp", "dp_sp", "dp_pp", "dp_fsdp",
                             "dp_fsdp_pp"))
    ap.add_argument("--steps", type=int, default=64,
                    help="total train steps (absolute, incl. resumed)")
    ap.add_argument("--save-every", type=int, default=8)
    ap.add_argument("--ckpt-mode", default=os.environ.get(
                        "TFR_CKPT_MODE", "async"),
                    choices=("async", "sync"),
                    help="async (default): background commit, the train "
                         "loop only pays for the device snapshot; sync: "
                         "the measurement twin, commit inline on the "
                         "step path (what made ckpt_bound verdicts)")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--digest-out", default=None,
                    help="write one {'step','digest','loss'} JSON line per "
                         "step (the kill/resume byte-identity evidence)")
    ap.add_argument("--data-dir", default="/tmp/tpu_tfrecord_lm/data")
    ap.add_argument("--ckpt-dir", default="/tmp/tpu_tfrecord_lm/ckpt")
    ap.add_argument("--spool", default=None, metavar="SPOOL_DIR",
                    help="spool this trainer's telemetry (role=trainer) "
                         "into SPOOL_DIR for TelemetryAggregator / "
                         "`tfrecord_doctor train`/`fleet`")
    ap.add_argument("--spool-interval", type=float, default=None,
                    metavar="SECONDS", help="spool snapshot cadence")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="enable the flight recorder and save the Chrome "
                         "trace (train.step spans + phase markers) here")
    ap.add_argument("--diagnostics", action="store_true",
                    help="in-jit model diagnostics: MoE expert counts/"
                         "drops/entropy and the measured pipeline bubble, "
                         "folded into gauges+histograms each step")
    ap.add_argument("--moe", type=int, default=0, metavar="EXPERTS",
                    help="swap every block's FFN for a top-2 MoE with "
                         "this many experts (0 = dense; dp/dp_sp only)")
    ap.add_argument("--virtual", type=int, default=1, choices=(1, 2),
                    metavar="V", help="interleaved virtual stages for "
                    "--mesh dp_pp: V round-robin layer chunks per device "
                    "(models.pipeline), bubble -> (S-1)/(V*M+S-1)")
    args = ap.parse_args()

    if args.virtual > 1 and args.mesh != "dp_pp":
        ap.error("--virtual > 1 needs --mesh dp_pp")
    generate(args.data_dir)
    mesh, axes, n_layers = pick_mesh(args.mesh, args.virtual)
    if args.moe and "pipe_axis" in axes:
        ap.error("--moe is not supported with --mesh dp_pp")
    cfg = lm.LMConfig(
        vocab_size=VOCAB, d_model=64, n_heads=4, n_layers=n_layers,
        max_len=SEQ_LEN, n_micro=8 if "pipe_axis" in axes else None,
        moe_experts=args.moe,
        n_virtual=args.virtual if "pipe_axis" in axes else 1,
    )
    print(_harness.device_banner())
    print(f"mesh: {_harness.report_mesh(mesh)} mode={args.mesh}")

    params = lm.init_params(jax.random.key(0), cfg)
    tx = optax.adam(3e-3)
    opt_state = tx.init(params)
    os.makedirs(args.ckpt_dir, exist_ok=True)
    ck = LMCheckpoint(args.ckpt_dir, sync=(args.ckpt_mode == "sync"))
    start_step, (params, opt_state), payload = ck.load((params, opt_state))
    placement = {
        k: axes[k] for k in ("pipe_axis", "fsdp_axis") if k in axes
    }
    if placement:
        # at-rest sharding: P(pipe) stage slicing and/or P(fsdp) weight
        # sharding; the restored host tree places under ANY layout — the
        # checkpoint itself is layout-free (tests/test_lm_fsdp.py pins
        # the interchange)
        params = jax.device_put(
            params, lm.param_shardings(mesh, params, **placement)
        )
        if "fsdp_axis" in placement:
            per_dev = _harness.report_fsdp_param_bytes(params)
            print(f"fsdp param bytes/device: {per_dev}")
    if start_step is None:
        start_step = 0
        print("fresh start")
    else:
        print(f"resumed at step {start_step}")

    ds = TFRecordDataset(
        args.data_dir, batch_size=64, schema=make_schema(),
        num_epochs=args.epochs, recordType="SequenceExample",
        shuffle=True, seed=0,
    )
    resume = (
        IteratorState.from_json(payload["input"]) if payload else None
    )
    packer = TokenPacker(BATCH, SEQ_LEN)
    if payload:
        packer.restore(payload["packer"])

    step_jit = jax.jit(
        functools.partial(
            lm.train_step, cfg=cfg, tx=tx, mesh=mesh,
            diagnostics=args.diagnostics, **axes,
        ),
        donate_argnums=(0, 1),
    )
    snaps: dict = {}
    digest_fh = open(args.digest_out, "a") if args.digest_out else None  # graftlint: allow(atomic-write: append-only one-line-per-step digest log; the kill -9 tests tolerate a torn tail line)
    last_diag: dict = {}

    def step_fn(state, gb):
        p, o = state
        if args.diagnostics:
            p, o, loss, diag = step_jit(p, o, gb["tokens"])
            last_diag["diag"] = diag
        else:
            p, o, loss = step_jit(p, o, gb["tokens"])
        return (p, o), loss

    def save(rel_step, _it, state):
        snap = snaps.get(rel_step - 1)  # stream position AFTER that batch
        if snap is None:
            return
        ck.save(
            start_step + rel_step, state,
            {"input": snap["input"], "packer": snap["packer"]},
        )

    def on_step(rel_step, loss):
        step = start_step + rel_step
        snap = snaps.get(rel_step - 1, {})
        line = {
            "step": step,
            "digest": snap.get("digest"),
            "loss": repr(float(loss)),
        }
        print("lm_step", json.dumps(line), flush=True)
        if digest_fh is not None:
            digest_fh.write(json.dumps(line) + "\n")
            digest_fh.flush()

    def fold_step(rel_step, loss):
        # the loss is already blocked on: fetching the tiny diag dict
        # adds no sync point of its own
        diag = last_diag.pop("diag", None)
        if diag is not None:
            _harness.fold_model_diagnostics(diag)
        if digest_fh is not None:
            on_step(rel_step, loss)

    if args.trace_out:
        from tpu_tfrecord import telemetry

        telemetry.enable()
    spool = _harness.trainer_spool(args.spool, args.spool_interval)
    phases = _harness.StepPhases()
    t0 = time.perf_counter()
    try:
        with ds.batches(resume) as it:
            with DeviceIterator(
                packed_stream(it, packer, snaps), mesh, axis=axes["data_axis"]
            ) as dev_it:
                (params, opt_state), steps, duty = _harness.run_train_loop(
                    dev_it,
                    produce=lambda gb: gb,  # DeviceIterator already placed it
                    step_fn=step_fn,
                    state=(params, opt_state),
                    save=save,
                    save_every=args.save_every,
                    on_step=(
                        fold_step
                        if (args.diagnostics or digest_fh is not None)
                        else None
                    ),
                    max_steps=(
                        args.steps - start_step if args.steps else None
                    ),
                    phases=phases,
                )
        if digest_fh is not None:
            digest_fh.close()
        ck.wait()  # drain the in-flight commit before judging completion
        completed = args.steps and start_step + steps >= args.steps
        if not completed:
            # the epoch budget is exhausted: next run starts a fresh pass
            ck.clear()
        if args.trace_out:
            from tpu_tfrecord import telemetry

            telemetry.RECORDER.save_chrome_trace(args.trace_out)
            print(f"trace saved: {args.trace_out}")
        _harness.finish(
            None, start_step + steps, BATCH, t0, duty, clear_state=False,
            stages=True, phases=phases,
        )
    finally:
        ck.close()  # drain the background commit thread
        # a clean exit lands the spool's `final: true` goodbye snapshot
        _harness.release_trainer_spool(spool)


if __name__ == "__main__":
    main()
