#!/usr/bin/env python
"""End-to-end example: SERVE the causal LM trained by examples/train_lm.py
through the microbatch-streamed pipeline (ISSUE 15 / ROADMAP #2).

The inference twin of the trainer: load the trainer's atomic checkpoint
(the ONE [n_layers, ...]-stacked block pytree every mesh shares), restack
it into S×V interleaved pipeline chunks, and answer requests one
[mb, L+1] microbatch at a time through `models.lm.LMStream`:

  - the per-call feed is exactly ONE microbatch slice riding the pipeline
    feed ring — no request stream is ever materialized (the compiled
    step's argument bytes are the pin, tests/test_pipeline_stream.py)
  - streamed logits are BITWISE equal to the batch path (`pipeline_apply`
    on the same slices) — checked here on every run, so the serving
    surface cannot drift from the trained graph
  - requests/s and per-request latency are measured and reported, and the
    `serve.requests` counter / `serve.latency` histogram feed the flight
    recorder like every other stage

With ``--serve`` the example becomes a long-running serving REPLICA over
the same checkpoint: the continuous-batching tier (tpu_tfrecord.serving)
multiplexes concurrent socket clients onto the one compiled per-tick
step, with admission control, per-request deadlines, and graceful drain —
SIGTERM/SIGINT stops admitting, finishes every in-flight request, lands
the telemetry spool's ``final: true`` snapshot, and exits 0. Read the
replica with ``tools/tfrecord_doctor.py serve SPOOL_DIR``.

The checkpoint read routes through the manifest-last restore path
(``load_checkpoint`` below) in BOTH modes: a generation the trainer is
still committing in the background has no manifest yet and is invisible,
so serving can never half-read it.

Run on any JAX backend; for a local simulation (after train_lm):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/train_lm.py --mesh dp_pp --steps 8
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/serve_lm.py --pipe 2 --virtual 2
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/serve_lm.py --serve --spool-dir /tmp/serve_spool
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from tpu_tfrecord import compile_cache

compile_cache.enable()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from train_lm import BATCH, SEQ_LEN, VOCAB, LMCheckpoint  # noqa: E402  (the trainer owns the model constants)

from tpu_tfrecord.metrics import METRICS  # noqa: E402
from tpu_tfrecord.models import lm  # noqa: E402
from tpu_tfrecord.tpu import create_mesh  # noqa: E402

# the dp_pp trainer's depth (train_lm.pick_mesh): the checkpoint this
# example loads carries 4 stacked blocks
N_LAYERS = 4


def load_checkpoint(ckpt_dir: str, template):
    """The ONE serving-side checkpoint read: route through
    ``LMCheckpoint.load`` — the manifest-last ``AsyncCheckpointer.restore``
    — never the ``gen-*/`` directory layout directly. A generation the
    trainer's background commit thread is still writing (or one a crash
    left half-written) has no ``MANIFEST.json`` yet, so it is invisible
    here and the newest COMPLETE generation is served instead; the
    serving tier can never half-read a checkpoint. Pinned with the
    checkpoint chaos park seam in tests/test_serving.py.

    Returns ``(step, state)``; ``(None, template)`` when no complete
    generation exists."""
    ck = LMCheckpoint(ckpt_dir)
    try:
        step, state, _payload = ck.load(template)
    finally:
        ck.close()
    return step, state


def serve(stream: "lm.LMStream", requests) -> dict:
    """Push every request through the stream, collecting outputs FIFO and
    per-request latency (submit -> pop). Returns outputs + timings."""
    outs, lat, submit_t = [], [], []
    t0 = time.perf_counter()

    def collect(ready):
        now = time.perf_counter()
        for o in ready:
            lat.append(now - submit_t[len(outs)])
            outs.append(o)
            METRICS.count("serve.requests")
            METRICS.observe("serve.latency", lat[-1])

    for r in requests:
        submit_t.append(time.perf_counter())
        collect(stream.submit(r))
    collect(stream.flush())
    wall = time.perf_counter() - t0
    return {"outs": outs, "latencies": lat, "wall_s": wall}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ckpt-dir", default="/tmp/tpu_tfrecord_lm/ckpt",
                    help="train_lm's checkpoint dir (gen-*/ generations)")
    ap.add_argument("--pipe", type=int, default=2, metavar="S",
                    help="pipeline stages (devices)")
    ap.add_argument("--virtual", type=int, default=2, metavar="V",
                    help="interleaved virtual stages per device "
                         "(n_layers must divide by S*V)")
    ap.add_argument("--requests", type=int, default=32, metavar="N",
                    help="streamed microbatches to serve (timed pass)")
    ap.add_argument("--mb", type=int, default=8,
                    help="sequences per request microbatch")
    ap.add_argument("--serve", action="store_true",
                    help="run as a long-lived serving replica "
                         "(continuous batching over sockets; graceful "
                         "SIGTERM/SIGINT drain) instead of the one-shot "
                         "timed pass")
    ap.add_argument("--host", default="127.0.0.1",
                    help="--serve: bind host")
    ap.add_argument("--port", type=int, default=0,
                    help="--serve: bind port (0 = ephemeral, printed on "
                         "the ready line)")
    ap.add_argument("--max-queue", type=int, default=16,
                    help="--serve: admission queue bound (beyond it "
                         "requests are shed with a Retry-After hint)")
    ap.add_argument("--default-deadline-s", type=float, default=None,
                    help="--serve: deadline applied to requests that "
                         "carry none")
    ap.add_argument("--slo-p99-ms", type=float, default=250.0,
                    help="--serve: p99 target the status verdict is "
                         "judged against")
    ap.add_argument("--spool-dir", default=None,
                    help="--serve: telemetry spool dir (read with "
                         "tfrecord_doctor serve)")
    ap.add_argument("--role", default="serving",
                    help="--serve: telemetry role stamped on the spool")
    args = ap.parse_args()

    cfg = lm.LMConfig(
        vocab_size=VOCAB, d_model=64, n_heads=4, n_layers=N_LAYERS,
        max_len=SEQ_LEN, n_micro=BATCH // args.mb, n_virtual=args.virtual,
    )
    n_dev = len(jax.devices())
    if args.pipe > n_dev:
        ap.error(f"--pipe {args.pipe} exceeds {n_dev} devices")
    mesh = create_mesh({"pipe": args.pipe}, jax.devices()[: args.pipe])

    # the trainer's checkpoint: params + opt state from the newest
    # COMPLETE generation (manifest-last layout); the serving path wants
    # only the params half of the (params, opt) tuple
    template = lm.init_params(jax.random.key(0), cfg)
    import optax

    tx = optax.adam(3e-3)
    step, (params, _opt) = load_checkpoint(
        args.ckpt_dir, (template, tx.init(template))
    )
    if step is None:
        print(f"no complete checkpoint generation in {args.ckpt_dir} — "
              f"run train_lm first", file=sys.stderr)
        sys.exit(1)
    params = jax.tree.map(np.asarray, params)
    print(f"serving checkpoint step {step} on pipe={args.pipe} "
          f"virtual={args.virtual} mb={args.mb}",
          file=sys.stderr if args.serve else sys.stdout)

    if args.serve:
        # replica mode: the overload-proof tier over this checkpoint.
        # run_server owns the signal story — SIGTERM/SIGINT drains
        # (stop admitting, finish in-flight, final spool snapshot) and
        # returns 0; the ready line (addr + pid JSON) goes to stdout so
        # spawners/scalers can find the ephemeral port
        from tpu_tfrecord import serving

        policy = serving.ServePolicy(
            mb=args.mb, max_queue=args.max_queue,
            default_deadline_s=args.default_deadline_s,
            slo_p99_ms=args.slo_p99_ms,
        )
        engine = serving.ServingEngine(
            params, cfg, mesh, pipe_axis="pipe", policy=policy
        )
        server = serving.ServeServer(
            engine, host=args.host, port=args.port
        ).start()
        sys.exit(serving.run_server(
            server, spool_dir=args.spool_dir, role=args.role,
            ready_fh=sys.stdout,
        ))

    stream = lm.LMStream(params, cfg, mesh, pipe_axis="pipe")
    reqs = [
        lm.make_synthetic_tokens(cfg, args.mb, seed=1000 + i)
        for i in range(args.requests)
    ]

    # warmup pass: compiles the embed/head/step programs and fills the
    # pipeline once; then reset and measure a clean serve
    serve(stream, reqs[: min(len(reqs), args.pipe * args.virtual + 2)])
    stream.reset()
    res = serve(stream, reqs)
    outs, lat = res["outs"], res["latencies"]
    assert len(outs) == len(reqs), (len(outs), len(reqs))

    # the serving surface may not drift from the trained graph: streamed
    # logits must equal the batch path (batch-mode pipeline_apply over
    # the same slices) BITWISE
    ref = stream.batch_reference(reqs)
    identical = all(np.array_equal(a, b) for a, b in zip(outs, ref))
    assert identical, "streamed logits diverged from the batch path"

    line = {
        "requests": len(reqs),
        "requests_per_s": round(len(reqs) / res["wall_s"], 1),
        "sequences_per_s": round(len(reqs) * args.mb / res["wall_s"], 1),
        "latency_ms_p50": round(
            float(np.percentile(lat, 50)) * 1e3, 2
        ),
        "latency_ms_p99": round(
            float(np.percentile(lat, 99)) * 1e3, 2
        ),
        "byte_identical_to_batch": identical,
        "ckpt_step": step,
        "shape": f"mb={args.mb} L={SEQ_LEN} S={args.pipe} V={args.virtual}",
    }
    print("serve_lm OK:", json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
