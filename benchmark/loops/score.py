"""The ``score`` loop: wire batch -> split_wire -> models.forward, logits
left on the device and fetched ``in_flight`` steps late; closed loop.

Every batch's logits are kept on the host (64 KB a batch). Once the window
has closed and the program's parameters are freed, the plain reference
scores a sample of the window's steps, drawn from the seed with the first
and the last in it, from the seed's weights and the generator's expected
rows; and every step's logits must equal, bit for bit, those of the first
step that scored the same rows.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from benchmark.harness import criteo_io, window
from benchmark.harness.feed import Feed


def sampled_steps(seed: int, steps: int, count: int) -> list:
    """``count`` of the window's step numbers from the seed, first and last
    among them."""
    if steps <= count:
        return list(range(steps))
    rng = np.random.default_rng([int(seed), 0x53434F])
    inner = rng.choice(np.arange(1, steps - 1), size=count - 2, replace=False)
    return sorted({0, steps - 1, *map(int, inner)})


def logit_gaps(got: np.ndarray, want: np.ndarray) -> dict:
    """All sampled batches together: the widest gap over max|want|, and the
    root mean square of the gap over that of ``want``, which one re-rounded
    logit does not move and a lower precision everywhere does."""
    return {"logit_gap": window.gap(got, want), "logit_rms_gap": window.rms_gap(got, want)}


def run(env) -> dict:
    import jax

    from tpu_tfrecord.models import forward

    cfg, mix, model, seed = env.cfg, env.mix, env.model, env.seed
    batch, n_v = mix["batch"], cfg["rows_per_table"]
    pcfg, _ = model.program(cfg)

    t0 = time.perf_counter()
    params = model.program_params(seed, cfg)
    jax.block_until_ready(params)
    env.info("state", seconds=time.perf_counter() - t0,
             table_bytes=int(params["embeddings"].nbytes))

    split_j = jax.jit(functools.partial(criteo_io.split_wire, vocab=n_v))
    fwd_j = jax.jit(functools.partial(forward, cfg=pcfg))

    split_c, fwd_c, ingest = window.compile_and_check_ingest(
        env, split_j, "forward", fwd_j, params, rows_per_table=n_v)

    fetched = []

    def one_step(gb):
        with env.spans.span("dispatch_split"):
            b = split_c(gb)
        with env.spans.span("dispatch_step"):
            return fwd_c(params, b)

    def observe(logits):
        fetched.append(np.asarray(logits))

    feed = Feed(env.data_dir, mix, env.mesh, num_epochs=None)
    try:
        loop = window.StepLoop(feed, one_step, observe, env.spans, mix["in_flight"])
        for _ in range(mix["warmup_steps"]):
            loop.step()
        loop.drain()
        warm = len(fetched)
        measured = env.measure(loop)
    finally:
        feed.close()
    del params

    steps = measured["steps"]
    per_epoch = env.expected.shape[0] // batch
    scores = fetched[warm: warm + steps]
    finite = np.asarray([np.isfinite(x).all() for x in scores])
    failed = int((~finite).sum())
    # step k of the window scores rows (warm + k) % per_epoch of the epoch
    place = [(warm + k) % per_epoch for k in range(steps)]
    first_at = {}
    repeat = 0.0
    for k, p in enumerate(place):
        if p in first_at:
            repeat = max(repeat, float(np.abs(scores[k] - scores[first_at[p]]).max()))
        else:
            first_at[p] = k

    t0 = time.perf_counter()
    chosen = sampled_steps(seed, steps, mix["verify_batches"])
    rows = np.concatenate(
        [env.expected[place[k] * batch:(place[k] + 1) * batch] for k in chosen]
    )
    want = model.reference_score(cfg, seed, rows, batch)
    got = np.concatenate([scores[k] for k in chosen])
    compared = {
        **logit_gaps(got, want),
        "repeat_gap": repeat,
        "rows_altered": float(ingest["rows_altered"]),
        "rows_missing": float(abs(ingest["rows_read"] - ingest["rows_written"])),
        "steps_not_finite": float(failed),
    }
    env.info("reference", seconds=time.perf_counter() - t0, steps_compared=chosen,
             logit_scale=float(np.abs(want).max()))
    measured.update(rows=steps * batch, batch=batch, attempted=steps, failed=failed)
    measured["correct"] = window.judge(env, compared, mix["limits"])
    return measured
