"""The ``train`` loop: wire batch -> split_wire -> sparse_train_step with
donated state, closed loop, and the comparison that decides ``correct``.

Set-up builds ONE object (the compiled step with its state), drives it
through its first ``verify_steps`` steps with the window's own call and
feed, reads what the comparison needs from its state, and hands the same
object to the window. After the window, with the program's state freed, the
plain reference (``benchmark/models/dlrm.py``) follows those steps from the
seed and the generator's expected rows.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from benchmark.harness import criteo_io, window
from benchmark.harness.feed import Feed


def _probe_fns(cfg: dict, model, batch: int):
    """Two small programs that read the table without copying it: squared
    norms over a padded key list (chunk by chunk), and a sample of rows."""
    import jax
    import jax.numpy as jnp

    n_v, dim = cfg["rows_per_table"], cfg["embed_dim"]

    @jax.jit
    def norms(table, accum, seed_word, key_f, key_v, weight):
        def chunk(args):
            f, v, w = args
            moved = table[f, v] - model.table_rows(seed_word, f, v, cfg["table_init_bound"], dim)
            return jnp.sum(w[:, None] * moved * moved), jnp.sum(w * accum[f, v])

        shape = (-1, batch)
        moved, acc = jax.lax.map(
            chunk, (key_f.reshape(shape), key_v.reshape(shape), weight.reshape(shape))
        )
        return jnp.sum(moved), jnp.sum(acc)

    @jax.jit
    def sample(table, accum, key_f, key_v):
        return table[key_f, key_v], accum[key_f, key_v]

    return norms, sample


def _padded(key_f, key_v, size: int):
    pad = size - key_f.shape[0]
    return (np.pad(key_f, (0, pad)), np.pad(key_v, (0, pad)),
            np.pad(np.ones(key_f.shape[0], np.float32), (0, pad)))


def _draw_sample(seed: int, key_f, key_v, counts, n_v: int, n_f: int, size: int):
    """(positions, among the distinct keys, of keys the first batch holds;
    untouched (f, v) pairs), ``size`` of each: the first batch's most
    repeated keys, then others of its keys drawn from the seed; and pairs
    drawn from the seed that none of the batches holds. ``counts`` is how
    often the first batch holds each distinct key."""
    rng = np.random.default_rng([int(seed), 0x53414D])
    size = min(size, int((counts > 0).sum()))
    order = np.argsort(-counts, kind="stable")[: int((counts > 0).sum())]
    hot, cold = order[: size // 8], order[size // 8:]
    touched = np.sort(np.concatenate(
        [hot, rng.choice(cold, size=size - hot.shape[0], replace=False)]
    ))
    held = key_f.astype(np.int64) * n_v + key_v
    f = rng.integers(0, n_f, size=4 * size)
    v = rng.integers(0, n_v, size=4 * size)
    free = ~np.isin(f * n_v + v, held)
    return touched, f[free][:size].astype(np.int32), v[free][:size].astype(np.int32)


def plan(cfg: dict, mix: dict, model, seed: int, expected: np.ndarray) -> dict:
    """What the first ``verify_steps`` batches touch, from the generator's rows
    alone: the distinct keys of all of them and of the first, the sample of
    the first batch's keys, rows that no batch holds and their seed values."""
    import jax

    steps, batch = mix["verify_steps"], mix["batch"]
    n_v, n_f = cfg["rows_per_table"], cfg["num_categorical"]
    _, _, cat = model.split_expected(expected[: steps * batch], cfg)
    cat = cat.reshape(steps, batch, n_f)
    key_f, key_v, cid = model.compact_keys(cat, n_v)
    first_f, first_v, _ = model.compact_keys(cat[:1], n_v)
    counts = np.bincount(cid[0].reshape(-1), minlength=key_f.shape[0])
    touched, free_f, free_v = _draw_sample(seed, key_f, key_v, counts, n_v, n_f,
                                           mix["sample_rows"])
    init_untouched = np.asarray(jax.jit(
        lambda w, f, v: model.table_rows(w, f, v, cfg["table_init_bound"], cfg["embed_dim"])
    )(model.seed32(seed), free_f, free_v))
    return {"key_f": key_f, "key_v": key_v, "first_f": first_f, "first_v": first_v,
            "touched": touched, "free_f": free_f, "free_v": free_v,
            "init_untouched": init_untouched, "pad_to": cat.size}


def _mlps_of(params) -> dict:
    import jax

    return jax.tree.map(np.asarray, {k: v for k, v in params.items() if k != "embeddings"})


def numbers(cfg: dict, mlps0: dict, got: dict, want: dict) -> tuple:
    """(the numbers compared, the leaf each worst-leaf number came from),
    between two views (the program's or the control's, and the
    reference's): losses, the first gradient's norm and the three steps'
    change by the worst leaf; after the FIRST step, from weights both sides
    made alike from the seed, sampled rows and accumulators (root mean
    square of the gap over that of the reference's change: steadier from
    seed to seed than a widest gap); and rows no batch holds, which must be
    the seed's bit for bit.

    Rows are compared after one step only. The backward pass is bfloat16
    arithmetic, so two sound programs leave the first step with rows about
    1e-3 of their change apart; from there a row's next gradient differs by
    whatever ReLUs and bfloat16 roundings that flips, tens of percent on
    single rows (measured, PERF.md). Steps 2 and 3 are held by the losses
    and by the norm of the whole change."""
    lr, dim = cfg["optimizer"]["mlp_lr"], cfg["embed_dim"]
    out = {}
    for s, (g, w) in enumerate(zip(got["losses"], want["losses"])):
        out[f"loss_gap_step{s + 1}"] = abs(g - w) / abs(w)
    # SGD keeps no state: its first gradient is the first step's change / lr
    g = window.leaf_norms(got["mlps_after_1"], mlps0, 1.0 / lr)
    w = window.leaf_norms(want["mlps_after_1"], mlps0, 1.0 / lr)
    # row-wise AdaGrad's state after one step is mean(G^2) a row
    g["embeddings"] = float(np.sqrt(dim * got["accum_sum_after_1"]))
    w["embeddings"] = float(np.sqrt(dim * want["accum_sum_after_1"]))
    out["grad1_norm_gap"], worst_grad = window.norm_gap_worst_leaf(g, w)
    g = window.leaf_norms(got["mlps"], mlps0)
    w = window.leaf_norms(want["mlps"], mlps0)
    g["embeddings"] = float(np.sqrt(got["moved_sq"]))
    w["embeddings"] = float(np.sqrt(want["moved_sq"]))
    out["change_norm_gap"], worst_change = window.norm_gap_worst_leaf(g, w)
    out["rows1_rms_gap"] = window.rms_gap(got["rows"], want["rows"], want["rows0"])
    out["accum1_rms_gap"] = window.rms_gap(got["accum"], want["accum"])
    out["untouched_gap"] = window.gap(got["untouched"], want["untouched"])
    return out, {"grad1_norm_gap": worst_grad, "change_norm_gap": worst_change}


def reference_view(ref: dict, touched, init_untouched) -> dict:
    """What :func:`numbers` reads, from a ``reference_train`` result: the
    reference's own, or the control's in the program's place. A row no
    batch holds never enters the compact table: its value is the seed's,
    kept in the table's type."""
    import jax.numpy as jnp

    moved = ref["table"] - ref["table0"]
    return {
        "losses": ref["losses"], "mlps_after_1": ref["mlps_after_1"], "mlps": ref["mlps"],
        "accum_sum_after_1": float(jnp.sum(ref["accum_after_1"])),
        "moved_sq": float(jnp.sum(moved * moved)),
        "rows": np.asarray(ref["table_after_1"][touched]),
        "rows0": np.asarray(ref["table0"][touched]),
        "accum": np.asarray(ref["accum_after_1"][touched]),
        "untouched": np.asarray(
            jnp.asarray(init_untouched).astype(ref["table_dtype"]).astype(jnp.float32)
        ),
    }


def run(env) -> dict:
    import jax

    from tpu_tfrecord.models import sparse_opt_init, sparse_train_step

    cfg, mix, model, seed = env.cfg, env.mix, env.model, env.seed
    batch, n_v, verify_steps = mix["batch"], cfg["rows_per_table"], mix["verify_steps"]
    pcfg, tx = model.program(cfg)

    t0 = time.perf_counter()
    params = model.program_params(seed, cfg)
    opt_state = sparse_opt_init(params, pcfg, tx)
    jax.block_until_ready((params, opt_state))
    env.info("state", seconds=time.perf_counter() - t0,
             table_bytes=int(params["embeddings"].nbytes))
    mlps0 = model.init_mlps(seed, cfg)

    split_j = jax.jit(functools.partial(criteo_io.split_wire, vocab=n_v))
    step_j = jax.jit(
        functools.partial(
            sparse_train_step, cfg=pcfg, tx=tx,
            embed_lr=cfg["optimizer"]["embed_lr"], embed_eps=cfg["optimizer"]["embed_eps"],
        ),
        donate_argnums=(0, 1),
    )

    split_c, step_c, ingest = window.compile_and_check_ingest(
        env, split_j, "sparse_train_step", step_j, params, opt_state, rows_per_table=n_v)

    todo = plan(cfg, mix, model, seed, env.expected)
    key_f, key_v, touched = todo["key_f"], todo["key_v"], todo["touched"]
    norms_j, sample_j = _probe_fns(cfg, model, batch)
    word = model.seed32(seed)

    state = {"params": params, "opt": opt_state}
    del params, opt_state
    losses = []

    def one_step(gb):
        with env.spans.span("dispatch_split"):
            b = split_c(gb)
        with env.spans.span("dispatch_step"):
            state["params"], state["opt"], loss = step_c(state["params"], state["opt"], b)
        losses.append(loss)
        return loss

    feed = Feed(env.data_dir, mix, env.mesh, num_epochs=None)
    try:
        loop = window.StepLoop(feed, one_step, jax.block_until_ready, env.spans,
                               mix["in_flight"])
        view = {}
        for s in range(verify_steps):
            loop.step()
            loop.drain()
            if s == 0:
                view["mlps_after_1"] = _mlps_of(state["params"])
                _, acc = norms_j(state["params"]["embeddings"], state["opt"].accum, word,
                                 *_padded(todo["first_f"], todo["first_v"], todo["pad_to"]))
                view["accum_sum_after_1"] = float(acc)
                rows, acc = sample_j(state["params"]["embeddings"], state["opt"].accum,
                                     key_f[touched], key_v[touched])
                view["rows"], view["accum"] = np.asarray(rows), np.asarray(acc)
        view["mlps"] = _mlps_of(state["params"])
        table, accum = state["params"]["embeddings"], state["opt"].accum
        moved, _ = norms_j(table, accum, word, *_padded(key_f, key_v, todo["pad_to"]))
        view["moved_sq"] = float(moved)
        view["untouched"] = np.asarray(
            sample_j(table, accum, todo["free_f"], todo["free_v"])[0])
        del table, accum
        view["losses"] = [float(x) for x in losses]
        del losses[:]

        measured = env.measure(loop)
    finally:
        feed.close()
    window_losses = np.asarray([float(x) for x in losses])
    measured.update(
        rows=measured["steps"] * batch, batch=batch,
        attempted=measured["steps"], loss_first=float(view["losses"][0]),
        loss_last=float(window_losses[-1]) if window_losses.size else float("nan"),
    )
    failed = int((~np.isfinite(window_losses)).sum())
    state.clear()  # the program's state goes before the reference comes

    t0 = time.perf_counter()
    ref = model.reference_train(cfg, seed, env.expected, verify_steps, batch)
    compared, notes = numbers(
        cfg, mlps0, view, reference_view(ref, touched, todo["init_untouched"]))
    compared["rows_altered"] = float(ingest["rows_altered"])
    compared["rows_missing"] = float(abs(ingest["rows_read"] - ingest["rows_written"]))
    compared["steps_not_finite"] = float(failed)
    env.info("reference", seconds=time.perf_counter() - t0, distinct_keys=int(key_f.shape[0]),
             of=todo["pad_to"])
    measured["failed"] = failed
    measured["correct"] = window.judge(env, compared, mix["limits"], notes)
    return measured
