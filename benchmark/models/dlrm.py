"""DLRM for the benchmark: the weights from ``--seed``, the program's step
built for a configuration file, the plain reference, and what a step needs.

Nothing here except :func:`program` imports ``tpu_tfrecord.models``. The
reference takes the seed and the generator's expected rows, never anything
the program has made.

The model, as the program computes it (``assumed.bottom_activation`` in the
configuration files names the departures from dlrm_s_pytorch.py):

    x      = bottom_mlp(log1p(dense))                  [B, D]   ReLU between layers
    rows   = table[f, cat[:, f]]                       [B, F, D]
    pairs  = <s_i, s_j> for i > j over s = [x; rows]   [B, 351] np.tril_indices order
    logit  = top_mlp([x; pairs])[:, 0]
    loss   = mean BCE with logits
    tables : row-wise AdaGrad, duplicates summed first; MLPs: SGD

with activations in the configuration's ``precision.activations`` and
everything stored in float32.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------


def _mix(x):
    """murmur3's 32-bit finalizer on a uint32 array (wraps by construction)."""
    import jax.numpy as jnp

    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> jnp.uint32(16))


def seed32(seed: int) -> np.uint32:
    seed = int(seed)
    return np.uint32((seed ^ (seed >> 32)) & 0xFFFFFFFF)


def table_rows(seed_word, f, v, bound: float, dim: int):
    """Initial float32 rows of table ``f`` at rows ``v`` (same-shaped integer
    arrays) -> ``[..., dim]``: uniform(+-bound), each element a pure
    function of (seed, f, v, d), so the whole table and any handful of its
    rows come out bit for bit alike. ``seed_word`` is :func:`seed32` of the
    seed as a uint32 scalar, an argument of the caller's jit and never a
    constant of it: a program compiled for one seed serves every seed."""
    import jax.numpy as jnp

    f = jnp.asarray(f).astype(jnp.uint32)
    v = jnp.asarray(v).astype(jnp.uint32)
    h = _mix(jnp.asarray(seed_word, jnp.uint32) ^ _mix(f * jnp.uint32(0x9E3779B1) + jnp.uint32(0x7F4A7C15)))
    h = _mix(h + v * jnp.uint32(0x85EBCA77))
    d = jnp.arange(dim, dtype=jnp.uint32) * jnp.uint32(0xC2B2AE3D) + jnp.uint32(1)
    h = _mix(h[..., None] ^ d)
    centred = (h >> jnp.uint32(8)).astype(jnp.int32) - jnp.int32(1 << 23)
    scale = np.float32(bound / (1 << 23))
    return centred.astype(jnp.float32) * scale


def make_table(seed: int, cfg: dict):
    """The whole ``[F, V, D]`` float32 table, in one jitted call on the device."""
    import jax
    import jax.numpy as jnp

    n_f, n_v, dim = cfg["num_categorical"], cfg["rows_per_table"], cfg["embed_dim"]

    @jax.jit
    def build(seed_word):
        f = jax.lax.broadcasted_iota(jnp.uint32, (n_f, n_v), 0)
        v = jax.lax.broadcasted_iota(jnp.uint32, (n_f, n_v), 1)
        return table_rows(seed_word, f, v, cfg["table_init_bound"], dim)

    return build(seed32(seed))


def interact_dim(cfg: dict) -> int:
    n = cfg["num_categorical"] + 1
    return cfg["bottom_mlp"][-1] + n * (n - 1) // 2


def mlp_shapes(cfg: dict) -> Dict[str, List[tuple]]:
    out = {}
    for name, fan, widths in (
        ("bottom", cfg["num_dense"], cfg["bottom_mlp"]),
        ("top", interact_dim(cfg), cfg["top_mlp"]),
    ):
        shapes = []
        for w in widths:
            shapes.append((fan, w))
            fan = w
        out[name] = shapes
    return out


def init_mlps(seed: int, cfg: dict) -> Dict[str, list]:
    """Both MLPs as numpy float32, dlrm_s_pytorch.py's rule: weights
    normal(0, sqrt(2 / (in + out))), biases normal(0, sqrt(1 / out))."""
    rng = np.random.default_rng([int(seed), 0x4D4C50])
    out = {}
    for name, shapes in mlp_shapes(cfg).items():
        out[name] = [
            {
                "w": (rng.standard_normal((m, n)) * np.sqrt(2.0 / (m + n))).astype(np.float32),
                "b": (rng.standard_normal((n,)) * np.sqrt(1.0 / n)).astype(np.float32),
            }
            for m, n in shapes
        ]
    return out


# ---------------------------------------------------------------------------
# The program, built for a configuration file
# ---------------------------------------------------------------------------


def program(cfg: dict):
    """(DLRMConfig, optax transform) of the system under test for ``cfg``."""
    import jax.numpy as jnp
    import optax

    from tpu_tfrecord.models import DLRMConfig

    if cfg["interaction"] != "dot":
        raise ValueError("only the dot interaction is configured")
    pcfg = DLRMConfig(
        num_dense=cfg["num_dense"], num_categorical=cfg["num_categorical"],
        vocab_size=cfg["rows_per_table"], embed_dim=cfg["embed_dim"],
        bottom_mlp=tuple(cfg["bottom_mlp"]), top_mlp=tuple(cfg["top_mlp"]),
        interaction="dot", dtype=jnp.dtype(cfg["precision"]["activations"]),
    )
    return pcfg, optax.sgd(cfg["optimizer"]["mlp_lr"])


def program_params(seed: int, cfg: dict):
    """The program's parameter tree on the device, from the seed."""
    import jax

    params = jax.device_put(init_mlps(seed, cfg))
    params["embeddings"] = make_table(seed, cfg)
    return params


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------


def split_expected(expected: np.ndarray, cfg: dict):
    """Expected rows ``[B, 40]`` int32 -> (label f32, dense ints f32, cat)."""
    keep = 1 + cfg["num_dense"]
    return (
        expected[:, 0].astype(np.float32),
        expected[:, 1:keep].astype(np.float32),
        (expected[:, keep:] % cfg["rows_per_table"]).astype(np.int32),
    )


def _mlp(layers, x, dt):
    import jax

    for i, layer in enumerate(layers):
        x = x @ layer["w"].astype(dt) + layer["b"].astype(dt)
        if i + 1 < len(layers):
            x = jax.nn.relu(x)
    return x


def forward_reference(mlps, rows, dense_ints, act_dtype):
    """Logits ``[B]`` float32 from gathered rows ``[B, F, D]``."""
    import jax.numpy as jnp

    dt = jnp.dtype(act_dtype)
    x = _mlp(mlps["bottom"], jnp.log1p(dense_ints).astype(dt), dt)
    stack = jnp.concatenate([x[:, None, :], rows.astype(dt)], axis=1)
    gram = jnp.einsum("bfd,bgd->bfg", stack, stack)
    i, j = np.tril_indices(stack.shape[1], k=-1)
    z = jnp.concatenate([x, gram[:, i, j].astype(dt)], axis=-1)
    return _mlp(mlps["top"], z, dt)[:, 0].astype(jnp.float32)


def _bce(logits, labels):
    import jax.numpy as jnp

    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def compact_keys(cats: np.ndarray, rows_per_table: int):
    """``[S, B, F]`` folded indices -> (key_f [U], key_v [U], cid [S, B, F]):
    the distinct (table, row) pairs and each index's place among them."""
    n_f = cats.shape[-1]
    flat = np.arange(n_f, dtype=np.int64)[None, None, :] * rows_per_table + cats
    uniq, inverse = np.unique(flat, return_inverse=True)
    return (
        (uniq // rows_per_table).astype(np.int32),
        (uniq % rows_per_table).astype(np.int32),
        inverse.reshape(cats.shape).astype(np.int32),
    )


def reference_train(cfg: dict, seed: int, expected: np.ndarray, steps: int,
                    batch: int, table_dtype="float32") -> dict:
    """``steps`` training steps on the first ``steps * batch`` expected rows.

    The table is the rows those batches touch and no others: a row outside
    them has a zero gradient and stays as made. On it: the dense gradient
    of the loss by ``jax.grad`` on the whole (compact) table, row-wise
    AdaGrad applied densely to the rows the batch holds, SGD on the MLPs.
    ``table_dtype`` below float32 is the control: the table is kept, and
    its row gradients are taken, in that type.

    Returns host values for the small things (``losses``, ``mlps_after_1``,
    ``mlps``, ``key_f``/``key_v``: the distinct keys in sorted order) and
    device arrays for the rows (``table0``, ``table_after_1``, ``table``,
    ``accum_after_1``, ``accum``), whose first ``len(key_f)`` entries belong to those keys and
    whose rest is padding no index reaches, all zero in the accumulators
    and unchanged in the table.
    """
    import jax
    import jax.numpy as jnp

    opt = cfg["optimizer"]
    act = cfg["precision"]["activations"]
    tdt = jnp.dtype(table_dtype)
    label, dense, cat = (
        a.reshape((steps, batch) + a.shape[1:])
        for a in split_expected(expected[: steps * batch], cfg)
    )
    key_f, key_v, cid = compact_keys(cat, cfg["rows_per_table"])
    n_keys = key_f.shape[0]
    # as many rows as the batches could hold at most, so that the compiled
    # step has one shape whatever the seed; the padding is never indexed
    pad = cat.size - n_keys
    table = jax.jit(
        lambda w, f, v: table_rows(w, f, v, cfg["table_init_bound"], cfg["embed_dim"])
    )(seed32(seed), np.pad(key_f, (0, pad)), np.pad(key_v, (0, pad)))
    table0 = table
    table = table.astype(tdt)
    mlps = jax.device_put(init_mlps(seed, cfg))
    accum = jnp.zeros((cat.size,), jnp.float32)

    @jax.jit
    def step(mlps, table, accum, label, dense, cid):
        def loss_of(mlps, table):
            return _bce(forward_reference(mlps, table[cid], dense, act), label)

        loss, (g_mlps, g_table) = jax.value_and_grad(loss_of, argnums=(0, 1))(mlps, table)
        g_table = g_table.astype(jnp.float32)
        held = jnp.zeros(accum.shape, bool).at[cid.reshape(-1)].set(True)
        accum = accum + jnp.where(held, jnp.mean(g_table * g_table, axis=-1), 0.0)
        scale = opt["embed_lr"] * jax.lax.rsqrt(accum + opt["embed_eps"])
        table = (
            table.astype(jnp.float32)
            - jnp.where(held[:, None], scale[:, None] * g_table, 0.0)
        ).astype(table.dtype)
        mlps = jax.tree.map(lambda p, g: p - opt["mlp_lr"] * g, mlps, g_mlps)
        return mlps, table, accum, loss

    out = {"losses": [], "key_f": key_f, "key_v": key_v, "table0": table0,
           "table_dtype": str(tdt)}
    for s in range(steps):
        mlps, table, accum, loss = step(mlps, table, accum, label[s], dense[s], cid[s])
        out["losses"].append(float(loss))
        if s == 0:
            out["mlps_after_1"] = jax.tree.map(np.asarray, mlps)
            out["accum_after_1"] = accum
            out["table_after_1"] = table.astype(jnp.float32)
    out["mlps"] = jax.tree.map(np.asarray, mlps)
    out["table"] = table.astype(jnp.float32)
    out["accum"] = accum
    return out


def reference_score(cfg: dict, seed: int, expected: np.ndarray, batch: int,
                    table_dtype="float32") -> np.ndarray:
    """Logits ``[n]`` float32 of the model as made from the seed on expected
    rows ``[n, 40]``, ``batch`` rows at a time. ``table_dtype="int8"`` is
    the control, one step below the activations' bfloat16: rows are rounded
    to 255 levels across the table's range. The rounding is arithmetic, not
    a pair of type conversions: XLA may drop a conversion that only loses
    precision (it did, on the chip: a float8 round trip scored bit for bit
    like none). Another ``table_dtype`` is a plain conversion."""
    import jax
    import jax.numpy as jnp

    act = cfg["precision"]["activations"]
    dim = cfg["embed_dim"]
    mlps = jax.device_put(init_mlps(seed, cfg))

    @jax.jit
    def score(seed_word, mlps, dense, cat):
        f = jnp.broadcast_to(jnp.arange(cat.shape[1], dtype=jnp.uint32)[None, :], cat.shape)
        rows = table_rows(seed_word, f, cat, cfg["table_init_bound"], dim)
        if table_dtype == "int8":
            level = np.float32(cfg["table_init_bound"] / 127.0)
            rows = jnp.round(rows / level) * level
        else:
            rows = rows.astype(jnp.dtype(table_dtype))
        return forward_reference(mlps, rows, dense, act)

    _, dense, cat = split_expected(expected, cfg)
    return np.concatenate([
        np.asarray(score(seed32(seed), mlps, dense[i: i + batch], cat[i: i + batch]))
        for i in range(0, expected.shape[0], batch)
    ])


# ---------------------------------------------------------------------------
# What a step needs, from its shapes
# ---------------------------------------------------------------------------


def needs(cfg: dict, batch: int, loop: str) -> dict:
    """FLOPs and bytes the algorithm cannot do without for one batch.

    Forward: every MLP product (2 x in x out a row), the 351 pair dots
    (2 x D each), one float32 read of each of the B x F gathered rows, one
    read of the wire batch and the MLP weights, one write of the logits.
    ``train`` does each product three times (forward, input gradient,
    weight gradient), reads and writes each gathered row and its
    accumulator once more for the update, and writes the MLP weights.
    Rows a batch holds twice are counted twice: the share of distinct keys
    is data, which this function does not see.
    """
    if loop not in ("train", "score"):
        raise ValueError(f"unknown loop {loop!r}")
    n_f, dim = cfg["num_categorical"], cfg["embed_dim"]
    mlp_params = sum(m * n + n for shapes in mlp_shapes(cfg).values() for m, n in shapes)
    mlp_macs = sum(m * n for shapes in mlp_shapes(cfg).values() for m, n in shapes)
    pairs = (n_f + 1) * n_f // 2
    flops = 2 * batch * (mlp_macs + pairs * dim)
    wire_lanes = 1 + cfg["num_dense"] + -(-n_f * 20 // 32)
    nbytes = batch * n_f * dim * 4 + batch * wire_lanes * 4 + mlp_params * 4 + batch * 4
    if loop == "train":
        flops *= 3
        nbytes += 2 * batch * n_f * dim * 4 + 2 * batch * n_f * 4 + 2 * mlp_params * 4
    return {"flops": float(flops), "bytes": float(nbytes)}
