"""Criteo-style DLRM: the flagship consumer of the ingest pipeline.

Pure-JAX functional model (params are a plain pytree) designed TPU-first:

- all compute is batched matmuls/gathers that tile onto the MXU; bfloat16
  activations with float32 params/accumulation;
- embedding tables are sharded over the 'model' mesh axis (row/vocab dim) —
  gathers on a sharded table make XLA insert the all-to-all/allgather
  collectives (tensor parallelism over ICI);
- an optional sequence tower consumes padded SequenceExample frames
  [B, L, D] with L shardable over a 'seq' axis (sequence/context
  parallelism for the long-context path);
- the train step is a single jit: loss -> grad -> optax update, donated
  params, no data-dependent Python control flow.

Batch layout matches tpu_tfrecord.tpu.ingest.host_batch_from_columnar output
for a Criteo-like schema: 'dense' [B, 13] f32, 'cat' [B, 26] i64 (hashed),
'label' [B] f32, optionally 'frames' [B, L, D] + 'frames_len' [B].
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class DLRMConfig:
    num_dense: int = 13
    num_categorical: int = 26
    vocab_size: int = 1024          # per-feature hash buckets
    embed_dim: int = 32
    bottom_mlp: Tuple[int, ...] = (64, 32)
    top_mlp: Tuple[int, ...] = (64, 1)
    seq_len: int = 0                # 0 = no sequence tower
    seq_dim: int = 0
    dtype: Any = jnp.bfloat16       # activation dtype (MXU-friendly)
    # 'cat': concatenate bottom output + flattened embeddings (simple);
    # 'dot': classic DLRM pairwise dot interaction over [bottom_out; embs]
    #        (Pallas kernel on TPU; requires bottom_mlp[-1] == embed_dim)
    interaction: str = "cat"


def _dense_init(rng, fan_in: int, fan_out: int, gain: float = 2.0):
    """He-style dense init ({'w','b'} dict); shared by the model families
    (gain=2 for relu stacks, 1 for pre-norm residual blocks)."""
    scale = np.sqrt(gain / fan_in)
    w = jax.random.normal(rng, (fan_in, fan_out), jnp.float32) * scale
    return {"w": w, "b": jnp.zeros((fan_out,), jnp.float32)}


def init_params(rng: jax.Array, cfg: DLRMConfig) -> Dict[str, Any]:
    keys = jax.random.split(rng, 8)
    params: Dict[str, Any] = {
        # one stacked table [F, V, D]: a single large gather beats F small
        # ones (fewer kernels, better HBM streaming)
        "embeddings": jax.random.normal(
            keys[0], (cfg.num_categorical, cfg.vocab_size, cfg.embed_dim), jnp.float32
        )
        * 0.05,
    }
    bottom = []
    fan = cfg.num_dense
    for i, width in enumerate(cfg.bottom_mlp):
        bottom.append(_dense_init(jax.random.fold_in(keys[1], i), fan, width))
        fan = width
    params["bottom"] = bottom
    if cfg.interaction == "dot":
        if cfg.bottom_mlp[-1] != cfg.embed_dim:
            raise ValueError(
                "interaction='dot' requires bottom_mlp[-1] == embed_dim "
                f"(got {cfg.bottom_mlp[-1]} vs {cfg.embed_dim})"
            )
        n_feat = cfg.num_categorical + 1  # embeddings + bottom output
        interact_dim = cfg.bottom_mlp[-1] + n_feat * (n_feat - 1) // 2
    elif cfg.interaction == "cat":
        interact_dim = cfg.bottom_mlp[-1] + cfg.num_categorical * cfg.embed_dim
    else:
        raise ValueError(f"unknown interaction {cfg.interaction!r}")
    if cfg.seq_len:
        interact_dim += cfg.embed_dim
        params["seq_proj"] = _dense_init(keys[3], cfg.seq_dim, cfg.embed_dim)
    top = []
    fan = interact_dim
    for i, width in enumerate(cfg.top_mlp):
        top.append(_dense_init(jax.random.fold_in(keys[2], i), fan, width))
        fan = width
    params["top"] = top
    return params


def _mlp(layers, x, dtype):
    for i, layer in enumerate(layers):
        x = x @ layer["w"].astype(dtype) + layer["b"].astype(dtype)
        if i + 1 < len(layers):
            x = jax.nn.relu(x)
    return x


def _gather_rows(table: jax.Array, idx: jax.Array) -> jax.Array:
    """Rows [B, F, D] of the stacked table [F, V, D] that ``idx`` [B, F]
    names, in the table's dtype: the one embedding lookup, shared by
    ``forward`` and ``sparse_train_step`` so a scored row and a trained row
    are the same row. Indexed with (feature, row) PAIRS, never a flattened
    [F*V] view, so a [F, V@model, D] table is gathered along its sharded V
    axis in place (see the scatter note in ``sparse_train_step``)."""
    return table[jnp.arange(table.shape[0])[None, :], idx]


def forward(
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    cfg: DLRMConfig,
    emb: Optional[jax.Array] = None,
) -> jax.Array:
    """Logits [B]. bfloat16 activations, float32 output.

    Only the rows ``batch['cat']`` names are read from the table and rounded
    to the activation dtype. An index past the end reads the feature's last
    row and a negative one counts from the end (NumPy indexing as JAX clamps
    it; the same in ``sparse_train_step``) — readers fold keys into [0, V).

    ``emb`` optionally supplies the gathered embedding rows [B, F, D]
    directly (the sparse-update path differentiates w.r.t. the rows, not
    the table — see ``sparse_train_step``); ``params['embeddings']`` is not
    touched when it is given."""
    dt = cfg.dtype
    with jax.named_scope("tfr.bottom_mlp"):
        dense = batch["dense"].astype(dt)
        bottom_out = _mlp(params["bottom"], dense, dt)      # [B, H]
    with jax.named_scope("tfr.gather"):
        if emb is None:
            emb = _gather_rows(params["embeddings"], batch["cat"])
        emb = emb.astype(dt)                                # [B, F, D]
    with jax.named_scope("tfr.interaction"):
        if cfg.interaction == "dot":
            from tpu_tfrecord.models.interaction import dot_interaction

            stack = jnp.concatenate([bottom_out[:, None, :], emb], axis=1)
            pairs = dot_interaction(stack)                   # [B, P]
            feats = [bottom_out, pairs.astype(dt)]
        else:
            feats = [bottom_out, emb.reshape(emb.shape[0], -1)]
    if cfg.seq_len:
        frames = batch["frames"].astype(dt)                  # [B, L, D_in]
        proj = _mlp([params["seq_proj"]], frames, dt)        # [B, L, D]
        mask = (
            jnp.arange(frames.shape[1])[None, :] < batch["frames_len"][:, None]
        ).astype(dt)
        pooled = (proj * mask[:, :, None]).sum(axis=1) / jnp.maximum(
            mask.sum(axis=1, keepdims=True), 1.0
        )
        feats.append(pooled)
    with jax.named_scope("tfr.top_mlp"):
        x = jnp.concatenate(feats, axis=-1)
        logits = _mlp(params["top"], x, dt)
        return logits[:, 0].astype(jnp.float32)


def loss_fn(params, batch, cfg: DLRMConfig, emb: Optional[jax.Array] = None) -> jax.Array:
    logits = forward(params, batch, cfg, emb=emb)
    labels = batch["label"].astype(jnp.float32)
    # numerically-stable BCE-with-logits
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def train_step(params, opt_state, batch, cfg: DLRMConfig, tx):
    """One SGD step: loss -> grad -> optax update. Jit this whole function.

    The embedding gradient here is DENSE ([F, V, D], same shape as the
    table): simple and exact, but at real Criteo vocabularies (2^20+ rows)
    each step would materialize a multi-GB zero-mostly tensor. Use
    ``sparse_train_step`` for large tables."""
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg)
    updates, opt_state = tx.update(grads, opt_state, params)
    params = jax.tree.map(lambda p, u: p + u, params, updates)
    return params, opt_state, loss


class SparseEmbOptState(NamedTuple):
    """Optimizer state for ``sparse_train_step``: the wrapped optax state
    for the non-embedding params plus the row-wise AdaGrad accumulators
    ([F, V] float32 — D-independent, so 2^20-row tables carry ~4MB of
    state per feature column instead of an optimizer-state copy of the
    table)."""

    dense: Any
    accum: jax.Array


def sparse_opt_init(params, cfg: DLRMConfig, tx) -> SparseEmbOptState:
    dense = {k: v for k, v in params.items() if k != "embeddings"}
    return SparseEmbOptState(
        dense=tx.init(dense),
        accum=jnp.zeros((cfg.num_categorical, cfg.vocab_size), jnp.float32),
    )


# Largest F*V for which a flat int32 (f*V + v) dedup key cannot wrap (the
# default JAX index dtype with x64 disabled). Module-level so tests can
# shrink it and pin both sort paths against each other at test scale.
_FLAT_KEY_MAX = 2**31 - 1

# The key of a slot that holds no run: before every real key, and as an
# index (negative even after NumPy's wrap-around, F*V < 2^31) outside
# every table, so a scatter drops it.
_NO_KEY = np.iinfo(np.int32).min


def _dedup_sort(f_flat, v_flat, vocab: int, force_pairs: bool = False):
    """Sorted grouping for the dedup-first embedding update: returns
    (order, slot, uf, uv, R). ``order`` sorts the flattened (f, v) element
    list lexicographically. Per run of equal pairs in that order: ``slot``
    is each sorted element's run, numbered so that the R runs take the LAST
    R of N slots in order, and ``uf``/``uv`` hold each slot's pair; the
    N - R slots before them hold ``_NO_KEY``, which indexes no table.
    Empty slots come first because the TPU compiler rewrites an index out
    of range to -1: behind the real keys that breaks the order a scatter
    is promised, and it then writes wrong rows (PERF.md §6, PR 28). The
    keys come out of sorts, never out of a gather through ``order`` (a sort
    of [N] keys costs a tenth of a gather of them on a TPU): the sorted
    keys beside the permutation, the runs' keys by sorting the run starts
    to the back.

    Two equivalent paths: flat int32 keys (the fast common case) while F*V
    fits int32, and a lexicographic (f, v) pair sort beyond that — int32
    flat keys would silently WRAP for F*V > 2^31, merging unrelated rows
    into one dedup group and corrupting their updates, and int64 keys are
    unavailable with x64 disabled. Both sorts are stable over the same
    total order (v < vocab), so they produce the identical permutation
    (pinned in tests/test_model.py)."""
    n = f_flat.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    first = jnp.ones((1,), bool)
    if force_pairs:
        sf, sv, order = jax.lax.sort((f_flat, v_flat, iota), num_keys=2)
        run_start = jnp.concatenate(
            [first, (sf[1:] != sf[:-1]) | (sv[1:] != sv[:-1])]
        )
        # stable on the feature alone: the run starts keep their (f, v) order
        uf, uv = jax.lax.sort(
            (jnp.where(run_start, sf, _NO_KEY), jnp.where(run_start, sv, _NO_KEY)),
            num_keys=1,
        )
    else:
        skey, order = jax.lax.sort((f_flat * vocab + v_flat, iota), num_keys=1)
        run_start = jnp.concatenate([first, skey[1:] != skey[:-1]])
        ukey = jnp.sort(jnp.where(run_start, skey, _NO_KEY))
        uf, uv = ukey // vocab, ukey % vocab
    runs = jnp.cumsum(run_start)                # 1-based run of each element
    n_runs = runs[-1]
    return order, runs + (n - 1 - n_runs), uf, uv, n_runs


def _block_slots(batch: int, n: int, d: int) -> int:
    """Slots a block of ``sparse_train_step``'s table update takes, from the
    step's shapes alone: the batch's B rows (F blocks make the N slots)
    where a table row fills whole 128-lane tiles. There the table lies
    row-major on a TPU and the loop over the blocks carries it in place. A
    narrower row (D = 16, 32, 64 compiled for a v5e: PERF.md §6, PR 30)
    makes the compiler keep the table V-minor and relayout all of it around
    a scatter, once a step around one scatter but once an ITERATION inside a
    loop: those shapes keep one block of all N slots and no loop."""
    return batch if d % 128 == 0 else n


def _blocks_with_runs(n_runs, block: int):
    """How many blocks of ``block`` slots, counted from the back of the
    slot arrays, hold the ``n_runs`` runs ``_dedup_sort`` laid there."""
    return (n_runs + block - 1) // block


def sparse_train_step(
    params,
    opt_state: SparseEmbOptState,
    batch,
    cfg: DLRMConfig,
    tx,
    embed_lr: float = 0.01,
    embed_eps: float = 1e-8,
):
    """One train step with SPARSE embedding updates (row-wise AdaGrad).

    The table gradient never materializes: the loss is differentiated
    w.r.t. the GATHERED rows [B, F, D] (gather is linear, so scatter-adding
    the row gradients reproduces the dense table gradient exactly), and
    only the touched rows are updated. Per-step embedding traffic is
    O(B·F·D) instead of O(F·V·D) — at Criteo scale (V=2^20, D=64) that is
    ~100MB instead of ~7GB per step, which is what makes large-vocab DLRM
    training feasible at all (the reference's TensorFlow consumers get the
    same effect from tf.IndexedSlices).

    Embedding rule: row-wise AdaGrad (the industry-standard DLRM choice —
    one accumulator per ROW, not per element), with DEDUP-FIRST duplicate
    semantics: indices repeated inside a batch first sum their row
    gradients, then the accumulator adds mean((sum g)^2) ONCE per unique
    row — exactly what dense row-wise AdaGrad on the full table gradient
    does (and what TF IndexedSlices consumers / torchrec do). The dedup is
    a sort + segment-sum over the N = B*F (feature, row) keys. What exists
    once per unique row is kept once per RUN of the sorted keys, and nothing
    is gathered back to the elements: the segment sum lays each run's
    summed gradient at the run's slot of an [N, D] array, ``_dedup_sort``
    lays its (f, v) pair at the same slot of the key arrays, so
    mean((sum g)^2) is a dense pass over the slots and reaches the
    accumulator in one scatter; the scale and the row's update are dense
    passes over a block of B slots and reach the table in one scatter a
    block, inside the loop over the blocks that hold runs. The R runs lie
    in the last R slots: the loop walks blocks from the back and stops after
    ceil(R / B), and the scatter inside it promises no order, so it pays per
    index OFFERED (written or dropped) where a promised one passes over the
    whole table a call: the table's update costs per distinct row, not per
    element (PERF.md §6, PR 30; rows narrower than a lane tile keep one
    promised call over all N slots and no loop: ``_block_slots``). The loop
    carries the table alone: a TPU's ``while`` that carries the accumulators
    beside a 7 GB table loses the writes to both. The slots that hold no run
    hold zeros under keys that index no table, which a scatter drops; only
    the block that straddles the first run offers any to the table. A row no
    key names is untouched bit for bit. Non-embedding params go through the
    wrapped optax transform unchanged.

    Jit this whole function (donate params + opt_state)."""
    table = params["embeddings"]                            # [F, V, D]
    fdim, vocab = cfg.num_categorical, cfg.vocab_size
    f_ix = jnp.arange(fdim)[None, :]                        # [1, F]
    with jax.named_scope("tfr.gather"):
        # an index outside [0, V) trains the row the lookup reads for it
        # (the dedup keys below are built from idx, so it has to name a row)
        idx = batch["cat"]                                  # [B, F]
        idx = jnp.clip(jnp.where(idx < 0, idx + vocab, idx), 0, vocab - 1)
        rows = _gather_rows(table, idx)                     # [B, F, D]
    dense_params = {k: v for k, v in params.items() if k != "embeddings"}

    def loss_of(dp, r):
        return loss_fn(dp, batch, cfg, emb=r)

    loss, (g_dense, g_rows) = jax.value_and_grad(loss_of, argnums=(0, 1))(
        dense_params, rows
    )
    with jax.named_scope("tfr.dense_update"):
        updates, new_dense_state = tx.update(g_dense, opt_state.dense, dense_params)
        dense_params = jax.tree.map(lambda p, u: p + u, dense_params, updates)
    with jax.named_scope("tfr.dedup_sort"):
        g_rows = g_rows.astype(jnp.float32)
        d = g_rows.shape[-1]
        n = idx.shape[0] * fdim
        f_flat = jnp.broadcast_to(f_ix, idx.shape).reshape(n)   # [N] feature id
        v_flat = idx.reshape(n)                                 # [N] vocab row
        order, slot, uf, uv, n_runs = _dedup_sort(
            f_flat, v_flat, vocab, force_pairs=fdim * vocab > _FLAT_KEY_MAX
        )
        sg = g_rows.reshape(n, d)[order]
    with jax.named_scope("tfr.segment_sum"):
        g_run = jax.ops.segment_sum(
            sg, slot, num_segments=n, indices_are_sorted=True
        )                                                   # [N, D]: a run a slot
    # Scatter with (f, v) index PAIRS, never a flattened [F*V] view: the
    # table/accum keep their [F, V@model, D] layout, so GSPMD scatters into
    # the model-sharded V axis instead of all-gathering a reshaped table
    # (both _dedup_sort paths emit (f, v) in lexicographic order).
    # The TPU compiler rewrites both scatters into fusions that carry no
    # op_name of their own; only the arithmetic fused into them tells a
    # trace reader which scope they belong to, so each scatter's operand is
    # computed inside the scatter's scope.
    with jax.named_scope("tfr.accum_update"):
        ms_run = jnp.mean(g_run * g_run, axis=-1)           # [N], once per run
        accum = opt_state.accum.at[uf, uv].add(ms_run, indices_are_sorted=True)
    block = _block_slots(idx.shape[0], n, d)
    one_call = block == n

    def update_block(i, table):
        # Blocks count from the BACK, where the runs lie. Runs are distinct
        # rows: no two blocks meet in a row, and a run's accumulator is final
        # once the scatter above has run.
        start = n - (i + 1) * block
        with jax.named_scope("tfr.accum_update"):
            bf = jax.lax.dynamic_slice_in_dim(uf, start, block)
            bv = jax.lax.dynamic_slice_in_dim(uv, start, block)
            # post-accumulation scale, once per run (an empty slot reads some row's)
            scale = embed_lr * jax.lax.rsqrt(accum[bf, bv] + embed_eps)     # [block]
        with jax.named_scope("tfr.table_scatter"):
            g_blk = jax.lax.dynamic_slice_in_dim(g_run, start, block)
            # The promise of sorted indices buys a scatter that passes over
            # the whole table once a call; without it a call pays per index
            # offered (PERF.md §6, PR 30): one call over all N slots keeps
            # the promise, a block of them does not make it.
            return table.at[bf, bv].add(-scale[:, None] * g_blk, indices_are_sorted=one_call)

    if one_call:
        table = update_block(0, table)
    else:
        table = jax.lax.fori_loop(0, _blocks_with_runs(n_runs, block), update_block, table)
    params = dict(dense_params, embeddings=table)
    return params, SparseEmbOptState(new_dense_state, accum), loss


def dense_rowwise_adagrad_reference(
    params, opt_state: SparseEmbOptState, batch, cfg: DLRMConfig, tx,
    embed_lr: float = 0.01, embed_eps: float = 1e-8,
):
    """The plain reference for ``sparse_train_step``: the FULL dense table
    gradient ([F, V, D], via ``loss_fn`` on the table itself) and row-wise
    AdaGrad applied densely. With dedup-first duplicate semantics it is
    exact for any index pattern — the dense gradient row IS the deduped
    sum (barring exact float cancellation making a touched row read zero).
    Only for vocabularies small enough to hold that gradient; shared by
    tests/test_model.py and chip_smoke.py."""
    loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg)
    g_table = grads.pop("embeddings").astype(jnp.float32)
    dense_params = {k: v for k, v in params.items() if k != "embeddings"}
    updates, dense_state = tx.update(grads, opt_state.dense, dense_params)
    dense_params = jax.tree.map(lambda p, u: p + u, dense_params, updates)
    touched = (g_table != 0).any(axis=-1)                       # [F, V]
    row_ms = (g_table * g_table).mean(axis=-1)                  # [F, V]
    accum = opt_state.accum + jnp.where(touched, row_ms, 0.0)
    scale = embed_lr * jax.lax.rsqrt(accum + embed_eps)         # [F, V]
    table = params["embeddings"] - jnp.where(
        touched[..., None], scale[..., None] * g_table, 0.0
    )
    return (
        dict(dense_params, embeddings=table),
        SparseEmbOptState(dense_state, accum),
        loss,
    )


# ---------------------------------------------------------------------------
# Shardings
# ---------------------------------------------------------------------------


def param_shardings(mesh: Mesh, params, model_axis: str = "model"):
    """Tensor-parallel layout: embedding tables sharded over the vocab dim,
    MLP hidden dims sharded over 'model', biases/small tensors replicated."""
    has_model = model_axis in mesh.shape and mesh.shape[model_axis] > 1

    axis_size = mesh.shape.get(model_axis, 1)

    def spec_of(path: Tuple[str, ...], leaf) -> NamedSharding:
        if not has_model:
            return NamedSharding(mesh, P())
        name = "/".join(str(p) for p in path)
        if name.startswith("embeddings") and leaf.shape[1] % axis_size == 0:
            return NamedSharding(mesh, P(None, model_axis, None))  # [F, V@model, D]
        if name.startswith("embeddings"):
            return NamedSharding(mesh, P())
        if leaf.ndim == 2 and leaf.shape[1] % axis_size == 0:
            return NamedSharding(mesh, P(None, model_axis))        # [in, out@model]
        return NamedSharding(mesh, P())

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, list):
            return [walk(v, path + (i,)) for i, v in enumerate(tree)]
        return spec_of(path, tree)

    return walk(params)


def batch_shardings(mesh: Mesh, batch, data_axis: str = "data", seq_axis: Optional[str] = None):
    """Batch dim on 'data'; optionally the sequence (L) dim of 3-D features
    on a 'seq' axis — sequence/context parallelism for long sequences."""
    out = {}
    for name, arr in batch.items():
        if arr.ndim >= 2 and seq_axis and name == "frames" and seq_axis in mesh.shape:
            out[name] = NamedSharding(mesh, P(data_axis, seq_axis, *([None] * (arr.ndim - 2))))
        else:
            out[name] = NamedSharding(mesh, P(data_axis, *([None] * (arr.ndim - 1))))
    return out


def make_synthetic_batch(
    cfg: DLRMConfig, batch_size: int, seed: int = 0
) -> Dict[str, np.ndarray]:
    """Deterministic synthetic Criteo-like host batch (numpy, host-side)."""
    rng = np.random.default_rng(seed)
    batch = {
        "dense": rng.normal(size=(batch_size, cfg.num_dense)).astype(np.float32),
        "cat": rng.integers(
            0, cfg.vocab_size, size=(batch_size, cfg.num_categorical), dtype=np.int64
        ),
        "label": rng.integers(0, 2, size=(batch_size,)).astype(np.float32),
    }
    if cfg.seq_len:
        batch["frames"] = rng.normal(
            size=(batch_size, cfg.seq_len, cfg.seq_dim)
        ).astype(np.float32)
        batch["frames_len"] = rng.integers(
            1, cfg.seq_len + 1, size=(batch_size,)
        ).astype(np.int32)
    return batch
