"""Causal language model: the consumer that proves the model-parallel
layer end to end.

The repo's most intricate compute (zigzag causal ring attention), its
scale-shaped pipeline (models.pipeline), and its pinned all-to-all MoE
dispatch (models.moe) have oracles but — before this model — no jitted,
checkpointed train step consuming real ingested data. This decoder LM is
that consumer: packed token batches from `tpu_tfrecord.tpu.ingest.TokenPacker`
-> next-token cross-entropy, with the parallelism style picked by which
mesh axes the caller passes:

- no mesh / dp only            -> dense causal attention (the reference
                                  trajectory every other mode must match)
- ``seq_axis``                 -> ZIGZAG causal ring attention over the
                                  sequence (models.attention, balanced
                                  causal schedule, ppermute K/V rotation)
- ``pipe_axis``                -> transformer blocks stacked as pipeline
                                  stages through `pipeline_apply` — the
                                  dp×pp composed mesh; attention is dense
                                  per stage (a stage's shard_map already
                                  owns the device, so the sequence stays
                                  whole within it). ``cfg.n_virtual`` > 1
                                  interleaves V round-robin chunks per
                                  device (models.pipeline), cutting the
                                  bubble toward (S-1)/(V·M+S-1)
- ``fsdp_axis``                -> GSPMD weight sharding (FSDP): every 2D+
                                  parameter shards one dimension over the
                                  axis at rest (`SpecLayout` is the spec
                                  table), an all-gather materializes each
                                  weight ON USE inside `_block`/`forward`,
                                  and `train_step` constrains grads back
                                  to the sharded layout so gradients and
                                  optimizer state NEVER gather — per-
                                  device param+opt bytes shrink ~linearly
                                  in the axis (pinned). Composes with dp,
                                  pp (the pipeline's param_spec boundary
                                  does the per-step gather of each stage's
                                  own weights), and EP (expert weights
                                  shard expert×fsdp; the MoE shard_map
                                  gathers only the fsdp dim — activations
                                  are never re-sharded through the host)
- `LMStream`                   -> the SERVING flavor: the same pipelined
                                  chunks behind a per-microbatch streamed
                                  step (push one [mb, L+1] request, pop
                                  logits), bitwise the batch path
- ``expert_axis``              -> every block's FFN swaps for the top-k
                                  MoE with the PINNED all-to-all dispatch
                                  (`moe_apply_ep`)

All modes share one parameter pytree (blocks stacked on a leading
[n_layers, ...] dim — exactly the pipeline's stage layout), so the same
checkpoint trains under any mesh and the composition tests can demand
same-params same-data same-loss-trajectory across modes.

TPU shaping follows models.long_doc: pre-norm residual blocks, batched
matmuls, one jit per train step, no data-dependent control flow.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_tfrecord.models import moe as _moe
from tpu_tfrecord.models import pipeline as _pipeline
from tpu_tfrecord.models.attention import attention_reference, ring_attention
from tpu_tfrecord.models.long_doc import _rms_norm


@dataclass(frozen=True)
class LMConfig:
    vocab_size: int = 256
    d_model: int = 32
    n_heads: int = 4
    n_layers: int = 2
    mlp_mult: int = 4
    max_len: int = 64        # L: the model reads L tokens, predicts L
    dtype: Any = jnp.float32
    # 'seq'-axis attention flavor: zigzag (balanced causal ring) is the
    # default — the schedule this model exists to prove; False falls back
    # to the contiguous causal ring
    zigzag: bool = True
    # > 0 swaps every block's dense FFN for the top-k MoE (models.moe);
    # with an ``expert_axis`` the dispatch is the pinned all-to-all EP
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # microbatches for the pipeline mode (must divide the batch); None =
    # 2 × pipe-axis size (a 2-slice block per device, 2/3 efficiency)
    n_micro: Optional[int] = None
    # interleaved virtual stages for the pipeline mode (GSPMD-style,
    # models.pipeline): device d owns V round-robin layer chunks
    # (d, d+S, ...), shrinking the bubble toward (S-1)/(V·M+S-1);
    # n_layers must divide by S·V
    n_virtual: int = 1


@dataclass(frozen=True)
class SpecLayout:
    """The LM's mesh-axis spec table: one place that says which axis each
    parameter dimension shards over (the SNIPPETS [3] `SpecLayout` idiom).
    Any axis may be None — the spec degrades to replication on that
    dimension — so ONE table serves every mesh composition: pure dp (all
    None), dp×fsdp, dp×pp, dp×fsdp×pp, and dp×fsdp×EP.

    Conventions: the stacked block dim ([n_layers, ...]) belongs to
    ``pipe_axis`` (stage slicing); the first WEIGHT dim after it (fan-in
    for dense kernels, d_model for the router, rows for embed/pos/head)
    belongs to ``fsdp_axis``; the expert dim of MoE kernels belongs to
    ``expert_axis``. 1-D-per-layer biases replicate over fsdp — sharding
    them buys nothing and costs a gather each.
    """

    fsdp_axis: Optional[str] = None
    pipe_axis: Optional[str] = None
    expert_axis: Optional[str] = None

    def embed(self) -> P:                       # [vocab, d_model]
        return P(self.fsdp_axis, None)

    def pos(self) -> P:                         # [max_len, d_model]
        return P(self.fsdp_axis, None)

    def head(self) -> Dict[str, P]:             # w [d_model, vocab]
        return {"w": P(self.fsdp_axis, None), "b": P()}

    def block_dense(self) -> Dict[str, P]:      # w [n_layers, fan_in, fan_out]
        return {
            "w": P(self.pipe_axis, self.fsdp_axis, None),
            "b": P(self.pipe_axis, None),
        }

    def moe(self) -> Dict[str, P]:              # w_in [n_layers, E, d_model, d_ff]
        return {
            "router": P(self.pipe_axis, self.fsdp_axis, None),
            "w_in": P(self.pipe_axis, self.expert_axis, self.fsdp_axis, None),
            "w_out": P(self.pipe_axis, self.expert_axis, self.fsdp_axis, None),
        }


def param_specs(params, layout: SpecLayout) -> Dict[str, Any]:
    """PartitionSpec pytree matching ``params``' structure, leaf-for-leaf,
    from the spec table. Used by `param_shardings` for placement and by
    `train_step` to constrain grads back to the sharded layout."""
    blocks: Dict[str, Any] = {}
    for name in params["blocks"]:
        blocks[name] = layout.moe() if name == "moe" else layout.block_dense()
    return {
        "embed": layout.embed(),
        "pos": layout.pos(),
        "head": layout.head(),
        "blocks": blocks,
    }


def _unshard_fn(mesh, fsdp_axis):
    """The FSDP gather-on-use: a pytree-wide ``with_sharding_constraint``
    to full replication, forcing XLA to all-gather the weight right where
    it is consumed (and, in the transpose, to keep the weight's cotangent
    from staying replicated — the grad constraint in `train_step` turns
    that into a reduce+slice, never a gather of grads). Identity when no
    fsdp axis is in play, so every other mode compiles the exact
    pre-fsdp program."""
    if mesh is None or fsdp_axis is None:
        return lambda t: t
    repl = NamedSharding(mesh, P())
    return lambda t: jax.tree.map(
        lambda a: jax.lax.with_sharding_constraint(a, repl), t
    )


def _dense_init(rng, fan_in: int, fan_out: int):
    kw, kb = jax.random.split(rng)
    scale = (1.0 / fan_in) ** 0.5
    return {
        "w": jax.random.normal(kw, (fan_in, fan_out), jnp.float32) * scale,
        "b": jax.random.normal(kb, (fan_out,), jnp.float32) * 0.0,
    }


def _dense(layer, x, dt):
    return x @ layer["w"].astype(dt) + layer["b"].astype(dt)


def init_params(rng: jax.Array, cfg: LMConfig) -> Dict[str, Any]:
    if cfg.d_model % cfg.n_heads:
        raise ValueError(
            f"n_heads ({cfg.n_heads}) must divide d_model ({cfg.d_model})"
        )
    keys = jax.random.split(rng, 3 + cfg.n_layers)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(
            keys[0], (cfg.vocab_size, cfg.d_model), jnp.float32
        )
        * 0.02,
        "pos": jax.random.normal(
            keys[1], (cfg.max_len, cfg.d_model), jnp.float32
        )
        * 0.02,
        "head": _dense_init(keys[2], cfg.d_model, cfg.vocab_size),
    }
    layers = []
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[3 + i], 4)
        layer = {
            "qkv": _dense_init(k[0], cfg.d_model, 3 * cfg.d_model),
            "proj": _dense_init(k[1], cfg.d_model, cfg.d_model),
        }
        if cfg.moe_experts > 0:
            layer["moe"] = _moe.init_params(k[2], _moe_cfg(cfg))
        else:
            layer["mlp_in"] = _dense_init(
                k[2], cfg.d_model, cfg.mlp_mult * cfg.d_model
            )
            layer["mlp_out"] = _dense_init(
                k[3], cfg.mlp_mult * cfg.d_model, cfg.d_model
            )
        layers.append(layer)
    # blocks STACKED on a leading [n_layers, ...] dim: the dense loop
    # slices it, the pipeline shards it — one checkpoint, every mesh
    params["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return params


def _moe_cfg(cfg: LMConfig) -> "_moe.MoEConfig":
    return _moe.MoEConfig(
        d_model=cfg.d_model,
        d_ff=cfg.mlp_mult * cfg.d_model,
        n_experts=cfg.moe_experts,
        capacity_factor=cfg.moe_capacity_factor,
        top_k=cfg.moe_top_k,
        dtype=cfg.dtype,
    )


def _block(
    layer, x, cfg: LMConfig, mesh=None, seq_axis=None, data_axis=None,
    expert_axis=None, fsdp_axis=None, segments=None, diagnostics=False,
):
    """One pre-norm decoder block on x [B, L, D]. Attention flavor: zigzag
    causal ring over ``seq_axis`` when given, else dense causal;
    ``segments`` [B, L] masks attention across packed-document boundaries
    in either flavor. With ``fsdp_axis``, every weight is gathered ON USE
    (`_unshard_fn`) — EXCEPT the EP path's expert weights, whose reshard
    belongs to the MoE shard_map boundary (it gathers the fsdp dim while
    KEEPING the expert dim sharded; a full gather here would undo EP).
    Returns (x, aux, moe_diag) — moe_diag is None unless ``diagnostics``
    is set on an MoE block (models.moe _diag_dict vocabulary)."""
    dt = cfg.dtype
    g = _unshard_fn(mesh, fsdp_axis)
    b, l, _ = x.shape
    h, dh = cfg.n_heads, cfg.d_model // cfg.n_heads
    qkv = _dense(g(layer["qkv"]), _rms_norm(x), dt)
    q, k, v = (a.reshape(b, l, h, dh) for a in jnp.split(qkv, 3, axis=-1))
    if mesh is not None and seq_axis is not None:
        att = ring_attention(
            q, k, v, mesh, seq_axis=seq_axis, data_axis=data_axis,
            causal=True, zigzag=cfg.zigzag, segments=segments,
        )
    else:
        att = attention_reference(q, k, v, causal=True, segments=segments)
    x = x + _dense(g(layer["proj"]), att.reshape(b, l, cfg.d_model), dt)
    if cfg.moe_experts > 0:
        if mesh is not None and expert_axis is not None:
            out = _moe.moe_apply_ep(
                layer["moe"], _rms_norm(x), _moe_cfg(cfg), mesh,
                expert_axis=expert_axis, data_axis=data_axis,
                diagnostics=diagnostics,
            )
        else:
            out = _moe.moe_apply(
                g(layer["moe"]), _rms_norm(x), _moe_cfg(cfg),
                diagnostics=diagnostics,
            )
        y, aux = out[0], out[1]
        return x + y, aux, (out[2] if diagnostics else None)
    y = _dense(g(layer["mlp_in"]), _rms_norm(x), dt)
    return (
        x + _dense(g(layer["mlp_out"]), jax.nn.gelu(y), dt),
        jnp.float32(0.0),
        None,
    )


def _embed_tokens(params, tokens, cfg: LMConfig, segments=None):
    """tokens [B, L+1] int32 -> x [B, L, D]: the model reads
    tokens[:, :-1]. Shared by the batch forward and the streamed server
    (LMStream) — one embedding program, no drift between paths.

    ``segments`` [B, L+1] (TokenPacker bin modes) switches the position
    embedding to PER-DOCUMENT positions derived in-jit from the ids: each
    segment restarts at position 0, so a document packed mid-row embeds
    exactly as it would alone at the row start — half of the per-document
    oracle (the attention segment mask is the other half). The data
    contract stays segment_ids-only; no position column is ever fed."""
    dt = cfg.dtype
    x_tok = tokens[:, :-1]
    l = x_tok.shape[1]
    if l != cfg.max_len:
        raise ValueError(
            f"packed batch carries {l} input tokens but cfg.max_len is "
            f"{cfg.max_len} (the packer's seq_len must match)"
        )
    if segments is None:
        pos = params["pos"][:l].astype(dt)[None]
    else:
        segs = segments[:, :-1]
        idx = jnp.arange(l, dtype=jnp.int32)
        # a segment starts where the id changes (position 0 always does);
        # running cummax of the start indices = each position's segment
        # start, so idx - start is the within-document position
        boundary = jnp.concatenate(
            [
                jnp.ones((segs.shape[0], 1), bool),
                segs[:, 1:] != segs[:, :-1],
            ],
            axis=1,
        )
        start = jax.lax.cummax(
            jnp.where(boundary, idx[None, :], 0), axis=1
        )
        pos = params["pos"].astype(dt)[idx[None, :] - start]   # [B, L, D]
    return params["embed"].astype(dt)[x_tok] + pos


def _head_logits(params, x, cfg: LMConfig):
    """Final-norm + LM head: [.., L, D] -> f32 logits [.., L, V]. Shared
    by the batch forward and LMStream."""
    return _dense(params["head"], _rms_norm(x), cfg.dtype).astype(
        jnp.float32
    )


def _chunk_count(cfg: LMConfig, n_stages: int) -> int:
    chunks = n_stages * cfg.n_virtual
    if cfg.n_layers % chunks:
        raise ValueError(
            f"n_layers ({cfg.n_layers}) must divide into the pipe axis × "
            f"n_virtual ({n_stages} stages × {cfg.n_virtual} virtual = "
            f"{chunks} chunks)"
        )
    return chunks


def _stage_stack(blocks, cfg: LMConfig, n_stages: int):
    """The stacked [n_layers, ...] block pytree in the pipeline's stage
    layout: [S, per_stage, ...] classic, or [S, V, per_chunk, ...]
    interleaved — virtual stage k = v·S + s (device s's chunk v) holds
    layers [k·pc, (k+1)·pc), the GSPMD round-robin assignment (device d
    owns layer chunks d, d+S, d+2S, …). The V>1 relayout is a strided
    transpose: place/checkpoint params in the canonical [n_layers, ...]
    stack and let XLA move them once per step, or pre-place the reshaped
    stack (LMStream does, serving from the same checkpoint)."""
    chunks = _chunk_count(cfg, n_stages)
    pc = cfg.n_layers // chunks
    if cfg.n_virtual == 1:
        return jax.tree.map(
            lambda a: a.reshape((n_stages, pc) + a.shape[1:]), blocks
        )
    v = cfg.n_virtual
    return jax.tree.map(
        lambda a: a.reshape((v, n_stages, pc) + a.shape[1:]).transpose(
            (1, 0) + tuple(range(2, a.ndim + 2))
        ),
        blocks,
    )


def _make_stage_fn(cfg: LMConfig):
    """One pipeline chunk: per_chunk decoder blocks, dense attention (a
    stage's shard_map already owns the device — the sequence stays whole
    within it)."""
    def stage_fn(p_chunk, xs):
        pc = jax.tree.leaves(p_chunk)[0].shape[0]
        for j in range(pc):
            layer = jax.tree.map(lambda a: a[j], p_chunk)
            xs, _, _ = _block(layer, xs, cfg)
        return xs

    return stage_fn


def forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LMConfig,
    mesh: Optional[Mesh] = None,
    data_axis: Optional[str] = None,
    seq_axis: Optional[str] = None,
    pipe_axis: Optional[str] = None,
    expert_axis: Optional[str] = None,
    fsdp_axis: Optional[str] = None,
    segments: Optional[jax.Array] = None,
    diagnostics: bool = False,
):
    """tokens [B, L+1] int32 -> (logits [B, L, V] f32, aux f32[, diag]).
    The model reads tokens[:, :-1]; the caller scores against
    tokens[:, 1:] (`loss_fn` does). Mesh axes select the parallelism
    (module docstring); pipe and seq modes are mutually exclusive (a
    pipeline stage owns its devices — the sequence stays whole within
    it).

    ``fsdp_axis`` adds GSPMD weight sharding to ANY of the other modes:
    embed/pos/head gather on use here, each dense-loop block gathers its
    own layer inside `_block` (peak unsharded weight residency = one
    layer), and the pipeline mode needs no change at all — its
    `pipeline_apply` param_spec (P(pipe)) boundary reshards each stage's
    weights from the at-rest P(pipe, fsdp, ...) placement, which IS the
    per-step gather-on-use, composed with stage slicing.

    ``segments`` [B, L+1] int32 (TokenPacker bin modes) masks attention
    across packed-document boundaries and switches to per-document
    positions (`_embed_tokens`); not supported in the pipeline mode —
    its stage stream carries activations only.

    ``diagnostics`` (a static flag — False compiles the exact pre-flag
    program) returns a third element: the in-jit model diagnostics dict
    (ISSUE 13). MoE models carry ``expert_tokens``/``expert_kept`` [E]
    (summed across layers), ``dropped_fraction``, ``gate_entropy``
    (averaged across layers); the pipeline mode carries the measured
    ``bubble_fraction``/``useful_ticks``/``total_ticks``. All
    static-shaped and stop_gradient'd by the underlying layers."""
    if pipe_axis is not None and seq_axis is not None:
        raise ValueError(
            "pipe_axis and seq_axis are mutually exclusive: inside a "
            "pipeline stage the sequence is not sharded"
        )
    if pipe_axis is not None and cfg.moe_experts > 0:
        raise ValueError(
            "moe_experts > 0 is not supported in the pipeline mode"
        )
    if pipe_axis is not None and segments is not None:
        raise ValueError(
            "segments are not supported in the pipeline mode: the stage "
            "stream carries activations only (pack with the default "
            "slice mode, or drop pipe_axis)"
        )
    b = tokens.shape[0]
    if fsdp_axis is not None:
        # gather-on-use for the non-stacked params; the blocks gather
        # per-layer in `_block` (dense loop) or at the pipeline_apply
        # boundary (pipe mode)
        g = _unshard_fn(mesh, fsdp_axis)
        params = dict(params)
        params["embed"] = g(params["embed"])
        params["pos"] = g(params["pos"])
        params["head"] = g(params["head"])
    # _embed_tokens owns the max_len validation
    x = _embed_tokens(params, tokens, cfg, segments=segments)  # [B, L, D]
    segs_in = segments[:, :-1] if segments is not None else None
    aux_total = jnp.float32(0.0)
    diag: Dict[str, jax.Array] = {}
    if pipe_axis is not None:
        n_stages = mesh.shape[pipe_axis]
        stage_params = _stage_stack(params["blocks"], cfg, n_stages)
        m = cfg.n_micro or 2 * n_stages
        if b % m:
            raise ValueError(f"batch {b} not divisible by n_micro {m}")
        stage_fn = _make_stage_fn(cfg)
        xs = x.reshape((m, b // m) + x.shape[1:])              # [M, mb, L, D]
        batch_spec = P(data_axis) if data_axis else P()
        out = _pipeline.pipeline_apply(
            stage_fn, stage_params, xs, mesh, pipe_axis=pipe_axis,
            batch_spec=batch_spec, n_virtual=cfg.n_virtual,
            diagnostics=diagnostics,
        )
        if diagnostics:
            xs, diag = out
        else:
            xs = out
        x = xs.reshape((b,) + xs.shape[2:])
    else:
        moe_diags = []
        for i in range(cfg.n_layers):
            layer = jax.tree.map(lambda a: a[i], params["blocks"])
            x, aux, mdiag = _block(
                layer, x, cfg, mesh=mesh, seq_axis=seq_axis,
                data_axis=data_axis, expert_axis=expert_axis,
                fsdp_axis=fsdp_axis, segments=segs_in,
                diagnostics=diagnostics,
            )
            aux_total = aux_total + aux
            if mdiag is not None:
                moe_diags.append(mdiag)
        if moe_diags:
            n = len(moe_diags)
            # counts SUM across layers (every layer routes the full
            # stream: expert_tokens sums to n_layers * T * top_k);
            # fractions/entropy AVERAGE — the per-layer regime
            diag = {
                "expert_tokens": sum(d["expert_tokens"] for d in moe_diags),
                "expert_kept": sum(d["expert_kept"] for d in moe_diags),
                "dropped_fraction":
                    sum(d["dropped_fraction"] for d in moe_diags) / n,
                "gate_entropy":
                    sum(d["gate_entropy"] for d in moe_diags) / n,
            }
    logits = _head_logits(params, x, cfg)
    if diagnostics:
        return logits, aux_total, diag
    return logits, aux_total


def loss_fn(params, tokens, cfg: LMConfig, mesh=None, data_axis=None,
            seq_axis=None, pipe_axis=None, expert_axis=None,
            fsdp_axis=None, segments=None, diagnostics: bool = False):
    """Mean next-token cross-entropy + the MoE aux loss. Without
    ``segments`` every position scores (slice packing leaves no padding);
    with them (bin packing) a position is valid only when the input token
    and its target share a nonzero segment — no document's last token is
    ever scored against the NEXT document's first, and pad positions
    (segment 0) never contribute. With ``diagnostics`` returns
    (loss, diag) — the has_aux shape value_and_grad wants."""
    out = forward(
        params, tokens, cfg, mesh, data_axis, seq_axis, pipe_axis,
        expert_axis, fsdp_axis=fsdp_axis, segments=segments,
        diagnostics=diagnostics,
    )
    logits, aux = out[0], out[1]
    targets = tokens[:, 1:].astype(jnp.int32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tok_ce = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if segments is None:
        ce = jnp.mean(tok_ce)
    else:
        valid = (segments[:, :-1] == segments[:, 1:]) & (segments[:, 1:] != 0)
        ce = jnp.sum(tok_ce * valid) / jnp.maximum(valid.sum(), 1)
    loss = ce + cfg.moe_aux_weight * aux
    if diagnostics:
        return loss, out[2]
    return loss


def train_step(params, opt_state, tokens, cfg: LMConfig, tx, mesh=None,
               data_axis=None, seq_axis=None, pipe_axis=None,
               expert_axis=None, fsdp_axis=None, segments=None,
               diagnostics: bool = False):
    """One optimizer step; jit this whole function (mesh static via
    closure/partial). Returns (params, opt_state, loss) — with
    ``diagnostics``, (params, opt_state, loss, diag): the in-jit model
    diagnostics ride the step's outputs, so reading them costs no extra
    compilation or device round trip beyond fetching the tiny dict.

    With ``fsdp_axis`` the grads are constrained back to the parameter
    layout (`param_specs`) right out of the backward pass: the optimizer
    update and its state run SHARDED — cross-replica grad reduction goes
    through a reduce+slice on the sharded layout, and no full all-gather
    of grads ever exists in the step."""
    if diagnostics:
        (loss, diag), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, cfg, mesh, data_axis, seq_axis, pipe_axis,
            expert_axis, fsdp_axis, segments, diagnostics=True,
        )
    else:
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, cfg, mesh, data_axis, seq_axis, pipe_axis,
            expert_axis, fsdp_axis, segments,
        )
    if mesh is not None and fsdp_axis is not None:
        layout = SpecLayout(
            fsdp_axis=fsdp_axis, pipe_axis=pipe_axis,
            expert_axis=expert_axis,
        )
        grads = jax.tree.map(
            lambda g, s: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, s)
            ),
            grads,
            param_specs(grads, layout),
        )
    updates, opt_state = tx.update(grads, opt_state, params)
    params = jax.tree.map(lambda p, u: p + u, params, updates)
    if diagnostics:
        return params, opt_state, loss, diag
    return params, opt_state, loss


def param_shardings(
    mesh: Mesh,
    params,
    pipe_axis: Optional[str] = None,
    expert_axis: Optional[str] = None,
    fsdp_axis: Optional[str] = None,
):
    """NamedShardings for the parameter pytree from the `SpecLayout` spec
    table: the stacked block dim shards on ``pipe_axis`` (stage weights
    never replicate — that is PP), the expert dim on ``expert_axis``
    (EP), and every 2D+ weight's leading weight dim on ``fsdp_axis``
    (FSDP at rest; the forward gathers on use). Axes left None degrade
    to replication on that dim, so this is exactly the old behavior for
    the old calls.

    The checkpoint keeps the canonical [n_layers, ...] stack under every
    mode; with ``cfg.n_virtual`` > 1 the forward's `_stage_stack` does
    the round-robin chunk relayout in-jit (XLA moves the weights once per
    step) — serving avoids even that by pre-placing the reshaped stack
    (LMStream)."""
    layout = SpecLayout(
        fsdp_axis=fsdp_axis, pipe_axis=pipe_axis, expert_axis=expert_axis
    )
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), param_specs(params, layout)
    )


def batch_shardings(mesh: Mesh, data_axis: str = "data"):
    """Packed token batches shard their batch dim on the data axis."""
    return {"tokens": NamedSharding(mesh, P(data_axis, None))}


class LMStream:
    """Microbatch-streamed LM inference — the serving flavor of the
    pipeline mode (ROADMAP #2's heavy-traffic path).

    Wraps `models.pipeline.PipelineStream` around the SAME decoder chunks
    the pipelined trainer runs: blocks from the trainer's checkpoint
    layout ([n_layers, ...] stacked — `examples/train_lm.py`'s npz loads
    straight in) are re-stacked into the stage layout host-side and
    device_put sharded on the pipe axis, so serving pays the V>1
    round-robin relayout ONCE at startup instead of per step. Embedding
    and head run per-microbatch in their own tiny jits (the exact
    programs the batch forward uses).

    Per request: ``submit(tokens [mb, L+1])`` feeds ONE microbatch-sized
    slice (the per-call pin — no request stream is ever materialized) and
    returns whatever logits completed, FIFO; ``flush()`` drains the tail.
    Streamed logits are BITWISE equal to `batch_reference` — the batch
    path over `pipeline_apply` on the same slices (pinned by tests), so
    the serving surface cannot drift from the trained graph.
    """

    def __init__(
        self,
        params: Dict[str, Any],
        cfg: LMConfig,
        mesh: Mesh,
        pipe_axis: str = "pipe",
    ):
        self.cfg = cfg
        self._n_stages = mesh.shape[pipe_axis]
        _chunk_count(cfg, self._n_stages)
        if cfg.moe_experts > 0:
            raise ValueError(
                "moe_experts > 0 is not supported in the pipeline mode"
            )
        self._stage_fn = _make_stage_fn(cfg)
        self._stage_params = jax.device_put(
            _stage_stack(params["blocks"], cfg, self._n_stages),
            NamedSharding(mesh, P(pipe_axis)),
        )
        self._ep = {"embed": params["embed"], "pos": params["pos"]}
        self._hp = {"head": params["head"]}
        self._embed = jax.jit(lambda p, t: _embed_tokens(p, t, cfg))
        self._head = jax.jit(lambda p, x: _head_logits(p, x, cfg))
        self._mesh = mesh
        self._pipe_axis = pipe_axis
        self.stream = _pipeline.PipelineStream(
            self._stage_fn, self._stage_params, mesh, pipe_axis=pipe_axis,
            n_virtual=cfg.n_virtual,
        )

    def submit(self, tokens) -> list:
        """One request: tokens [mb, L+1] int32 in, zero or more finished
        [mb, L, V] f32 logits out (FIFO — outputs lag by the pipeline's
        S·V-tick latency)."""
        return [out for out, _ in self.submit_tagged(tokens)]

    def submit_tagged(self, tokens, tag=None) -> list:
        """`submit` riding an opaque host-side tag on the microbatch (see
        `PipelineStream.push_tagged`); returns ``(logits, tag)`` pairs so
        a multiplexer can map each popped [mb, L, V] back to the requests
        packed into its slots. The tag stays on the host — the compiled
        step and its argument bytes are untouched."""
        x = self._embed(self._ep, jnp.asarray(tokens))
        return [
            (np.asarray(self._head(self._hp, o)), t)
            for o, t in self.stream.push_tagged(x, tag)
        ]

    def flush(self) -> list:
        """Drain the in-flight tail; returns the remaining logits FIFO."""
        return [out for out, _ in self.flush_tagged()]

    def flush_tagged(self) -> list:
        """`flush` returning ``(logits, tag)`` pairs (see `submit_tagged`)."""
        return [
            (np.asarray(self._head(self._hp, o)), t)
            for o, t in self.stream.flush_tagged()
        ]

    def reset(self) -> None:
        self.stream.reset()

    def batch_reference(self, batches) -> list:
        """The batch path on the same slices: the SAME embed/head jits
        around batch-mode `pipeline_apply` over the stacked [M, mb, ...]
        stream — what the streamed outputs must equal bitwise."""
        xs = jnp.stack(
            [self._embed(self._ep, jnp.asarray(t)) for t in batches]
        )
        out = _pipeline.pipeline_apply(
            self._stage_fn, self._stage_params, xs, self._mesh,
            pipe_axis=self._pipe_axis, n_virtual=self.cfg.n_virtual,
        )
        return [
            np.asarray(self._head(self._hp, out[i]))
            for i in range(len(batches))
        ]


def pack_slots(windows, mb: int, max_len: int) -> np.ndarray:
    """Pack up to ``mb`` per-request token windows ([L] int32 each) into
    one [mb, L+1] microbatch for `LMStream.submit`: row i holds request
    i's window plus a zero trailing token (column L is the training
    target slot — `_embed_tokens` drops it, so its value never reaches
    the forward), and unused slots are all-zero. Slot VALIDITY lives
    host-side (the submit tag), not in the array: every model op is
    batch-row independent, so a garbage slot cannot perturb a valid one
    bitwise (the per-slot isolation pin continuous batching rests on)."""
    if len(windows) > mb:
        raise ValueError(f"{len(windows)} windows > {mb} slots")
    out = np.zeros((mb, max_len + 1), np.int32)
    for i, w in enumerate(windows):
        w = np.asarray(w, dtype=np.int32)
        if w.shape != (max_len,):
            raise ValueError(f"window {i} shape {w.shape} != ({max_len},)")
        out[i, :max_len] = w
    return out


def make_synthetic_tokens(
    cfg: LMConfig, batch_size: int, seed: int = 0, n_next: int = 4
) -> np.ndarray:
    """[B, L+1] int32 batches from a fixed sparse-bigram language: each
    token has ``n_next`` plausible successors, so next-token CE can fall
    from ~ln(V) toward ~ln(n_next) — training signal without real text."""
    rng = np.random.default_rng(seed)
    table = bigram_table(cfg.vocab_size, n_next, seed=1234)
    out = np.empty((batch_size, cfg.max_len + 1), np.int32)
    for i in range(batch_size):
        t = int(rng.integers(cfg.vocab_size))
        for j in range(cfg.max_len + 1):
            out[i, j] = t
            t = int(table[t, rng.integers(n_next)])
    return out


def bigram_table(vocab: int, n_next: int, seed: int = 1234) -> np.ndarray:
    """[V, n_next] successor table — the synthetic 'language' shared by
    tests, the example generator, and the bench probe."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(vocab, n_next)).astype(np.int32)
