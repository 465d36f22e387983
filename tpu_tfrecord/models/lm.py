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
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_tfrecord.models import head as _head
from tpu_tfrecord.models import linear_attn as _la
from tpu_tfrecord.models import moe as _moe
from tpu_tfrecord.models import pipeline as _pipeline
from tpu_tfrecord.models import sparse_attn as _sa
from tpu_tfrecord.models.attention import (
    attention_reference, blockwise_attention, flash_attention_widths, pair_kinds, ring_attention,
)
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
    tests and the example generator."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, size=(vocab, n_next)).astype(np.int32)


# ---------------------------------------------------------------------------
# The pattern model: a layer pattern as data, scored over packed rows
# ---------------------------------------------------------------------------
#
# `LMConfig` stacks ONE kind of block n_layers deep. Hybrid decoders
# alternate kinds — a softmax layer, then a few linear-attention layers —
# and put a sparse expert FFN behind each. `PatternLMConfig.layer_pattern`
# names the mixer of each layer and `ffn_pattern` its feed-forward part
# (experts, or a dense gated unit: leading dense layers are data); either may
# say "none" where a layer is ONE pre-normed branch, a mixer or a feed-forward
# part alone (never both: such a layer would be no layer). The
# parameters are a list of per-layer dicts (layers of different kinds do
# not stack). The model reads no position table: order comes from the
# causal mask, the short convolution and the recurrence, and in a latent-
# attention or sliding-window layer from rotary angles of each token's index
# in its own document, derived on the device from ``segment_ids``. Policy: bfloat16
# weights and activations; norms, router, rotary angles, softmax, the
# recurrent state, logits and log-probabilities in float32. The rows are
# TokenPacker's bin-mode batches as they are: ``tokens`` and
# ``segment_ids`` [B, L+1]; every document comes out as it would alone in
# a row (attention, positions, taps and state all stop at a boundary).

MIXERS = ("gqa", "kda", "mla", "swa", "gdn", "ssm", "bda")
FFNS = ("moe", "dense")
NONE = "none"  # in either pattern: the layer has no such part


@dataclass(frozen=True)
class PatternLMConfig:
    vocab_size: int = 256          # rows of the embedding, columns of the head, held here
    d_model: int = 64
    layer_pattern: Tuple[str, ...] = ("gqa", "kda", "kda", "kda")  # a mixer, or "none", for each layer
    ffn_pattern: Tuple[str, ...] = ()  # "moe", "dense" or "none" for each layer; () = experts in every one
    n_heads: int = 4               # softmax and latent-attention layers: query heads
    n_kv_heads: int = 2
    head_dim: int = 16
    window: int = 0                # sliding-window layer ("swa"): the keys a query sees, its own among them
    diffusion_block: int = 0       # block-diffusion layers ("bda"): tokens a block, counted from a document's first (0: none),
    mask_id: int = 0               # ... and the id that stands for a noised token: scored where the noised row holds it
    qk_norm: bool = False          # softmax and sliding-window layers: an RMSNorm over each head of q and of k,
    qk_norm_whole: bool = False    # ... or over the WHOLE projection before it is cut into heads (a weight its width)
    gqa_gate: bool = True          # ... and a sigmoid gate from the layer's input on the attention's output
    kda_heads: int = 4             # recurrent layers ("kda", "gdn", "ssm"): heads of kda_head_dim channels
    kda_head_dim: int = 16         # ... (a delta-rule head's d_k, and its d_v but for "gdn" below; a state-space head's P)
    conv_taps: int = 4
    gate_rank: int = 8             # rank of the "kda" layer's decay and output gates
    gdn_key_heads: int = 0         # "gdn" layer: key heads, a divisor of its kda_heads value heads (0: as many),
    gdn_value_dim: int = 0         # ... a value head's d_v where it is not kda_head_dim (0: it is): a state [d_k, d_v],
    gdn_neg_eigval: bool = False   # ... beta in (0, 2), a negative eigenvalue of the transition allowed, where it is (0, 1),
    gdn_gate: str = "sigmoid2"     # ... and the gate on a head's normed output: "sigmoid2" (2 sigmoid(z)) or "silu"
    ssm_state: int = 16            # "ssm" layer: a head's state is kda_head_dim x ssm_state,
    ssm_groups: int = 1            # ... and its kda_heads read B and C in so many groups (head h reads h // (H / G))
    qk_nope_dim: int = 16          # latent-attention layer: a head's query/key width without positions,
    qk_rope_dim: int = 8           # ... its rotary width (the key's is one head shared by all),
    v_head_dim: int = 16           # ... its value width,
    kv_rank: int = 32              # ... the rank of the latent that keys and values are expanded from
    q_rank: int = 0                # ... and of the normed latent its queries are expanded from (0: one matrix)
    attn_gate: bool = False        # ... and a sigmoid gate from the layer's input on its output, a head and channel
    rope_theta: float = 10000.0
    rope_scaling: Tuple[float, ...] = ()  # YaRN: (factor, original length, beta_fast, beta_slow); () = none
    index_heads: int = 0           # latent-attention layer's indexer: heads of ``index_dim`` a query,
    index_dim: int = 0             # ... against ONE key of that width a token,
    index_topk: int = 0            # ... and the keys a query attends: its best so many (0: every key, no indexer)
    d_dense: int = 64              # width of a dense feed-forward part
    n_experts: int = 16            # the router's width: every expert of the layer
    experts_held: int = 16         # how many of them this chip holds ...
    held_offset: int = 0           # ... starting at this one
    top_k: int = 2
    d_expert: int = 32
    n_shared: int = 1
    d_shared: int = 0              # width of the shared unit (0: n_shared experts' summed, d_expert * n_shared)
    expert_unit: str = "gated"     # a routed or shared expert: "gated" (three matrices, SiLU) or "relu2" (two)
    routed_scale: float = 1.0
    router_bias: bool = False      # a per-expert bias beside the router: it picks, it never weighs
    router_scoring: str = "sigmoid"  # the router's scores: "sigmoid", or "softmax" over all experts (moe.route_top_k)
    n_group: int = 1               # the router's group limit: the experts in so many equal runs,
    topk_group: int = 1            # ... of which a token's choice may touch so many (moe.route_top_k)
    norm_eps: float = 1e-5
    centred_norms: bool = False    # a norm's gain is 2 sigmoid(w), 1 at w = 0, where it is w
    swiglu_limit: float = 0.0      # every gated unit clipped at it before it multiplies (moe.gated_ffn; 0: not)
    branch_norms: bool = False     # sandwich: x + norm(branch(norm(x))), the mixer's and the feed-forward's
    pre_norms: bool = True         # False: no norm on a branch's way IN (with branch_norms: x + norm(branch(x)))
    embed_scale: bool = False      # the embedding's rows times sqrt(d_model)
    max_len: int = 64              # L: a row is L + 1 tokens
    dtype: Any = jnp.bfloat16
    # how the program cuts the work (no effect on the result beyond rounding)
    attn_block: int = 1024         # query and key block of the softmax and latent-attention layers
    kda_chunk: int = 64            # tokens a step of the chunked recurrence (delta rule, state space)
    expert_tile: int = 256         # visits a tile of the expert loop
    head_block: int = 2048         # tokens a block of the head's logits in the plain form (models.head: not the kernel's tiles)


def ffn_kinds(cfg: PatternLMConfig) -> Tuple[str, ...]:
    """Each layer's feed-forward part: ``cfg.ffn_pattern``, or experts
    everywhere; ``"none"`` where the layer is its mixer alone."""
    kinds = cfg.ffn_pattern or ("moe",) * len(cfg.layer_pattern)
    if len(kinds) != len(cfg.layer_pattern) or set(kinds) - set(FFNS) - {NONE}:
        raise ValueError(f"ffn_pattern {kinds} has to name one of {FFNS} for each of the "
                         f"{len(cfg.layer_pattern)} layers")
    return kinds


def pattern_param_shapes(cfg: PatternLMConfig) -> Dict[str, Any]:
    """{name: (shape, dtype)} as a pytree shaped like the parameters:
    matrices in ``cfg.dtype``, vectors, the router and its bias in float32."""
    d, dt, f32 = cfg.d_model, cfg.dtype, jnp.float32
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    kd, r = cfg.kda_heads * cfg.kda_head_dim, cfg.gate_rank
    fs = cfg.d_shared or cfg.d_expert * cfg.n_shared
    after = {"post_ffn_norm": ((d,), f32)} if cfg.branch_norms else {}
    ffns = {"dense": {
        **after, "ffn_norm": ((d,), f32),
        "dense": {"w_gate": ((d, cfg.d_dense), dt), "w_up": ((d, cfg.d_dense), dt),
                  "w_down": ((cfg.d_dense, d), dt)},
    }}
    ffns["moe"] = moe = {
        **after, "moe_norm": ((d,), f32),
        "router": ((d, cfg.n_experts), f32),
        "w_gate": ((cfg.experts_held, d, cfg.d_expert), dt),
        "w_up": ((cfg.experts_held, d, cfg.d_expert), dt),
        "w_down": ((cfg.experts_held, cfg.d_expert, d), dt),
        "shared": {"w_gate": ((d, fs), dt), "w_up": ((d, fs), dt), "w_down": ((fs, d), dt)},
    }
    mixers = {
        "gqa": {
            "attn_norm": ((d,), f32), "wq": ((d, hq), dt), "wk": ((d, hkv), dt),
            "wv": ((d, hkv), dt), "wg": ((d, hq), dt), "wo": ((hq, d), dt),
        },
        "kda": {
            "attn_norm": ((d,), f32), "wq": ((d, kd), dt), "wk": ((d, kd), dt),
            "wv": ((d, kd), dt), "conv_q": ((cfg.conv_taps, kd), f32),
            "conv_k": ((cfg.conv_taps, kd), f32), "conv_v": ((cfg.conv_taps, kd), f32),
            "f_down": ((d, r), dt), "f_up": ((r, kd), dt), "f_bias": ((kd,), f32),
            "a_log": ((cfg.kda_heads,), f32), "w_beta": ((d, cfg.kda_heads), dt),
            "g_down": ((d, r), dt), "g_up": ((r, kd), dt),
            "o_norm": ((cfg.kda_head_dim,), f32), "wo": ((kd, d), dt),
        },
        "mla": {
            "attn_norm": ((d,), f32),
            "wq": ((d, cfg.n_heads * (cfg.qk_nope_dim + cfg.qk_rope_dim)), dt),
            "wkv_a": ((d, cfg.kv_rank + cfg.qk_rope_dim), dt), "kv_norm": ((cfg.kv_rank,), f32),
            "wkv_b": ((cfg.kv_rank, cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim)), dt),
            "wo": ((cfg.n_heads * cfg.v_head_dim, d), dt),
        },
    }
    mixers["swa"] = mixers["gqa"]  # a softmax layer under a window, with rotary positions
    kk = (cfg.gdn_key_heads or cfg.kda_heads) * cfg.kda_head_dim
    dv = cfg.gdn_value_dim or cfg.kda_head_dim
    vd = cfg.kda_heads * dv
    mixers["gdn"] = {  # "kda" with full-rank gates, one decay a head and token, fewer key heads, values of their own width
        "attn_norm": ((d,), f32), "wq": ((d, kk), dt), "wk": ((d, kk), dt), "wv": ((d, vd), dt),
        "wz": ((d, vd), dt), "conv_q": ((cfg.conv_taps, kk), f32), "conv_k": ((cfg.conv_taps, kk), f32),
        "conv_v": ((cfg.conv_taps, vd), f32), "w_a": ((d, cfg.kda_heads), dt),
        "dt_bias": ((cfg.kda_heads,), f32), "a_log": ((cfg.kda_heads,), f32),
        "w_beta": ((d, cfg.kda_heads), dt), "o_norm": ((dv,), f32), "wo": ((vd, d), dt),
    }
    inner, bc = kd, cfg.ssm_groups * cfg.ssm_state
    mixers["ssm"] = {  # one projection in, [z | x B C | dt]; one convolution over x, B and C; one out
        "attn_norm": ((d,), f32), "w_in": ((d, 2 * inner + 2 * bc + cfg.kda_heads), dt),
        "conv_x": ((cfg.conv_taps, inner + 2 * bc), f32), "conv_bias": ((inner + 2 * bc,), f32),
        "a_log": ((cfg.kda_heads,), f32), "d_skip": ((cfg.kda_heads,), f32),
        "dt_bias": ((cfg.kda_heads,), f32), "o_norm": ((inner,), f32), "wo": ((inner, d), dt),
    }
    mixers[NONE] = ffns[NONE] = {}
    for kind, ffn in zip(cfg.layer_pattern, ffn_kinds(cfg)):
        if kind not in MIXERS and kind != NONE:
            raise ValueError(f"layer_pattern names {kind!r}; the mixers are {MIXERS}")
        if kind == NONE and ffn == NONE:
            raise ValueError("a layer is a mixer, a feed-forward part or both: 'none' in one pattern alone")
    if "ssm" in cfg.layer_pattern and cfg.kda_heads % cfg.ssm_groups:
        raise ValueError(f"ssm_groups {cfg.ssm_groups} has to divide the {cfg.kda_heads} heads")
    if cfg.expert_unit not in ("gated", "relu2"):
        raise ValueError(f"expert_unit {cfg.expert_unit!r}: 'gated' or 'relu2'")
    if cfg.gdn_gate not in ("sigmoid2", "silu"):
        raise ValueError(f"gdn_gate {cfg.gdn_gate!r}: 'sigmoid2' or 'silu'")
    if cfg.qk_norm and cfg.qk_norm_whole:
        raise ValueError("qk_norm (a head) or qk_norm_whole (the projection): one of the two")
    if "swa" in cfg.layer_pattern and cfg.window < 1:
        raise ValueError("a sliding-window layer needs cfg.window: the keys a query sees")
    if ("bda" in cfg.layer_pattern) != (cfg.diffusion_block > 0) or (
            cfg.diffusion_block and set(cfg.layer_pattern) - {"bda", NONE}):
        raise ValueError("block diffusion runs two streams through EVERY layer: diffusion_block > 0 and "
                         "'bda' for every mixer of the pattern, or neither")
    if cfg.router_scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"router_scoring {cfg.router_scoring!r}: 'sigmoid' or 'softmax'")
    if cfg.kda_heads % (cfg.gdn_key_heads or 1):
        raise ValueError(f"gdn_key_heads {cfg.gdn_key_heads} has to divide the {cfg.kda_heads} value heads")
    if not cfg.gqa_gate:
        mixers["gqa"].pop("wg")
    if cfg.expert_unit == "relu2":  # two matrices a unit: no gate beside the way up
        moe.pop("w_gate")
        moe["shared"].pop("w_gate")
    if cfg.qk_norm:  # one weight for all heads
        mixers["gqa"].update({"q_norm": ((cfg.head_dim,), f32), "k_norm": ((cfg.head_dim,), f32)})
    if cfg.qk_norm_whole:  # a weight a channel of the projection
        mixers["gqa"].update({"q_norm": ((hq,), f32), "k_norm": ((hkv,), f32)})
    if cfg.branch_norms:
        for mixer in mixers.values():
            mixer["post_attn_norm"] = ((d,), f32)
    if not cfg.pre_norms:  # a branch reads the stream as it is
        for part in (*mixers.values(), *ffns.values()):
            for name in ("attn_norm", "ffn_norm", "moe_norm"):
                part.pop(name, None)
    if cfg.q_rank:  # the query through a normed latent, as the keys and values go
        wq = mixers["mla"].pop("wq")[0]
        mixers["mla"].update({"wq_a": ((d, cfg.q_rank), dt), "q_norm": ((cfg.q_rank,), f32),
                              "wq_b": ((cfg.q_rank, wq[1]), dt)})
    if cfg.attn_gate:
        mixers["mla"]["wg"] = ((d, cfg.n_heads * cfg.v_head_dim), dt)
    if cfg.index_topk:
        if not cfg.q_rank:
            raise ValueError("the indexer's queries come from the query latent: index_topk needs q_rank")
        mixers["mla"].update({
            "wq_idx": ((cfg.q_rank, cfg.index_heads * cfg.index_dim), dt),
            "wk_idx": ((d, cfg.index_dim), dt), "k_idx_norm": ((cfg.index_dim,), f32),
            "k_idx_bias": ((cfg.index_dim,), f32), "w_idx": ((d, cfg.index_heads), dt)})
    if cfg.n_shared < 1:
        moe.pop("shared")
    if cfg.router_bias:
        moe["router_bias"] = ((cfg.n_experts,), f32)
    # the softmax layer under block diffusion's mask: what "swa" holds, and never an output gate
    mixers["bda"] = {name: shape for name, shape in mixers["gqa"].items() if name != "wg"}
    return {
        "embed": ((cfg.vocab_size, d), dt), "head": ((d, cfg.vocab_size), dt),
        "final_norm": ((d,), f32),
        "layers": [{**mixers[kind], **ffns[ffn]}
                   for kind, ffn in zip(cfg.layer_pattern, ffn_kinds(cfg))],
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def pattern_init_params(rng: jax.Array, cfg: PatternLMConfig) -> Dict[str, Any]:
    """Random parameters for tests and examples: matrices normal(0, 1/fan_in),
    norms 1 (under ``cfg.centred_norms`` normal(0, 0.2): gains of 1 +- 0.1;
    a delta-rule head's own ``o_norm`` is a plain gain either way), decays of
    0.001 to 0.1 a token (``a_log`` 0, ``f_bias`` / ``dt_bias`` the inverse
    softplus of a log-uniform rate), taps that favour the current token (a
    convolution's bias normal(0, 0.1)), a state-space head's skip 1, a
    router bias normal(0, 0.05)."""
    shapes = pattern_param_shapes(cfg)
    leaves, tree = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)
    out = []
    for i, (path, (shape, dtype)) in enumerate(leaves):
        name, key = path[-1].key, jax.random.fold_in(rng, i)
        if name.endswith("norm") and cfg.centred_norms and name != "o_norm":
            value = jax.random.normal(key, shape) * 0.2
        elif name.endswith("norm"):
            value = jnp.ones(shape)
        elif name == "d_skip":
            value = jnp.ones(shape)
        elif name == "conv_bias":
            value = jax.random.normal(key, shape) * 0.1
        elif name == "a_log":
            value = jnp.zeros(shape)
        elif name in ("f_bias", "dt_bias"):
            rate = jnp.exp(jax.random.uniform(key, shape, minval=np.log(1e-3), maxval=np.log(1e-1)))
            value = jnp.log(jnp.expm1(rate))
        elif name.startswith("conv_"):
            value = jax.random.normal(key, shape) * 0.2 + (jnp.arange(shape[0]) == 0)[:, None]
        elif name == "embed":
            value = jax.random.normal(key, shape)
        elif name == "router_bias":
            value = jax.random.normal(key, shape) * 0.05
        elif name == "k_idx_bias":
            value = jax.random.normal(key, shape) * 0.1
        else:
            value = jax.random.normal(key, shape) * shape[-2] ** -0.5
        out.append(value.astype(dtype))
    return jax.tree.unflatten(tree, out)


def weighted_rms_norm(x, weight, eps: float):
    """``x / rms(x) * weight`` over the last axis, in float32, rounded to x's dtype."""
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (x32 * scale * weight).astype(x.dtype)


def _norm(x, weight, cfg: "PatternLMConfig"):
    """:func:`weighted_rms_norm` as ``cfg`` has it: the gain ``weight`` itself,
    or under ``cfg.centred_norms`` ``2 sigmoid(weight)``, a gain in (0, 2)
    that is 1 where the weight is 0."""
    if cfg.centred_norms:
        weight = 2.0 * jax.nn.sigmoid(weight)
    return weighted_rms_norm(x, weight, cfg.norm_eps)


def _pre_norm(x, p, name: str, cfg: "PatternLMConfig"):
    """What a branch reads: the stream under the branch's own norm ``p[name]``,
    or without ``cfg.pre_norms`` the stream as it is (the layer holds no such weight)."""
    return _norm(x, p[name], cfg) if cfg.pre_norms else x


def _norm_whole(x, weight, cfg: "PatternLMConfig"):
    """:func:`_norm` over a WHOLE projection that lies head-major: x [B, H, L, Dh],
    ``weight`` [H * Dh] in the projection's column order; the mean square runs
    over a token's H * Dh channels."""
    if cfg.centred_norms:
        weight = 2.0 * jax.nn.sigmoid(weight)
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=(1, 3), keepdims=True) + cfg.norm_eps)
    return (x32 * scale * weight.reshape(x.shape[1], 1, x.shape[3])).astype(x.dtype)


def _takes_kernel(l: int, dv: int, block: int) -> bool:
    """Whether :func:`_attend` runs its Pallas kernel for rows of ``l`` tokens
    and values ``dv`` wide: on a TPU, whole blocks of whole 128s."""
    tile = min(block, l)
    return jax.default_backend() == "tpu" and dv % 128 == 0 and tile % 128 == 0 and l % tile == 0


def _takes_block_kernel(stream: int, dv: int, block: int, n: int) -> bool:
    """Whether :func:`_attend` runs its Pallas kernel under block diffusion's
    mask for two streams of ``stream`` tokens and blocks of ``n``: what
    :func:`_takes_kernel` asks of a stream, and a power of two that divides
    the rows a pass of the kernel takes."""
    return _takes_kernel(stream, dv, block) and n & (n - 1) == 0 and 128 % n == 0


def _attend(q, k, v, segments, block: int, scale=None, keep=None, window=None, diffusion=None):
    """Causal attention inside each document; q [B, H, L, D], k [B, Hkv, L, D],
    v [B, Hkv, L, Dv] -> [B, H, L, Dv], over the keys ``keep`` [B, L, L] marks
    non-zero (every key of the document at or before the query where none is
    given: ``sparse_attn.select_keys`` makes one) and, with a ``window``, of
    those the query's own and the ``window - 1`` before it. ``q`` and ``k`` may
    each be a pair of arrays, a plain part and a second part (latent attention's
    rotary part: (q [B, H, L, D], q_rope [B, H, L, R]) and (k [B, Hkv, L, D],
    k_rope [B, 1, L, R]), the keys' one head shared by all): a score is the
    product over D plus the product over R. Scores are scaled by ``scale``
    ((D + R) ** -0.5 where none is given). On a TPU, for rows of whole blocks
    of 128s, every layer takes ONE Pallas kernel,
    ``attention.flash_attention_widths``: the full softmax layer, whose
    grouped K and V heads it reads where the projections wrote them (8 heads
    under 48 or 64: no copy a query head exists); a second part, which it is
    handed as it is, so that no array as wide as both parts exists and the
    one rotary key head is read by every head's block from where it lies; a
    selection; and a window, where its grid walks the band of block pairs
    alone (a 4,096-key window over 32,768 tokens in blocks of 1,024: 150
    pairs of 528, of a query block's five the oldest compared against the
    window, the newest against the diagonal, the three between neither).
    That kernel sees from two block indices and four segment ids what a pair
    of blocks needs: nothing (the blocks share no document: 28 in 100 of the
    pairs of packed rows of 8,192 tokens, counted); segment ids and no position (under the
    diagonal: 120 of the 136 pairs of a 16,384-token document, where every
    key is seen and the compare is hidden under the products); or positions
    too and half the keys (on the diagonal: 10 of a pair's 16 tiles);
    ``record_pair_kinds`` counts them for a step's rows. Float32 scores,
    maximum, sum and accumulator, probabilities in the values' type into the
    second product, and ``block`` are the same in every kind. Elsewhere (the
    kernel exists for no other backend), and for shapes it does not take,
    ``attention.blockwise_attention``: plain JAX, the same mask, the same
    answer (tests/test_pattern_lm.py, tests/test_mla_lm.py,
    tests/test_swa_lm.py and tests/test_dsa_lm.py hold the kernel to it);
    there, and only there, the two parts are joined and the rotary key head
    is copied to every head.

    ``diffusion`` = n: block diffusion's mask in the triangle's place. The
    row is two streams, the clean one then the noised one, ``segments`` the
    same in both; a token's block is its PLACE in its stream over ``n``, which
    is its block in its own document where every document starts at a whole
    multiple of ``n`` (``TokenPacker(noise=)`` packs so; :func:`score` counts
    the documents that do not: ``starts_off_block``). A clean query sees its
    document's clean keys to the end of its own block, a noised one the clean
    keys before its block and the noised keys of its block
    (``attention.attention_reference`` has the rule). The same kernel on a
    TPU, which walks the pairs the mask can see; ``blockwise_attention``
    elsewhere: one rule for a block in both."""
    if diffusion is not None:
        n, half = diffusion, q.shape[2] // 2
        if _takes_block_kernel(half, v.shape[-1], block, n):
            return flash_attention_widths(q, k, v, segments, scale or q.shape[-1] ** -0.5, block, block,
                                          diffusion_block=n)
        numbers = jnp.broadcast_to(jnp.tile(jnp.arange(half, dtype=jnp.int32) // n, 2), segments.shape)
        out = blockwise_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), segments,
            scale=scale, block=block, blocks=(numbers, n))
        return jnp.swapaxes(out, 1, 2)
    parts = {}
    if isinstance(q, tuple):
        (q, parts["q_rope"]), (k, parts["k_rope"]) = q, k
    (l, d), dv = q.shape[2:], v.shape[-1]
    if _takes_kernel(l, dv, block):
        width = d + (parts["q_rope"].shape[-1] if parts else 0)
        return flash_attention_widths(q, k, v, segments, scale or width ** -0.5, block, block,
                                      keep=keep, window=window, **parts)
    if parts:
        q_rope, k_rope = parts["q_rope"], parts["k_rope"]
        q = jnp.concatenate([q, q_rope], axis=-1)
        k = jnp.concatenate([k, jnp.broadcast_to(k_rope, k.shape[:3] + k_rope.shape[3:])], axis=-1)
    out = blockwise_attention(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2), segments,
        scale=scale, block=block, keep=keep, window=window)
    return jnp.swapaxes(out, 1, 2)


def gqa_mixer(p, x, segments, cfg: PatternLMConfig, sliding: bool = False):
    """The softmax layer: grouped causal attention inside each document; with
    ``cfg.gqa_gate`` (the default: the layers that came first have it) an
    elementwise sigmoid gate ``sigmoid(u wg)`` on its output, without it the
    attention's output goes to ``wo`` as it is and the layer holds no ``wg``;
    with ``cfg.qk_norm`` an RMSNorm over each head of q and of k. Without positions, every key of the
    document before the query; ``sliding`` (the "swa" layers): rotary turns
    over the whole head by each token's index in its own document, and of
    those keys the query's own and the ``cfg.window - 1`` before it. x
    [B, L, D]. Heads are written head-major ``[B, H, L, D]`` by the
    projections, which is how the attention reads them: nothing is transposed."""
    return gqa_mixer_probed(p, x, segments, cfg, sliding)[0]


def gqa_mixer_probed(p, x, segments, cfg: PatternLMConfig, sliding: bool = False, sample_at=None,
                     probe_head=None, streams: bool = False):
    """(:func:`gqa_mixer`'s y, a record of one head's attention or None).
    With ``sample_at`` [B, S] and ``probe_head`` (an int32 scalar naming a
    query head) what the attention call was given and gave, float32, so that
    a caller can walk the same inputs and see which keys a query saw:
    ``record["scan"]`` = that head's keys and values ``k_swa``, ``v_swa``
    [B, L, Dh], ``record["router"]`` = at the sampled positions its queries
    and outputs ``q_swa``, ``att_swa`` [B, S, Dh] and ``swa_pos`` [B, S], a
    position's index in its own document.

    ``streams`` (the "bda" layers, block diffusion): x [B, 2L, D] and
    ``segments`` [B, 2L] are a row's clean stream then its noised one. Rotary
    turns as in a sliding layer, by a token's index in its own document, the
    same in both streams; no window and never a gate; the attention under the
    block mask of ``cfg.diffusion_block`` tokens (:func:`_attend`). Its record:
    ``scan`` = the head's keys and values of each stream ``k_bda``, ``v_bda``,
    ``k_bda_noised``, ``v_bda_noised`` [B, L, Dh]; ``router`` = at the sampled
    positions of each stream the queries and outputs ``q_bda``, ``att_bda``
    (the noised stream's) and ``q_bda_clean``, ``att_bda_clean`` [B, S, Dh],
    and ``bda_pos``, ``bda_block`` [B, S]: a position's index and block in its
    own document."""
    d, h, hkv, dh = x.shape[-1], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    probed = sample_at is not None and probe_head is not None
    around, call = ("tfr.swa_proj", "tfr.swa_attn") if sliding else ("tfr.gqa", "tfr.gqa")
    if streams:
        around, call = "tfr.bda_proj", "tfr.bda_attn"
    with jax.named_scope(around):
        u = _pre_norm(x, p, "attn_norm", cfg)
        q = jnp.einsum("bld,dhk->bhlk", u, p["wq"].reshape(d, h, dh))
        k = jnp.einsum("bld,dhk->bhlk", u, p["wk"].reshape(d, hkv, dh))
        v = jnp.einsum("bld,dhk->bhlk", u, p["wv"].reshape(d, hkv, dh))
        if cfg.qk_norm:
            q = _norm(q, p["q_norm"], cfg)
            k = _norm(k, p["k_norm"], cfg)
        if cfg.qk_norm_whole:
            q = _norm_whole(q, p["q_norm"], cfg)
            k = _norm_whole(k, p["k_norm"], cfg)
        if streams:  # a token's index in its document: one stream's, for both
            half = segments.shape[1] // 2
            at = jnp.tile(segment_positions(segments[:, :half]), (1, 2))
        else:
            at = segment_positions(segments) if sliding or probed else None
        if sliding or streams:
            q, k = rotary(q, at, cfg.rope_theta), rotary(k, at, cfg.rope_theta)
        if probed:  # the call and the record of it read these very arrays (see pattern_hidden's note)
            q, k, v = jax.lax.optimization_barrier((q, k, v))
    with jax.named_scope(call):
        if streams:
            att = _attend(q, k, v, segments, cfg.attn_block, diffusion=cfg.diffusion_block)
        else:
            att = _attend(q, k, v, segments, cfg.attn_block, window=cfg.window if sliding else None)
    record = None
    if probed and streams:
        def of(a, lo):  # [B, H', 2L, Dh] -> one head's rows of the stream that starts at ``lo``, float32
            return jax.lax.dynamic_slice_in_dim(a, lo, half, axis=1).astype(jnp.float32)

        held = probe_head // (h // hkv)
        keys, values = jnp.take(k, held, axis=1), jnp.take(v, held, axis=1)
        asked, given = jnp.take(q, probe_head, axis=1), jnp.take(att, probe_head, axis=1)
        where, pos = sample_at[:, :, None], jnp.take_along_axis(at[:, :half], sample_at, axis=1)
        record = {"scan": {"k_bda": of(keys, 0), "v_bda": of(values, 0),
                           "k_bda_noised": of(keys, half), "v_bda_noised": of(values, half)},
                  "router": {"q_bda": jnp.take_along_axis(of(asked, half), where, axis=1),
                             "att_bda": jnp.take_along_axis(of(given, half), where, axis=1),
                             "q_bda_clean": jnp.take_along_axis(of(asked, 0), where, axis=1),
                             "att_bda_clean": jnp.take_along_axis(of(given, 0), where, axis=1),
                             "bda_pos": pos, "bda_block": pos // cfg.diffusion_block}}
    elif probed:
        def sampled(a):  # [B, H, L, Dh] -> the probed head's rows at sample_at, [B, S, Dh]
            return jnp.take_along_axis(jnp.take(a, probe_head, axis=1), sample_at[:, :, None],
                                       axis=1).astype(jnp.float32)

        held = probe_head // (h // hkv)
        record = {"scan": {"k_swa": jnp.take(k, held, axis=1).astype(jnp.float32),
                           "v_swa": jnp.take(v, held, axis=1).astype(jnp.float32)},
                  "router": {"q_swa": sampled(q), "att_swa": sampled(att),
                             "swa_pos": jnp.take_along_axis(at, sample_at, axis=1)}}
    with jax.named_scope(around):
        if cfg.gqa_gate and not streams:
            gate = jax.nn.sigmoid(
                jnp.einsum("bld,dhk->bhlk", u, p["wg"].reshape(d, h, dh)).astype(jnp.float32))
            gated = (att.astype(jnp.float32) * gate).astype(x.dtype)
        else:
            gated = att.astype(x.dtype)
        return jnp.einsum("bhlk,hkd->bld", gated, p["wo"].reshape(h, dh, d)), record


def segment_positions(segments):
    """Each token's index in its own document, [B, L] int32 from ``segments``
    [B, L]: its index in the row less the index of its segment's first token
    (a running maximum over the boundaries' indices); pads get 0."""
    at = jnp.arange(segments.shape[1], dtype=jnp.int32)[None]
    starts = jnp.concatenate(
        [jnp.ones_like(segments[:, :1], bool), segments[:, 1:] != segments[:, :-1]], axis=1)
    first = jax.lax.cummax(jnp.where(starts, at, 0), axis=1)
    return jnp.where(segments != 0, at - first, 0)


def rotary(x, positions, theta: float, scaling=()):
    """x [B, H, L, R] turned by its tokens' ``positions`` [B, L]: the pair
    (i, i + R/2) by the angle ``position * theta ** (-2i / R)``, angles and
    products in float32, rounded to x's dtype. ``scaling``: YaRN's four
    numbers, which slow the frequencies that turn too rarely over the
    original length to have been learnt (``sparse_attn.yarn_blend``)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if scaling:
        freq = freq * _sa.yarn_blend(half, theta, scaling)
    angle = positions.astype(jnp.float32)[:, None, :, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


def mla_mixer(p, x, segments, cfg: PatternLMConfig):
    """The latent-attention layer in its expanded (prefill) form: queries
    projected whole (or, with ``cfg.q_rank``, expanded from a normed latent
    of that rank), keys and values expanded per head from one normed
    latent of ``kv_rank``, a rotary part on every query head and ONE rotary
    key head shared by all, positions that restart at every document (YaRN's
    frequencies and softmax gain with ``cfg.rope_scaling``);
    causal softmax inside each document over ``qk_nope_dim + qk_rope_dim``
    wide queries and keys against ``v_head_dim`` wide values; with
    ``cfg.index_topk`` over the keys an indexer chose (:func:`index_select`).
    x [B, L, D]. Heads are written head-major ``[B, H, L, .]`` by the
    projections, and each of the four things the attention reads is an array
    of its own: the plain queries and keys (``qk_nope_dim``), the values, the
    rotary queries and the one rotary key head (``qk_rope_dim``), from the
    plain and the rotary columns of ``wq_b`` (or ``wq``) and the key and the
    value columns of ``wkv_b``, taken as views of the weights. A score is
    ``q_nope . k_nope + q_pe . k_pe``, so nothing is gained by joining them
    first: a joined array would be written, read and written again around the
    rotary turn, the shared rotary key stored once a head, and the values cut
    out of a wider array by a copy (:func:`_attend` hands the parts on). With
    ``cfg.attn_gate`` the attention's output is weighed, a head and channel, by
    a sigmoid of the layer's own normed input (``wg``) before ``wo``, as the
    softmax layer's is."""
    return mla_mixer_probed(p, x, segments, cfg)[0]


def mla_mixer_probed(p, x, segments, cfg: PatternLMConfig, sample_at=None):
    """(:func:`mla_mixer`'s y, :func:`index_select`'s record of the selection
    or None where the layer has no indexer)."""
    d, h = x.shape[-1], cfg.n_heads
    dn, dr, dv, rank = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_rank
    yarn = (tuple(cfg.rope_scaling),) if cfg.rope_scaling else ()

    def turn(a):  # by the tokens' positions in their own documents
        return rotary(a, at, cfg.rope_theta, *yarn)

    with jax.named_scope("tfr.mla_proj"):
        u = _pre_norm(x, p, "attn_norm", cfg)
        if cfg.q_rank:
            c_q = _norm(u @ p["wq_a"], p["q_norm"], cfg)
            q_in, wq = c_q, p["wq_b"].reshape(cfg.q_rank, h, dn + dr)
        else:
            q_in, wq = u, p["wq"].reshape(d, h, dn + dr)
        latent = u @ p["wkv_a"]                                          # [B, L, rank + dr]
        c = _norm(latent[..., :rank], p["kv_norm"], cfg)
        wkv = p["wkv_b"].reshape(rank, h, dn + dv)
        k = jnp.einsum("blr,rhk->bhlk", c, wkv[..., :dn])
        v = jnp.einsum("blr,rhk->bhlk", c, wkv[..., dn:])
        at = segment_positions(segments)
        k_pe = turn(latent[:, None, :, rank:])                           # one head, for all
        q = jnp.einsum("bld,dhk->bhlk", q_in, wq[..., :dn])
        q_pe = turn(jnp.einsum("bld,dhk->bhlk", q_in, wq[..., dn:]))
    chosen, index = {}, None
    if yarn:
        chosen["scale"] = (dn + dr) ** -0.5 * _sa.yarn_softmax_gain(cfg.rope_scaling)
    if cfg.index_topk:
        chosen["keep"], index = index_select(p, u, c_q, turn, at, segments, cfg, sample_at)
    with jax.named_scope("tfr.mla_attn"):
        att = _attend((q, q_pe), (k, k_pe), v, segments, cfg.attn_block, **chosen)
    with jax.named_scope("tfr.mla_proj"):
        if cfg.attn_gate:
            gate = jax.nn.sigmoid(
                jnp.einsum("bld,dhk->bhlk", u, p["wg"].reshape(d, h, dv)).astype(jnp.float32))
            att = (att.astype(jnp.float32) * gate).astype(x.dtype)
        return jnp.einsum("bhlk,hkd->bld", att, p["wo"].reshape(h, dv, d)), index


def index_select(p, u, c_q, turn, at, segments, cfg: PatternLMConfig, sample_at=None):
    """The lightning indexer of a latent-attention layer and its selection:
    ``index_heads`` queries of ``index_dim`` from the query latent c_q
    [B, L, q_rank], ONE key a token from the normed input u [B, L, D] through
    a LayerNorm, the layer's rotary ``turn`` on the first ``qk_rope_dim`` columns
    of both (``at``: the positions it turns by), a weight a head from u; then
    ``sparse_attn.select_keys``: every query's ``index_topk`` best keys inside
    its own document. Returns (keep [B, L, L] int8, record): ``record["counts"]``
    int32 [2], the keys kept by and the candidates of the row's real queries;
    with ``sample_at`` [B, S] also ``record["scan"]`` = {"k_index" [B, L, Di]}
    and ``record["router"]`` = what the selection was made from at those
    positions, float32: "q_index" [B, S, Hi, Di], "w_index" [B, S, Hi], "kept"
    [B, S, L] int8 (the mask's rows), "index_pos" and "index_start" [B, S] (a
    position's index in its document and its document's first index in the row)."""
    hi, di, dr, f32 = cfg.index_heads, cfg.index_dim, cfg.qk_rope_dim, jnp.float32
    with jax.named_scope("tfr.dsa_proj"):
        q_idx = jnp.einsum("blr,rhk->bhlk", c_q, p["wq_idx"].reshape(cfg.q_rank, hi, di))
        k32 = (u @ p["wk_idx"]).astype(f32)
        k32 = k32 - k32.mean(axis=-1, keepdims=True)
        k32 = k32 * jax.lax.rsqrt(jnp.mean(jnp.square(k32), axis=-1, keepdims=True) + cfg.norm_eps)
        k_idx = (k32 * p["k_idx_norm"] + p["k_idx_bias"]).astype(u.dtype)[:, None]
        q_idx = jnp.concatenate([turn(q_idx[..., :dr]), q_idx[..., dr:]], axis=-1)
        k_idx = jnp.concatenate([turn(k_idx[..., :dr]), k_idx[..., dr:]], axis=-1)[:, 0]
        w = jnp.dot(u, p["w_idx"], preferred_element_type=f32) * (hi ** -0.5 * di ** -0.5)
        # the selection and the record of it read these very arrays (see pattern_hidden's note)
        q_idx, k_idx, w = jax.lax.optimization_barrier((q_idx, k_idx, w))
    with jax.named_scope("tfr.dsa_index"):
        keep, kept = _sa.select_keys(q_idx, k_idx, w, segments, cfg.index_topk, cfg.attn_block)
        real = segments != 0
        record = {"counts": jnp.stack([jnp.where(real, kept, 0).sum(),
                                       jnp.where(real, at + 1, 0).sum()])}
    if sample_at is not None:
        def take(a):
            return jnp.take_along_axis(a, sample_at.reshape(sample_at.shape + (1,) * (a.ndim - 2)),
                                       axis=1)

        pos = take(at)
        record["scan"] = {"k_index": k_idx.astype(f32)}
        record["router"] = {"q_index": take(jnp.swapaxes(q_idx, 1, 2)).astype(f32),
                            "w_index": take(w), "kept": take(keep), "index_pos": pos,
                            "index_start": sample_at - pos}
    return keep, record


def kda_mixer(p, x, segments, cfg: PatternLMConfig, probe_head=None):
    """The gated delta-rule layer (models.linear_attn has the equations):
    projections, a 4-tap convolution and SiLU on q, k, v, unit-norm q and k
    (``linear_attn.prepared``: q, k and v reach the recurrence in the dtype the
    projections wrote them), a per-channel decay (float32, of v's shape) and
    a per-head beta in (0, 2), the chunked recurrence, a per-head RMSNorm and
    a low-rank sigmoid gate. Everything per head is head-major
    ``[B, H, L, D]`` from projection to projection. ``linear_attn.delta_rule_layer``
    takes the projections and the taps: on a TPU its kernel prepares its own
    tiles and ``tfr.kda_conv`` holds no operation.

    Returns (y, probe). ``probe`` is None unless ``probe_head`` (an int32
    scalar) names a head: then what the chunked recurrence was given and what
    it gave for that head, ``q``, ``k``, ``v``, ``log_decay``, ``o``
    [B, L, D] and ``beta`` [B, L], float32 for that head alone, so that a
    caller can walk the same inputs token by token and see what the state's
    precision cost."""
    d, h, dh, f32 = x.shape[-1], cfg.kda_heads, cfg.kda_head_dim, jnp.float32

    def heads(a, w):  # a [B, L, m] through w [m, H * dh] -> [B, H, L, dh]
        return jnp.einsum("blm,mhk->bhlk", a, w.reshape(w.shape[0], h, dh))

    with jax.named_scope("tfr.kda_proj"):
        u = _pre_norm(x, p, "attn_norm", cfg)
        q, k, v = heads(u, p["wq"]), heads(u, p["wk"]), heads(u, p["wv"])
        rate = jax.nn.softplus(
            heads(u @ p["f_down"], p["f_up"]).astype(f32) + p["f_bias"].reshape(h, 1, dh))
        log_decay = -jnp.exp(p["a_log"])[:, None, None] * rate
        beta = 2.0 * jax.nn.sigmoid(jnp.einsum("bld,dh->bhl", u, p["w_beta"]).astype(f32))
        gate = jax.nn.sigmoid(heads(u @ p["g_down"], p["g_up"]).astype(f32))
    o, handed = _la.delta_rule_layer(
        q, k, v, (p["conv_q"], p["conv_k"], p["conv_v"]), log_decay, beta, segments, scale=dh ** -0.5,
        chunk=cfg.kda_chunk, scope="tfr.kda", handed=probe_head is not None)
    probe = None
    if probe_head is not None:
        q, k, v = handed
        probe = {name: jnp.take(a, probe_head, axis=1).astype(f32) for name, a in dict(
            q=q, k=k, v=v, log_decay=log_decay, beta=beta, o=o).items()}
    with jax.named_scope("tfr.kda_proj"):
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps)
        o = (o * p["o_norm"] * gate).astype(x.dtype)
        return jnp.einsum("bhlk,hkd->bld", o, p["wo"].reshape(h, dh, d)), probe


def gdn_mixer(p, x, segments, cfg: PatternLMConfig, probe_head=None):
    """The gated delta-net layer: :func:`kda_mixer`'s convolution, unit norms
    and recurrence with ONE decay a head and token,
    ``exp(-exp(a_log) softplus(u w_a + dt_bias))``, a beta in (0, 1) or, with
    ``cfg.gdn_neg_eigval``, ``2 sigmoid(.)`` in (0, 2) (a negative eigenvalue
    of the transition allowed), ``cfg.gdn_key_heads`` key heads under
    ``cfg.kda_heads`` value heads (value head h reads key head ``h //
    (kda_heads / gdn_key_heads)``), keys ``cfg.kda_head_dim`` wide under values
    ``cfg.gdn_value_dim`` wide (0: as wide; else the state is [d_k, d_v] and
    not square; scores are scaled by ``d_k ** -0.5``), and a full-rank output
    gate on the per-head RMSNorm over a head's d_v: ``2 sigmoid(u wz)`` or,
    with ``cfg.gdn_gate == "silu"``, ``silu(u wz)``. The recurrence is handed
    what the mechanism has: q and k at their own heads and widths and v as
    ``linear_attn.prepared`` leaves them (prepared once a KEY head, on a TPU
    by the kernel itself: ``linear_attn.delta_rule_layer``), a decay and a
    beta ``[B, H, L]``; nothing as large as v is float32 before the
    recurrence's own output.

    Returns (y, probe) as :func:`kda_mixer` does: with ``probe_head`` (a value
    head) that head's ``v``, ``o`` [B, L, Dv], ``log_decay``, ``beta`` [B, L]
    and its key head's ``q``, ``k`` [B, L, Dk], float32 for that head alone."""
    d, h, dh, f32 = x.shape[-1], cfg.kda_heads, cfg.kda_head_dim, jnp.float32
    hk, dv = cfg.gdn_key_heads or h, cfg.gdn_value_dim or cfg.kda_head_dim

    def heads(a, w, n, width=dh):  # a [B, L, m] through w [m, n * width] -> [B, n, L, width]
        return jnp.einsum("blm,mhk->bhlk", a, w.reshape(w.shape[0], n, width))

    def by_head(a, w):  # a [B, L, m] through w [m, H] -> [B, H, L] float32
        return jnp.einsum("bld,dh->bhl", a, w).astype(f32)

    with jax.named_scope("tfr.gdn_proj"):
        u = _pre_norm(x, p, "attn_norm", cfg)
        q, k, v = heads(u, p["wq"], hk), heads(u, p["wk"], hk), heads(u, p["wv"], h, dv)
        rate = jax.nn.softplus(by_head(u, p["w_a"]) + p["dt_bias"][:, None])
        log_decay = -jnp.exp(p["a_log"])[:, None] * rate
        beta = jax.nn.sigmoid(by_head(u, p["w_beta"]))
        if cfg.gdn_neg_eigval:
            beta = 2.0 * beta
    o, handed = _la.delta_rule_layer(
        q, k, v, (p["conv_q"], p["conv_k"], p["conv_v"]), log_decay, beta, segments, scale=dh ** -0.5,
        chunk=cfg.kda_chunk, scope="tfr.gdn", handed=probe_head is not None)
    probe = None
    if probe_head is not None:
        q, k, v = handed
        key_head = probe_head // (h // hk)
        probe = {name: jnp.take(a, at, axis=1).astype(f32) for name, (a, at) in dict(
            q=(q, key_head), k=(k, key_head), v=(v, probe_head), log_decay=(log_decay, probe_head),
            beta=(beta, probe_head), o=(o, probe_head)).items()}
    with jax.named_scope("tfr.gdn_proj"):
        z = heads(u, p["wz"], h, dv)
        if dv % 128 and jax.default_backend() == "tpu":
            # ONE array in the dtype the projection writes. Left alone at a width that fills no whole
            # lane block, the TPU compiler widens inside the projection, writes float32 with the tokens
            # along the lanes and copies it into the layout the recurrence's output has: 0.6 GB more
            # written a layer and 0.5 GB more held (the layer compiled for a described v5e; at whole
            # 128s it writes bfloat16 where the gate reads it, and the program is the one it was)
            z = jax.lax.optimization_barrier(z)
        z = z.astype(f32)
        gate = jax.nn.silu(z) if cfg.gdn_gate == "silu" else 2.0 * jax.nn.sigmoid(z)
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps)
        o = (o * p["o_norm"] * gate).astype(x.dtype)
        return jnp.einsum("bhlk,hkd->bld", o, p["wo"].reshape(h, dv, d)), probe


def ssm_mixer(p, x, segments, cfg: PatternLMConfig, probe_head=None):
    """The state-space layer (Mamba-2's; ``models.linear_attn`` has the
    recurrence): ONE projection in, cut by columns into the gate z, the
    convolution's input ``[x | B | C]`` and a step a head (three products over
    views of ``w_in``: no slice of a wider array is copied); one causal
    convolution of ``cfg.conv_taps`` taps with a bias over all of ``[x | B | C]``
    and SiLU (no unit norm), its output behind a barrier in the dtype it was
    written: the recurrence and a probe of it read that very array,
    token-major, the heads' x by column block and a group's B and C from where
    they lie; ``dt = softplus(. + dt_bias)``, ONE decay ``exp(-exp(a_log) dt)``
    a head and token, both float32 ``[B, L, H]``; the skip ``d_skip * x``; the
    gate BEFORE the norm, ``rms(y * silu(z))`` over each group's channels; out.

    Returns (y, probe) as :func:`gdn_mixer` does: with ``probe_head`` that
    head's ``x``, ``o`` [B, L, P], its group's ``b``, ``c`` [B, L, N] and its
    ``dt``, ``log_decay`` [B, L], float32 for that head alone."""
    h, ph, n, g, f32 = cfg.kda_heads, cfg.kda_head_dim, cfg.ssm_state, cfg.ssm_groups, jnp.float32
    inner, bc = h * ph, g * n
    with jax.named_scope("tfr.ssm_proj"):
        u = _pre_norm(x, p, "attn_norm", cfg)
        z = u @ p["w_in"][:, :inner]
        xbc = u @ p["w_in"][:, inner:2 * inner + 2 * bc]
        dt = jax.nn.softplus(jnp.dot(u, p["w_in"][:, 2 * inner + 2 * bc:], preferred_element_type=f32)
                             + p["dt_bias"])
        log_decay = -jnp.exp(p["a_log"]) * dt
    with jax.named_scope("tfr.ssm_conv"):
        mixed = _la.short_conv(xbc[:, None], p["conv_x"][:, None], segments, p["conv_bias"][None])[:, 0]
        xbc = jax.lax.optimization_barrier(jax.nn.silu(mixed.astype(f32)).astype(xbc.dtype))
    with jax.named_scope("tfr.ssm_scan"):
        o = _la.ssm_chunked(xbc, dt, log_decay, segments, h, g, n, chunk=cfg.kda_chunk)
    probe = None
    if probe_head is not None:
        group = probe_head // (h // g)

        def cut(a, first, width):  # [B, L, .] -> the columns first .. first + width, float32
            return jax.lax.dynamic_slice_in_dim(a, first, width, axis=2).astype(f32)

        probe = {"x": cut(xbc, probe_head * ph, ph), "b": cut(xbc, inner + group * n, n),
                 "c": cut(xbc, inner + bc + group * n, n), "dt": jnp.take(dt, probe_head, axis=2),
                 "log_decay": jnp.take(log_decay, probe_head, axis=2), "o": cut(o, probe_head * ph, ph)}
    with jax.named_scope("tfr.ssm_proj"):
        # everything stays [B, L, channels]: cut into [.., heads, P] or [.., groups, 512] the channels
        # would change tiles, and each float32 array of o's size would be copied into the new order
        # (three copies a layer, 0.8 ms each on a v5e). So the skip's gain is spread to a channel
        # each, and a group's mean square is a product with the groups' indicator, there and back.
        y = o + xbc[..., :inner].astype(f32) * jnp.repeat(p["d_skip"], ph)
        y = y * jax.nn.silu(z.astype(f32))
        in_group = (jnp.arange(inner)[:, None] // (inner // g) == jnp.arange(g)).astype(f32)
        mean_sq = jnp.einsum("blc,cg->blg", jnp.square(y), in_group, precision=_la._HIGHEST) / (inner // g)
        scale = jnp.einsum("blg,cg->blc", jax.lax.rsqrt(mean_sq + cfg.norm_eps), in_group,
                           precision=_la._HIGHEST)
        return (y * scale * p["o_norm"]).astype(x.dtype) @ p["wo"], probe


#: where a mixer's branch norm is counted: with the layer's other projections
_BRANCH_SCOPE = {"gqa": "tfr.gqa", "swa": "tfr.swa_proj", "mla": "tfr.mla_proj", "kda": "tfr.kda_proj",
                 "gdn": "tfr.gdn_proj", "ssm": "tfr.ssm_proj", "bda": "tfr.bda_proj"}
_RECURRENT = {"kda": kda_mixer, "gdn": gdn_mixer, "ssm": ssm_mixer}


def _joined(x, y, weight, cfg: PatternLMConfig, scope: str):
    """The residual stream after a branch: ``x + y``, or under
    ``cfg.branch_norms`` (sandwich norms: ``weight`` is the branch's own)
    ``x + rms(y; weight)``."""
    if weight is None:
        return x + y
    with jax.named_scope(scope):
        return x + _norm(y, weight, cfg)


def pattern_hidden(params, tokens, segment_ids, cfg: PatternLMConfig, sample_at=None,
                   probe_head=None, noised=None):
    """tokens, segment_ids [B, L+1] -> (x [B, L, D] before the final norm,
    visits [n_layers, experts_held], dropped [n_layers], probes);
    ``n_layers`` counts the layers that have experts.

    ``probes`` is what a caller checks single layers by, on the layer's own
    inputs: ``router`` (with ``sample_at`` [B, S]: at those positions of
    every expert layer the router's input ``u`` [n_layers, B, S, D] and its
    ``experts`` and ``gates`` [n_layers, B, S, top_k]) and ``scan`` (with
    ``probe_head``: :func:`kda_mixer`'s, :func:`gdn_mixer`'s or
    :func:`ssm_mixer`'s probe of the first recurrent layer).
    Where latent-attention layers have an indexer, ``selected`` [layers, 2]
    (:func:`index_select`'s counts) and, with ``sample_at``, the first expert
    layer's selection: its keys under ``scan`` and the rest beside the
    router's entries, each with a leading axis of 1. Likewise, with both
    ``sample_at`` and ``probe_head``, the first sliding-window layer's record
    of that head's attention (:func:`gqa_mixer_probed`).

    Under ``cfg.diffusion_block`` (block diffusion; ``noised`` [B, L+1], the
    row with a share of its tokens replaced by ``cfg.mask_id``, is then
    required) every layer runs TWO streams as one row of 2 L positions, the
    clean row then the noised one: x comes back [B, 2L, D]; the streams share
    every projection, the router and the one expert loop a layer (a pad of
    either stream visits no expert) and part only inside the attention
    (:func:`_attend`). The router's probe is then of the NOISED stream's
    positions ``sample_at``, and the first layer's attention is recorded as a
    sliding layer's is (:func:`gqa_mixer_probed`)."""
    l = tokens.shape[1] - 1
    if l != cfg.max_len:
        raise ValueError(
            f"packed batch carries {l} input tokens but cfg.max_len is {cfg.max_len} "
            f"(the packer's seq_len must match)")
    segments = segment_ids[:, :-1]
    if bool(cfg.diffusion_block) != (noised is not None):
        raise ValueError("a block-diffusion pattern is scored from the clean row AND the noised one; "
                         "no other pattern takes a noised row")
    with jax.named_scope("tfr.embed"):
        if noised is not None:  # two streams, one row: what follows sees 2 L positions
            x = params["embed"][jnp.concatenate([tokens[:, :-1], noised[:, :-1]], axis=1)]
            segments, l = jnp.tile(segments, (1, 2)), 2 * l
            if sample_at is not None:
                sample_at = sample_at + l // 2
        else:
            x = params["embed"][tokens[:, :-1]]
        if cfg.embed_scale:
            x = (x.astype(jnp.float32) * cfg.d_model ** 0.5).astype(x.dtype)
    b, _, d = x.shape
    limit = cfg.swiglu_limit or None
    visits, dropped, routed, probes, selected, selection = [], [], [], {}, [], None
    for kind, ffn, layer in zip(cfg.layer_pattern, ffn_kinds(cfg), params["layers"]):
        if kind == "swa" and "scan" not in probes:  # the first sliding layer, one head probed
            y, window = gqa_mixer_probed(layer, x, segments, cfg, True, sample_at, probe_head)
            if window is not None:
                selection, probes["scan"] = window["router"], window["scan"]
        elif kind == "bda":  # likewise the first block-diffusion layer (its record samples a stream's own places)
            first = "scan" not in probes and sample_at is not None
            y, window = gqa_mixer_probed(layer, x, segments, cfg, False, sample_at - l // 2 if first else None,
                                         probe_head if first else None, streams=True)
            if window is not None:
                selection, probes["scan"] = window["router"], window["scan"]
        elif kind in ("gqa", "swa"):
            y = gqa_mixer(layer, x, segments, cfg, sliding=kind == "swa")
        elif kind == "mla" and cfg.index_topk:
            probed = selection is None and ffn == "moe"
            y, index = mla_mixer_probed(layer, x, segments, cfg, sample_at if probed else None)
            selected.append(index["counts"])
            if probed and sample_at is not None:
                selection, probes["scan"] = index["router"], index["scan"]
        elif kind == "mla":
            y = mla_mixer(layer, x, segments, cfg)
        elif kind != NONE:
            y, scan = _RECURRENT[kind](layer, x, segments, cfg, None if "scan" in probes else probe_head)
            if scan is not None:
                probes["scan"] = scan
        if kind != NONE:  # else the layer is its feed-forward part alone
            x = _joined(x, y, layer.get("post_attn_norm"), cfg, _BRANCH_SCOPE[kind])
        if ffn == NONE:   # the layer is its mixer alone
            continue
        if ffn == "dense":
            with jax.named_scope("tfr.dense_ffn"):
                u = _pre_norm(x, layer, "ffn_norm", cfg)
                w = layer["dense"]
                y = _moe.gated_ffn(u, w["w_gate"], w["w_up"], w["w_down"], limit).astype(x.dtype)
                x = _joined(x, y, layer.get("post_ffn_norm"), cfg, "tfr.dense_ffn")
            continue
        with jax.named_scope("tfr.moe_route"):
            u = _pre_norm(x, layer, "moe_norm", cfg)
            if cfg.n_group > 1 or cfg.branch_norms or kind in (NONE, "bda"):
                # ONE array for the router and for the probe of it. Left alone, the compiler
                # computes the norm once for each reader, the two fusions round a few
                # elements in a thousand to different bfloat16 neighbours, and the probe held
                # the router to inputs it never saw (gates 2.6e-4 apart on the chip where
                # float32 reads 2e-7; under sandwich norms 5.3e-4). So does every pattern since,
                # a layer that is its experts alone among them; the patterns that came before
                # either keep the program they had (tests/test_mla_lm.py holds their jaxprs).
                u = jax.lax.optimization_barrier(u)
        y, n, lost, (experts, gates) = _moe.held_experts_apply(
            layer, u.reshape(b * l, d), held_offset=cfg.held_offset, top_k=cfg.top_k,
            routed_scale=cfg.routed_scale, tile=cfg.expert_tile,
            valid=(segments != 0).reshape(b * l), n_group=cfg.n_group, topk_group=cfg.topk_group,
            limit=limit, scoring=cfg.router_scoring)
        x = _joined(x, y.reshape(b, l, d), layer.get("post_ffn_norm"), cfg, "tfr.moe_experts")
        visits.append(n)
        dropped.append(lost)
        if sample_at is not None:
            at = sample_at[:, :, None]
            routed.append({"u": jnp.take_along_axis(u, at, axis=1),
                           "experts": jnp.take_along_axis(experts.reshape(b, l, -1), at, axis=1),
                           "gates": jnp.take_along_axis(gates.reshape(b, l, -1), at, axis=1)})
    if routed:
        probes["router"] = {k: jnp.stack([r[k] for r in routed]) for k in routed[0]}
    if selected:
        probes["selected"] = jnp.stack(selected)
    if selection is not None:
        probes.setdefault("router", {}).update({k: a[None] for k, a in selection.items()})
    if not visits:  # no layer has experts
        return x, jnp.zeros((0, cfg.experts_held), jnp.int32), jnp.zeros((0,), jnp.int32), probes
    return x, jnp.stack(visits), jnp.stack(dropped), probes


def score(params, tokens, segment_ids, sample_at, cfg: PatternLMConfig, probe_head=None, noised=None):
    """The scoring step over one packed batch. Returns a dict:

    ``logprob`` [B, L] float32: log p(tokens[:, t+1] | its document up to t)
        over the vocabulary held here; 0 where t+1 is a pad or another document
    ``logits``  [B, S, V] float32 at the positions ``sample_at`` [B, S]
    ``visits``  [n_layers, experts_held] int32, ``dropped`` [n_layers] int32
    ``probes``  :func:`pattern_hidden`'s: the router's inputs and choices at
        ``sample_at``, and with ``probe_head`` that head's recurrence
        (``scan`` is ``{}`` where the pattern has no delta-rule layer and no
        indexer; with an indexer it and ``router`` carry a layer's selection)
    ``selected`` [layers, 2] int32, only where layers have an indexer: the keys
        kept by and the candidates of the real queries (:func:`record_selected`)

    The head's float32 logits never exist whole: on a TPU they exist a VMEM
    tile at a time, inside ``models.head``'s kernel (the product, the running
    log-sum-exp and the target's pick; gauge ``head.fused`` 1), elsewhere and
    at shapes ``head.head_tile`` declines a block of ``cfg.head_block`` tokens
    at a time (the plain form; ``head.fused`` 0). The sampled positions' are an
    einsum over a handful of rows.

    A block-diffusion pattern (``cfg.diffusion_block``; ``TokenPacker(noise=)``
    feeds it) takes ``noised`` [B, L+1] beside the clean row, runs both
    streams through every layer (:func:`pattern_hidden`) and hands the noised
    one alone to the head. Nothing is shifted there: ``logprob`` [B, L] is
    ``log p(tokens[:, i])`` from the noised stream's position ``i`` where the
    noised row holds ``cfg.mask_id`` (a masked position's logits are over its
    OWN token), 0 elsewhere (pads, positions left as they were);
    ``logits`` and the router's probe are the noised stream's at
    ``sample_at``; ``probes`` carries the first layer's attention on its own
    inputs (:func:`gqa_mixer_probed`). A document's bound is the caller's sum:
    ``sum over blocks of (1 / t) sum of -logprob`` with the feed's ``noise_level``.
    Every document has to start at a whole multiple of the block length in its
    row, as that packer starts them: the attention takes a token's block from
    its place (:func:`_attend`), on every backend. ``starts_off_block`` (int32)
    counts the batch's documents that do not, whose scores are then of another
    mask; a caller holds it to 0."""
    from tpu_tfrecord.metrics import METRICS

    x, visits, dropped, probes = pattern_hidden(params, tokens, segment_ids, cfg, sample_at,
                                                probe_head, noised)
    if not set(_RECURRENT) & set(cfg.layer_pattern):
        probes.setdefault("scan", {})
    # the delta-rule layers of a kind have one shape, so one answer a kind of the
    # function that decides the dispatch (as the program is traced, not as it runs)
    dk, dv = cfg.kda_head_dim, cfg.gdn_value_dim or cfg.kda_head_dim

    def fused(width: int) -> bool:  # values ``width`` wide under keys of ``dk``
        return _la.fused_tile((tokens.shape[0], cfg.kda_heads, cfg.max_len, width), cfg.kda_chunk, dk) is not None

    kda_fused, gdn_fused = (cfg.layer_pattern.count(kind) if fused(width) else 0
                            for kind, width in (("kda", dk), ("gdn", dv)))
    METRICS.gauge("kda.fused_layers", kda_fused)
    # and of those layers, of either decay, the ones whose kernel prepared q, k and v from the projections
    METRICS.gauge("conv.kernel_layers", kda_fused + gdn_fused)
    if "gdn" in cfg.layer_pattern:  # the same kernel under its other decay, and how the key heads are shared
        METRICS.gauge("gdn.fused_layers", gdn_fused)
        METRICS.gauge("gdn.key_group", cfg.kda_heads // (cfg.gdn_key_heads or cfg.kda_heads))
        # the state a head keeps in the kernel, and what of the lanes its tiles of q, k and v occupy is published
        METRICS.gauge("gdn.state_shape", dk * dv if gdn_fused else 0)
        METRICS.gauge("gdn.lane_fill", round(_la.lane_fill(dk, dv), 6) if gdn_fused else 0.0)
    if "ssm" in cfg.layer_pattern:  # the state-space layers' own kernel, and the heads that share a B and a C
        width = cfg.kda_heads * cfg.kda_head_dim + 2 * cfg.ssm_groups * cfg.ssm_state
        in_kernel = _la.ssm_tile((tokens.shape[0], cfg.max_len, width), cfg.dtype, cfg.kda_heads, cfg.ssm_groups,
                                 cfg.ssm_state, cfg.kda_chunk) is not None
        METRICS.gauge("ssm.fused_layers", cfg.layer_pattern.count("ssm") if in_kernel else 0)
        METRICS.gauge("ssm.group", cfg.kda_heads // cfg.ssm_groups)
    # likewise the selection: one shape for every layer that has an indexer
    in_kernel = cfg.index_topk and _sa.select_tile(
        (tokens.shape[0], cfg.index_heads, cfg.max_len, cfg.index_dim), cfg.index_topk) is not None
    METRICS.gauge("dsa.kernel_layers", cfg.layer_pattern.count("mla") if in_kernel else 0)
    # and the attention call of every latent-attention layer: handed q and k in their two parts
    in_kernel = _takes_kernel(cfg.max_len, cfg.v_head_dim, cfg.attn_block)
    METRICS.gauge("mla.split_layers", cfg.layer_pattern.count("mla") if in_kernel else 0)
    # and of every softmax layer, full or under a window: grouped K/V heads read as they lie
    in_kernel = _takes_kernel(cfg.max_len, cfg.head_dim, cfg.attn_block)
    METRICS.gauge("gqa.kernel_layers", cfg.layer_pattern.count("gqa") if in_kernel else 0)
    # and the expert loop's tiling: the rows of a tail tile, 0 where the one loop of whole tiles runs
    global _last_scored
    _last_scored = cfg
    unit = _moe.row_unit(cfg.expert_tile)
    METRICS.gauge("moe.tail_unit", unit if unit != cfg.expert_tile else 0)
    if "swa" in cfg.layer_pattern:  # one shape for every sliding layer
        METRICS.gauge("swa.kernel_layers", cfg.layer_pattern.count("swa") if in_kernel else 0)
        tile = min(cfg.attn_block, cfg.max_len)
        one_document = np.ones((1, -(-cfg.max_len // tile) * tile), np.int32)
        band, triangle = (sum(pair_kinds(one_document, tile, tile, w)) for w in (cfg.window, None))
        METRICS.gauge("swa.pairs_walked_share", round(band / triangle, 6))
    if cfg.diffusion_block:  # one shape for every layer: the kernel under the block mask, and what its grid walks
        n, tile = cfg.diffusion_block, min(cfg.attn_block, cfg.max_len)
        in_kernel = _takes_block_kernel(cfg.max_len, cfg.head_dim, cfg.attn_block, n)
        METRICS.gauge("bda.kernel_layers", cfg.layer_pattern.count("bda") if in_kernel else 0)
        METRICS.gauge("bda.block", n)
        a_stream = np.ones((1, -(-cfg.max_len // tile) * tile), np.int32)
        walked = sum(pair_kinds(np.tile(a_stream, 2), tile, tile, streams=True))
        METRICS.gauge("bda.pairs_walked_share", round(walked / (2 * sum(pair_kinds(a_stream, tile, tile))), 6))
        with jax.named_scope("tfr.lm_head"):
            x = x[:, cfg.max_len:]  # the noised stream alone goes to the head
    b, l, d = x.shape
    with jax.named_scope("tfr.lm_head"):
        xn = _norm(x, params["final_norm"], cfg)
        shifted = not cfg.diffusion_block  # a masked position's logits are over its own token
        flat, targets = xn.reshape(b * l, d), tokens[:, 1:] if shifted else tokens[:, :-1]
        out, fused = _head.logprob(flat, params["head"], targets.reshape(b * l), cfg.head_block)
        # 1 where the logits stay in VMEM a tile at a time, 0 where the plain form's blocks run
        METRICS.gauge("head.fused", int(fused))
        if shifted:
            scored = (segment_ids[:, 1:] == segment_ids[:, :-1]) & (segment_ids[:, :-1] != 0)
        else:
            scored = (noised[:, :-1] == cfg.mask_id) & (segment_ids[:, :-1] != 0)
        logprob = jnp.where(scored, out.reshape(b, l), 0.0)
        sampled = jnp.take_along_axis(xn, sample_at[:, :, None], axis=1)
        sample_logits = jnp.einsum("bsd,dv->bsv", sampled, params["head"],
                                   preferred_element_type=jnp.float32)
    out = {"logprob": logprob, "logits": sample_logits, "visits": visits, "dropped": dropped,
           "probes": probes}
    if cfg.diffusion_block:  # what the attention's rule for a block asks of the rows (:func:`_attend`): stays 0
        segs = segment_ids[:, :-1]
        first = jnp.concatenate([segs[:, :1], jnp.where(segs[:, 1:] != segs[:, :-1], segs[:, 1:], 0)], axis=1) != 0
        out["starts_off_block"] = jnp.sum(first & (jnp.arange(l)[None] % cfg.diffusion_block != 0), dtype=jnp.int32)
    if "selected" in probes:
        out["selected"] = probes.pop("selected")
    return out


#: the configuration of the score program last traced (:func:`score` sets it as it sets the gauges):
#: what :func:`record_moe_counters` reckons the expert loops' rows by where its caller names none
_last_scored: Optional[PatternLMConfig] = None


def record_moe_counters(visits, dropped, cfg: Optional[PatternLMConfig] = None) -> float:
    """A step's expert counters into ``metrics.METRICS``, beside the packer's
    ``pack.density``: the gauge ``moe.visits_max_over_mean`` (the busiest
    held expert of the step's most uneven layer against that layer's mean),
    the counter ``moe.visits_dropped`` (stays 0) and the gauge
    ``moe.tile_fill``: a layer's real visits over the rows its expert loops
    compute for them (``moe.region_units`` under ``cfg``'s tile and share of
    the experts), of the step's emptiest layer. ``cfg``: the program's; left
    out, that of the score program last traced, and with none traced
    ``moe.tile_fill`` is left alone. Returns the first gauge."""
    from tpu_tfrecord.metrics import METRICS

    visits = np.asarray(visits, np.int64)
    uneven = float((visits.max(axis=1) / np.maximum(visits.mean(axis=1), 1e-30)).max())
    METRICS.gauge("moe.visits_max_over_mean", round(uneven, 4))
    METRICS.count("moe.visits_dropped", int(np.asarray(dropped).sum()))
    cfg = cfg or _last_scored
    if cfg is not None:
        units = _moe.region_units(visits, cfg.expert_tile,
                                  _moe.adds_as_computed(visits.shape[1], cfg.n_experts))
        rows = units.sum(axis=1) * _moe.row_unit(cfg.expert_tile)
        METRICS.gauge("moe.tile_fill", round(float((visits.sum(axis=1) / np.maximum(rows, 1)).min()), 4))
    return uneven


def record_selected(selected) -> float:
    """A step's selection counters (``score``'s ``selected``) into
    ``metrics.METRICS``: the gauge ``dsa.selected_share``, the keys the
    indexers kept over the candidates they chose from (a step's mean over its
    layers and real queries; 1.0 while no document is longer than
    ``index_topk``). Returns the gauge."""
    from tpu_tfrecord.metrics import METRICS

    kept, candidates = np.asarray(selected, np.float64).sum(axis=0)
    share = float(kept / max(candidates, 1.0))
    METRICS.gauge("dsa.selected_share", round(share, 6))
    return share


def record_pair_kinds(segment_ids, cfg: PatternLMConfig) -> float:
    """What the latent-attention kernel makes of a step's rows (``score``'s
    ``segment_ids`` [B, L+1]) into ``metrics.METRICS``: the gauge
    ``mla.plain_pair_share``, of the block pairs it computes those it
    computes with every key seen (``attention.pair_kinds``: 120 of 136 for one
    document of 16,384 tokens in blocks of 1,024). Returns the gauge."""
    from tpu_tfrecord.metrics import METRICS

    _, plain, masked = pair_kinds(np.asarray(segment_ids)[:, :-1], cfg.attn_block, cfg.attn_block)
    share = plain / max(plain + masked, 1)
    METRICS.gauge("mla.plain_pair_share", round(share, 6))
    return share
