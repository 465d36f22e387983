"""Mixture-of-Experts layer with expert parallelism (EP) over a mesh axis.

The reference framework ships no model code (SURVEY.md §2: parallelism rows
beyond DP are N/A) — this is the EP member of the consumer-model family
that exercises the ingestion pipeline under every parallelism style the
mesh supports (dp/tp/sp are covered by models.dlrm and models.attention;
pp by models.pipeline).

TPU-first construction (the Switch-Transformer / Mesh-TensorFlow dispatch
formulation, arXiv:2101.03961 §2.2, top-k per GShard arXiv:2006.16668):
- top-k routing (k=1 Switch default, k=2 the GShard/LM default) with a
  FIXED per-expert capacity: every tensor keeps a static shape, so the
  whole layer jits once and lands on the MXU as three einsums (dispatch,
  expert FFN, combine) — no gather/scatter with data-dependent shapes, no
  host round trips.
- dispatch/combine are one-hot einsums: tokens beyond an expert's capacity
  contribute zero to the combine (dropped tokens ride the residual
  connection — exactly the Switch behavior). Arrival order is rank-major:
  every rank-0 (first-choice) assignment queues before any rank-1
  assignment, then token order within a rank — the GShard "second-place
  experts ride behind first-place" rule. Combine gates are the RAW router
  probabilities of each chosen expert (no top-k renormalization), so
  ``top_k=1`` reproduces the original Switch layer bit-for-bit.
- two EP flavors:
  * `moe_apply` — the auto-sharded layer: expert-indexed [E, ...] tensors
    carry NamedShardings and XLA inserts whatever collectives its cost
    model picks. Composable anywhere (models.long_doc uses it), but the
    collective pattern is XLA's choice, not a contract.
  * `moe_apply_ep` — the comms-PINNED layer: an explicit `shard_map` over
    the expert axis with the token stream sharded on the same axis. Each
    device routes its own tokens, `lax.all_to_all` exchanges the
    dispatched capacity slices so every device runs ONLY its E/P experts,
    and the inverse all_to_all brings expert outputs home for the local
    combine. The compiled HLO contains `all-to-all` and NO `all-gather`
    of tokens or expert weights — asserted by tests/hlo_util, the
    contract `moe_apply` claims but cannot pin. Capacity is per
    (expert, token-shard): each shard applies its own ceil(Tl·cf·k/E)
    budget — the real distributed Switch semantics, mirrored exactly by
    ``moe_reference(shards=P)``. Its per-device body is exposed as
    `moe_ep_body` so EP composes under an ENCLOSING shard_map — the
    interleaved pipeline (models.pipeline, ``param_spec``) runs it as a
    virtual-stage chunk on a pipe×expert mesh, all-to-all intact.
- the router adds the standard load-balance auxiliary loss (mean fraction
  of FIRST-choice assignments * mean router prob per expert, scaled by E)
  so training spreads tokens.

`moe_reference` is the per-token oracle used by the tests;
`param_shardings` places the expert tensors on the EP axis.

`held_experts_apply` is the other construction, for a chip that holds a
SHARE of a layer's experts (``experts_held`` of ``n_experts``, from
``held_offset``): it routes over all of them, computes the part of the
result that its own experts give, and drops nothing. No capacity and no
``[T, E, C]`` one-hots: the visits to held experts are sorted by expert
and walked a tile at a time by a loop whose trip count is the batch's
own, so a skewed router costs time, never a token.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



@dataclass(frozen=True)
class MoEConfig:
    d_model: int = 32
    d_ff: int = 64          # per-expert hidden width
    n_experts: int = 4
    # capacity = ceil(tokens * factor * top_k / n_experts); 1.0 =
    # perfectly balanced routing just fits, >1 gives slack before drops
    # (Switch default 1.25)
    capacity_factor: float = 1.25
    # experts per token: 1 = Switch, 2 = GShard-style top-2 (second choice
    # queues behind every first choice; raw-prob gates, no renorm)
    top_k: int = 1
    dtype: Any = jnp.float32


def init_params(rng: jax.Array, cfg: MoEConfig) -> Dict[str, Any]:
    kr, k1, k2 = jax.random.split(rng, 3)
    scale_in = (2.0 / cfg.d_model) ** 0.5
    scale_out = (2.0 / cfg.d_ff) ** 0.5
    return {
        "router": jax.random.normal(kr, (cfg.d_model, cfg.n_experts)) * 0.02,
        # expert-stacked FFN weights: [E, ...] is the EP-sharded dim
        "w_in": jax.random.normal(k1, (cfg.n_experts, cfg.d_model, cfg.d_ff))
        * scale_in,
        "w_out": jax.random.normal(k2, (cfg.n_experts, cfg.d_ff, cfg.d_model))
        * scale_out,
    }


def param_shardings(mesh: Mesh, expert_axis: str = "model") -> Dict[str, Any]:
    """NamedShardings placing the expert dim on ``expert_axis`` (router
    replicated). Apply with jax.device_put / as jit out_shardings."""
    return {
        "router": NamedSharding(mesh, P()),
        "w_in": NamedSharding(mesh, P(expert_axis, None, None)),
        "w_out": NamedSharding(mesh, P(expert_axis, None, None)),
    }


def _capacity(tokens: int, cfg: MoEConfig) -> int:
    # ceil, per the config contract: factor 1.0 must JUST FIT perfectly
    # balanced routing (floor would drop tokens even when balanced); the
    # top_k assignments per token scale the budget the same way GShard's
    # 2N/E does.
    #
    # ``tokens`` is the STATIC flattened count INCLUDING padding, even
    # when ``moe_apply`` is given a ``valid`` mask (ADVICE r5 #3 — a
    # deliberate choice, documented here): capacity must be a
    # compile-time constant for the static-shape dispatch/combine
    # einsums, and the valid-token count is a runtime value. The effect
    # is CONSERVATIVE relative to the Switch formulation on heavily
    # padded batches — effective capacity_factor over valid tokens is
    # inflated, so FEWER tokens drop than factor implies, at the cost of
    # dispatch/combine tensors sized for the padded length. Callers
    # wanting a tighter match can shrink capacity_factor by their static
    # worst-case valid fraction. Under ``moe_apply_ep`` the count is the
    # per-shard token count: capacity is a per-(expert, shard) budget.
    cap = -(-int(tokens * cfg.capacity_factor) * cfg.top_k // cfg.n_experts)
    return max(1, cap)


def _route(probs: jax.Array, cfg: MoEConfig, c: int,
           valid: Optional[jax.Array] = None):
    """Shared top-k routing: probs [T, E] -> (dispatch [T, E, C],
    combine [T, E, C], onehot0 [T, E] first-choice assignment,
    routed [E] total assignments per expert across all ranks — the
    diagnostics' "tokens routed" count, kept or dropped — and kept [E],
    the assignments that won a capacity slot. kept is summed from the
    per-rank [T, E] masks here, NOT from the [T, E, C] dispatch tensor:
    a dispatch.sum would force that tensor to materialize instead of
    fusing into the dispatch einsum (measured at ~6% step overhead);
    unused outputs cost nothing — XLA DCEs them when diagnostics is off.

    Arrival order is rank-major (all rank-0 choices in token order, then
    rank-1, ...): rank-k queue positions start after every lower rank's
    TOTAL per-expert assignment count, so a flood of first choices can
    push second choices past capacity but never vice versa."""
    e = cfg.n_experts
    if not (1 <= cfg.top_k <= e):
        raise ValueError(
            f"top_k must be in [1, n_experts={e}], got {cfg.top_k}"
        )
    masked = probs
    prev_total = jnp.zeros((e,), jnp.float32)
    kept_total = jnp.zeros((e,), jnp.float32)
    dispatch = jnp.zeros(probs.shape + (c,), jnp.float32)
    combine = jnp.zeros(probs.shape + (c,), jnp.float32)
    onehot0 = None
    for _ in range(cfg.top_k):
        expert = jnp.argmax(masked, axis=-1)                    # [T]
        gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
        onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)   # [T, E]
        if valid is not None:
            onehot = onehot * valid[:, None]  # padding: no expert, no slot
            gate = gate * valid
        # position of each token within its expert's queue (0-based),
        # continuing after every lower rank's arrivals
        pos = (jnp.cumsum(onehot, axis=0) - 1.0 + prev_total[None, :]) * onehot
        kept = (pos < c) & (onehot > 0)                         # [T, E]
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), c, dtype=jnp.float32)
        d_k = jnp.where(kept[..., None], pos_oh, 0.0)           # [T, E, C]
        dispatch = dispatch + d_k
        combine = combine + d_k * gate[:, None, None]
        if onehot0 is None:
            onehot0 = onehot
        prev_total = prev_total + onehot.sum(axis=0)
        kept_total = kept_total + kept.astype(jnp.float32).sum(axis=0)
        # exclude this rank's pick from the next argmax
        masked = jnp.where(onehot > 0, -jnp.inf, masked)
    return dispatch, combine, onehot0, prev_total, kept_total


def _expert_ffn(params: Dict[str, Any], expert_in: jax.Array, dt) -> jax.Array:
    """[E, C, D] -> [E, C, D] through each expert's gelu FFN (einsum dims
    are expert-local, so the same code serves the dense and EP bodies)."""
    h = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"].astype(dt))
    )
    return jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(dt))


def _moe_local(params, xt, cfg: MoEConfig, valid_flat, *, c: int,
               exchange=None, diagnostics: bool = False):
    """Route + dispatch + FFN + combine over ONE token shard — the ONE
    per-shard body both flavors share. Returns (y [T, D], aux numerator
    pieces, diag numerator pieces or None): the caller owns how the
    aux-loss/diagnostic sums reduce (locally for the dense layer, psum
    for the EP layer). ``exchange`` is an optional (to_experts,
    from_experts) pair wrapped around the expert FFN — identity for the
    dense layer, the all_to_all pair for EP.

    ``diagnostics`` (a STATIC flag: off-path jits to exactly the old
    program) additionally returns (routed [E] assignments per expert
    across all ranks, kept [E] assignments that won a capacity slot,
    entropy_sum scalar — router-prob entropy summed over valid tokens).
    Every piece is a sum, so cross-shard reduction is one psum."""
    logits = xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # [T, E]
    dispatch, combine, onehot0, routed, kept = _route(
        probs, cfg, c, valid_flat
    )
    if valid_flat is not None:
        n_tokens = valid_flat.sum()
        probs_for_aux = probs * valid_flat[:, None]
    else:
        n_tokens = jnp.float32(xt.shape[0])
        probs_for_aux = probs
    assign_sum = onehot0.sum(axis=0)                           # [E]
    prob_sum = probs_for_aux.sum(axis=0)                       # [E]
    diag = None
    if diagnostics:
        ent = -(probs * jnp.log(probs + 1e-9)).sum(axis=-1)    # [T]
        if valid_flat is not None:
            ent = ent * valid_flat
        diag = (
            jax.lax.stop_gradient(routed),
            jax.lax.stop_gradient(kept),
            jax.lax.stop_gradient(ent.sum()),
        )

    dt = cfg.dtype
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dt), xt.astype(dt))
    if exchange is not None:
        expert_in = exchange[0](expert_in)
    expert_out = _expert_ffn(params, expert_in, dt)
    if exchange is not None:
        expert_out = exchange[1](expert_out)
    y = jnp.einsum("tec,ecd->td", combine.astype(dt), expert_out)
    return y, (assign_sum, prob_sum, n_tokens), diag


def _aux_loss(assign_sum, prob_sum, n_tokens, e: int) -> jax.Array:
    # load-balance aux loss (Switch eq. 4): E * mean(frac_tokens *
    # mean_prob), fractions over FIRST-choice assignments and VALID tokens
    n = jnp.maximum(n_tokens, 1.0)
    return ((assign_sum / n) * (prob_sum / n)).sum() * e


def _diag_dict(routed, kept, entropy_sum, n_tokens) -> Dict[str, jax.Array]:
    """The diagnostics contract both flavors return (GLOBAL sums for EP —
    the caller psums the pieces before building this):

    - ``expert_tokens`` [E] f32: assignments routed to each expert across
      every rank (kept or dropped) — sums to valid_tokens * top_k.
    - ``expert_kept`` [E] f32: assignments that won a capacity slot.
    - ``dropped_fraction`` scalar: 1 - kept/routed (the Switch overflow
      rate; dropped tokens ride the residual).
    - ``gate_entropy`` scalar: mean router-prob entropy per valid token
      (nats; ln(E) = maximally undecided router, ~0 = collapsed).

    All static-shaped, all stop_gradient'd — reading them costs no
    backward pass and cannot perturb training numerics."""
    routed_total = jnp.maximum(routed.sum(), 1.0)
    return {
        "expert_tokens": routed,
        "expert_kept": kept,
        "dropped_fraction": 1.0 - kept.sum() / routed_total,
        "gate_entropy": entropy_sum / jnp.maximum(n_tokens, 1.0),
    }


def moe_apply(
    params: Dict[str, Any],
    x: jax.Array,
    cfg: MoEConfig,
    valid: Optional[jax.Array] = None,
    diagnostics: bool = False,
):
    """Top-k MoE FFN, auto-sharded flavor. x: [..., T, D] (leading dims
    flattened internally). Returns (y, aux_loss) with y.shape == x.shape;
    dropped tokens yield 0 (add the residual outside). All shapes static —
    jits once. EP comes from `param_shardings` on the [E, ...] tensors;
    the collective pattern is XLA's pick (use `moe_apply_ep` when the
    all-to-all must be a contract).

    ``valid``: optional boolean mask shaped like x without the feature dim
    ([..., T]). Invalid (padding) tokens are excluded ENTIRELY: they get
    zero output, consume no expert capacity (cannot displace later valid
    tokens), and contribute nothing to the aux loss — so results depend
    only on valid positions' content.

    ``diagnostics`` (static flag; False jits the exact pre-flag program)
    returns (y, aux_loss, diag) instead, where diag is the `_diag_dict`
    contract (per-expert routed/kept counts, dropped fraction, gate
    entropy) — pinned against `moe_reference(..., return_diag=True)`.
    """
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)                                     # [T, D]
    c = _capacity(xt.shape[0], cfg)
    valid_flat = (
        valid.reshape(-1).astype(jnp.float32) if valid is not None else None
    )
    y, (assign_sum, prob_sum, n_tokens), diag = _moe_local(
        params, xt, cfg, valid_flat, c=c, diagnostics=diagnostics
    )
    aux = _aux_loss(assign_sum, prob_sum, n_tokens, cfg.n_experts)
    y = y.reshape(orig_shape).astype(x.dtype)
    aux = aux.astype(jnp.float32)
    if not diagnostics:
        return y, aux
    return y, aux, _diag_dict(*diag, n_tokens)


def moe_ep_body(
    params_local: Dict[str, Any],
    x_local: jax.Array,
    cfg: MoEConfig,
    expert_axis: str,
    data_axis: Optional[str] = None,
    valid_local: Optional[jax.Array] = None,
    diagnostics: bool = False,
):
    """The per-device EP body — the all-to-all dispatch WITHOUT the
    enclosing shard_map, so EP composes under someone else's manual mesh
    (the interleaved pipeline runs it inside a pipe×V×expert shard_map as
    a virtual-stage chunk; `moe_apply_ep` is this body wrapped in its own
    shard_map).

    Call it only inside a shard_map whose mesh carries ``expert_axis``.
    ``params_local`` holds THIS device's expert shard ([E/P, ...] w_in /
    w_out, replicated router); ``x_local`` is this device's token shard
    [..., T_local, D] (leading dims flattened into the token count, which
    sets the per-shard capacity budget). Returns (y, aux) with y shaped
    like ``x_local`` — or (y, aux, diag) with ``diagnostics``, the
    `_diag_dict` contract psum'd over ``expert_axis`` (+ ``data_axis``)
    so the ratios are global, exactly like `moe_apply_ep`'s."""
    xt = x_local.reshape(-1, x_local.shape[-1])
    vf = (
        valid_local.reshape(-1).astype(jnp.float32)
        if valid_local is not None else None
    )
    c = _capacity(xt.shape[0], cfg)
    # THE exchange around the shared per-shard body: slice the expert
    # dim P ways, every device keeps its E/P experts and receives the
    # matching [E, C, D] capacity slices from all peers (concat on the
    # capacity dim -> [E/P, P*C, D]); the inverse brings expert
    # outputs back to the token-owning device — tokens move, weights
    # never do
    exchange = (
        lambda a: jax.lax.all_to_all(
            a, expert_axis, split_axis=0, concat_axis=1, tiled=True
        ),
        lambda a: jax.lax.all_to_all(
            a, expert_axis, split_axis=1, concat_axis=0, tiled=True
        ),
    )
    y, (assign_sum, prob_sum, n_tok), diag = _moe_local(
        params_local, xt, cfg, vf, c=c, exchange=exchange,
        diagnostics=diagnostics,
    )
    # aux loss over the GLOBAL token stream: tiny [E] reductions
    axes = (expert_axis,) + ((data_axis,) if data_axis else ())
    aux = _aux_loss(
        jax.lax.psum(assign_sum, axes),
        jax.lax.psum(prob_sum, axes),
        jax.lax.psum(n_tok, axes),
        cfg.n_experts,
    )
    out = (
        y.reshape(x_local.shape).astype(x_local.dtype),
        aux.astype(jnp.float32),
    )
    if not diagnostics:
        return out
    routed, kept, ent_sum = diag
    # GLOBAL diagnostics: psum the sums, THEN form the ratios
    return out + (_diag_dict(
        jax.lax.psum(routed, axes),
        jax.lax.psum(kept, axes),
        jax.lax.psum(ent_sum, axes),
        jax.lax.psum(n_tok, axes),
    ),)


def moe_apply_ep(
    params: Dict[str, Any],
    x: jax.Array,
    cfg: MoEConfig,
    mesh: Mesh,
    expert_axis: str = "expert",
    data_axis: Optional[str] = None,
    valid: Optional[jax.Array] = None,
    diagnostics: bool = False,
):
    """Comms-pinned EP flavor: explicit shard_map over ``expert_axis``
    with the TOKEN dim sharded on the same axis.

    x: [..., T, D] with T divisible by the expert-axis size P (and
    n_experts % P == 0). Each device routes its own T/P tokens under a
    per-shard capacity, one `lax.all_to_all` scatters the dispatched
    [E, C, D] capacity slices so device p computes ONLY its E/P experts
    over the P·C slots it received, and the inverse all_to_all returns
    expert outputs for the local combine. Expert weights and tokens never
    gather — per-device memory is the shard (E/P experts + T/P tokens +
    the exchanged capacity slices) and the HLO contains `all-to-all`, no
    `all-gather` (pinned by tests). Pass ``data_axis`` to keep leading
    batch dims sharded as well. Numerics == `moe_reference(shards=P)`.

    ``diagnostics`` (static flag; off = the exact pre-flag program)
    returns (y, aux, diag): every diag piece (routed/kept per expert,
    entropy sum, token count) is psum'd over the expert axis (and
    ``data_axis`` when given) BEFORE the ratios form — a per-shard
    dropped fraction averaged across shards would not equal the global
    overflow rate. Tiny [E]/scalar reductions, same cost class as the
    aux loss.
    """
    p = mesh.shape[expert_axis]
    e = cfg.n_experts
    if e % p:
        raise ValueError(
            f"moe_apply_ep needs n_experts % mesh['{expert_axis}'] == 0 "
            f"(got E={e}, axis size {p})"
        )
    t_dim = x.shape[-2]
    if t_dim % p:
        raise ValueError(
            f"moe_apply_ep needs the token dim % mesh['{expert_axis}'] == 0 "
            f"(got T={t_dim}, axis size {p}); pad or re-bucket the stream"
        )
    # per-shard token count is static inside the body: the local capacity
    # budget (moe_ep_body derives it from its shard's flattened shape)
    lead = x.shape[:-2]
    dp = (data_axis,) if data_axis is not None and lead else ()
    x_spec = P(*dp, *([None] * (len(lead) - len(dp))), expert_axis, None)
    v_spec = P(*dp, *([None] * (len(lead) - len(dp))), expert_axis)

    def body(params_l, x_l, valid_l=None):
        return moe_ep_body(
            params_l, x_l, cfg, expert_axis, data_axis=data_axis,
            valid_local=valid_l, diagnostics=diagnostics,
        )

    w_spec = {
        "router": P(),
        "w_in": P(expert_axis, None, None),
        "w_out": P(expert_axis, None, None),
    }
    diag_spec = {
        "expert_tokens": P(), "expert_kept": P(),
        "dropped_fraction": P(), "gate_entropy": P(),
    }
    out_specs = (x_spec, P()) + ((diag_spec,) if diagnostics else ())
    if valid is None:
        fn = jax.shard_map(
            body, mesh=mesh, in_specs=(w_spec, x_spec),
            out_specs=out_specs,
        )
        return fn(params, x)
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(w_spec, x_spec, v_spec),
        out_specs=out_specs,
    )
    return fn(params, x, valid)


def moe_reference(
    params: Dict[str, Any],
    x: jax.Array,
    cfg: MoEConfig,
    valid: Optional[Any] = None,
    shards: int = 1,
    return_diag: bool = False,
) -> Any:
    """Per-token oracle: route each token to its top-k experts' FFNs
    (rank-major arrival: every first choice queues before any second
    choice), gate by the raw router prob, drop assignments beyond
    capacity; invalid tokens (``valid`` mask) are skipped entirely —
    definitionally what the einsum dance computes. ``shards`` splits the
    flat token stream into P contiguous blocks with INDEPENDENT per-block
    capacity budgets — the `moe_apply_ep` distributed semantics.

    ``return_diag`` additionally returns (out, diag): the `_diag_dict`
    vocabulary computed by literal counting — routed/kept tallies per
    expert accumulated GLOBALLY across shard blocks (exactly what the
    EP flavor's psum'd diagnostics must equal), the dropped fraction,
    and the mean router-prob entropy over valid tokens."""
    import numpy as np

    xt = np.asarray(x, dtype=np.float64).reshape(-1, x.shape[-1])
    vmask = (
        np.asarray(valid).reshape(-1) if valid is not None
        else np.ones(xt.shape[0], dtype=bool)
    )
    router = np.asarray(params["router"], dtype=np.float64)
    w_in = np.asarray(params["w_in"], dtype=np.float64)
    w_out = np.asarray(params["w_out"], dtype=np.float64)
    t = xt.shape[0]
    assert t % shards == 0, (t, shards)
    t_l = t // shards
    cap = _capacity(t_l, cfg)
    logits = xt @ router
    z = np.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = z / z.sum(axis=-1, keepdims=True)
    out = np.zeros_like(xt)
    routed = np.zeros(cfg.n_experts)
    kept = np.zeros(cfg.n_experts)

    def ffn(ei, v):
        h = v @ w_in[ei]
        h = 0.5 * h * (1.0 + np.tanh(np.sqrt(2 / np.pi) * (h + 0.044715 * h**3)))
        return h @ w_out[ei]

    for b in range(shards):
        lo, hi = b * t_l, (b + 1) * t_l
        counts = {ei: 0 for ei in range(cfg.n_experts)}
        taken = [set() for _ in range(t_l)]  # experts already chosen per token
        for rank in range(cfg.top_k):
            for i in range(lo, hi):
                if not vmask[i]:
                    continue
                order = np.argsort(-probs[i])
                ei = next(int(e) for e in order if int(e) not in taken[i - lo])
                taken[i - lo].add(ei)
                routed[ei] += 1
                if counts[ei] >= cap:
                    continue
                counts[ei] += 1
                kept[ei] += 1
                out[i] += probs[i, ei] * ffn(ei, xt[i])
    out = out.reshape(x.shape)
    if not return_diag:
        return out
    n_valid = max(int(vmask.sum()), 1)
    ent = -(probs * np.log(probs + 1e-9)).sum(axis=-1)
    diag = {
        "expert_tokens": routed,
        "expert_kept": kept,
        "dropped_fraction": 1.0 - kept.sum() / max(routed.sum(), 1.0),
        "gate_entropy": float(ent[vmask].sum() / n_valid),
    }
    return out, diag


# ---------------------------------------------------------------------------
# A share of the experts, dropless
# ---------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST


def gated_ffn(x, w_gate, w_up, w_down, limit=None):
    """``w_down(silu(w_gate x) * w_up x)``: products accumulate in float32,
    the hidden activation is rounded to x's dtype; returns float32. With a
    ``limit`` the unit is clipped before it multiplies: the gate's
    pre-activation from above, ``silu(min(w_gate x, limit))``, the other
    factor on both sides, ``clip(w_up x, -limit, limit)``."""
    f32 = jnp.float32
    above = (lambda a: a) if limit is None else (lambda a: jnp.minimum(a, limit))
    both = (lambda a: a) if limit is None else (lambda a: jnp.clip(a, -limit, limit))
    hidden = jax.nn.silu(above(jnp.dot(x, w_gate, preferred_element_type=f32))) * both(jnp.dot(
        x, w_up, preferred_element_type=f32))
    return jnp.dot(hidden.astype(x.dtype), w_down, preferred_element_type=f32)


def relu2_ffn(x, w_up, w_down):
    """``w_down(relu(w_up x)^2)``, the unit of two matrices (no gate): products
    accumulate in float32, the hidden activation is rounded to x's dtype;
    returns float32."""
    f32 = jnp.float32
    hidden = jnp.square(jax.nn.relu(jnp.dot(x, w_up, preferred_element_type=f32)))
    return jnp.dot(hidden.astype(x.dtype), w_down, preferred_element_type=f32)


def expert_unit(x, w: Dict[str, Any], limit=None, e=None):
    """One feed-forward unit on x by the matrices ``w`` holds: with ``w_gate``
    :func:`gated_ffn`'s three, without it :func:`relu2_ffn`'s two; ``e``
    picks one expert of stacked matrices."""
    pick = (lambda a: a) if e is None else (lambda a: a[e])
    if "w_gate" in w:
        return gated_ffn(x, pick(w["w_gate"]), pick(w["w_up"]), pick(w["w_down"]), limit)
    return relu2_ffn(x, pick(w["w_up"]), pick(w["w_down"]))


def route_top_k(x, router, top_k: int, routed_scale: float = 1.0, bias=None, n_group: int = 1,
                topk_group: int = 1, scoring: str = "sigmoid"):
    """Sigmoid scores over ALL experts in float32, the ``top_k`` largest,
    their gates renormalised to sum to ``routed_scale``. ``scoring``
    ``"softmax"``: the scores are a softmax over all experts in float32
    instead (then the ``top_k``, then the same renormalisation: a gate is
    ``p_e`` over the sum of the chosen ``p``); with ``"sigmoid"`` the function
    is what it always was. With ``bias`` [E]
    (a correction the trainer balances the load by) the experts are the
    ``top_k`` largest of ``scores + bias`` and the gates still come from the
    scores alone: the bias picks and never weighs. With ``n_group`` > 1 the
    choice is group-limited: the experts lie in ``n_group`` equal runs, a
    run's score is the sum of its two largest ``scores + bias`` (its largest
    score where there is no bias), only the ``topk_group`` best runs stay,
    and the ``top_k`` are chosen inside them.
    x [T, D], router [D, E] -> (experts [T, k] int32, gates [T, k] float32)."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring {scoring!r}: 'sigmoid' or 'softmax'")
    squash = jax.nn.sigmoid if scoring == "sigmoid" else functools.partial(jax.nn.softmax, axis=-1)
    scores = squash(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32), precision=_HIGHEST))
    if n_group > 1:
        picking = scores if bias is None else scores + bias
        runs = picking.reshape(scores.shape[0], n_group, -1)
        run_score = runs.max(axis=-1) if bias is None else jax.lax.top_k(runs, 2)[0].sum(axis=-1)
        _, best = jax.lax.top_k(run_score, topk_group)
        stays = jnp.zeros(run_score.shape, bool).at[jnp.arange(scores.shape[0])[:, None], best].set(True)
        picking = jnp.where(stays[:, :, None], runs, -jnp.inf).reshape(scores.shape)
        _, experts = jax.lax.top_k(picking, top_k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    elif bias is None:
        top, experts = jax.lax.top_k(scores, top_k)
    else:
        _, experts = jax.lax.top_k(scores + bias, top_k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, top / top.sum(axis=-1, keepdims=True) * routed_scale


#: Rows of a tail tile, the unit an expert's visits are rounded up to. One tile of m rows does
#: 6·D·F·m FLOPs on 6·D·F bytes of one expert's bfloat16 weights, m FLOPs a byte, and a v5e turns
#: 197 TFLOP/s over 819 GB/s = 240 FLOPs a byte: under about 240 rows a tile costs its expert's
#: weights' read whatever it holds, so a smaller unit buys nothing.
TAIL_UNIT = 256


def row_unit(tile: int) -> int:
    """The rows :func:`held_experts_apply` rounds an expert's visits up to under
    tiles of ``tile``: ``TAIL_UNIT`` where the tile is several whole units (what
    is left of a run under one tile then goes through tail tiles of a unit),
    the tile itself everywhere else (one loop, no tails)."""
    return TAIL_UNIT if tile > TAIL_UNIT and tile % TAIL_UNIT == 0 else tile


def tail_units(per: int, scattered: bool) -> int:
    """The most units of a run's tail that go through tail tiles, under tiles
    of ``per`` units; a longer tail takes one more whole tile. A tile of 256
    rows costs its expert's weights' read BESIDE its products (XLA's dot at
    that size overlaps neither with the other: 62 microseconds a tail where a
    whole tile of four units is 131, at 2,048 x 1,408 on a v5e), and at 240
    FLOPs a byte the two are equal: ``r`` tail tiles cost ``2 r`` units'
    products where the whole tile costs ``per + 1``. ``scattered``: the form
    that adds a tile's rows to their tokens, where a row's scatter-add costs
    more than its products and every tail pays."""
    return per - 1 if scattered else min((per + 1) // 2, per - 1)


def adds_as_computed(n_held: int, n_experts: int) -> bool:
    """Which form :func:`held_experts_apply` takes: under an eighth of the
    experts held, a tile's rows are added to their tokens as it is computed;
    from an eighth on they are laid down and read back."""
    return n_held * 8 < n_experts


def region_units(visits, tile: int, scattered: bool):
    """The units of :func:`row_unit` rows that :func:`held_experts_apply`'s
    loops compute for each expert's ``visits`` (numpy or JAX integers, any
    shape): the visits rounded up to whole units, and a tail of more units
    than :func:`tail_units` allows rounded up to one more whole tile."""
    unit = row_unit(tile)
    units, per = (visits + unit - 1) // unit, tile // unit
    if per > 1:
        tail = units % per
        units = units + (tail > tail_units(per, scattered)) * (per - tail)
    return units


def buffer_units(slots: int, n_held: int, tile: int, scattered: bool) -> int:
    """The units of :func:`row_unit` rows that :func:`held_experts_apply`'s loops
    can compute at most: every one of ``slots`` visits to a held expert, and
    each expert's rounding (a unit, or the units a tail can be rounded up by)."""
    unit = row_unit(tile)
    per = tile // unit
    return -(-slots // unit) + n_held * max(1, per - tail_units(per, scattered))


def tile_tables(visits, tile: int, scattered: bool, max_units: int):
    """What every tile of :func:`held_experts_apply`'s loops is, worked out once
    a layer from the held experts' ``visits`` [Eh] so that a tile reads its row
    and searches nothing. Returns (``first`` [Eh]: an expert's first place in
    the sorted order, ``region`` [Eh]: the first unit of its region of the
    buffer, and for each loop, whole tiles and then tails (one loop where the
    tile is the unit), (rows a tile, the tiles there are, ``table`` int32
    [J, 4])). Row ``j`` of a table, for ``j`` under the tiles there are: the
    tile's expert, its first place in the sorted order, the unit of the buffer
    it lies at, and how many of its rows are real; the rows from there on are
    never read. ``J`` is the worst case under ``max_units`` units of buffer:
    ``max_units // per`` whole tiles of ``per`` units, :func:`tail_units` tails
    an expert. A tile's expert is found by comparing every ``j`` with every
    expert's run of tiles at once, J x Eh comparisons and no search."""
    unit = row_unit(tile)
    per, n_held = tile // unit, visits.shape[0]
    first = jnp.cumsum(visits) - visits
    units = region_units(visits, tile, scattered)
    region = jnp.cumsum(units) - units
    # the loops: (rows a tile, an expert's tiles, the units of its run before them, the most there can be)
    if per == 1:
        loops = [(tile, units, 0 * units, max_units)]
    else:
        whole = units // per
        loops = [(tile, whole, 0 * units, max_units // per),
                 (unit, units - whole * per, whole * per, n_held * tail_units(per, scattered))]
    tables = []
    for rows, n, before, most in loops:
        upto = jnp.cumsum(n)
        j = jnp.arange(most, dtype=jnp.int32)
        mine = (j[:, None] >= (upto - n)[None]) & (j[:, None] < upto[None])    # [J, Eh]: tile j is expert e's
        facts = jnp.stack([jnp.arange(n_held, dtype=jnp.int32), upto - n, before, first, first + visits, region])
        e, lo, before, first_of, end, region_of = jnp.sum(      # each [J]: what tile j's expert has
            jnp.where(mine[None], facts[:, None], 0), axis=2, dtype=jnp.int32)
        begins = (j - lo) * (rows // unit) + before             # in units of the run
        start = first_of + begins * unit
        real = jnp.minimum(jnp.maximum(end - start, 0), rows)
        tables.append((rows, upto[-1], jnp.stack([e, start, region_of + begins, real], axis=1)))
    return first, region, tables


def held_experts_apply(params: Dict[str, Any], x, *, held_offset: int, top_k: int,
                       routed_scale: float = 1.0, tile: int = 256, valid=None,
                       n_group: int = 1, topk_group: int = 1, limit=None, scoring: str = "sigmoid"):
    """``shared(x) + sum of gate_e * expert_e(x)`` over the chosen experts
    THIS chip holds; what the absent experts would add is left out.

    params: ``router`` [D, E] (all E experts), optional ``router_bias`` [E]
    (:func:`route_top_k`'s), ``w_gate`` / ``w_up`` [Eh, D, F], ``w_down``
    [Eh, F, D] (the Eh experts held, numbers ``held_offset`` ..
    ``held_offset + Eh``), optional ``shared`` with the same three names
    un-stacked (several shared experts are one unit of their summed width).
    Without ``w_gate`` (in the held experts, in the shared one) the unit is
    the one of two matrices, ``relu(w_up x)^2`` (:func:`expert_unit`).
    x [T, D]; ``valid`` [T] bool: tokens that
    are pads visit no expert (their rows get the shared expert only);
    ``n_group`` / ``topk_group``: :func:`route_top_k`'s group limit, ``scoring`` its
    scores (sigmoid, or a softmax over all experts);
    ``limit``: :func:`gated_ffn`'s clip, in every held expert and the shared one.
    Returns (y [T, D] in x's dtype, visits [Eh] int32 to each held expert,
    dropped: visits to held experts that were not computed, always 0,
    (experts [T, top_k] int32, gates [T, top_k] float32): the routing).

    The T * top_k visits are sorted by held expert (absent ones last) and an
    expert's run of ``v`` visits is rounded up to whole UNITS of rows
    (:func:`row_unit`): ``TAIL_UNIT`` = 256 where ``tile`` is several whole
    units, else the tile. The whole tiles of ``tile`` rows in that,
    ``floor(q * unit / tile)`` of ``q = ceil(v / unit)`` units, come first;
    what is left, under one tile, is TAIL tiles of one unit each, where that
    many tails cost less than the one whole tile they stand for
    (:func:`tail_units`; where they do not, the run takes the whole tile, as a
    run whose tail would fill a tile's units does: 960 visits under tiles of
    1,024 are one tile, not four tails). An expert whose tail goes through
    tail tiles costs ``q * unit`` rows, at most ``unit - 1`` of them empty,
    where whole tiles alone cost ``ceil(v / tile) * tile``
    (:func:`region_units` is the count). Why 256: a tile of ``m`` rows does
    ``6 D F m`` FLOPs on the ``6 D F`` bytes of one expert's bfloat16 weights,
    ``m`` FLOPs a byte, and a v5e turns 240 FLOPs a byte, so under about 240
    rows a tile costs its expert's weights' read whatever it holds. A loop
    over the whole tiles there ARE, then one with the same body over the tails
    there are (none where the tile is the unit: every ``tile`` up to 256, or no
    multiple of it), gathers a tile's tokens, runs that expert's FFN and lays
    the result down where the tile lies in the sorted order, an expert's region
    its units, whole tiles first (a contiguous write; a buffer for the worst
    case, every visit to a held expert and each expert's rounding, is
    ``(ceil(T * top_k / unit) + Eh * spare) * unit + 1`` rows of x's dtype,
    ``spare`` 1 where every tail goes through tail tiles and else the units a
    tail can be rounded up by; handed from the first loop to the second as its
    carry). What a tile is, it reads: its expert, its first place in the
    sorted order, the unit of the buffer it lies at and the count of its real
    rows are row ``j`` of its loop's table, made once a layer where the routing
    is (:func:`tile_tables`: every tile compared with every expert's run at
    once), and its visits are a slice of the sorted order, padded by one tile
    so that the last run's last tile may reach past the end. A loop's body
    searches nothing and gathers no index: its one gather is the tile's rows
    of x. Each token then reads its own ``top_k`` rows back and sums them
    under its gates in float32: a gather, where a scatter-add of the same rows
    costs three times as much a row on a TPU.

    Where this chip holds less than an eighth of the experts there is no
    buffer and no read-back: each tile's rows, whole or tail, are added to
    their tokens as the tile is computed, under their gates, in float32 (a
    scatter-add of the tile's rows; a token is in a tile once, and the tiles
    are summed in the order the two loops walk them). The read-back
    gathers ``top_k`` rows a token whatever the share, a row of 7,168 costs
    0.12 microseconds to gather and 1.1 to scatter-add on a v5e, so the two
    cross at a share of a ninth: with 16 of 256 held, 16,384 tokens make 8,192
    visits a layer and the read-back would gather 131,072 rows (nearly all of
    them the zero row) out of a 2 GB buffer sized for the worst case."""
    t, d = x.shape
    n_held = params["w_down"].shape[0]
    f32 = jnp.float32
    unit = row_unit(tile)
    scattered = adds_as_computed(n_held, params["router"].shape[1])
    with jax.named_scope("tfr.moe_route"):
        grouped = {} if n_group == 1 else {"n_group": n_group, "topk_group": topk_group}
        if scoring != "sigmoid":
            # named only where it is not the default, as the group limit is: the benchmark's rehearsal
            # tests put a router of the first five arguments in this one's place
            # (benchmark/tests/test_rehearsal_kimi.py, test_rehearsal_dsv32.py; not a model PR's to edit)
            grouped["scoring"] = scoring
        experts, gates = route_top_k(x, params["router"], top_k, routed_scale,
                                     params.get("router_bias"), **grouped)
        local = experts - held_offset
        held = (local >= 0) & (local < n_held)
        if valid is not None:
            held = held & valid[:, None]
        key = jnp.where(held, local, n_held).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        visits = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:n_held]
        max_units = buffer_units(t * top_k, n_held, tile, scattered)
        first, region, loops = tile_tables(visits, tile, scattered, max_units)
        # where a visit's result will lie: its expert's first unit, then its place in the run
        rank = jnp.zeros((t * top_k,), jnp.int32).at[order].set(
            jnp.arange(t * top_k, dtype=jnp.int32))
        safe = jnp.minimum(key, n_held - 1)
        lies_at = region[safe] * unit + rank - first[safe]
        lies_at = jnp.where(held.reshape(-1), lies_at, max_units * unit).reshape(t, top_k)
        # a tile's visits are a contiguous run of the sorted order, and the last run's last tile may
        # reach past its end by less than a tile: those rows are not real, what they read is never used
        padded = jnp.concatenate([order, jnp.zeros((tile,), jnp.int32)])
    with jax.named_scope("tfr.moe_experts"):
        def walk(lay, acc):
            """Every loop's tiles through their expert, each handed to ``lay`` with
            where it lies; returns (``acc``, the visits computed)."""
            def one_tile(rows, table, lane, j, carry):
                acc, done = carry
                e, start, lies, count = jax.lax.dynamic_index_in_dim(
                    table, j, keepdims=False, allow_negative_indices=False)
                visit = jax.lax.dynamic_slice(padded, (start,), (rows,), allow_negative_indices=False)
                # in units, so that every start is seen to be whole units
                acc = lay(acc, lies * unit, visit, lane < count,
                          expert_unit(x[visit // top_k], params, limit, e))
                return acc, done + count

            carry = (acc, jnp.int32(0))
            for rows, tiles, table in loops:
                carry = jax.lax.fori_loop(0, tiles, functools.partial(
                    one_tile, rows, table, jnp.arange(rows, dtype=jnp.int32)), carry)
            return carry

        if not scattered:
            def lay(laid, lies, visit, real, y):
                # no start is negative; said, the start stays whole units to the compiler (the
                # wrap-around's select hides it) and the write stays in the last product
                return jax.lax.dynamic_update_slice(laid, y.astype(x.dtype), (lies, 0),
                                                    allow_negative_indices=False)

            # one spare row past the worst case stays zero: what a token reads for an absent expert
            laid, done = walk(lay, jnp.zeros((max_units * unit + 1, d), x.dtype))
            out = jnp.zeros((t, d), f32)
            for slot in range(top_k):
                out = out + jnp.where(held[:, slot], gates[:, slot], 0.0)[:, None] * laid[
                    lies_at[:, slot]].astype(f32)
        else:
            flat_gates = gates.reshape(-1)

            def add(out, lies, visit, real, y):
                gate = jnp.where(real, flat_gates[visit], 0.0)
                # a row past the run's end goes nowhere (index t is dropped)
                return out.at[jnp.where(real, visit // top_k, t)].add(
                    gate[:, None] * y.astype(x.dtype).astype(f32), mode="drop")

            out, done = walk(add, jnp.zeros((t, d), f32))
    if "shared" in params:
        with jax.named_scope("tfr.moe_shared"):
            out = out + expert_unit(x, params["shared"], limit)
    return out.astype(x.dtype), visits, visits.sum() - done, (experts, gates)
