"""The plain reference of the gated delta-net / gated latent-attention decoder
(``models.lm.score`` with ``gdn`` and ``mla`` mixers, zero-centred sandwich
norms and clipped gated units): the forward pass in straightforward
``jax.numpy`` and float32, with no kernels, no packing and no ``segment_ids``
— each document scored alone from position 0 and from an empty state, the
delta rule token by token with the key heads copied to their value heads and
the decay spread over the channels (what the program never writes), one
head's full ``[n, n]`` scores at a time, every held expert by a loop, the
head's logits a block of rows at a time. It shares the plain norm, the
convolution, the token-by-token rule, the buckets and the expert loop's
bookkeeping with ``pattern_reference``, the biased router with
``mla_reference`` and YaRN's blend and the rotary turn with ``dsa_reference``,
and calls nothing else in ``tpu_tfrecord.models``;
``benchmark/models/gigachat35.py`` carries a copy (the benchmark's files stand
alone), and ``tests/test_gdn_lm.py`` holds the two to each other line for line.

It reads a configuration with the published names (``full_attention_layers``,
``linear_num_key_heads``, ``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_conv_kernel_dim``, ``q_lora_rank``, ``kv_lora_rank``,
``swiglu_limit``, ``first_k_dense_replace``; ``first_layer``: the published
number of the first layer here; ``n_routed_experts_held`` + ``held_offset``)
and flat weight names. For ONE document, with ``N(x; w) = x / rms(x) *
2 sigmoid(w)``:

    gdn    : u = N(x; w_in); q, k = u Wq, u Wk -> Hk heads; v, z = u Wv, u Wz -> H heads;
             q, k, v = silu(conv(.)); q, k to unit length; value head h reads key head
             h // (H / Hk); a_t = exp(-exp(A_h) softplus(u w_a + dt_h)), b_t = sigmoid(u w_b),
             one of each a head and token; S_t = a_t S_{t-1} + b_t k_t (v_t - k_t^T a_t S_{t-1})^T;
             o_t = S_t^T q_t Dk^-1/2; y = (o / rms(o) * w_o * 2 sigmoid(z)) Wo
    mla    : c_q = N(u Wqa; .); q = c_q Wqb -> heads of [nope | rope]; [c | k_pe] = u Wkva;
             [k_nope | v] = N(c; .) Wkvb; rope under YaRN by the token's index, k_pe one head
             for all; softmax of the scores times (nope + rope)^-1/2 (0.1 ln factor + 1)^2,
             causal; y = (att * sigmoid(u Wg)) Wo
    either : x = x + N(y; w_post)
    ffn    : f(u) = (silu(min(u Wg, limit)) * clip(u Wu, -limit, limit)) Wd; dense, or
             s = sigmoid(u2 Wr), the top-k of s + b, gates s_e / sum times the scale,
             shared(u2) + the chosen experts held; x = x + N(m; w_post)

Departures from the published model: rotary pairs (i, i + r/2) where the
published code interleaves them; bfloat16-valued weights; no multi-token
prediction blocks. What the published config names without spelling out
(the gain's form, the gate's shape, the decay's laws, the clip) is listed
with the reading taken in ``benchmark/configs/gigachat35_ep16.json``.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

from tpu_tfrecord.models.dsa_reference import ref_rope, ref_yarn
from tpu_tfrecord.models.mla_reference import HEAD_ROWS, ref_route_biased
from tpu_tfrecord.models.pattern_reference import (
    _bucket, _jitted, _room, ref_conv, ref_delta_rule, ref_norm)


def layer_plan(cfg: dict) -> List[Tuple[str, str]]:
    """[("gdn" | "mla", "dense" | "moe")] of the layers here: the published
    numbers ``first_layer`` .. ``first_layer + num_hidden_layers``, latent
    attention in ``full_attention_layers``, the first
    ``first_k_dense_replace`` of the layers here dense."""
    first, full = cfg.get("first_layer", 0), set(cfg["full_attention_layers"])
    return [("mla" if first + i in full else "gdn", "dense" if i < cfg["first_k_dense_replace"] else "moe")
            for i in range(cfg["num_hidden_layers"])]


def ref_gain_norm(x, weight, cfg, lower=None):
    """``N(x; w) = x / rms(x) * 2 sigmoid(w)``, the zero-centred gated gain
    (``lower["plain_norm_gain"]``: ``1 + w``, zero-centred without its gate:
    a control)."""
    import jax

    gain = 1.0 + weight if (lower or {}).get("plain_norm_gain") else 2.0 * jax.nn.sigmoid(weight)
    return ref_norm(x, gain, cfg["rms_norm_eps"])


def ref_clipped_ffn(u, w_gate, w_up, w_down, limit):
    """The gated unit clipped before it multiplies: the gate's pre-activation
    from above, the other factor on both sides."""
    import jax
    import jax.numpy as jnp

    return (jax.nn.silu(jnp.minimum(u @ w_gate, limit)) * jnp.clip(u @ w_up, -limit, limit)) @ w_down


def ref_gdn(p, u, cfg, lower=None, state0=None, probe_head=None):
    """The gated delta-net layer on one document u [n, D]: (y before the
    branch's norm, the last state [H, Dk, Dv], probe). With ``probe_head`` (a
    value head) what the recurrence was given and gave for it: ``q``, ``k``,
    ``v``, ``o`` [n, d], ``log_decay``, ``beta`` [n]. ``lower`` names a
    control's departures: ``state_dtype`` (the state kept in a lower
    precision), ``per_key_head_off`` (value head h reads key head h mod Hk),
    ``decay_per_channel`` (the decay's rate times a fixed per-channel factor),
    ``beta_times_2`` (a beta in (0, 2))."""
    import jax
    import jax.numpy as jnp

    lower = lower or {}
    n, eps = u.shape[0], cfg["rms_norm_eps"]
    hk, h, dh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(jax.nn.silu(ref_conv(u @ p["wq"], p["conv_q"])).reshape(n, hk, dh))
    k = unit(jax.nn.silu(ref_conv(u @ p["wk"], p["conv_k"])).reshape(n, hk, dh))
    v = jax.nn.silu(ref_conv(u @ p["wv"], p["conv_v"])).reshape(n, h, dh)
    reads = jnp.arange(h) % hk if lower.get("per_key_head_off") else jnp.arange(h) // (h // hk)
    q, k = q[:, reads], k[:, reads]                                        # a key head once a value head
    rate = jax.nn.softplus(u @ p["w_a"] + p["dt_bias"])
    log_decay = (-jnp.exp(p["a_log"]) * rate)[:, :, None]                 # [n, H, 1]: one a head and token
    if lower.get("decay_per_channel"):
        spread = np.exp(0.5 * np.random.default_rng(0x44454341).standard_normal((h, dh)))
        log_decay = log_decay * jnp.asarray(spread, jnp.float32)
    beta = jax.nn.sigmoid(u @ p["w_beta"]) * (2.0 if lower.get("beta_times_2") else 1.0)
    o, last = ref_delta_rule(q, k, v, jnp.broadcast_to(log_decay, v.shape), beta, dh ** -0.5, state0,
                             lower.get("state_dtype"))
    probe = None
    if probe_head is not None:
        probe = {"q": q[:, probe_head], "k": k[:, probe_head], "v": v[:, probe_head],
                 "log_decay": log_decay[:, probe_head, 0], "beta": beta[:, probe_head],
                 "o": o[:, probe_head]}
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * p["o_norm"]
    gate = 2.0 * jax.nn.sigmoid(u @ p["wz"])
    return (o.reshape(n, h * dh) * gate) @ p["wo"], last, probe


def ref_gated_mla(p, u, cfg, lower=None):
    """Latent attention with compressed queries, YaRN and an output gate on
    one document u [n, D], one head's [n, n] scores at a time. ``lower``
    names a control's departures: ``no_attn_gate``, ``no_yarn``,
    ``angle_dtype``, ``plain_norm_gain`` (the two latents' norms)."""
    import jax
    import jax.numpy as jnp

    lower = lower or {}
    n, h = u.shape[0], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, theta = cfg["kv_lora_rank"], float(cfg["rope_theta"])
    blend, gain = (None, 1.0) if lower.get("no_yarn") else ref_yarn(cfg, dr // 2)
    angle = lower.get("angle_dtype")
    q = (ref_gain_norm(u @ p["wq_a"], p["q_norm"], cfg, lower) @ p["wq_b"]).reshape(n, h, dn + dr)
    latent = u @ p["wkv_a"]
    kv = (ref_gain_norm(latent[:, :rank], p["kv_norm"], cfg, lower) @ p["wkv_b"]).reshape(n, h, dn + dv)
    at = jnp.arange(n)
    q_pe = ref_rope(q[..., dn:], at, theta, blend, angle)
    k_pe = ref_rope(latent[:, None, rank:], at, theta, blend, angle)[:, 0]
    causal = jnp.tril(jnp.ones((n, n), bool))

    def one_head(head):
        q_nope, q_rot, k_nope, v = head
        scores = (q_nope @ k_nope.T + q_rot @ k_pe.T) * ((dn + dr) ** -0.5 * gain)
        scores = jnp.where(causal, scores, -jnp.inf)
        weights = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        return (weights / weights.sum(axis=-1, keepdims=True)) @ v

    by_head = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731  [n, h, .] -> [h, n, .]
    att = by_head(jax.lax.map(one_head, (by_head(q[..., :dn]), by_head(q_pe), by_head(kv[..., :dn]),
                                         by_head(kv[..., dn:])))).reshape(n, h * dv)
    if not lower.get("no_attn_gate"):
        att = att * jax.nn.sigmoid(u @ p["wg"])
    return att @ p["wo"]


def ref_join(x, y, weight, cfg, lower=None):
    """x + N(y; weight): the sandwich's second norm, on the branch
    (``lower["no_branch_norms"]``: x + y, a control)."""
    return x + (y if (lower or {}).get("no_branch_norms") else ref_gain_norm(y, weight, cfg, lower))


def ref_hybrid_front(kind, p, x, cfg, lower=None, state0=None, probe_head=None):
    """The mixer's branch joined to one document's x [n, D], then what the
    layer's feed-forward part needs: a dense layer is finished here
    (x, None, ..), an expert layer hands back (x, N(x; w_pre), ..) for
    :func:`ref_moe_clipped`, whose output :func:`ref_join` joins; then a
    delta-net layer's last state and its probe (None, None after latent
    attention)."""
    mixer, ffn = kind
    u = ref_gain_norm(x, p["attn_norm"], cfg, lower)
    if mixer == "mla":
        y, state, probe = ref_gated_mla(p, u, cfg, lower), None, None
    else:
        y, state, probe = ref_gdn(p, u, cfg, lower, state0, probe_head)
    x = ref_join(x, y, p["post_attn_norm"], cfg, lower)
    if ffn == "dense":
        y = ref_clipped_ffn(ref_gain_norm(x, p["ffn_norm"], cfg, lower), p["dense.w_gate"],
                            p["dense.w_up"], p["dense.w_down"], float(cfg["swiglu_limit"]))
        return ref_join(x, y, p["post_ffn_norm"], cfg, lower), None, state, probe
    return x, ref_gain_norm(x, p["moe_norm"], cfg, lower), state, probe


def _clipped_front(router, bias, w_gate, w_up, w_down, u, cfg_items, router_dtype):
    cfg = dict(cfg_items)
    chosen, gates = ref_route_biased(u, router, bias, cfg, router_dtype)
    return chosen, gates, ref_clipped_ffn(u, w_gate, w_up, w_down, cfg["swiglu_limit"])


def _clipped_expert_part(y, u, at, gate, w_gate, w_up, w_down, e, limit):
    """y + gate * expert_e(u[at]) laid down at ``at`` (an index past the end
    reads zeros and writes nothing)."""
    import jax.numpy as jnp

    part = ref_clipped_ffn(jnp.take(u, at, axis=0, mode="fill", fill_value=0.0),
                           w_gate[e], w_up[e], w_down[e], limit)
    return y.at[at].add(gate[:, None] * part, mode="drop")


def ref_moe_clipped(p, u, cfg, router_dtype=None, capacity=None):
    """The expert layer on one document: routing by ``ref_route_biased``
    (sigmoid scores, the bias picks, one group), the shared expert, plus every
    HELD expert's part, expert by expert, each over the tokens that chose it
    (picked on the host), every unit clipped; ``capacity`` drops an expert's
    visits beyond that many (a control). Returns (y, visits dropped,
    (chosen, gates))."""
    import jax.numpy as jnp

    n, e0, held = u.shape[0], cfg.get("held_offset", 0), cfg["n_routed_experts_held"]
    limit = float(cfg["swiglu_limit"])
    static = (("num_experts_per_tok", cfg["num_experts_per_tok"]),
              ("routed_scaling_factor", cfg["routed_scaling_factor"]), ("swiglu_limit", limit))
    front = _jitted(_clipped_front, static_argnums=(6, 7))
    routing = front(p["router"], p["router_bias"], p["shared.w_gate"], p["shared.w_up"],
                    p["shared.w_down"], u, static,
                    jnp.dtype(router_dtype).name if router_dtype else None)
    chosen, gates, y = np.asarray(routing[0]), np.asarray(routing[1]), routing[2]
    part = _jitted(_clipped_expert_part, static_argnums=8)
    dropped = 0
    for e in range(held):
        hit = chosen == e0 + e                                  # a token picks an expert once
        tokens = np.flatnonzero(hit.any(axis=1))
        if capacity is not None:
            dropped += max(0, len(tokens) - capacity)
            tokens = tokens[:capacity]
        if not len(tokens):
            continue
        room = _room(len(tokens), n)
        at = np.full(room, n, np.int32)                         # n: past the end
        at[: len(tokens)] = tokens
        gate = np.zeros(room, np.float32)
        gate[: len(tokens)] = gates[tokens][hit[tokens]]
        y = part(y, u, at, gate, p["w_gate"], p["w_up"], p["w_down"], np.int32(e), limit)
    return y, dropped, routing[:2]


def ref_gain_head(p, x, targets, cfg, lower=None):
    """(log p(targets) [n], logits [n, V]) of one block of hidden states."""
    import jax
    import jax.numpy as jnp

    logits = ref_gain_norm(x, p["final_norm"], cfg, lower) @ p["head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0], logits


def reference_score(cfg: dict, docs: list, weights: Callable, logits_at: Optional[list] = None,
                    lower: Optional[dict] = None, carry_state: bool = False,
                    probe_head: Optional[int] = None) -> dict:
    """Each document scored alone, in float32 at the highest matmul precision.

    docs: int arrays ``t_0 .. t_n`` (the end-of-document id included);
    ``weights(part)`` -> that part's float32 tensors (one part is held at a
    time); ``logits_at[i]``: positions of document i whose full logits to keep.
    Returns {"logprob": [log p(t_1..t_n)] a document, "logits": [[len(at), V]]
    a document, "dropped": visits a ``capacity`` control dropped, "router":
    a document's {"u", "experts", "gates"} [n_expert_layers, len(at), ..] at
    ``logits_at``: each expert layer's router input and what it chose,
    "scan": with ``probe_head`` a document's :func:`ref_gdn` probe of the
    first delta-net layer, else {}}. ``lower`` names a control's departures:
    :func:`ref_gdn`'s, :func:`ref_gated_mla`'s, ``no_branch_norms``,
    ``plain_norm_gain``, ``router_dtype``, ``capacity``; ``carry_state``
    plants the fault of a state that outlives its document: each delta-net
    layer starts a document from the last one's final state."""
    import json

    import jax
    import jax.numpy as jnp

    lower = dict(lower or {})
    plan = layer_plan(cfg)
    where = [np.asarray(a, np.int64) for a in (logits_at or [[]] * len(docs))]
    with jax.default_matmul_precision("highest"):
        embed = weights("embed")["embed"]
        xs = []
        for doc in docs:
            ids = np.zeros(_bucket(len(doc) - 1), np.int32)
            ids[: len(doc) - 1] = doc[:-1]
            xs.append(embed[ids])
        del embed
        out = {"logprob": [], "logits": [], "dropped": 0, "scan": [{} for _ in docs],
               "router": [{"u": [], "experts": [], "gates": []} for _ in docs]}
        router_dtype, capacity = lower.pop("router_dtype", None), lower.pop("capacity", None)
        same = json.dumps(cfg, sort_keys=True, default=repr)  # programs are kept by what cfg says
        said = json.dumps(lower, sort_keys=True, default=repr)
        delta_layers = [i for i, (mixer, _) in enumerate(plan) if mixer == "gdn"]
        probed = delta_layers[0] if probe_head is not None and delta_layers else None
        front = {(kind, probe): _jitted(
            lambda p, x, s, kind=kind, probe=probe: ref_hybrid_front(kind, p, x, cfg, lower, s, probe),
            key=("hybrid_front", same, kind, said, probe))
            for kind in set(plan) for probe in (None, probe_head)}
        join = _jitted(lambda x, y, w: ref_join(x, y, w, cfg, lower), key=("hybrid_join", same, said))
        for i, kind in enumerate(plan):
            p = weights(i)
            state = None
            for j, x in enumerate(xs):
                xs[j], u, state, scan = front[kind, probe_head if i == probed else None](
                    p, x, state if carry_state else None)
                if scan is not None:
                    n = len(docs[j]) - 1
                    out["scan"][j] = {name: np.asarray(a)[:n] for name, a in scan.items()}
                if u is None:
                    continue
                y, lost, (chosen, gates) = ref_moe_clipped(p, u, cfg, router_dtype, capacity)
                xs[j], out["dropped"] = join(xs[j], y, p["post_ffn_norm"]), out["dropped"] + lost
                for name, a in (("u", u), ("experts", chosen), ("gates", gates)):
                    out["router"][j][name].append(np.asarray(a)[where[j]])
            del p
        p = weights("head")
        head = _jitted(lambda p, x, t: ref_gain_head(p, x, t, cfg, lower), key=("hybrid_head", same, said))
        for j, (doc, x) in enumerate(zip(docs, xs)):
            n = len(doc) - 1
            targets = np.zeros(x.shape[0], np.int32)
            targets[:n] = doc[1:]
            logp, kept = [], np.zeros((len(where[j]), p["head"].shape[1]), np.float32)
            for r0 in range(0, x.shape[0], HEAD_ROWS):
                lp, logits = head(p, x[r0:r0 + HEAD_ROWS], jnp.asarray(targets[r0:r0 + HEAD_ROWS]))
                logp.append(np.asarray(lp))
                here = (where[j] >= r0) & (where[j] < r0 + HEAD_ROWS)
                if here.any():
                    kept[here] = np.asarray(logits[where[j][here] - r0])
            out["logprob"].append(np.concatenate(logp)[:n])
            out["logits"].append(kept)
            out["router"][j] = {k: np.stack(v) for k, v in out["router"][j].items()}
    return out
