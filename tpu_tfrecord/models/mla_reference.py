"""The plain reference of the latent-attention decoder (``models.lm.score``
with ``mla`` mixers, a leading dense layer and a biased router): the forward
pass in straightforward ``jax.numpy`` and float32, with no kernels, no
packing and no ``segment_ids`` — each document scored alone from position 0,
full ``[n, n]`` scores a head, every held expert by a loop, the head's
logits a block of rows at a time. It shares norms, the gated unit, the
expert loop's pieces and the buckets with ``pattern_reference`` and calls
nothing else in ``tpu_tfrecord.models``; ``benchmark/models/kimi_vl_lm.py``
carries a copy (the benchmark's files stand alone), and
``tests/test_mla_lm.py`` holds the two to each other line for line.

It reads a configuration with the published names of the DeepSeek-V3
family (``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``kv_lora_rank``, ``first_k_dense_replace``, ``n_routed_experts`` with
``n_routed_experts_held`` + ``held_offset``, ...) and flat weight names
(``shared.w_gate``, ``dense.w_gate``). For ONE document, pre-norm residual:

    mla    : q = u W_q -> heads of [nope | rope]; [c | k_pe] = u W_kva;
             [k_nope | v] = norm_kv(c) W_kvb per head; rope(q_pe), rope(k_pe)
             by the token's index in the document, k_pe ONE head shared by
             all; softmax(([q_nope | q_pe] . [k_nope | k_pe]) (nope + rope)^-1/2,
             causal) v; y = att W_o
    dense  : W_down(silu(W_gate u) * W_up u), the first ``first_k_dense_replace`` layers
    moe    : s = sigmoid(u W_r) over all experts; the top-k of s + b; gates
             s_e / sum of the chosen s, times ``routed_scaling_factor``;
             shared(u) + sum of gate_e expert_e(u) over the chosen experts held

Departures from the published model: the rotary pairs are (i, i + rope/2)
of a head's rotary columns where the published code interleaves them (with
weights from a seed that is a fixed permutation of ``W_q``'s and ``W_kva``'s
rotary columns); no vision tower and no projector (token ids only).
"""

from __future__ import annotations

from typing import Callable, List, Optional

import numpy as np

from tpu_tfrecord.models.pattern_reference import (
    _bucket, _expert_part, _jitted, _room, ref_ffn, ref_norm, ref_round)


def ffn_kinds(cfg: dict) -> List[str]:
    dense = cfg["first_k_dense_replace"]
    return ["dense" if i < dense else "moe" for i in range(cfg["num_hidden_layers"])]


def ref_rope(x, positions, theta, angle_dtype=None):
    """x [n, h, r] turned by ``positions`` [n]: the pair (i, i + r/2) by
    ``position * theta ** (-2i / r)``. ``angle_dtype`` computes the angles
    in a lower precision (a control)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * freq
    if angle_dtype:
        angle = ref_round(ref_round(positions.astype(jnp.float32), angle_dtype)[:, None, None]
                          * ref_round(freq, angle_dtype), angle_dtype)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def ref_mla(p, u, cfg, key_start=0, softmax_dtype=None, angle_dtype=None):
    """Latent attention on one document u [n, D], one head's [n, n] scores
    at a time (16 heads of an 8,192-token document are 4.3 GB at once; a
    loop over heads, so that a program holds one head's operations).
    ``key_start`` plants a fault: the keys' positions start there, the
    queries' at 0 (a restart applied on one side). ``softmax_dtype`` rounds
    scores, exponentials and weights to a lower precision (a control)."""
    import jax
    import jax.numpy as jnp

    n, h = u.shape[0], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, theta = cfg["kv_lora_rank"], float(cfg["rope_theta"])
    r = (lambda a: ref_round(a, softmax_dtype)) if softmax_dtype else (lambda a: a)
    q = (u @ p["wq"]).reshape(n, h, dn + dr)
    latent = u @ p["wkv_a"]
    kv = (ref_norm(latent[:, :rank], p["kv_norm"], cfg["rms_norm_eps"]) @ p["wkv_b"]).reshape(
        n, h, dn + dv)
    at = jnp.arange(n)
    q_pe = ref_rope(q[..., dn:], at, theta, angle_dtype)
    k_pe = ref_rope(latent[:, None, rank:], at + key_start, theta, angle_dtype)[:, 0]
    causal = jnp.tril(jnp.ones((n, n), bool))

    def one_head(head):
        q_nope, q_rot, k_nope, v = head
        scores = r((q_nope @ k_nope.T + q_rot @ k_pe.T) * (dn + dr) ** -0.5)
        scores = jnp.where(causal, scores, -jnp.inf)
        weights = r(jnp.exp(r(scores - scores.max(axis=-1, keepdims=True))))
        return r(weights / r(weights.sum(axis=-1, keepdims=True))) @ v

    by_head = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731  [n, h, .] -> [h, n, .]
    att = jax.lax.map(one_head, (by_head(q[..., :dn]), by_head(q_pe), by_head(kv[..., :dn]),
                                 by_head(kv[..., dn:])))
    return by_head(att).reshape(n, h * dv) @ p["wo"]


def ref_route_biased(u, router, bias, cfg, router_dtype=None):
    """Sigmoid scores over ALL experts; the top-k of ``scores + bias``; their
    gates from the scores alone, renormalised and scaled: (chosen [n, k],
    gates [n, k]). ``router_dtype`` computes the whole router in a lower
    precision (a control): scores, their order, the gates."""
    import jax
    import jax.numpy as jnp

    k, scale = cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]
    if router_dtype:
        r = lambda a: ref_round(a, router_dtype)  # noqa: E731
        scores = r(jax.nn.sigmoid(r(jnp.dot(r(u), r(router), precision="default"))))
        _, chosen = jax.lax.top_k(r(scores + r(bias)), k)
        top = jnp.take_along_axis(scores, chosen, axis=-1)
        return chosen, r(r(top / r(top.sum(axis=-1, keepdims=True))) * scale)
    scores = jax.nn.sigmoid(u @ router)
    _, chosen = jax.lax.top_k(scores + bias, k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, top / top.sum(axis=-1, keepdims=True) * scale


def _biased_front(router, bias, w_gate, w_up, w_down, u, cfg_items, router_dtype):
    chosen, gates = ref_route_biased(u, router, bias, dict(cfg_items), router_dtype)
    return chosen, gates, ref_ffn(u, w_gate, w_up, w_down)


def ref_moe_biased(p, u, cfg, router_dtype=None, capacity=None, no_bias=False):
    """The expert layer on one document: routing by :func:`ref_route_biased`
    (``no_bias``: the bias left out, a control), the shared experts as one
    unit, plus every HELD expert's part, expert by expert, each over the
    tokens that chose it (picked on the host); ``capacity`` drops an expert's
    visits beyond that many (a control). Returns (y, visits dropped,
    (chosen, gates))."""
    import jax.numpy as jnp

    n, e0, held = u.shape[0], cfg.get("held_offset", 0), cfg["n_routed_experts_held"]
    static = tuple((k, cfg[k]) for k in ("num_experts_per_tok", "routed_scaling_factor"))
    front = _jitted(_biased_front, static_argnums=(6, 7))
    bias = jnp.zeros_like(p["router_bias"]) if no_bias else p["router_bias"]
    routing = front(p["router"], bias, p["shared.w_gate"], p["shared.w_up"], p["shared.w_down"],
                    u, static, jnp.dtype(router_dtype).name if router_dtype else None)
    chosen, gates, y = np.asarray(routing[0]), np.asarray(routing[1]), routing[2]
    part = _jitted(_expert_part)
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
        y = part(y, u, at, gate, p["w_gate"], p["w_up"], p["w_down"], np.int32(e))
    return y, dropped, routing[:2]


def ref_layer_front(ffn, p, x, cfg, key_start=0, softmax_dtype=None, angle_dtype=None):
    """x + MLA(RMSNorm(x)) on one document x [n, D], then what the layer's
    feed-forward part needs: a dense layer is finished here (x, None), an
    expert layer hands back (x, RMSNorm(x)) for :func:`ref_moe_biased`."""
    x = x + ref_mla(p, ref_norm(x, p["attn_norm"], cfg["rms_norm_eps"]), cfg, key_start,
                    softmax_dtype, angle_dtype)
    if ffn == "dense":
        u = ref_norm(x, p["ffn_norm"], cfg["rms_norm_eps"])
        return x + ref_ffn(u, p["dense.w_gate"], p["dense.w_up"], p["dense.w_down"]), None
    return x, ref_norm(x, p["moe_norm"], cfg["rms_norm_eps"])


HEAD_ROWS = 1024  # rows of logits at a time: 8,192 x 163,840 float32 are 5.4 GB at once


def ref_head_block(p, x, targets, cfg):
    """(log p(targets) [n], logits [n, V]) of one block of hidden states."""
    import jax
    import jax.numpy as jnp

    logits = ref_norm(x, p["final_norm"], cfg["rms_norm_eps"]) @ p["head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0], logits


def reference_score(cfg: dict, docs: list, weights: Callable, logits_at: Optional[list] = None,
                    lower: Optional[dict] = None, key_starts: Optional[list] = None) -> dict:
    """Each document scored alone, in float32 at the highest matmul precision.

    docs: int arrays ``t_0 .. t_n`` (the end-of-document id included);
    ``weights(part)`` -> that part's float32 tensors (one part is held at a
    time); ``logits_at[i]``: positions of document i whose full logits to keep.
    Returns {"logprob": [log p(t_1..t_n)] a document, "logits": [[len(at), V]]
    a document, "dropped": visits a ``capacity`` control dropped, "router":
    a document's {"u", "experts", "gates"} [n_expert_layers, len(at), ..] at
    ``logits_at``: each expert layer's router input and what it chose,
    "scan": {} a document (no layer here has a recurrence)}. ``lower`` names
    a control's departures (``router_dtype``, ``softmax_dtype``,
    ``angle_dtype``, ``capacity``, ``no_bias``); ``key_starts[i]`` plants the
    fault of positions that restart on one side only: document i's keys
    count from there, its queries from 0."""
    import json

    import jax
    import jax.numpy as jnp

    lower = lower or {}
    kinds = ffn_kinds(cfg)
    where = [np.asarray(a, np.int64) for a in (logits_at or [[]] * len(docs))]
    starts = key_starts or [0] * len(docs)
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
        soft, angle = lower.get("softmax_dtype"), lower.get("angle_dtype")
        same = json.dumps(cfg, sort_keys=True, default=repr)  # programs are kept by what cfg says
        front = {ffn: _jitted(
            lambda p, x, start, ffn=ffn: ref_layer_front(ffn, p, x, cfg, start, soft, angle),
            key=("mla_front", same, ffn, soft and jnp.dtype(soft).name,
                 angle and jnp.dtype(angle).name)) for ffn in set(kinds)}
        for i, ffn in enumerate(kinds):
            p = weights(i)
            for j, x in enumerate(xs):
                xs[j], u = front[ffn](p, x, jnp.int32(starts[j]))
                if u is None:
                    continue
                y, lost, (chosen, gates) = ref_moe_biased(
                    p, u, cfg, lower.get("router_dtype"), lower.get("capacity"),
                    lower.get("no_bias", False))
                xs[j], out["dropped"] = xs[j] + y, out["dropped"] + lost
                for name, a in (("u", u), ("experts", chosen), ("gates", gates)):
                    out["router"][j][name].append(np.asarray(a)[where[j]])
            del p
        p = weights("head")
        head = _jitted(lambda p, x, t: ref_head_block(p, x, t, cfg), key=("mla_head", same))
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
