"""The plain reference of the sparse latent-attention decoder (``models.lm.score``
with ``mla`` mixers that compress their queries, turn by YaRN's frequencies
and attend the keys a lightning indexer chose, under a group-limited
router): the forward pass in straightforward ``jax.numpy`` and float32, with
no kernels, no packing and no ``segment_ids`` — each document scored alone
from position 0, full ``[n, n]`` index scores and attention scores a head,
the selection by a sort of every query's row, every held expert by a loop,
the head's logits a block of rows at a time. It shares norms, the gated
unit, the expert loop's pieces and the buckets with ``pattern_reference``,
the layer order and the head with ``mla_reference``, and calls nothing else
in ``tpu_tfrecord.models``; ``benchmark/models/deepseek_v32.py`` carries a
copy (the benchmark's files stand alone), and ``tests/test_dsa_lm.py`` holds
the two to each other line for line.

It reads a configuration with the published names of the DeepSeek-V3.2
family and flat weight names. For ONE document, pre-norm residual:

    c_q = RMSNorm_q(u W_qa);  q = c_q W_qb -> heads of [nope | rope]
    [c | k_pe] = u W_kva;  [k_nope | v] = RMSNorm_kv(c) W_kvb per head
    rope   : the pair (i, i + r/2) by position x f_i', f_i = theta^(-2i/r),
             low, high = floor, ceil of r ln(L0 / (2 pi b)) / (2 ln theta) at
             b = beta_fast, beta_slow; ramp_i = clip((i - low) / (high - low), 0, 1);
             f_i' = f_i (ramp_i / factor + 1 - ramp_i)          (YaRN)
    indexer: q^I = c_q W^I_q -> Hi heads of Di; k^I = LayerNorm(u W^I_k), ONE a
             token; rope on the first ``qk_rope_head_dim`` columns of both;
             w = u W^I_w Hi^-1/2 Di^-1/2;  I(t, s) = sum_j w(t, j) relu(q^I(t, j) . k^I(s))
    S(t)   : the keys s <= t with I(t, s) >= the ``index_topk``-th largest of
             I(t, 0..t) (all of them where t < index_topk)
    att    : softmax over S(t) of ([q_nope | q_rope] . [k_nope | k_rope])
             (nope + rope)^-1/2 (0.1 ln factor + 1)^2, times v;  y = att W_o
    moe    : s = sigmoid(u W_r); ``n_group`` runs of experts, a run's score the
             sum of its 2 largest (s + b); the ``topk_group`` best runs stay;
             the top-k of s + b inside them; gates s_e / sum of the chosen s,
             times ``routed_scaling_factor``; shared(u) + the chosen experts held

Departures from the published model: bfloat16-valued weights where the
checkpoint and the indexer are FP8, so the Hadamard rotation that serves
that quantisation (an orthogonal map of q^I and k^I alike: it changes no
dot product) is not computed; rotary pairs (i, i + r/2) where the published
code interleaves them; keys tied at the threshold are all kept
(``torch.topk``'s choice among equals is unspecified); LayerNorm with a bias
and ``rms_norm_eps``; no multi-token-prediction module.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import numpy as np

from tpu_tfrecord.models.mla_reference import HEAD_ROWS, ffn_kinds, ref_head_block
from tpu_tfrecord.models.pattern_reference import (
    _bucket, _expert_part, _jitted, _room, ref_ffn, ref_norm, ref_round)


def ref_yarn(cfg: dict, half: int):
    """(what the ``half`` rotary frequencies are multiplied by, what the
    softmax scale is multiplied by) under ``cfg["rope_scaling"]``; (None, 1.0)
    where there is none."""
    scaling = cfg.get("rope_scaling")
    if not scaling:
        return None, 1.0
    theta, dim, factor = float(cfg["rope_theta"]), 2 * half, float(scaling["factor"])
    original = float(scaling["original_max_position_embeddings"])

    def index_turning(turns):
        return dim * math.log(original / (turns * 2.0 * math.pi)) / (2.0 * math.log(theta))

    low = max(math.floor(index_turning(scaling["beta_fast"])), 0)
    high = min(math.ceil(index_turning(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    gain = 0.1 * float(scaling.get("mscale", 1)) * math.log(factor) + 1.0
    return (ramp / factor + 1.0 - ramp).astype(np.float32), gain * gain


def ref_rope(x, positions, theta, blend=None, angle_dtype=None):
    """x [n, h, r] turned by ``positions`` [n]: the pair (i, i + r/2) by
    ``position * theta ** (-2i / r) * blend_i``. ``angle_dtype`` computes the
    angles in a lower precision (a control)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if blend is not None:
        freq = freq * blend
    angle = positions.astype(jnp.float32)[:, None, None] * freq
    if angle_dtype:
        angle = ref_round(ref_round(positions.astype(jnp.float32), angle_dtype)[:, None, None]
                          * ref_round(freq, angle_dtype), angle_dtype)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def ref_index_scores(q_idx, k_idx, w, index_dtype=None):
    """``I`` [n, n]: sum over the heads j of w[:, j] relu(q_idx[:, j] . k_idx),
    a head's full scores at a time. ``index_dtype`` rounds the products, the
    weighted terms and the running sum to a lower precision (a control)."""
    import jax
    import jax.numpy as jnp

    r = (lambda a: ref_round(a, index_dtype)) if index_dtype else (lambda a: a)
    k_t = r(k_idx).T

    def one_head(j, acc):
        s = jnp.dot(r(q_idx[:, j]), k_t, precision="default" if index_dtype else None)
        return r(acc + r(r(w[:, j])[:, None] * jnp.maximum(r(s), 0.0)))

    n = q_idx.shape[0]
    return jax.lax.fori_loop(0, q_idx.shape[1], one_head, jnp.zeros((n, n), jnp.float32))


SORT_ROWS = 2048  # queries whose rows are sorted at once: 2,048 x 16,384 float32 are 128 MB


def ref_select(scores, topk: int):
    """keep [n, n] bool from index scores [n, n]: query t keeps the keys
    s <= t whose score is at least the ``topk``-th largest of its candidates'
    (a sort of every row; all of them where it has ``topk`` or fewer)."""
    import jax
    import jax.numpy as jnp

    n = scores.shape[0]
    causal = jnp.tril(jnp.ones((n, n), bool))
    if topk >= n:
        return causal
    masked = jnp.where(causal, scores, -jnp.inf)
    rows = min(SORT_ROWS, n)
    kth = jax.lax.map(lambda block: jnp.sort(block, axis=-1)[:, n - topk],
                      masked.reshape(n // rows, rows, n))
    return causal & (masked >= kth.reshape(n, 1))


def ref_dsa(p, u, cfg, lower=None):
    """Sparse latent attention on one document u [n, D]: (y, the selection's
    record: {"k_index" [n, Di], "q_index" [n, Hi, Di], "w_index" [n, Hi],
    "kept" [n, n] int8}). One head's [n, n] scores at a time. ``lower`` names
    a control's departures: ``no_selection`` (every key attended),
    ``index_topk`` (another number of keys), ``index_dtype`` (the index
    scores in a lower precision), ``no_yarn``, ``angle_dtype``."""
    import jax
    import jax.numpy as jnp

    lower = lower or {}
    n, h = u.shape[0], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    rank, theta, eps = cfg["kv_lora_rank"], float(cfg["rope_theta"]), cfg["rms_norm_eps"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    blend, gain = (None, 1.0) if lower.get("no_yarn") else ref_yarn(cfg, dr // 2)
    angle = lower.get("angle_dtype")
    c_q = ref_norm(u @ p["wq_a"], p["q_norm"], eps)
    q = (c_q @ p["wq_b"]).reshape(n, h, dn + dr)
    latent = u @ p["wkv_a"]
    kv = (ref_norm(latent[:, :rank], p["kv_norm"], eps) @ p["wkv_b"]).reshape(n, h, dn + dv)
    at = jnp.arange(n)
    q_pe = ref_rope(q[..., dn:], at, theta, blend, angle)
    k_pe = ref_rope(latent[:, None, rank:], at, theta, blend, angle)[:, 0]

    q_idx = (c_q @ p["wq_idx"]).reshape(n, hi, di)
    k_idx = u @ p["wk_idx"]
    k_idx = k_idx - k_idx.mean(axis=-1, keepdims=True)
    k_idx = k_idx * jax.lax.rsqrt(jnp.mean(k_idx * k_idx, axis=-1, keepdims=True) + eps)
    k_idx = k_idx * p["k_idx_norm"] + p["k_idx_bias"]
    q_idx = jnp.concatenate([ref_rope(q_idx[..., :dr], at, theta, blend, angle), q_idx[..., dr:]],
                            axis=-1)
    k_idx = jnp.concatenate([ref_rope(k_idx[:, None, :dr], at, theta, blend, angle)[:, 0],
                             k_idx[:, dr:]], axis=-1)
    w = (u @ p["w_idx"]) * (hi ** -0.5 * di ** -0.5)
    if lower.get("no_selection"):
        keep = jnp.tril(jnp.ones((n, n), bool))
    else:
        keep = ref_select(ref_index_scores(q_idx, k_idx, w, lower.get("index_dtype")),
                          lower.get("index_topk", cfg["index_topk"]))

    def one_head(head):
        q_nope, q_rot, k_nope, v = head
        scores = (q_nope @ k_nope.T + q_rot @ k_pe.T) * ((dn + dr) ** -0.5 * gain)
        scores = jnp.where(keep, scores, -jnp.inf)
        weights = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        return (weights / weights.sum(axis=-1, keepdims=True)) @ v

    by_head = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731  [n, h, .] -> [h, n, .]
    att = jax.lax.map(one_head, (by_head(q[..., :dn]), by_head(q_pe), by_head(kv[..., :dn]),
                                 by_head(kv[..., dn:])))
    record = {"k_index": k_idx, "q_index": q_idx, "w_index": w, "kept": keep.astype(jnp.int8)}
    return by_head(att).reshape(n, h * dv) @ p["wo"], record


def ref_route_grouped(u, router, bias, cfg, router_dtype=None, no_group_limit=False):
    """Sigmoid scores over ALL experts; the experts in ``n_group`` equal runs,
    a run's score the sum of its two largest ``scores + bias``, the
    ``topk_group`` best runs stay; the top-k of ``scores + bias`` inside
    them; gates from the scores alone, renormalised and scaled: (chosen
    [n, k], gates [n, k]). ``router_dtype`` computes the whole router in a
    lower precision and ``no_group_limit`` chooses among all experts (controls)."""
    import jax
    import jax.numpy as jnp

    k, scale = cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]
    groups, stay = cfg["n_group"], cfg["topk_group"]
    r = (lambda a: ref_round(a, router_dtype)) if router_dtype else (lambda a: a)
    if router_dtype:
        scores = r(jax.nn.sigmoid(r(jnp.dot(r(u), r(router), precision="default"))))
    else:
        scores = jax.nn.sigmoid(u @ router)
    picking = r(scores + r(bias))
    if groups > 1 and not no_group_limit:
        runs = picking.reshape(picking.shape[0], groups, -1)
        run_score = r(jax.lax.top_k(runs, 2)[0].sum(axis=-1))
        _, best = jax.lax.top_k(run_score, stay)
        stays = (best[:, :, None] == jnp.arange(groups)[None, None, :]).any(axis=1)
        picking = jnp.where(stays[:, :, None], runs, -jnp.inf).reshape(picking.shape)
    _, chosen = jax.lax.top_k(picking, k)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, r(r(top / r(top.sum(axis=-1, keepdims=True))) * scale)


def _grouped_front(router, bias, w_gate, w_up, w_down, u, cfg_items, router_dtype, no_group_limit):
    chosen, gates = ref_route_grouped(u, router, bias, dict(cfg_items), router_dtype, no_group_limit)
    return chosen, gates, ref_ffn(u, w_gate, w_up, w_down)


def ref_moe_grouped(p, u, cfg, router_dtype=None, capacity=None, no_group_limit=False):
    """The expert layer on one document: routing by :func:`ref_route_grouped`,
    the shared expert, plus every HELD expert's part, expert by expert, each
    over the tokens that chose it (picked on the host); ``capacity`` drops an
    expert's visits beyond that many (a control). Returns (y, visits
    dropped, (chosen, gates))."""
    import jax.numpy as jnp

    n, e0, held = u.shape[0], cfg.get("held_offset", 0), cfg["n_routed_experts_held"]
    static = tuple((k, cfg[k]) for k in ("num_experts_per_tok", "routed_scaling_factor",
                                         "n_group", "topk_group"))
    front = _jitted(_grouped_front, static_argnums=(6, 7, 8))
    routing = front(p["router"], p["router_bias"], p["shared.w_gate"], p["shared.w_up"],
                    p["shared.w_down"], u, static,
                    jnp.dtype(router_dtype).name if router_dtype else None, bool(no_group_limit))
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


def ref_dsa_front(ffn, p, x, cfg, lower=None):
    """x + DSA(RMSNorm(x)) on one document x [n, D], then what the layer's
    feed-forward part needs: a dense layer is finished here (x, None,
    record), an expert layer hands back (x, RMSNorm(x), record) for
    :func:`ref_moe_grouped`."""
    y, record = ref_dsa(p, ref_norm(x, p["attn_norm"], cfg["rms_norm_eps"]), cfg, lower)
    x = x + y
    if ffn == "dense":
        u = ref_norm(x, p["ffn_norm"], cfg["rms_norm_eps"])
        return x + ref_ffn(u, p["dense.w_gate"], p["dense.w_up"], p["dense.w_down"]), None, record
    return x, ref_norm(x, p["moe_norm"], cfg["rms_norm_eps"]), record


def reference_score(cfg: dict, docs: list, weights: Callable, logits_at: Optional[list] = None,
                    lower: Optional[dict] = None) -> dict:
    """Each document scored alone, in float32 at the highest matmul precision.

    docs: int arrays ``t_0 .. t_n`` (the end-of-document id included);
    ``weights(part)`` -> that part's float32 tensors (one part is held at a
    time); ``logits_at[i]``: positions of document i whose full logits to keep.
    Returns {"logprob": [log p(t_1..t_n)] a document, "logits": [[len(at), V]]
    a document, "dropped": visits a ``capacity`` control dropped, "router":
    a document's {"u", "experts", "gates"} [n_expert_layers, len(at), ..] at
    ``logits_at`` (each expert layer's router input and what it chose) and,
    of the FIRST expert layer's selection with a leading axis of 1, "q_index",
    "w_index", "kept" (the mask's rows, as wide as the padded document),
    "index_pos" (= the positions) and "index_start" (0: a document starts its
    own row), "scan": a document's {"k_index" [n, Di]} of that layer}.
    ``lower`` names a control's departures: :func:`ref_dsa`'s,
    ``router_dtype``, ``no_group_limit``, ``capacity``."""
    import json

    import jax
    import jax.numpy as jnp

    lower = lower or {}
    kinds = ffn_kinds(cfg)
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
        mixer = {k: v for k, v in lower.items()
                 if k in ("no_selection", "index_topk", "index_dtype", "no_yarn", "angle_dtype")}
        same = json.dumps(cfg, sort_keys=True, default=repr)  # programs are kept by what cfg says
        front = {ffn: _jitted(
            lambda p, x, ffn=ffn: ref_dsa_front(ffn, p, x, cfg, mixer),
            key=("dsa_front", same, ffn, json.dumps(mixer, sort_keys=True, default=repr)))
            for ffn in set(kinds)}
        probed = kinds.index("moe") if "moe" in kinds else None
        for i, ffn in enumerate(kinds):
            p = weights(i)
            for j, x in enumerate(xs):
                xs[j], u, record = front[ffn](p, x)
                if i == probed:
                    n = len(docs[j]) - 1
                    out["scan"][j] = {"k_index": np.asarray(record["k_index"])[:n]}
                    out["router"][j]["index"] = {
                        **{k: np.asarray(record[k][where[j]])[None]
                           for k in ("q_index", "w_index", "kept")},
                        "index_pos": where[j].astype(np.int32)[None],
                        "index_start": np.zeros((1, len(where[j])), np.int32)}
                del record
                if u is None:
                    continue
                y, lost, (chosen, gates) = ref_moe_grouped(
                    p, u, cfg, lower.get("router_dtype"), lower.get("capacity"),
                    lower.get("no_group_limit", False))
                xs[j], out["dropped"] = xs[j] + y, out["dropped"] + lost
                for name, a in (("u", u), ("experts", chosen), ("gates", gates)):
                    out["router"][j][name].append(np.asarray(a)[where[j]])
            del p
        p = weights("head")
        head = _jitted(lambda p, x, t: ref_head_block(p, x, t, cfg), key=("dsa_head", same))
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
            index = out["router"][j].pop("index", {})
            out["router"][j] = {**{k: np.stack(v) for k, v in out["router"][j].items()}, **index}
    return out
