"""Kimi-VL-A3B-Instruct's language decoder for the benchmark: the weights
from ``--seed``, the program built for a configuration file, the plain
reference, the router's probe, and what a step needs.

Nothing here except :func:`program` imports ``tpu_tfrecord.models``; the
tensor law, the norms, the gated unit, the expert loop's pieces and the
reference's buckets are ``solar_open2.py``'s, imported. The reference takes
the seed and the generator's documents, never anything the program has made.

The model (``configs/kimi_vl_a3b_lm.json`` has the source, what is
``assumed`` and what is ``left_out``: the vision tower), for ONE document of
tokens ``t_0 .. t_n``, pre-norm residual, weighted RMSNorm:

    x      = embed[t_0 .. t_{n-1}]
    layer  : x += MLA(RMSNorm(x));  x += Dense(RMSNorm(x)) in layer 0, MoE(RMSNorm(x)) after it
    mla    : q = u W_q -> 16 heads of [128 | 64]; [c | k_pe] = u W_kva -> 512 + 64;
             [k_nope | v] = RMSNorm_kv(c) W_kvb -> 16 x (128 + 128); rope(q_pe), rope(k_pe)
             by the token's index in ITS document (theta 800,000 over 64, no scaling),
             k_pe one head shared by all 16; softmax([q_nope | q_pe] . [k_nope | k_pe]
             / sqrt(192), causal) v; y = att W_o
    dense  : W_down(silu(W_gate u) * W_up u), width 11,264
    moe    : s = sigmoid(u W_r) over the 64 experts; the 6 largest of s + b (the bias
             picks and never weighs; one group, no group limit); gates s_e / sum of the
             6 chosen s, times 2.446; shared(u) of width 2 x 1,408 + sum of gate_e *
             expert_e(u) over the chosen experts HELD HERE (all 64)
    score  : log_softmax(head(RMSNorm(x)))[t_1 .. t_n] over 163,840 ids

The program computes this in bfloat16 with float32 norms, router, rotary
angles, softmax and logits, over packed rows with positions that restart at
every document; the reference in float32 throughout
(``jax.default_matmul_precision("highest")``), each document alone from
position 0, one head's full scores at a time, every expert by a loop, the
head's logits 1,024 rows at a time, one layer's weights on the device at a
time. Both hold the same weights: pointwise functions of the seed, rounded
to bfloat16.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

from benchmark.models.solar_open2 import (  # noqa: F401
    _bucket, _expert_part, _jitted, _room, make_tensor, ref_ffn, ref_norm, ref_round, through_int8)

# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------


def weight_specs(cfg: dict, part) -> Dict[str, tuple]:
    """{name: (shape held here, uncut leading size, first row held, law)} of one
    part: ``"embed"``, ``"head"`` or a layer's number (``solar_open2.py``'s laws)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    if part == "embed":
        return {"embed": ((v, d), v, 0, ("normal", 1.0))}
    if part == "head":
        return {"head": ((d, v), d, 0, ("normal", d ** -0.5)),
                "final_norm": ((d,), d, 0, ("about_one", 0.1))}
    h, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]

    def dense(m, n, gain=1.0):
        return ((m, n), m, 0, ("normal", (gain / m) ** 0.5))

    specs = {
        "attn_norm": ((d,), d, 0, ("about_one", 0.1)),
        "wq": dense(d, h * (dn + dr)), "wkv_a": dense(d, rank + dr),
        "kv_norm": ((rank,), rank, 0, ("about_one", 0.1)),
        "wkv_b": dense(rank, h * (dn + dv)), "wo": dense(h * dv, d),
    }
    if ffn_kinds(cfg)[part] == "dense":
        wide = cfg["intermediate_size"]
        specs.update({"ffn_norm": ((d,), d, 0, ("about_one", 0.1)), "dense.w_gate": dense(d, wide),
                      "dense.w_up": dense(d, wide), "dense.w_down": dense(wide, d)})
        return specs
    f, fs = cfg["moe_intermediate_size"], cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    e_all, e_held, e0 = cfg["n_routed_experts"], cfg["n_routed_experts_held"], cfg.get("held_offset", 0)
    k, scale = cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]
    specs.update({
        "moe_norm": ((d,), d, 0, ("about_one", 0.1)),
        "router": dense(d, e_all),
        "router_bias": ((e_all,), e_all, 0, ("normal", 0.05)),
        "w_gate": ((e_held, d, f), e_all, e0, ("normal", d ** -0.5)),
        "w_up": ((e_held, d, f), e_all, e0, ("normal", d ** -0.5)),
        # k gates of about scale / k each: their squares sum to scale^2 / k
        "w_down": ((e_held, f, d), e_all, e0, ("normal", (k / scale ** 2 / f) ** 0.5)),
        "shared.w_gate": dense(d, fs), "shared.w_up": dense(d, fs), "shared.w_down": dense(fs, d),
    })
    return specs


def part_weights(seed: int, cfg: dict, part, through: Optional[Callable] = None,
                 names: Optional[tuple] = None) -> dict:
    """{name: float32 array} of one part (:func:`weight_specs`), or of its
    ``names`` only; ``through`` is applied to every matrix (a control's
    lower precision)."""
    out = {}
    for name, (shape, _, first, law) in weight_specs(cfg, part).items():
        if names is not None and name not in names:
            continue
        w = make_tensor(seed, f"{part}.{name}", tuple(shape), first, law)
        out[name] = through(w) if through is not None and w.ndim >= 2 else w
    return out


def program(cfg: dict, mix: dict):
    """The configuration file as the program's own configuration."""
    import jax.numpy as jnp
    from tpu_tfrecord.models import lm

    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_pattern=("mla",) * cfg["num_hidden_layers"], ffn_pattern=tuple(ffn_kinds(cfg)),
        n_heads=cfg["num_attention_heads"], qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        kv_rank=cfg["kv_lora_rank"], rope_theta=float(cfg["rope_theta"]),
        d_dense=cfg["intermediate_size"], n_experts=cfg["n_routed_experts"],
        experts_held=cfg["n_routed_experts_held"], held_offset=cfg.get("held_offset", 0),
        top_k=cfg["num_experts_per_tok"], d_expert=cfg["moe_intermediate_size"],
        n_shared=cfg["n_shared_experts"], routed_scale=float(cfg["routed_scaling_factor"]),
        router_bias=True, norm_eps=cfg["rms_norm_eps"], max_len=mix["row_tokens"],
        dtype=jnp.bfloat16, **cfg.get("program", {}),
    )


def program_params(seed: int, cfg: dict) -> dict:
    """The program's parameter tree on the device: matrices bfloat16 (the
    values the reference holds in float32), vectors and the router float32."""
    import jax.numpy as jnp

    def tree(part):
        out = {}
        for name in weight_specs(cfg, part):  # one tensor in float32 at a time
            w = part_weights(seed, cfg, part, names=(name,))[name]
            w = w if w.ndim < 2 or name == "router" else w.astype(jnp.bfloat16)
            if "." in name:
                group, leaf = name.split(".")
                out.setdefault(group, {})[leaf] = w
            else:
                out[name] = w
        return out

    return {**tree("embed"), **tree("head"),
            "layers": [tree(i) for i in range(cfg["num_hidden_layers"])]}


# ---------------------------------------------------------------------------
# The plain reference (a copy of tpu_tfrecord/models/mla_reference.py;
# tests/test_mla_lm.py holds the two to each other line for line)
# ---------------------------------------------------------------------------
# --- reference: begin ---


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


# --- reference: end ---


def reference_weights(seed: int, cfg: dict, through: Optional[Callable] = None) -> Callable:
    """``weights(part)`` for :func:`reference_score` from the seed, a part at a time."""
    return lambda part: part_weights(seed, cfg, part, through)


def probe_numbers(cfg: dict, seed: int, scans: list, routed: list) -> dict:
    """The router held to float64 ON ITS OWN INPUTS, where the end-to-end
    numbers cannot tell its precision from the bfloat16 activations around
    it. Plain numpy on the host: only the router's weights and bias, the
    seed's, come from the device.

    ``router_gate_gap``  a document's ``u``, ``experts``, ``gates``
        [n_expert_layers, s, ..] at its sampled positions: the gates, bias
        and scale and all, against the float64 router's on the same ``u``
        (the 6 largest of ``sigmoid(u W_r) + b``, gates from the scores
        alone, times 2.446), as the largest difference over the 64 experts;
        the 90th percentile over positions and layers (a maximum would hang
        on one near-tie).

    ``scans`` is empty for every document: no layer here has a recurrence,
    and ``scan_state_gap`` is not reported."""
    top_k, gaps = cfg["num_experts_per_tok"], []
    layers = [i for i, ffn in enumerate(ffn_kinds(cfg)) if ffn == "moe"]
    for nth, layer in enumerate(layers):
        u = np.concatenate([np.asarray(r["u"][nth], np.float64) for r in routed])
        if not len(u):
            continue
        experts = np.concatenate([r["experts"][nth] for r in routed])
        got = np.concatenate([np.asarray(r["gates"][nth], np.float64) for r in routed])
        w = part_weights(seed, cfg, layer, names=("router", "router_bias"))
        scores = 1.0 / (1.0 + np.exp(-(u @ np.asarray(w["router"], np.float64))))
        chosen = np.argsort(-(scores + np.asarray(w["router_bias"], np.float64)), axis=1,
                            kind="stable")[:, :top_k]
        top = np.take_along_axis(scores, chosen, axis=1)
        gates = top / top.sum(axis=1, keepdims=True) * cfg["routed_scaling_factor"]
        dense, at = np.zeros((2,) + scores.shape), np.arange(len(u))[:, None]
        dense[0, at, experts] = got
        dense[1, at, chosen] = gates
        gaps.append(np.abs(dense[0] - dense[1]).max(axis=1))
    return {"router_gate_gap": float(np.percentile(np.concatenate(gaps), 90.0)) if gaps else 0.0}


# ---------------------------------------------------------------------------
# What a step needs
# ---------------------------------------------------------------------------


def needs(cfg: dict, batch: int, loop: str) -> dict:
    """The least a scoring step asks of the chip, for the roofline shares:
    {"flops", "bytes", "scopes": {scope: {"flops", "bytes"}}}.

    What the mathematics asks, not what the program does: attention over
    each document's own triangle at (128 + 64) + 128 products a pair and
    head, the rotary key read as the one head it is, the experts by the
    visits the batch makes, no work for pads, every weight read once a
    step, activations in bfloat16 once in and once out of a layer, the
    head's logits never stored. What the seed's rows held is the loop's to
    say: ``cfg["observed"]`` = {"tokens": scored positions a step,
    "triangle": sum over a step's documents of n (n + 1) / 2, "visits":
    visits to held experts a step and expert layer}."""
    seen = cfg["observed"]
    t, tri, visits = float(seen["tokens"]), float(seen["triangle"]), float(seen["visits"])
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    dn, dr, dv, rank = (cfg[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                                          "kv_lora_rank"))
    f, wide, shared = cfg["moe_intermediate_size"], cfg["intermediate_size"], cfg["n_shared_experts"]
    kinds = ffn_kinds(cfg)
    n_layers, n_dense, n_moe = len(kinds), kinds.count("dense"), kinds.count("moe")
    act = 2.0 * t * d * 2.0                                # a layer's rows in and out, bf16
    mla_w = d * h * (dn + dr) + d * (rank + dr) + rank * h * (dn + dv) + h * dv * d
    scopes = {
        "tfr.embed": {"flops": 0.0, "bytes": act + 4.0 * t},
        "tfr.mla_proj": {"flops": n_layers * 2.0 * t * mla_w, "bytes": n_layers * (2.0 * mla_w + act)},
        # q, k_nope, one k_pe, v in and the heads' values out, bf16
        "tfr.mla_attn": {"flops": n_layers * 2.0 * tri * h * (dn + dr + dv),
                         "bytes": n_layers * 2.0 * t * (h * (dn + dr) + h * dn + dr + 2 * h * dv)},
        "tfr.dense_ffn": {"flops": n_dense * t * 6.0 * d * wide,
                          "bytes": n_dense * (3 * d * wide * 2.0 + act)},
        "tfr.moe_route": {"flops": n_moe * 2.0 * t * d * cfg["n_routed_experts"],
                          "bytes": n_moe * (4.0 * d * cfg["n_routed_experts"] + t * d * 2.0)},
        "tfr.moe_experts": {"flops": n_moe * visits * 6.0 * d * f,
                            "bytes": n_moe * (cfg["n_routed_experts_held"] * 3 * d * f * 2.0
                                              + 2.0 * visits * d * 2.0)},
        "tfr.moe_shared": {"flops": n_moe * t * 6.0 * d * f * shared,
                           "bytes": n_moe * (3 * d * f * shared * 2.0 + act)},
        "tfr.lm_head": {"flops": 2.0 * t * d * v, "bytes": 2.0 * d * v + t * d * 2.0 + 4.0 * t},
    }
    return {"flops": sum(s["flops"] for s in scopes.values()),
            "bytes": sum(s["bytes"] for s in scopes.values()), "scopes": scopes}
