"""SDAR-30B-A3B-Chat for the benchmark: the weights from ``--seed``, the program
built for a configuration file, the plain reference, the probes of the router
and of one head's attention under the block mask, and what a step needs.

Nothing here except :func:`program` imports ``tpu_tfrecord.models``; the
tensor law, the norms, the gated unit, the expert loop's pieces and the
reference's buckets are ``solar_open2.py``'s, the rotary turn and the head's
block ``kimi_vl_lm.py``'s, imported. The reference takes the seed, the
generator's documents and each document's own noised copy as the feed made it
(the noise is data, not model: ``loops/score_docs_bd.py`` holds the feed's noise
to its law apart), never anything the program has computed.

The model (``configs/sdar_30b_a3b_pp8.json`` has the source, the cut and what
is ``assumed``), for ONE document of ``n`` tokens ``t_0 .. t_{n-1}`` (its end id
among them) and its noised copy ``z`` (``z_i`` is ``t_i`` or the mask id), with
``x`` the residual stream of BOTH streams, ``2 n`` rows (the clean stream C,
then the noised stream N), pre-norm residual, weighted RMSNorm eps 1e-6:

    x0     = [embed[t] ; embed[z]]
    mixer  : u = rms(x; w_in); q = u Wq -> 32 heads of 128; k = u Wk, v = u Wv -> 4 heads of
             128; q = rms(q; w_qn), k = rms(k; w_kn) over the 128 of a head, one weight for all
             heads; rotary(q), rotary(k) over all 128 columns (theta 1,000,000, pairs
             (i, i + 64)) by the token's index in its document, THE SAME in both streams;
             a(i) = softmax_s(q(i) . k(s) / sqrt(128)) v(s) over the s that M(i, s) allows;
             x = x + a Wo                                                  (no gate)
    M      : block(i) = floor(index in the document / 4);
             C query, C key: block(s) <= block(i)   (its own block whole: keys after it too)
             N query, C key: block(s) <  block(i)
             N query, N key: block(s) == block(i)
             C query, N key: never
    moe    : p = softmax(u2 W_r) in float32 over the 128 experts, u2 = rms(x; w_pre_mlp); the
             8 largest; gates p_e / sum of the 8 chosen p (norm_topk_prob), times 1;
             x = x + sum of gate_e * expert_e(u2) over the chosen experts (all 128 held);
             expert(u) = W_down(silu(W_gate u) * W_up u), width 768; no shared expert
    score  : log_softmax(head(rms(x_N[i]; w_final)))[t_i]: NO shift, a masked position's
             logits are over its own token; a caller reads it where z_i is the mask id
    bound  : sum over blocks j of (1 / t_j) sum over i masked in j of -score_i

The program computes this in bfloat16 with float32 norms, router, rotary
angles, softmax and logits, over packed rows of two streams with positions
and blocks that restart at every document; the reference in float32 throughout
(``jax.default_matmul_precision("highest")``), each document alone from
position 0, the mask written out from two integers a token (1,024 queries of
one key-value head against all ``2 n`` keys at a time), every expert by a loop,
the head's logits 1,024 rows at a time, one layer's weights on the device at a
time. Both hold the same weights: pointwise functions of the seed, rounded to
bfloat16. Departures: none in rotary (the family's own pairs (i, i + 64));
bfloat16 weights as served; every ``assumed`` of the configuration file.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import numpy as np

from benchmark.models.kimi_vl_lm import HEAD_ROWS, ref_head_block, ref_rope  # noqa: F401
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
    dh = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh
    f, e_all, k = cfg["moe_intermediate_size"], cfg["num_experts"], cfg["num_experts_per_tok"]
    e_held, e0 = cfg["n_routed_experts_held"], cfg.get("held_offset", 0)

    def dense(m, n):
        return ((m, n), m, 0, ("normal", m ** -0.5))

    def gain(n):
        return ((n,), n, 0, ("about_one", 0.1))

    return {
        "attn_norm": gain(d), "wq": dense(d, hq), "wk": dense(d, hkv), "wv": dense(d, hkv),
        "wo": dense(hq, d), "q_norm": gain(dh), "k_norm": gain(dh),
        "moe_norm": gain(d), "router": dense(d, e_all),
        "w_gate": ((e_held, d, f), e_all, e0, ("normal", d ** -0.5)),
        "w_up": ((e_held, d, f), e_all, e0, ("normal", d ** -0.5)),
        # k gates that sum to 1, of about 1 / k each: their squares sum to 1 / k
        "w_down": ((e_held, f, d), e_all, e0, ("normal", (k / f) ** 0.5)),
    }


def part_weights(seed: int, cfg: dict, part, through: Optional[Callable] = None,
                 names: Optional[tuple] = None) -> dict:
    """{name: float32 array} of one part (:func:`weight_specs`), or of its
    ``names`` only; ``through`` is applied to every matrix (a control's lower
    precision). A matrix is rounded to bfloat16's values here, by arithmetic on
    the bits (``deepseek_v32.py`` has why: on a TPU the compiler drops
    ``make_tensor``'s own pair of conversions)."""
    import jax.numpy as jnp

    rounded = _jitted(ref_round, static_argnums=1)
    out = {}
    for name, (shape, _, first, law) in weight_specs(cfg, part).items():
        if names is not None and name not in names:
            continue
        w = make_tensor(seed, f"{part}.{name}", tuple(shape), first, law)
        if w.ndim >= 2:
            w = rounded(w, jnp.bfloat16)
        out[name] = through(w) if through is not None and w.ndim >= 2 else w
    return out


def program(cfg: dict, mix: dict):
    """The configuration file as the program's own configuration; the block
    length and the mask id are the mix's (``assumed`` in the configuration)."""
    import jax.numpy as jnp
    from tpu_tfrecord.models import lm

    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_pattern=("bda",) * cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"], qk_norm=True,
        gqa_gate=False, diffusion_block=mix["block_length"], mask_id=mix["mask_id"],
        rope_theta=float(cfg["rope_theta"]), n_experts=cfg["num_experts"],
        experts_held=cfg["n_routed_experts_held"], held_offset=cfg.get("held_offset", 0),
        top_k=cfg["num_experts_per_tok"], d_expert=cfg["moe_intermediate_size"], n_shared=0,
        routed_scale=1.0, router_scoring="softmax", norm_eps=cfg["rms_norm_eps"],
        max_len=mix["row_tokens"], dtype=jnp.bfloat16, **cfg.get("program", {}),
    )


def program_params(seed: int, cfg: dict) -> dict:
    """The program's parameter tree on the device: matrices bfloat16 (the values
    the reference holds in float32; the router too, which the program widens
    to float32 before it multiplies), vectors float32."""
    import jax
    import jax.numpy as jnp

    def tree(part):
        out = {}
        for name in weight_specs(cfg, part):  # one tensor in float32 at a time
            w = part_weights(seed, cfg, part, names=(name,))[name]
            # waited for: dispatched ahead, the float32 tensors pile up until the device is full
            # (peak_bytes_in_use 16.13 GB at [state], my chip run, PR 53, where the step needs 11.5)
            out[name] = jax.block_until_ready(w if w.ndim < 2 else w.astype(jnp.bfloat16))
        return out

    return {**tree("embed"), **tree("head"),
            "layers": [tree(i) for i in range(cfg["num_hidden_layers"])]}


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------
# --- reference: begin ---

QUERY_ROWS = 1024  # queries whose scores exist at once: 8 heads x 1,024 x 16,384 float32 are 537 MB
MASKED = -1e30     # a padded row sees nothing: finite, so that its softmax is a number nobody reads


def block_mask(q_at, q_noised, q_real, k_at, k_noised, k_real, length: int, lower=None):
    """M as it is written: [queries, keys] bool from two integers a token, its
    index in its document ``at`` and its stream ``noised`` (and ``real``: not
    the padding of a bucket). ``lower`` names a control's fault:
    ``causal_clean`` (a clean query sees no key after it: no sight inside a
    block), ``own_clean_seen`` (a noised query sees the clean copy of its own
    block: the leak that flatters every score), ``block_origin`` (blocks
    counted from so many tokens before the document's first)."""
    import jax.numpy as jnp

    lower = lower or {}
    origin = lower.get("block_origin", 0)
    qb, kb = (q_at[:, None] + origin) // length, (k_at[None, :] + origin) // length
    qn, kn = q_noised[:, None], k_noised[None, :]
    clean_clean = (k_at[None, :] <= q_at[:, None]) if lower.get("causal_clean") else kb <= qb
    noised_clean = kb <= qb if lower.get("own_clean_seen") else kb < qb
    seen = jnp.where(kn, qn & (kb == qb), jnp.where(qn, noised_clean, clean_clean))
    return seen & q_real[:, None] & k_real[None, :]


def ref_block_attention(q, k, v, at, noised, real, length: int, lower=None):
    """softmax(q . k / sqrt(d)) v over the keys M allows. q [m, g, r, d] (g
    key-value heads, each serving r query heads), k, v [m, g, d], ``at``,
    ``noised``, ``real`` [m] (:func:`block_mask`) -> [m, g, r, d]; a block of
    ``QUERY_ROWS`` queries of one key-value head against every key at a time.
    ``lower["softmax_dtype"]`` rounds scores, exponentials and weights to a
    lower precision (a control)."""
    import jax
    import jax.numpy as jnp

    lower = lower or {}
    m, _, r, d = q.shape
    rows = min(QUERY_ROWS, m)
    dtype = lower.get("softmax_dtype")
    rnd = (lambda a: ref_round(a, dtype)) if dtype else (lambda a: a)

    def one_group(group):
        qg, kg, vg = group                                     # [m, r, d], [m, d], [m, d]

        def one_block(block):
            qb, q_at, q_noised, q_real = block
            seen = block_mask(q_at, q_noised, q_real, at, noised, real, length, lower)
            scores = rnd(jnp.einsum("ihd,jd->hij", qb, kg) * d ** -0.5)
            scores = jnp.where(seen, scores, MASKED)
            weights = rnd(jnp.exp(rnd(scores - scores.max(axis=-1, keepdims=True))))
            probs = rnd(weights / rnd(weights.sum(axis=-1, keepdims=True)))
            return jnp.einsum("hij,jd->ihd", probs, vg)

        cut = lambda a: a.reshape(m // rows, rows, *a.shape[1:])  # noqa: E731
        return jax.lax.map(one_block, (cut(qg), cut(at), cut(noised), cut(real))).reshape(m, r, d)

    by_group = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731  [m, g, ..] -> [g, m, ..]
    return by_group(jax.lax.map(one_group, (by_group(q), by_group(k), by_group(v))))


def ref_bda(p, u, n, cfg, length, lower=None, probe_head=None):
    """The attention layer on one document's two streams u [2 b, D] (b its
    bucket: the clean stream's rows, then the noised stream's; ``n`` real
    tokens in each): (y, the record of ``probe_head``'s attention or None:
    ``q``, ``att`` [2 b, dh] and its key-value head's ``k``, ``v`` [2 b, dh], as
    the attention was given and gave them). ``lower`` names a control's
    departures: :func:`block_mask`'s, ``no_qk_norm``, ``carried_positions`` (the
    noised stream's positions go on from the clean stream's last: counted
    across the row, not in the document), ``softmax_dtype``."""
    import jax.numpy as jnp

    lower = lower or {}
    m, eps, theta = u.shape[0], cfg["rms_norm_eps"], float(cfg["rope_theta"])
    h, g, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    half = m // 2
    at = jnp.tile(jnp.arange(half), 2)
    noised = jnp.arange(m) >= half
    real = at < n
    q = (u @ p["wq"]).reshape(m, h, dh)
    k = (u @ p["wk"]).reshape(m, g, dh)
    v = (u @ p["wv"]).reshape(m, g, dh)
    if not lower.get("no_qk_norm"):
        q, k = ref_norm(q, p["q_norm"], eps), ref_norm(k, p["k_norm"], eps)
    turned = at + jnp.where(noised, n, 0) if lower.get("carried_positions") else at
    q, k = ref_rope(q, turned, theta), ref_rope(k, turned, theta)
    att = ref_block_attention(q.reshape(m, g, h // g, dh), k, v, at, noised, real, length,
                              lower).reshape(m, h, dh)
    record = None
    if probe_head is not None:
        held = probe_head // (h // g)
        record = {"q": q[:, probe_head], "k": k[:, held], "v": v[:, held], "att": att[:, probe_head]}
    return att.reshape(m, h * dh) @ p["wo"], record


def ref_route_softmax(u, router, cfg, lower=None):
    """A softmax over ALL experts in float32, the top-k, their gates
    renormalised to sum 1: (chosen [m, k], gates [m, k]). ``lower``:
    ``router_dtype`` computes the whole router in a lower precision (scores,
    their order, the gates), ``sigmoid_router`` scores by a sigmoid (controls)."""
    import jax
    import jax.numpy as jnp

    lower = lower or {}
    k, dtype = cfg["num_experts_per_tok"], lower.get("router_dtype")
    squash = jax.nn.sigmoid if lower.get("sigmoid_router") else (lambda a: jax.nn.softmax(a, axis=-1))
    if dtype:
        r = lambda a: ref_round(a, dtype)  # noqa: E731
        top, chosen = jax.lax.top_k(r(squash(r(jnp.dot(r(u), r(router), precision="default")))), k)
        return chosen, r(top / r(top.sum(axis=-1, keepdims=True)))
    top, chosen = jax.lax.top_k(squash(u @ router), k)
    return chosen, top / top.sum(axis=-1, keepdims=True)


def _softmax_front(router, u, cfg_items, lower_items):
    return ref_route_softmax(u, router, dict(cfg_items), dict(lower_items))


def ref_moe_softmax(p, u, real, cfg, lower=None):
    """The expert layer on one document's two streams u [m, D]: routing by
    :func:`ref_route_softmax`, every HELD expert's part, expert by expert, each
    over the REAL tokens that chose it (picked on the host; ``real`` [m] bool:
    a bucket's padding visits nothing); no shared expert.
    ``lower["capacity"]`` drops an expert's visits beyond that many (a
    control). Returns (y, visits dropped, (chosen, gates))."""
    import jax.numpy as jnp

    lower = lower or {}
    m, e0, held = u.shape[0], cfg.get("held_offset", 0), cfg["n_routed_experts_held"]
    static = (("num_experts_per_tok", cfg["num_experts_per_tok"]),)
    said = tuple(sorted((k, jnp.dtype(v).name if k == "router_dtype" else v) for k, v in lower.items()
                        if k in ("router_dtype", "sigmoid_router")))
    front = _jitted(_softmax_front, static_argnums=(2, 3))
    routing = front(p["router"], u, static, said)
    chosen, gates = np.asarray(routing[0]), np.asarray(routing[1])
    part = _jitted(_expert_part)
    y, dropped, capacity = jnp.zeros_like(u), 0, lower.get("capacity")
    for e in range(held):
        hit = (chosen == e0 + e) & real[:, None]                # a token picks an expert once
        tokens = np.flatnonzero(hit.any(axis=1))
        if capacity is not None:
            dropped += max(0, len(tokens) - capacity)
            tokens = tokens[:capacity]
        if not len(tokens):
            continue
        room = _room(len(tokens), m)
        at = np.full(room, m, np.int32)                         # m: past the end
        at[: len(tokens)] = tokens
        gate = np.zeros(room, np.float32)
        gate[: len(tokens)] = gates[tokens][hit[tokens]]
        y = part(y, u, at, gate, p["w_gate"], p["w_up"], p["w_down"], np.int32(e))
    return y, dropped, routing


def ref_layer_front(p, x, n, cfg, length, lower=None, probe_head=None):
    """x + BDA(rms(x)) on one document's two streams, then what the experts
    read: (x, rms(x; w_pre_mlp), the attention's record or None)."""
    eps = cfg["rms_norm_eps"]
    y, record = ref_bda(p, ref_norm(x, p["attn_norm"], eps), n, cfg, length, lower, probe_head)
    x = x + y
    return x, ref_norm(x, p["moe_norm"], eps), record


def reference_score(cfg: dict, docs: list, weights: Callable, logits_at: Optional[list] = None,
                    lower: Optional[dict] = None, probe_head: int = 0, noised: Optional[list] = None,
                    block_length: int = 4) -> dict:
    """Each document scored alone, in float32 at the highest matmul precision.

    docs: int arrays ``t_0 .. t_{n-1}`` (the end-of-document id included, a
    token like any other); ``noised[i]``: document i's noised copy as the feed
    made it (``t`` or the mask id a token); ``weights(part)`` -> that part's
    float32 tensors (one part is held at a time); ``logits_at[i]``: positions
    of document i whose full logits (the noised stream's) to keep.
    Returns {"logprob": [log p(t_i) from the noised stream's position i, every
    i] a document (a caller reads the masked ones), "logits": [[len(at), V]] a
    document, "dropped": visits a ``capacity`` control dropped, "router": a
    document's {"u", "experts", "gates"} [n_layers, len(at), ..] at the noised
    stream's ``logits_at`` and, of head ``probe_head`` of the FIRST layer with
    a leading axis of 1, "q_bda", "att_bda" (the noised stream's), "q_bda_clean",
    "att_bda_clean" [1, len(at), dh] and "bda_pos" (= the positions), "scan": a
    document's {"k_bda", "v_bda", "k_bda_noised", "v_bda_noised"} [n, dh] of that
    head's key-value head}. ``lower`` names a control's departures:
    :func:`ref_bda`'s, :func:`ref_route_softmax`'s, ``capacity``,
    ``shifted_targets`` (position i scored against token i + 1)."""
    import json

    import jax
    import jax.numpy as jnp

    lower = lower or {}
    where = [np.asarray(a, np.int64) for a in (logits_at or [[]] * len(docs))]
    mixer = {k: v for k, v in lower.items() if k in (
        "causal_clean", "own_clean_seen", "block_origin", "no_qk_norm", "carried_positions", "softmax_dtype")}
    router = {k: v for k, v in lower.items() if k in ("router_dtype", "sigmoid_router", "capacity")}
    with jax.default_matmul_precision("highest"):
        embed = weights("embed")["embed"]
        xs, buckets = [], []
        for doc, copy in zip(docs, noised):
            n, b = len(doc), _bucket(len(doc))
            ids = np.zeros(2 * b, np.int32)
            ids[:n], ids[b: b + n] = doc, copy
            xs.append(embed[ids])
            buckets.append(b)
        del embed
        out = {"logprob": [], "logits": [], "dropped": 0, "scan": [{} for _ in docs],
               "router": [{"u": [], "experts": [], "gates": []} for _ in docs]}
        same = json.dumps({k: v for k, v in cfg.items() if k != "observed"}, sort_keys=True, default=repr)
        said = json.dumps(mixer, sort_keys=True, default=repr)
        front = {probe: _jitted(
            lambda p, x, n, probe=probe: ref_layer_front(p, x, n, cfg, block_length, mixer, probe),
            key=("bda_front", same, block_length, said, probe)) for probe in (None, probe_head)}
        for i in range(cfg["num_hidden_layers"]):
            p = weights(i)
            for j, x in enumerate(xs):
                n, b = len(docs[j]), buckets[j]
                x, u, record = front[probe_head if i == 0 else None](p, x, jnp.int32(n))
                if record is not None:
                    rec = {name: np.asarray(a) for name, a in record.items()}
                    out["scan"][j] = {"k_bda": rec["k"][:n], "v_bda": rec["v"][:n],
                                      "k_bda_noised": rec["k"][b: b + n], "v_bda_noised": rec["v"][b: b + n]}
                    out["router"][j]["probe"] = {
                        "q_bda": rec["q"][b + where[j]][None], "att_bda": rec["att"][b + where[j]][None],
                        "q_bda_clean": rec["q"][where[j]][None], "att_bda_clean": rec["att"][where[j]][None],
                        "bda_pos": where[j].astype(np.int32)[None]}
                del record
                real = np.tile(np.arange(b) < n, 2)
                y, lost, (chosen, gates) = ref_moe_softmax(p, u, real, cfg, router)
                xs[j], out["dropped"] = x + y, out["dropped"] + lost
                for name, a in (("u", u), ("experts", chosen), ("gates", gates)):
                    out["router"][j][name].append(np.asarray(a)[b + where[j]])
            del p
        p = weights("head")
        head = _jitted(lambda p, x, t: ref_head_block(p, x, t, cfg), key=("bda_head", same))
        for j, (doc, x) in enumerate(zip(docs, xs)):
            n, b = len(doc), buckets[j]
            targets = np.zeros(b, np.int32)
            targets[:n] = np.append(doc[1:], doc[-1]) if lower.get("shifted_targets") else doc
            x = x[b:]  # the noised stream alone goes to the head
            logp, kept = [], np.zeros((len(where[j]), p["head"].shape[1]), np.float32)
            for r0 in range(0, b, HEAD_ROWS):
                lp, logits = head(p, x[r0:r0 + HEAD_ROWS], jnp.asarray(targets[r0:r0 + HEAD_ROWS]))
                logp.append(np.asarray(lp))
                here = (where[j] >= r0) & (where[j] < r0 + HEAD_ROWS)
                if here.any():
                    kept[here] = np.asarray(logits[where[j][here] - r0])
            out["logprob"].append(np.concatenate(logp)[:n])
            out["logits"].append(kept)
            probe = out["router"][j].pop("probe", {})
            out["router"][j] = {**{k: np.stack(v) for k, v in out["router"][j].items()}, **probe}
    return out


# --- reference: end ---


def reference_weights(seed: int, cfg: dict, through: Optional[Callable] = None) -> Callable:
    """``weights(part)`` for :func:`reference_score` from the seed, a part at a time."""
    return lambda part: part_weights(seed, cfg, part, through)


def seen_keys(scan: dict, pos: int, noised: bool, length: int, rule: str = "M"):
    """(keys, values) [s, dh] float64 that the query at index ``pos`` of its
    document, of the ``noised`` stream or the clean one, sees under the block
    mask of ``length`` tokens, from a document's ``scan`` record. ``rule``
    names another mask for :func:`probe_numbers` to tell M from: ``causal``
    (a clean query sees no key after it), ``leak`` (a noised query sees the
    clean copy of its own block too), ``origin`` (blocks counted from one
    token before the document's first)."""
    n = len(scan["k_bda"])
    shift = 1 if rule == "origin" else 0
    block = (pos + shift) // length
    first, end = max(0, block * length - shift), min(n, (block + 1) * length - shift)  # its block's tokens
    if not noised:
        runs = [("", 0, pos + 1 if rule == "causal" else end)]
    else:
        runs = [("", 0, end if rule == "leak" else first), ("_noised", first, end)]
    keys = np.concatenate([np.asarray(scan["k_bda" + s][a:z], np.float64) for s, a, z in runs])
    values = np.concatenate([np.asarray(scan["v_bda" + s][a:z], np.float64) for s, a, z in runs])
    return keys, values


def attend(q, keys, values) -> np.ndarray:
    """One query's softmax attention over ``keys``, float64 on the host."""
    scores = keys @ q * len(q) ** -0.5
    weights = np.exp(scores - scores.max())
    return weights @ values / weights.sum()


def probe_numbers(cfg: dict, seed: int, scans: list, routed: list, block_length: int = 4) -> dict:
    """The router and one head's attention under the block mask held to float64
    ON THEIR OWN INPUTS, where the end-to-end numbers cannot tell a layer's
    precision, or one block more or less in sight, from the bfloat16
    activations around it. Plain numpy on the host: only the router's
    weights, the seed's, come from the device.

    ``router_gate_gap``  a document's ``u``, ``experts``, ``gates``
        [n_layers, s, ..] at its sampled positions (the noised stream's): the
        gates against the float64 router's on the same ``u`` (a softmax over
        all 128, the 8 largest, renormalised), as the largest difference over
        the experts; the 90th percentile over positions and layers (a maximum
        would hang on one near-tie).

    ``bda_attn_gap``  one head of the first layer at the sampled positions of
        BOTH streams: its outputs ``att_bda`` and ``att_bda_clean`` against
        float64 attention over the very queries (``routed``), keys and values
        (``scans``: both streams') the call was given, over the keys M allows;
        root mean square over all sampled queries, relative.

    ``bda_keys_wrong``  sampled queries whose output another mask explains at
        least twice as well as M does (distance to float64 attention under
        ``causal``, ``leak`` or ``origin`` of :func:`seen_keys`, under half the
        distance to M's): limit 0. A query for which the other mask allows the
        very keys M does cannot tell them apart and counts for nothing."""
    top_k, gaps = cfg["num_experts_per_tok"], []
    for layer in range(cfg["num_hidden_layers"]):
        u = np.concatenate([np.asarray(r["u"][layer], np.float64) for r in routed if "u" in r] or [np.zeros((0, 1))])
        if not len(u):
            continue
        experts = np.concatenate([r["experts"][layer] for r in routed])
        got = np.concatenate([np.asarray(r["gates"][layer], np.float64) for r in routed])
        router = np.asarray(part_weights(seed, cfg, layer, names=("router",))["router"], np.float64)
        logits = u @ router
        scores = np.exp(logits - logits.max(axis=1, keepdims=True))
        scores /= scores.sum(axis=1, keepdims=True)
        chosen = np.argsort(-scores, axis=1, kind="stable")[:, :top_k]
        top = np.take_along_axis(scores, chosen, axis=1)
        gates = top / top.sum(axis=1, keepdims=True)
        dense, at = np.zeros((2,) + scores.shape), np.arange(len(u))[:, None]
        dense[0, at, experts] = got
        dense[1, at, chosen] = gates
        gaps.append(np.abs(dense[0] - dense[1]).max(axis=1))
    err = norm = 0.0
    wrong = 0
    for scan, r in zip(scans, routed):
        if "k_bda" not in scan or "q_bda" not in r:
            continue
        for stream, noised in (("", True), ("_clean", False)):
            for q, got, pos in zip(np.asarray(r["q_bda" + stream][0], np.float64),
                                   np.asarray(r["att_bda" + stream][0], np.float64), r["bda_pos"][0]):
                keys, values = seen_keys(scan, int(pos), noised, block_length)
                want = attend(q, keys, values)
                off = float(((got - want) ** 2).sum())
                err, norm = err + off, norm + float((want ** 2).sum())
                for rule in ("causal", "leak", "origin"):
                    other = seen_keys(scan, int(pos), noised, block_length, rule)
                    if other[0].shape != keys.shape and float(
                            ((got - attend(q, *other)) ** 2).sum()) < off / 4.0:
                        wrong += 1
                        break
    return {"router_gate_gap": float(np.percentile(np.concatenate(gaps), 90.0)) if gaps else 0.0,
            "bda_attn_gap": float(np.sqrt(err / norm)) if norm else 0.0,
            "bda_keys_wrong": float(wrong)}


# ---------------------------------------------------------------------------
# What a step needs
# ---------------------------------------------------------------------------


def seen_pairs(n: int, length: int) -> int:
    """The (query, key) pairs M allows in a document of ``n`` tokens, both
    streams: a clean query sees its document to the end of its own block, a
    noised one the clean blocks before its own and its own noised block."""
    at = np.arange(n, dtype=np.int64)
    end = np.minimum(at // length * length + length, n)    # where a token's block ends
    return int(2 * end.sum())   # clean: the document to there; noised: as many, the block's own noised in the clean's place


def needs(cfg: dict, batch: int, loop: str) -> dict:
    """The least a scoring step asks of the chip, for the roofline shares:
    {"flops", "bytes", "scopes": {scope: {"flops", "bytes"}}}.

    What the mathematics asks, not what the program does: both streams
    through every projection, the router and the experts (2 t positions a
    layer); attention over the pairs M allows in each document (each stream
    over its own mask, not the square: :func:`seen_pairs`) at 128 + 128
    products a pair and query head, keys and values read as the 4 heads they
    are; the experts by the visits the batch makes; the head over the MASKED
    positions alone (a bound reads no other); no work for pads, every weight
    read once a step, activations in bfloat16 once in and once out of a layer,
    the head's logits never stored. What the seed's rows held is the loop's to
    say: ``cfg["observed"]`` = {"tokens": a stream's real positions a step,
    "pairs": sum over a step's documents of :func:`seen_pairs`, "masked":
    positions the noised row masks a step, "visits": visits to held experts a
    step and layer}."""
    seen = cfg["observed"]
    t, pairs = 2.0 * float(seen["tokens"]), float(seen["pairs"])
    masked, visits = float(seen["masked"]), float(seen["visits"])
    d, v, dh = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    h, hkv, f = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["moe_intermediate_size"]
    n_layers = cfg["num_hidden_layers"]
    act = 2.0 * t * d * 2.0                                # a layer's rows in and out, bf16
    mixer_w = d * (h * dh + 2 * hkv * dh) + h * dh * d
    heads_io = 2.0 * t * (2 * h * dh + 2 * hkv * dh)       # q in and a out, k and v in, bf16
    scopes = {
        "tfr.embed": {"flops": 0.0, "bytes": act + 4.0 * t},
        "tfr.bda_proj": {"flops": n_layers * 2.0 * t * mixer_w, "bytes": n_layers * (2.0 * mixer_w + act)},
        "tfr.bda_attn": {"flops": n_layers * 4.0 * pairs * h * dh, "bytes": n_layers * heads_io},
        "tfr.moe_route": {"flops": n_layers * 2.0 * t * d * cfg["num_experts"],
                          "bytes": n_layers * (2.0 * d * cfg["num_experts"] + t * d * 2.0)},
        "tfr.moe_experts": {"flops": n_layers * visits * 6.0 * d * f,
                            "bytes": n_layers * (cfg["n_routed_experts_held"] * 3 * d * f * 2.0
                                                 + 2.0 * visits * d * 2.0)},
        "tfr.lm_head": {"flops": 2.0 * masked * d * v,
                        "bytes": 2.0 * d * v + masked * d * 2.0 + 4.0 * masked},
    }
    return {"flops": sum(s["flops"] for s in scopes.values()),
            "bytes": sum(s["bytes"] for s in scopes.values()), "scopes": scopes}
