"""Olmo-Hybrid-7B for the benchmark: the weights from ``--seed``, the program
built for a configuration file, the plain reference, the probe of one head's
recurrence, and what a step needs.

Nothing here except :func:`program` imports ``tpu_tfrecord.models``; the tensor
law, the plain norm, the convolution, the gated unit, the rounding and the
reference's buckets are ``solar_open2.py``'s, the rows of a head's block
``kimi_vl_lm.py``'s, the softmax a block of queries at a time
``trinity_large.py``'s, the rotary turn (a control alone reads it)
``deepseek_v32.py``'s, imported. The reference takes the seed and the
generator's documents, never anything the program has made.

The model (``configs/olmo_hybrid_7b_pp4.json`` has the source, the cut and what
is ``assumed`` with the reading taken), for ONE document of tokens ``t_0 ..
t_n``, with ``x`` the residual stream and ``N(x; w) = x / rms(x) * w`` (eps
1e-6). No expert anywhere, and no norm on a branch's way IN: each branch is
normed on its way out alone.

    x0     = embed[t_0 .. t_{n-1}]
    layer  : x = x + N(M(x); w_1);  x = x + N(F(x); w_2)
             F(x) = (silu(x Wg) * (x Wu)) Wd at width 11,008
    gdn    : q = x Wq, k = x Wk -> 30 heads of 96; v = x Wv, z = x Wz -> 30 heads of 192;
             q, k, v = silu(conv4(.)), causal, no bias, nothing before the document;
             q = q / |q| / sqrt(96), k = k / |k| over a head's 96;
             a_t = exp(-exp(A_h) softplus(x w_a + dt_h)), b_t = 2 sigmoid(x w_b) in (0, 2):
             ONE of each a head and token;
             S_t = a_t S_{t-1} + b_t k_t (v_t - (a_t S_{t-1})^T k_t)^T, a [96 x 192] float32
             state a head, S = 0 before the document; o_t = S_t^T q_t [192];
             M(x) = (o / rms(o) * w_o * silu(z)) Wo                    (3 layers of 4)
    gqa    : q = N(x Wq; w_q), k = N(x Wk; w_k) over the WHOLE 3,840 of the projection,
             v = x Wv; 30 heads of 128 on 30 key-value heads, no positions;
             softmax over s <= t of q . k 128^-1/2, times v; M(x) = att Wo   (1 layer of 4)
    score  : log_softmax(head(N(x; w_final)))[t_1 .. t_n] over all 100,352 ids

The program computes this in bfloat16 with float32 norms, decay, beta, state,
softmax and logits, over packed rows whose taps and state restart at every
document; the reference in float32 throughout
(``jax.default_matmul_precision("highest")``), each document alone from an
empty state, the recurrence token by token, a block of queries' full scores at
a time, the head's logits 1,024 rows at a time, one layer's weights on the
device at a time. Both hold the same weights: pointwise functions of the seed,
rounded to bfloat16.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

from benchmark.models.deepseek_v32 import ref_rope
from benchmark.models.kimi_vl_lm import HEAD_ROWS, ref_head_block
from benchmark.models.solar_open2 import (  # noqa: F401
    _bucket, _jitted, make_tensor, ref_conv, ref_ffn, ref_norm, ref_round, through_int8)
from benchmark.models.trinity_large import ref_window_attention

KINDS = {"linear_attention": "gdn", "full_attention": "gqa"}
ROTARY_CONTROL_THETA = 500000.0  # what the control ``rotary_on_full`` turns the full layers' heads by

# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------


def widths(cfg: dict) -> tuple:
    """(key heads, value heads, d_k, d_v) of a delta-net layer."""
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def head_dim(cfg: dict) -> int:
    """A full-attention head's width: the configuration names none, so hidden over heads."""
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def weight_specs(cfg: dict, part) -> Dict[str, tuple]:
    """{name: (shape held here, uncut leading size, first row held, law)} of one
    part: ``"embed"``, ``"head"`` or a layer's number (``solar_open2.py``'s
    laws)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]

    def gain(n):
        return ((n,), n, 0, ("about_one", 0.1))

    def dense(m, n):
        return ((m, n), m, 0, ("normal", m ** -0.5))

    if part == "embed":
        return {"embed": ((v, d), v, 0, ("normal", 1.0))}
    if part == "head":
        return {"head": dense(d, v), "final_norm": gain(d)}
    wide = cfg["intermediate_size"]
    specs = {"post_attn_norm": gain(d), "post_ffn_norm": gain(d), "dense.w_gate": dense(d, wide),
             "dense.w_up": dense(d, wide), "dense.w_down": dense(wide, d)}
    if layer_plan(cfg)[part] == "gdn":
        hk, h, dk, dv = widths(cfg)
        taps = cfg["linear_conv_kernel_dim"]

        def conv(n):
            return ((taps, n), taps, 0, ("taps", 0.5))

        specs.update(
            wq=dense(d, hk * dk), wk=dense(d, hk * dk), wv=dense(d, h * dv), wz=dense(d, h * dv),
            conv_q=conv(hk * dk), conv_k=conv(hk * dk), conv_v=conv(h * dv), w_a=dense(d, h),
            dt_bias=((h,), h, 0, ("rate_bias", 1e-3, 1e-1)), a_log=((h,), h, 0, ("log_between", 0.5, 2.0)),
            w_beta=dense(d, h), o_norm=gain(dv), wo=dense(h * dv, d))
    else:
        hq = cfg["num_attention_heads"] * head_dim(cfg)
        hkv = cfg["num_key_value_heads"] * head_dim(cfg)
        specs.update(wq=dense(d, hq), wk=dense(d, hkv), wv=dense(d, hkv), wo=dense(hq, d),
                     q_norm=gain(hq), k_norm=gain(hkv))
    return specs


def part_weights(seed: int, cfg: dict, part, through: Optional[Callable] = None,
                 names: Optional[tuple] = None) -> dict:
    """{name: float32 array} of one part (:func:`weight_specs`) as the seed's
    law gives it, or of its ``names`` only; ``through`` is applied to every
    matrix (a control's lower precision). A matrix is rounded to bfloat16's
    values here, by arithmetic on the bits (``deepseek_v32.py`` has why: on a
    TPU the compiler drops ``make_tensor``'s own pair of conversions)."""
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
    """The configuration file as the program's own configuration."""
    import jax.numpy as jnp
    from tpu_tfrecord.models import lm

    plan = layer_plan(cfg)
    hk, h, dk, dv = widths(cfg)
    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_pattern=tuple(plan), ffn_pattern=("dense",) * len(plan),
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        qk_norm_whole=True, gqa_gate=False,
        kda_heads=h, gdn_key_heads=hk, kda_head_dim=dk, gdn_value_dim=dv,
        conv_taps=cfg["linear_conv_kernel_dim"], gdn_neg_eigval=bool(cfg["linear_allow_neg_eigval"]),
        gdn_gate="silu", d_dense=cfg["intermediate_size"], norm_eps=cfg["rms_norm_eps"],
        branch_norms=True, pre_norms=False, max_len=mix["row_tokens"], dtype=jnp.bfloat16,
        **cfg.get("program", {}),
    )


def program_params(seed: int, cfg: dict) -> dict:
    """The program's parameter tree on the device: matrices bfloat16 (the
    values the reference holds in float32), vectors and taps float32."""
    import jax.numpy as jnp

    def tree(part):
        out = {}
        for name in weight_specs(cfg, part):  # one tensor in float32 at a time
            w = part_weights(seed, cfg, part, names=(name,))[name]
            w = w if w.ndim < 2 or name.startswith("conv_") else w.astype(jnp.bfloat16)
            if "." in name:
                group, leaf = name.split(".")
                out.setdefault(group, {})[leaf] = w
            else:
                out[name] = w
        return out

    return {**tree("embed"), **tree("head"),
            "layers": [tree(i) for i in range(cfg["num_hidden_layers"])]}


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------
# --- reference: begin ---


def layer_plan(cfg: dict) -> List[str]:
    """["gdn" | "gqa"] of the layers here: ``layer_types`` at the published
    numbers ``first_layer`` .. ``first_layer + num_hidden_layers``."""
    first = cfg.get("first_layer", 0)
    return [KINDS[kind] for kind in cfg["layer_types"][first: first + cfg["num_hidden_layers"]]]


def ref_delta_rule_wide(q, k, v, log_decay, beta, scale, state0=None, state_dtype=None):
    """The gated delta rule token by token over ONE document, keys and values
    of their own widths: q, k [n, h, dk], v [n, h, dv], log_decay, beta [n, h]
    (one decay a head and token) -> (o [n, h, dv], the last state [h, dk, dv]).
    ``state_dtype`` keeps the state in a lower precision (a control)."""
    import jax
    import jax.numpy as jnp

    keep = (lambda s: ref_round(s, state_dtype)) if state_dtype else (lambda s: s)

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[:, None, None]                         # a_t S
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        state = keep(state + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - seen)))
        return state, jnp.einsum("hkv,hk->hv", state, q_t) * scale

    first = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32) if state0 is None else state0
    last, o = jax.lax.scan(token, first, (q, k, v, log_decay, beta))
    return o, last


def ref_gdn(p, u, cfg, lower=None, carried=None, probe_head=None):
    """The gated delta-net layer on one document u [n, D] (the stream as it
    is: the layer has no norm on its way in): (y before the branch's norm,
    what a document that followed would be handed if state and taps outlived
    this one: (the last state [H, Dk, Dv], the last three rows of the three
    convolutions' inputs), probe). With ``probe_head`` what the recurrence was
    given and gave for that head: ``q``, ``k`` [n, dk], ``v``, ``o`` [n, dv],
    ``log_decay``, ``beta`` [n]. ``carried``: such a pair from the document
    before (the controls ``carried_state`` and ``carried_taps`` hand over one
    half each). ``lower`` names a control's departures: ``state_dtype``,
    ``sigmoid_gate`` (2 sigmoid(z) on the head's norm), ``beta_times_1`` (beta
    in (0, 1): no negative eigenvalue), ``scale_by_dv`` (q times dv^-1/2)."""
    import jax
    import jax.numpy as jnp

    lower = lower or {}
    n, eps = u.shape[0], cfg["rms_norm_eps"]
    hk, h, dk, dv = widths(cfg)
    reach = cfg["linear_conv_kernel_dim"] - 1
    state0, tails = carried if carried is not None else (None, None)
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731

    def mixed(name, i):  # the projection under its taps, with what came before the document
        x = u @ p["w" + name]
        before = jnp.zeros((reach, x.shape[1]), x.dtype) if tails is None else tails[i]
        return jax.nn.silu(ref_conv(jnp.concatenate([before, x]), p["conv_" + name])[reach:]), x[n - reach:]

    (q, tail_q), (k, tail_k), (v, tail_v) = (mixed(name, i) for i, name in enumerate("qkv"))
    q, k, v = unit(q.reshape(n, hk, dk)), unit(k.reshape(n, hk, dk)), v.reshape(n, h, dv)
    reads = jnp.arange(h) // (h // hk)
    q, k = q[:, reads], k[:, reads]                                        # a key head once a value head
    rate = jax.nn.softplus(u @ p["w_a"] + p["dt_bias"])
    log_decay = -jnp.exp(p["a_log"]) * rate                               # [n, H]: one a head and token
    top = 2.0 if cfg["linear_allow_neg_eigval"] and not lower.get("beta_times_1") else 1.0
    beta = top * jax.nn.sigmoid(u @ p["w_beta"])
    scale = (dv if lower.get("scale_by_dv") else dk) ** -0.5
    o, last = ref_delta_rule_wide(q, k, v, log_decay, beta, scale, state0, lower.get("state_dtype"))
    probe = None
    if probe_head is not None:
        probe = {"q": q[:, probe_head], "k": k[:, probe_head], "v": v[:, probe_head],
                 "log_decay": log_decay[:, probe_head], "beta": beta[:, probe_head], "o": o[:, probe_head]}
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * p["o_norm"]
    z = u @ p["wz"]
    gate = 2.0 * jax.nn.sigmoid(z) if lower.get("sigmoid_gate") else jax.nn.silu(z)
    return (o.reshape(n, h * dv) * gate) @ p["wo"], (last, (tail_q, tail_k, tail_v)), probe


def ref_full_attention(p, u, cfg, lower=None):
    """The full-attention layer on one document u [n, D]: q and k normed over
    the WHOLE projection before they are cut into heads, no positions, no
    gate. ``lower`` names a control's departures: ``per_head_qk_norm`` (the
    norm over each head's own channels, the same weights), ``no_qk_norm``,
    ``rotary_on_full`` (the heads turned by their positions, theta 500,000)."""
    import jax.numpy as jnp

    lower = lower or {}
    n, h, g, dh = u.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    eps = cfg["rms_norm_eps"]

    def normed(x, weight, heads):
        if lower.get("no_qk_norm"):
            return x.reshape(n, heads, dh)
        if lower.get("per_head_qk_norm"):
            return ref_norm(x.reshape(n, heads, dh), weight.reshape(heads, dh), eps)
        return ref_norm(x, weight, eps).reshape(n, heads, dh)

    q, k = normed(u @ p["wq"], p["q_norm"], h), normed(u @ p["wk"], p["k_norm"], g)
    if lower.get("rotary_on_full"):
        q, k = (ref_rope(a, jnp.arange(n), ROTARY_CONTROL_THETA) for a in (q, k))
    att = ref_window_attention(q.reshape(n, g, h // g, dh), k, (u @ p["wv"]).reshape(n, g, dh))
    return att.reshape(n, h * dh) @ p["wo"]


def ref_layer(kind, p, x, cfg, lower=None, carried=None, probe_head=None):
    """One layer on a document's x [n, D]: each branch reads the stream as it
    is and is normed on its way out -> (x, what a delta-net layer would hand
    on, its probe). ``lower["pre_norm_gdn"]``: a delta-net layer's mixer
    pre-normed instead, x + M(N(x; w_1)) (a control)."""
    lower = lower or {}
    eps, handed, probe = cfg["rms_norm_eps"], None, None
    if kind == "gqa":
        x = x + ref_norm(ref_full_attention(p, x, cfg, lower), p["post_attn_norm"], eps)
    elif lower.get("pre_norm_gdn"):
        y, handed, probe = ref_gdn(p, ref_norm(x, p["post_attn_norm"], eps), cfg, lower, carried, probe_head)
        x = x + y
    else:
        y, handed, probe = ref_gdn(p, x, cfg, lower, carried, probe_head)
        x = x + ref_norm(y, p["post_attn_norm"], eps)
    y = ref_ffn(x, p["dense.w_gate"], p["dense.w_up"], p["dense.w_down"])
    return x + ref_norm(y, p["post_ffn_norm"], eps), handed, probe


def reference_score(cfg: dict, docs: list, weights: Callable, logits_at: Optional[list] = None,
                    lower: Optional[dict] = None, carry: Optional[str] = None,
                    probe_head: Optional[int] = None) -> dict:
    """Each document scored alone, in float32 at the highest matmul precision.

    docs: int arrays ``t_0 .. t_n`` (the end-of-document id included);
    ``weights(part)`` -> that part's float32 tensors (one part is held at a
    time); ``logits_at[i]``: positions of document i whose full logits to keep.
    Returns {"logprob": [log p(t_1..t_n)] a document, "logits": [[len(at), V]]
    a document, "router": {} a document (the model has no router), "scan":
    with ``probe_head`` a document's :func:`ref_gdn` probe of the first
    delta-net layer, else {}}. ``lower`` names a control's departures:
    :func:`ref_gdn`'s, :func:`ref_full_attention`'s, ``pre_norm_gdn``;
    ``carry`` plants the fault of something that outlives its document:
    ``"state"``: each delta-net layer starts a document from the last one's
    final state; ``"taps"``: its convolutions read the last one's last three
    tokens."""
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
        out = {"logprob": [], "logits": [], "scan": [{} for _ in docs], "router": [{} for _ in docs]}
        same = json.dumps(cfg, sort_keys=True, default=repr)  # programs are kept by what cfg says
        said = json.dumps(lower, sort_keys=True, default=repr)
        delta_layers = [i for i, kind in enumerate(plan) if kind == "gdn"]
        probed = delta_layers[0] if probe_head is not None and delta_layers else None
        layer = {(kind, probe): _jitted(
            lambda p, x, c, kind=kind, probe=probe: ref_layer(kind, p, x, cfg, lower, c, probe),
            key=("olmo_hybrid_layer", same, kind, said, probe))
            for kind in set(plan) for probe in (None, probe_head)}
        for i, kind in enumerate(plan):
            p = weights(i)
            handed = None
            for j, x in enumerate(xs):
                n = len(docs[j]) - 1
                carried = None
                if carry and handed is not None:  # what the padded document before left: a fault either way
                    carried = (handed[0], None) if carry == "state" else (None, handed[1])
                xs[j], handed, scan = layer[kind, probe_head if i == probed else None](p, x, carried)
                if scan is not None:
                    out["scan"][j] = {name: np.asarray(a)[:n] for name, a in scan.items()}
            del p
        p = weights("head")
        head = _jitted(lambda p, x, t: ref_head_block(p, x, t, cfg), key=("olmo_hybrid_head", same))
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
    return out


# --- reference: end ---


def reference_weights(seed: int, cfg: dict, through: Optional[Callable] = None) -> Callable:
    """``weights(part)`` for :func:`reference_score` from the seed, a part at a time."""
    return lambda part: part_weights(seed, cfg, part, through)


def walk_head(q, k, v, log_decay, beta, scale) -> np.ndarray:
    """One head's recurrence over one document, token by token from an empty
    [dk x dv] state, in float64 on the host: q, k [n, dk], v [n, dv],
    log_decay and beta [n] (one decay a token) -> o [n, dv]."""
    q, k, v, log_decay, beta = (np.asarray(a, np.float64) for a in (q, k, v, log_decay, beta))
    state, out = np.zeros((q.shape[1], v.shape[1])), np.empty_like(v)
    for t in range(len(q)):
        state *= np.exp(log_decay[t])
        state += np.outer(k[t], beta[t] * (v[t] - k[t] @ state))
        out[t] = q[t] @ state
    return out * scale


def probe_numbers(cfg: dict, seed: int, scans: list, routed: list) -> dict:
    """One layer held to float64 ON ITS OWN INPUTS, where the end-to-end
    numbers cannot tell a layer's precision from the bfloat16 activations
    around it; plain numpy on the host.

    ``scan_state_gap``   a document's probe of the recurrence (``q``, ``k``
        [n, dk], ``v``, ``o`` [n, dv], ``log_decay``, ``beta`` [n]: one seeded
        head of the first delta-net layer, as the program or a control
        computed it): ``o`` against :func:`walk_head` over the same inputs at
        the published scale ``dk ** -0.5``; root mean square over all
        documents, relative. (The model has no router: ``routed`` is not read.)"""
    scale = cfg["linear_key_head_dim"] ** -0.5
    err = norm = 0.0
    for scan in scans:
        if "o" not in scan:
            continue
        want = walk_head(*(scan[name] for name in ("q", "k", "v", "log_decay", "beta")), scale)
        err += float(((np.asarray(scan["o"], np.float64) - want) ** 2).sum())
        norm += float((want ** 2).sum())
    return {"scan_state_gap": float(np.sqrt(err / norm)) if norm else 0.0}


# ---------------------------------------------------------------------------
# What a step needs
# ---------------------------------------------------------------------------


def scan_needs(cfg: dict, tokens: float) -> dict:
    """What the delta-net recurrence of ONE layer asks for ``tokens`` tokens,
    whatever form a kernel takes and whatever it pads: a token and head decays
    a 96 x 192 state, reads it against k, adds a rank-one update and reads it
    against q (1 + 2 + 2 + 2 operations an element of the state); the operands
    as the mechanism has them: q and k 96 wide and v 192 wide in bfloat16, one
    float32 decay and one beta a head and token, the float32 output."""
    hk, h, dk, dv = widths(cfg)
    return {"flops": tokens * h * 7.0 * dk * dv,
            "bytes": tokens * (2 * hk * dk * 2.0 + h * dv * 2.0 + 2 * h * 4.0 + h * dv * 4.0)}


def needs(cfg: dict, batch: int, loop: str) -> dict:
    """The least a scoring step asks of the chip, for the roofline shares:
    {"flops", "bytes", "scopes": {scope: {"flops", "bytes"}}}.

    What the mathematics asks, not what the program does: the recurrence at
    its token-by-token count and published widths (:func:`scan_needs`),
    attention over each document's own triangle at 128 + 128 products a pair
    and head, no work for pads, every weight read once a step, activations in
    bfloat16 once in and once out of a layer, the head's logits never stored.
    What the seed's rows held is the loop's to say: ``cfg["observed"]`` =
    {"tokens": scored positions a step, "triangle": sum over a step's
    documents of n (n + 1) / 2}."""
    seen = cfg["observed"]
    t, tri = float(seen["tokens"]), float(seen["triangle"])
    d, v, wide = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    hk, h, dk, dv = widths(cfg)
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    plan = layer_plan(cfg)
    n_gdn, n_gqa, n_layers = plan.count("gdn"), plan.count("gqa"), len(plan)
    act = 2.0 * t * d * 2.0                                # a layer's rows in and out, bf16
    gdn_w = d * (2 * hk * dk + 2 * h * dv) + 2 * d * h + h * dv * d
    gqa_w = d * (hq * dh + 2 * hkv * dh) + hq * dh * d
    conv_cols = 2 * hk * dk + h * dv
    scan = scan_needs(cfg, t)
    scopes = {
        "tfr.embed": {"flops": 0.0, "bytes": act + 4.0 * t},
        "tfr.gdn_proj": {"flops": n_gdn * 2.0 * t * gdn_w, "bytes": n_gdn * (2.0 * gdn_w + act)},
        "tfr.gdn_conv": {"flops": n_gdn * 2.0 * t * conv_cols * cfg["linear_conv_kernel_dim"],
                         "bytes": n_gdn * 2.0 * t * conv_cols * 2.0},
        "tfr.gdn_scan": {"flops": n_gdn * scan["flops"], "bytes": n_gdn * scan["bytes"]},
        "tfr.gqa": {"flops": n_gqa * (2.0 * t * gqa_w + 4.0 * tri * hq * dh),
                    "bytes": n_gqa * (2.0 * gqa_w + act)},
        "tfr.dense_ffn": {"flops": n_layers * t * 6.0 * d * wide,
                          "bytes": n_layers * (3 * d * wide * 2.0 + act)},
        "tfr.lm_head": {"flops": 2.0 * t * d * v, "bytes": 2.0 * d * v + t * d * 2.0 + 4.0 * t},
    }
    return {"flops": sum(scope["flops"] for scope in scopes.values()),
            "bytes": sum(scope["bytes"] for scope in scopes.values()), "scopes": scopes}
