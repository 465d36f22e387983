"""Solar-Open2 for the benchmark: the weights from ``--seed``, the program
built for a configuration file, the plain reference, and what a step needs.

Nothing here except :func:`program` imports ``tpu_tfrecord.models``. The
reference takes the seed and the generator's documents, never anything the
program has made.

The model (``configs/solar_open2_ep8.json`` has the source and what is
``assumed``), for ONE document of tokens ``t_0 .. t_n``, pre-norm residual:

    x      = embed[t_0 .. t_{n-1}]                                  no positions
    layer  : x += Mixer(RMSNorm(x));  x += MoE(RMSNorm(x))          weighted RMSNorm
    gqa    : softmax(q k^T / sqrt(128), causal) v, 64 query heads on 8 key-value
             heads of 128; y = W_o(att * sigmoid(W_g u))
    kda    : q, k = l2norm(silu(conv4(W u))), v = silu(conv4(W_v u));
             a_t = exp(-exp(A_h) softplus(W_f^up W_f^down u_t + b)) per channel,
             b_t = 2 sigmoid(w_b u_t);
             S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T, S = 0 first;
             o_t = S_t^T q_t / sqrt(128); y = W_o(rmsnorm_head(o) * sigmoid(W_g^up W_g^down u))
    moe    : sigmoid scores over all 320 experts, the 8 largest, gates renormalised
             to sum 1; shared(u) + sum of gate_e * expert_e(u) over the chosen experts
             HELD HERE (n_routed_experts_held from held_offset; what the absent
             ones would add is left out); expert(u) = W_down(silu(W_gate u) * W_up u)
    score  : log_softmax(head(RMSNorm(x)))[t_1 .. t_n] over the vocabulary slice

The program computes this in bfloat16 with float32 norms, router, softmax,
state and logits, over packed rows; the reference in float32 throughout
(``jax.default_matmul_precision("highest")``), each document alone, the
recurrence token by token, every expert by a loop, one layer's weights on
the device at a time. Both hold the same weights: pointwise functions of
the seed, rounded to bfloat16.
"""

from __future__ import annotations

import zlib
from typing import Callable, Dict, List, Optional

import numpy as np

GATE_RANK = 128  # assumed: the delta-rule layer's decay and output gates are rank 128


def layer_kinds(cfg: dict) -> List[str]:
    softmax_layers = set(cfg["gqa_layers"])
    return ["gqa" if i in softmax_layers else "kda" for i in range(cfg["num_hidden_layers"])]


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------


def weight_specs(cfg: dict, part) -> Dict[str, tuple]:
    """{name: (shape held here, uncut leading size, first row held, law)} of one
    part: ``"embed"``, ``"head"`` or a layer's number. A law is how the
    tensor's draws become weights (``assumed.init``)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    if part == "embed":
        return {"embed": ((v, d), v, 0, ("normal", 1.0))}
    if part == "head":
        return {"head": ((d, v), d, 0, ("normal", d ** -0.5)),
                "final_norm": ((d,), d, 0, ("about_one", 0.1))}
    lin = cfg["linear_attn_config"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    kd, taps, r = lin["num_heads"] * lin["head_dim"], lin["short_conv_kernel_size"], GATE_RANK
    f, fs = cfg["moe_intermediate_size"], cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    e_all, e_held, e0 = cfg["n_routed_experts"], cfg["n_routed_experts_held"], cfg.get("held_offset", 0)

    def dense(m, n, gain=1.0):
        return ((m, n), m, 0, ("normal", (gain / m) ** 0.5))

    specs = {
        "attn_norm": ((d,), d, 0, ("about_one", 0.1)),
        "moe_norm": ((d,), d, 0, ("about_one", 0.1)),
        "router": dense(d, e_all),
        "w_gate": ((e_held, d, f), e_all, e0, ("normal", d ** -0.5)),
        "w_up": ((e_held, d, f), e_all, e0, ("normal", d ** -0.5)),
        # each routed expert's output is weighed by a gate of about 1/8
        "w_down": ((e_held, f, d), e_all, e0, ("normal", (8.0 / f) ** 0.5)),
        "shared.w_gate": dense(d, fs), "shared.w_up": dense(d, fs), "shared.w_down": dense(fs, d),
    }
    if layer_kinds(cfg)[part] == "gqa":
        specs.update(wq=dense(d, hq), wk=dense(d, hkv), wv=dense(d, hkv), wg=dense(d, hq),
                     wo=dense(hq, d))
    else:
        specs.update(
            wq=dense(d, kd), wk=dense(d, kd), wv=dense(d, kd), wo=dense(kd, d),
            conv_q=((taps, kd), taps, 0, ("taps", 0.5)), conv_k=((taps, kd), taps, 0, ("taps", 0.5)),
            conv_v=((taps, kd), taps, 0, ("taps", 0.5)),
            f_down=dense(d, r), f_up=dense(r, kd), f_bias=((kd,), kd, 0, ("rate_bias", 1e-3, 1e-1)),
            a_log=((lin["num_heads"],), lin["num_heads"], 0, ("log_between", 0.5, 2.0)),
            w_beta=dense(d, lin["num_heads"]), g_down=dense(d, r), g_up=dense(r, kd),
            o_norm=((lin["head_dim"],), lin["head_dim"], 0, ("about_one", 0.1)),
        )
    return specs


def _mix(x):
    """murmur3's 32-bit finalizer on a uint32 array (wraps by construction)."""
    import jax.numpy as jnp

    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> jnp.uint32(16))


def seed32(seed: int) -> np.uint32:
    seed = int(seed)
    return np.uint32((seed ^ (seed >> 32)) & 0xFFFFFFFF)


_MAKERS: Dict[tuple, Callable] = {}


def make_tensor(seed: int, key: str, shape: tuple, first_row: int, law: tuple):
    """One tensor on the device, float32 holding bfloat16 values: each element
    a pure function of (seed, key, its index in the UNCUT tensor), so an
    expert's weights are the same on whichever chip holds it. The seed and
    the key are arguments of the jitted maker, never constants of it."""
    import jax
    import jax.numpy as jnp

    if (shape, law) not in _MAKERS:
        inner = int(np.prod(shape[1:]))

        def build(seed_word, key_word, first):
            at = jax.lax.iota(jnp.uint32, int(np.prod(shape))) + first * jnp.uint32(inner)
            h = _mix(seed_word ^ _mix(key_word))
            h = _mix(h + at * jnp.uint32(0x9E3779B1))
            u = ((h >> jnp.uint32(8)).astype(jnp.int32) - jnp.int32(1 << 23)).astype(
                jnp.float32) * np.float32(1.0 / (1 << 23))              # uniform(-1, 1)
            kind = law[0]
            if kind == "normal":  # Box-Muller on this draw and a second one of the same element
                h2 = _mix(h ^ jnp.uint32(0x68E31DA4))
                u2 = ((h2 >> jnp.uint32(8)).astype(jnp.float32) + 0.5) * np.float32(1.0 / (1 << 24))
                radius = jnp.sqrt(-2.0 * jnp.log((u + 1.0) * 0.5 + np.float32(2.0 ** -25)))
                u = radius * jnp.cos(np.float32(2.0 * np.pi) * u2)
            u = u.reshape(shape)
            if kind == "normal":
                w = u * np.float32(law[1])
            elif kind == "about_one":
                w = 1.0 + u * np.float32(law[1])
            elif kind == "taps":  # the current token near 1, the three before it near 0
                w = u * np.float32(law[1]) + (jnp.arange(shape[0]) == 0)[:, None]
            elif kind == "rate_bias":  # softplus(bias) log-uniform between the two rates
                lo, hi = np.log(law[1]), np.log(law[2])
                w = jnp.log(jnp.expm1(jnp.exp(lo + (u + 1.0) * 0.5 * (hi - lo))))
            elif kind == "log_between":
                w = jnp.log(law[1] + (u + 1.0) * 0.5 * (law[2] - law[1]))
            else:
                raise ValueError(f"unknown law {law!r}")
            return w.astype(jnp.bfloat16).astype(jnp.float32)

        _MAKERS[(shape, law)] = jax.jit(build)
    return _MAKERS[(shape, law)](seed32(seed), np.uint32(zlib.crc32(key.encode())),
                                 np.uint32(first_row))


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

    lin = cfg["linear_attn_config"]
    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_pattern=tuple(layer_kinds(cfg)), n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_taps=lin["short_conv_kernel_size"], gate_rank=GATE_RANK,
        n_experts=cfg["n_routed_experts"], experts_held=cfg["n_routed_experts_held"],
        held_offset=cfg.get("held_offset", 0), top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], n_shared=cfg["n_shared_experts"],
        routed_scale=float(cfg["routed_scaling_factor"]), norm_eps=cfg["rms_norm_eps"],
        max_len=mix["row_tokens"], dtype=jnp.bfloat16, **cfg.get("program", {}),
    )


def program_params(seed: int, cfg: dict) -> dict:
    """The program's parameter tree on the device: matrices bfloat16 (the
    values the reference holds in float32), vectors, taps and router float32."""
    import jax.numpy as jnp

    def tree(part):
        out = {}
        for name, w in part_weights(seed, cfg, part).items():
            small = w.ndim < 2 or name == "router" or name.startswith("conv_")
            w = w if small else w.astype(jnp.bfloat16)
            if "." in name:
                group, leaf = name.split(".")
                out.setdefault(group, {})[leaf] = w
            else:
                out[name] = w
        return out

    return {**tree("embed"), **tree("head"),
            "layers": [tree(i) for i in range(cfg["num_hidden_layers"])]}


# ---------------------------------------------------------------------------
# The plain reference (a copy of tpu_tfrecord/models/pattern_reference.py;
# tests/test_pattern_lm.py holds the two to each other line for line)
# ---------------------------------------------------------------------------
# --- reference: begin ---


def ref_norm(x, weight, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def ref_gqa(p, u, cfg):
    """Dense causal softmax, one key-value head (its 8 query heads) at a
    time: 64 heads of an 8,192-token document are 17 GB of scores at once."""
    import jax
    import jax.numpy as jnp

    n = u.shape[0]
    h, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (u @ p["wq"]).reshape(n, hkv, h // hkv, dh)
    k = (u @ p["wk"]).reshape(n, hkv, dh)
    v = (u @ p["wv"]).reshape(n, hkv, dh)
    causal = jnp.tril(jnp.ones((n, n), bool))
    out = []
    for g in range(hkv):
        scores = jnp.einsum("ihd,jd->hij", q[:, g], k[:, g]) * dh ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hij,jd->ihd", probs, v[:, g]))
    att = jnp.concatenate(out, axis=1).reshape(n, h * dh)
    return (att * jax.nn.sigmoid(u @ p["wg"])) @ p["wo"]


def ref_conv(x, taps):
    """y_t = sum_j taps[j] x_{t-j}: causal, nothing before the document."""
    import jax.numpy as jnp

    n = x.shape[0]
    return sum(jnp.pad(x, ((j, 0), (0, 0)))[:n] * taps[j] for j in range(taps.shape[0]))


def ref_delta_rule(q, k, v, log_decay, beta, scale, state0=None, state_dtype=None):
    """The gated delta rule token by token over ONE document. q, k, v,
    log_decay [n, h, d], beta [n, h] -> (o [n, h, d], the last state
    [h, d, d]). ``state_dtype`` keeps the state in a lower precision (a control)."""
    import jax
    import jax.numpy as jnp

    h, d = q.shape[1:]
    keep = (lambda s: ref_round(s, state_dtype)) if state_dtype else (lambda s: s)

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = state * jnp.exp(g_t)[:, :, None]                            # diag(a) S
        seen = jnp.einsum("hkv,hk->hv", state, k_t)
        state = keep(state + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - seen)))
        return state, jnp.einsum("hkv,hk->hv", state, q_t) * scale

    first = jnp.zeros((h, d, d), jnp.float32) if state0 is None else state0
    last, o = jax.lax.scan(token, first, (q, k, v, log_decay, beta))
    return o, last


def ref_kda(p, u, cfg, state0=None, state_dtype=None, probe_head=None):
    """The gated delta-rule layer on one document. Returns (y, the last
    state, probe): with ``probe_head`` what the recurrence was given and gave
    for that head (``q``, ``k``, ``v``, ``log_decay``, ``o`` [n, d],
    ``beta`` [n]), else None."""
    import jax
    import jax.numpy as jnp

    lin = cfg["linear_attn_config"]
    n, h, dh = u.shape[0], lin["num_heads"], lin["head_dim"]
    heads = lambda a: a.reshape(n, h, dh)  # noqa: E731
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(heads(jax.nn.silu(ref_conv(u @ p["wq"], p["conv_q"]))))
    k = unit(heads(jax.nn.silu(ref_conv(u @ p["wk"], p["conv_k"]))))
    v = heads(jax.nn.silu(ref_conv(u @ p["wv"], p["conv_v"])))
    rate = jax.nn.softplus((u @ p["f_down"]) @ p["f_up"] + p["f_bias"])
    log_decay = -jnp.exp(p["a_log"])[:, None] * heads(rate)                 # log a_t, per channel
    beta = 2.0 * jax.nn.sigmoid(u @ p["w_beta"])                           # [n, h]
    o, last = ref_delta_rule(q, k, v, log_decay, beta, dh ** -0.5, state0, state_dtype)
    probe = None
    if probe_head is not None:
        probe = {name: a[:, probe_head] for name, a in dict(
            q=q, k=k, v=v, log_decay=log_decay, beta=beta, o=o).items()}
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    o = (o * p["o_norm"]).reshape(n, h * dh)
    return (o * jax.nn.sigmoid((u @ p["g_down"]) @ p["g_up"])) @ p["wo"], last, probe


def ref_round(x, dtype):
    """float32 x rounded to ``dtype``'s values and kept in float32. bfloat16
    by arithmetic on the bits (round to nearest even): XLA drops a pair of
    conversions that only loses precision."""
    import jax
    import jax.numpy as jnp

    if jnp.dtype(dtype) != jnp.bfloat16:
        return x.astype(dtype).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> jnp.uint32(16)) & jnp.uint32(1))) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def ref_ffn(u, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def ref_route(u, router, cfg, router_dtype=None):
    """Sigmoid scores over ALL experts, the top-k, their gates renormalised:
    (chosen [n, k], gates [n, k]). ``router_dtype`` computes the whole router
    in a lower precision (a control): scores, their order, the gates."""
    import jax
    import jax.numpy as jnp

    if router_dtype:
        r = lambda a: ref_round(a, router_dtype)  # noqa: E731
        logits = r(jnp.dot(r(u), r(router), precision="default"))
        top, chosen = jax.lax.top_k(r(jax.nn.sigmoid(logits)), cfg["num_experts_per_tok"])
        return chosen, r(r(top / r(top.sum(axis=-1, keepdims=True))) * cfg["routed_scaling_factor"])
    top, chosen = jax.lax.top_k(jax.nn.sigmoid(u @ router), cfg["num_experts_per_tok"])
    return chosen, top / top.sum(axis=-1, keepdims=True) * cfg["routed_scaling_factor"]


_PROGRAMS: dict = {}


def _jitted(fn: Callable, key=None, **static) -> Callable:
    """One ``jax.jit`` for the process of a module-level function, or under
    ``key`` of a closure built the same way each time, so that a call's
    shapes find the program an earlier call built."""
    import jax

    key = fn if key is None else key
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(fn, **static)
    return _PROGRAMS[key]


def _moe_front(router, w_gate, w_up, w_down, u, cfg_items, router_dtype):
    cfg = dict(cfg_items)
    chosen, gates = ref_route(u, router, cfg, router_dtype)
    return chosen, gates, ref_ffn(u, w_gate, w_up, w_down)


def _expert_part(y, u, at, gate, w_gate, w_up, w_down, e):
    """y + gate * expert_e(u[at]) laid down at ``at`` (an index past the end
    reads zeros and writes nothing)."""
    import jax.numpy as jnp

    part = ref_ffn(jnp.take(u, at, axis=0, mode="fill", fill_value=0.0),
                   w_gate[e], w_up[e], w_down[e])
    return y.at[at].add(gate[:, None] * part, mode="drop")


def _room(count: int, n: int) -> int:
    """An expert's token list is padded to 64, 512, 4,096 or the document's
    own (padded) length: a dozen programs serve every count."""
    return min(n, next(r for r in (64, 512, 4096, 1 << 62) if r >= count))


def ref_moe(p, u, cfg, router_dtype=None, capacity=None):
    """Scores over all experts, the top-k, gates renormalised; the shared
    expert plus every HELD expert's part, expert by expert, each over the
    tokens that chose it (picked on the host). ``router_dtype`` computes the
    scores in a lower precision and ``capacity`` drops an expert's visits
    beyond that many (controls). Returns (y, visits dropped, (chosen, gates))."""
    import jax
    import jax.numpy as jnp

    n, e0, held = u.shape[0], cfg.get("held_offset", 0), cfg["n_routed_experts_held"]
    static = tuple((k, cfg[k]) for k in ("num_experts_per_tok", "routed_scaling_factor"))
    front = _jitted(_moe_front, static_argnums=(5, 6))
    routing = front(p["router"], p["shared.w_gate"], p["shared.w_up"], p["shared.w_down"], u,
                    static, jnp.dtype(router_dtype).name if router_dtype else None)
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


def ref_mixer(kind, p, x, cfg, state0=None, state_dtype=None, probe_head=None):
    """x + Mixer(RMSNorm(x)) on one document x [n, D] -> (x, kda's last
    state or None, kda's probe or None), and RMSNorm(x) before the experts."""
    u = ref_norm(x, p["attn_norm"], cfg["rms_norm_eps"])
    if kind == "gqa":
        x, state, probe = x + ref_gqa(p, u, cfg), None, None
    else:
        y, state, probe = ref_kda(p, u, cfg, state0, state_dtype, probe_head)
        x = x + y
    return x, state, probe, ref_norm(x, p["moe_norm"], cfg["rms_norm_eps"])


def ref_head(p, x, targets, cfg):
    """(log p(targets) [n], logits [n, V]) from the hidden states."""
    import jax
    import jax.numpy as jnp

    logits = ref_norm(x, p["final_norm"], cfg["rms_norm_eps"]) @ p["head"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0], logits


def _bucket(n: int) -> int:
    """Documents are padded at the END to 128, 512, 2,048 or 8,192 tokens
    (whole 8,192s beyond), so that four programs of each kind serve every
    length: nothing here looks ahead, so what follows a document's last
    token changes nothing before it."""
    n = int(n)
    return next((b for b in (128, 512, 2048) if n <= b), -(-n // 8192) * 8192)


def reference_score(cfg: dict, docs: list, weights: Callable, logits_at: Optional[list] = None,
                    lower: Optional[dict] = None, carry_state: bool = False,
                    probe_head: Optional[int] = None) -> dict:
    """Each document scored alone, in float32 at the highest matmul precision.

    docs: int arrays ``t_0 .. t_n`` (the end-of-document id included);
    ``weights(part)`` -> that part's float32 tensors (one part is held at a
    time); ``logits_at[i]``: positions of document i whose full logits to keep.
    Returns {"logprob": [log p(t_1..t_n)] a document, "logits": [[len(at), V]]
    a document, "dropped": visits a ``capacity`` control dropped, "router":
    a document's {"u", "experts", "gates"} [n_layers, len(at), ..] at
    ``logits_at``: each layer's router input and what it chose, "scan": with
    ``probe_head`` a document's :func:`ref_kda` probe of the first delta-rule
    layer}. ``lower`` names a control's departures (``state_dtype``,
    ``router_dtype``, ``capacity``); ``carry_state`` plants the fault of a
    state that outlives its document: each delta-rule layer starts a document
    from the last one's final state."""
    import json

    import jax
    import jax.numpy as jnp

    lower = lower or {}
    kinds = layer_kinds(cfg)
    where = [np.asarray(a, np.int64) for a in (logits_at or [[]] * len(docs))]
    with jax.default_matmul_precision("highest"):
        embed = weights("embed")["embed"]
        xs = []
        for doc in docs:
            ids = np.zeros(_bucket(len(doc) - 1), np.int32)
            ids[: len(doc) - 1] = doc[:-1]
            xs.append(embed[ids])
        del embed
        out = {"logprob": [], "logits": [], "dropped": 0, "scan": [None] * len(docs),
               "router": [{"u": [], "experts": [], "gates": []} for _ in docs]}
        probed = kinds.index("kda") if probe_head is not None and "kda" in kinds else None
        state_dtype = lower.get("state_dtype")
        same = json.dumps(cfg, sort_keys=True, default=repr)  # programs are kept by what cfg says
        mixer = {(kind, probe): _jitted(
            lambda p, x, s, kind=kind, probe=probe: ref_mixer(kind, p, x, cfg, s, state_dtype, probe),
            key=("mixer", same, kind, probe, state_dtype and jnp.dtype(state_dtype).name))
            for kind in set(kinds) for probe in (None, probe_head)}
        for i, kind in enumerate(kinds):
            p = weights(i)
            state = None
            for j, x in enumerate(xs):
                x, state, scan, u = mixer[kind, probe_head if i == probed else None](
                    p, x, state if carry_state else None)
                if scan is not None:
                    n = len(docs[j]) - 1
                    out["scan"][j] = {name: np.asarray(a)[:n] for name, a in scan.items()}
                y, lost, (chosen, gates) = ref_moe(p, u, cfg, lower.get("router_dtype"),
                                                   lower.get("capacity"))
                xs[j], out["dropped"] = x + y, out["dropped"] + lost
                for name, a in (("u", u), ("experts", chosen), ("gates", gates)):
                    out["router"][j][name].append(np.asarray(a)[where[j]])
            del p
        p = weights("head")
        head = _jitted(lambda p, x, t: ref_head(p, x, t, cfg), key=("head", same))
        for j, (doc, x) in enumerate(zip(docs, xs)):
            n = len(doc) - 1
            targets = np.zeros(x.shape[0], np.int32)
            targets[:n] = doc[1:]
            logp, logits = head(p, x, jnp.asarray(targets))
            out["logprob"].append(np.asarray(logp)[:n])
            out["logits"].append(np.asarray(logits[where[j]]) if len(where[j])
                                 else np.zeros((0, logits.shape[1]), np.float32))
            out["router"][j] = {k: np.stack(v) for k, v in out["router"][j].items()}
    return out


# --- reference: end ---


def reference_weights(seed: int, cfg: dict, through: Optional[Callable] = None) -> Callable:
    """``weights(part)`` for :func:`reference_score` from the seed."""
    return lambda part: part_weights(seed, cfg, part, through)


def walk_head(q, k, v, log_decay, beta, scale) -> np.ndarray:
    """One head's recurrence over one document, token by token from an empty
    state, in float64 on the host: [n, d] and beta [n] -> o [n, d]."""
    q, k, v, log_decay, beta = (np.asarray(a, np.float64) for a in (q, k, v, log_decay, beta))
    state, out = np.zeros((q.shape[1], v.shape[1])), np.empty_like(v)
    for t in range(len(q)):
        state *= np.exp(log_decay[t])[:, None]
        state += np.outer(k[t], beta[t] * (v[t] - k[t] @ state))
        out[t] = q[t] @ state
    return out * scale


def probe_numbers(cfg: dict, seed: int, scans: list, routed: list) -> dict:
    """Two layers held to float64 ON THEIR OWN INPUTS, where the end-to-end
    numbers cannot tell a layer's precision from the bfloat16 activations
    around it (a bfloat16 state costs a third of what they cost). Both are
    plain numpy on the host (the chip's own float32 token-by-token walk is
    6e-5 to 9e-5 from this one): only the router's weights, the seed's, come
    from the device.

    ``scan_state_gap``   a document's probe of the recurrence (``q``, ``k``,
        ``v``, ``log_decay``, ``o`` [n, d], ``beta`` [n]: one head of the first
        delta-rule layer, as the program or a control computed it): ``o``
        against :func:`walk_head` over the same inputs; root mean square over
        all documents, relative.
    ``router_gate_gap``  a document's ``u``, ``experts``, ``gates``
        [n_layers, s, ..] at its sampled positions: the gates against the
        float64 router's (this seed's weights) on the same ``u``, as the
        largest difference over the 320 experts; the 90th percentile over
        positions and layers (a maximum would hang on one near-tie)."""
    scale, top_k = cfg["linear_attn_config"]["head_dim"] ** -0.5, cfg["num_experts_per_tok"]
    err = norm = 0.0
    for scan in scans:
        want = walk_head(*(scan[name] for name in ("q", "k", "v", "log_decay", "beta")), scale)
        err += float(((np.asarray(scan["o"], np.float64) - want) ** 2).sum())
        norm += float((want ** 2).sum())
    gaps = []
    for layer in range(cfg["num_hidden_layers"]):
        u = np.concatenate([np.asarray(r["u"][layer], np.float64) for r in routed])
        if not len(u):
            continue
        experts = np.concatenate([r["experts"][layer] for r in routed])
        got = np.concatenate([np.asarray(r["gates"][layer], np.float64) for r in routed])
        router = np.asarray(part_weights(seed, cfg, layer, names=("router",))["router"], np.float64)
        scores = 1.0 / (1.0 + np.exp(-(u @ router)))
        chosen = np.argsort(-scores, axis=1, kind="stable")[:, :top_k]
        top = np.take_along_axis(scores, chosen, axis=1)
        gates = top / top.sum(axis=1, keepdims=True) * cfg["routed_scaling_factor"]
        dense, at = np.zeros((2,) + scores.shape), np.arange(len(u))[:, None]
        dense[0, at, experts] = got
        dense[1, at, chosen] = gates
        gaps.append(np.abs(dense[0] - dense[1]).max(axis=1))
    return {"scan_state_gap": float(np.sqrt(err / norm)) if norm else 0.0,
            "router_gate_gap": float(np.percentile(np.concatenate(gaps), 90.0)) if gaps else 0.0}


def through_int8(w):
    """A matrix through int8's 255 levels of its own largest magnitude (the
    weights control; by arithmetic: XLA drops a conversion that only loses
    precision)."""
    import jax.numpy as jnp

    step = jnp.abs(w).max() / 127.0
    return jnp.round(w / step) * step


# ---------------------------------------------------------------------------
# What a step needs
# ---------------------------------------------------------------------------


def needs(cfg: dict, batch: int, loop: str) -> dict:
    """The least a scoring step asks of the chip, for the roofline shares:
    {"flops", "bytes", "scopes": {scope: {"flops", "bytes"}}}.

    What the mathematics asks, not what the program does: attention over
    each document's own triangle, the recurrence at its token-by-token
    count, the experts by the visits the batch makes, no work for pads,
    every weight read once a step, activations in bfloat16 once in and once
    out of a layer, the head's logits never stored. What the seed's rows
    held is the loop's to say: ``cfg["observed"]`` = {"tokens": scored
    positions a step, "triangle": sum over a step's documents of
    n (n + 1) / 2, "visits": visits to held experts a step and layer}."""
    seen = cfg["observed"]
    t, tri, visits = float(seen["tokens"]), float(seen["triangle"]), float(seen["visits"])
    d, v, lin = cfg["hidden_size"], cfg["vocab_size"], cfg["linear_attn_config"]
    h, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    kh, kdh, r = lin["num_heads"], lin["head_dim"], GATE_RANK
    kd, f = kh * kdh, cfg["moe_intermediate_size"]
    kinds = layer_kinds(cfg)
    n_gqa, n_kda, n_layers = kinds.count("gqa"), kinds.count("kda"), len(kinds)
    act = 2.0 * t * d * 2.0                                # a layer's rows in and out, bf16
    gqa_w = d * (2 * h * dh + 2 * hkv * dh) + h * dh * d
    kda_w = 4 * d * kd + 2 * (d * r + r * kd) + d * kh
    scopes = {
        "tfr.embed": {"flops": 0.0, "bytes": act + 4.0 * t},
        "tfr.gqa": {"flops": n_gqa * (2.0 * t * gqa_w + 4.0 * tri * h * dh),
                    "bytes": n_gqa * (2.0 * gqa_w + act)},
        "tfr.kda_proj": {"flops": n_kda * 2.0 * t * kda_w, "bytes": n_kda * (2.0 * kda_w + act)},
        "tfr.kda_conv": {"flops": n_kda * 2.0 * t * 3 * kd * lin["short_conv_kernel_size"],
                         "bytes": n_kda * 2.0 * t * 3 * kd * 2.0},
        # a token and head: decay, S^T k, the rank-one update, S^T q on a 128 x 128 state
        "tfr.kda_scan": {"flops": n_kda * t * kh * 7.0 * kdh * kdh,
                         "bytes": n_kda * t * kh * (3 * kdh * 2.0 + kdh * 4.0 + 4.0 + kdh * 4.0)},
        "tfr.moe_route": {"flops": n_layers * 2.0 * t * d * cfg["n_routed_experts"],
                          "bytes": n_layers * (4.0 * d * cfg["n_routed_experts"] + t * d * 2.0)},
        "tfr.moe_experts": {"flops": n_layers * visits * 6.0 * d * f,
                            "bytes": n_layers * (cfg["n_routed_experts_held"] * 3 * d * f * 2.0
                                                 + 2.0 * visits * d * 2.0)},
        "tfr.moe_shared": {"flops": n_layers * t * 6.0 * d * f * cfg["n_shared_experts"],
                           "bytes": n_layers * (3 * d * f * cfg["n_shared_experts"] * 2.0 + act)},
        "tfr.lm_head": {"flops": 2.0 * t * d * v, "bytes": 2.0 * d * v + t * d * 2.0 + 4.0 * t},
    }
    return {"flops": sum(s["flops"] for s in scopes.values()),
            "bytes": sum(s["bytes"] for s in scopes.values()), "scopes": scopes}
