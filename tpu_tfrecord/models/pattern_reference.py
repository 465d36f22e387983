"""The plain reference of the pattern model (``models.lm.score``): the
architecture's forward pass in straightforward ``jax.numpy`` and float32,
with no kernels, no packing and no ``segment_ids`` — each document scored
alone, the delta-rule recurrence token by token, dense masked softmax,
every held expert by a loop. It calls nothing else in ``tpu_tfrecord.models``
and is what the tests hold the program to; ``benchmark/models/solar_open2.py``
carries a copy (the benchmark's files stand alone), and
``tests/test_pattern_lm.py`` holds the two to each other line for line.

It reads a configuration with the published names (``hidden_size``,
``num_attention_heads``, ``linear_attn_config``, ``n_routed_experts``,
``n_routed_experts_held`` + ``held_offset``: the share of the experts
computed here, ...) and flat weight names (``shared.w_gate``); the layer's
equations are in that benchmark file's docstring and in ``models.linear_attn``.
"""

from __future__ import annotations

from typing import Callable, List, Optional

import numpy as np


def layer_kinds(cfg: dict) -> List[str]:
    softmax_layers = set(cfg["gqa_layers"])
    return ["gqa" if i in softmax_layers else "kda" for i in range(cfg["num_hidden_layers"])]


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
