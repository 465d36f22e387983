"""GigaChat3.5-432B-A28B for the benchmark: the weights from ``--seed``, the
program built for a configuration file, the plain reference, the probes of one
head's recurrence and of the router, and what a step needs.

Nothing here except :func:`program` and :func:`_place` (which observes the
program) imports ``tpu_tfrecord.models``; the tensor law, the plain norm, the
convolution, the token-by-token rule and the reference's buckets are
``solar_open2.py``'s, the biased router and the rows of a head's block
``kimi_vl_lm.py``'s, YaRN's blend, the rotary turn, the observed row and the
choice among loads ``deepseek_v32.py``'s, imported. The reference takes the
seed and the generator's documents, never anything the program has made.

The model (``configs/gigachat35_ep16.json`` has the source, the cut, what is
``assumed`` with the reading taken, and what is ``left_out``: the two
multi-token-prediction blocks), for ONE document of tokens ``t_0 .. t_n``,
with ``x`` the residual stream, positions counted inside the document and
``N(x; w) = x / rms(x) * 2 sigmoid(w)`` (eps 1e-6; a per-channel gain in
(0, 2) that is 1 at ``w = 0``):

    x0     = embed[t_0 .. t_{n-1}]
    gdn    : u = N(x; w_in); q = u Wq, k = u Wk -> 32 heads of 128; v = u Wv, z = u Wz -> 64
             heads of 128; q, k, v = silu(conv4(.)), causal, nothing before the document;
             q = q / |q| / sqrt(128), k = k / |k|; value head h reads key head h // 2;
             a_t = exp(-exp(A_h) softplus(u w_a + dt_h)), b_t = sigmoid(u w_b): ONE of each a
             head and token; S_t = a_t S_{t-1} - b_t k_t (k_t^T a_t S_{t-1}) + b_t k_t v_t^T,
             S = 0 before the document; o_t = S_t^T q_t, a [128 x 128] float32 state a value
             head; y = (o / rms(o) * w_o * 2 sigmoid(z)) Wo            (layers 2, 4, 5, 6)
    mla    : u = N(x; w_in); c_q = N(u Wqa; .) [1536]; [q_nope | q_pe] = c_q Wqb -> 64 x
             (128 + 64); [c | k_pe] = u Wkva -> 512 + 64; [k_nope | v] = N(c; .) Wkvb -> 64 x
             (128 + 128); rotary(q_pe), rotary(k_pe) under YaRN (theta 100,000 over 64; factor
             8 over 32,768, beta 32 / 1), k_pe ONE head for all; softmax over s <= t of
             (q_nope . k_nope + q_pe . k_pe) 192^-1/2 (0.1 ln 8 + 1)^2, times v;
             y = (att * sigmoid(u Wg)) Wo, Wg 7168 -> 64 x 128               (layer 3)
    either : x = x + N(y; w_post)
    ffn    : f(u) = (silu(min(u Wg', 10)) * clip(u Wu, -10, 10)) Wd; u2 = N(x; w_pre);
             dense (layer 2): f at width 18,432; moe (layers 3-6): s = sigmoid(u2 Wr) in float32
             over the 256 experts (one group); the 8 largest of s + b; gates s_e / sum of the 8
             chosen s, times 2.5; shared(u2) of width 2,048 (not gated) + sum of gate_e f_e(u2)
             over the chosen experts HELD HERE (16 of 256); x = x + N(m; w_post)
    score  : log_softmax(head(N(x; w_final)))[t_1 .. t_n] over the 16,032 ids held here

The program computes this in bfloat16 with float32 norms, router, rotary
angles, softmax, decay, beta, state and logits, over packed rows whose taps,
state and positions restart at every document; the reference in float32
throughout (``jax.default_matmul_precision("highest")``), each document alone
from position 0 and an empty state, the recurrence token by token with the key
heads copied to their value heads and the decay spread over the channels (what
the program never writes), one head's full scores at a time, every expert by a
loop, the head's logits 1,024 rows at a time, one layer's weights on the
device at a time. Both hold the same weights: pointwise functions of the seed,
rounded to bfloat16, the routers' columns in the order :func:`placement`
observes at set-up (which 16 of the 256 this chip holds: the deployment's
placement by load, so that every seed's step has the same tiles of the expert
loop to compute).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from benchmark.models.deepseek_v32 import pick_experts, ref_rope, ref_yarn
from benchmark.models.kimi_vl_lm import HEAD_ROWS, ref_route_biased
from benchmark.models.solar_open2 import (  # noqa: F401
    _bucket, _jitted, _room, make_tensor, ref_conv, ref_delta_rule, ref_norm, ref_round, through_int8)

# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------


def weight_specs(cfg: dict, part) -> Dict[str, tuple]:
    """{name: (shape held here, uncut leading size, first row held, law)} of one
    part: ``"embed"``, ``"head"`` or a layer's number (``solar_open2.py``'s
    laws; a zero-centred norm's weight normal(0, 0.2): gains of 1 +- 0.1, as a
    plain gain's ``about_one`` is everywhere here)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]

    def centred(n):
        return ((n,), n, 0, ("normal", 0.2))

    if part == "embed":
        return {"embed": ((v, d), v, 0, ("normal", 1.0))}
    if part == "head":
        return {"head": ((d, v), d, 0, ("normal", d ** -0.5)), "final_norm": centred(d)}

    def dense(m, n, gain=1.0):
        return ((m, n), m, 0, ("normal", (gain / m) ** 0.5))

    mixer, ffn = layer_plan(cfg)[part]
    specs = {"attn_norm": centred(d), "post_attn_norm": centred(d), "post_ffn_norm": centred(d)}
    if mixer == "gdn":
        hk, h, dh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
        taps = cfg["linear_conv_kernel_dim"]
        specs.update(
            wq=dense(d, hk * dh), wk=dense(d, hk * dh), wv=dense(d, h * dh), wz=dense(d, h * dh),
            conv_q=((taps, hk * dh), taps, 0, ("taps", 0.5)), conv_k=((taps, hk * dh), taps, 0, ("taps", 0.5)),
            conv_v=((taps, h * dh), taps, 0, ("taps", 0.5)), w_a=dense(d, h),
            dt_bias=((h,), h, 0, ("rate_bias", 1e-3, 1e-1)), a_log=((h,), h, 0, ("log_between", 0.5, 2.0)),
            w_beta=dense(d, h), o_norm=((dh,), dh, 0, ("about_one", 0.1)), wo=dense(h * dh, d))
    else:
        h, rank, q_rank = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
        dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
        specs.update(
            wq_a=dense(d, q_rank), q_norm=centred(q_rank), wq_b=dense(q_rank, h * (dn + dr)),
            wkv_a=dense(d, rank + dr), kv_norm=centred(rank), wkv_b=dense(rank, h * (dn + dv)),
            wg=dense(d, h * dv), wo=dense(h * dv, d))
    if ffn == "dense":
        wide = cfg["intermediate_size"]
        specs.update({"ffn_norm": centred(d), "dense.w_gate": dense(d, wide),
                      "dense.w_up": dense(d, wide), "dense.w_down": dense(wide, d)})
        return specs
    f, fs = cfg["moe_intermediate_size"], cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    e_all, e_held, e0 = cfg["n_routed_experts"], cfg["n_routed_experts_held"], cfg.get("held_offset", 0)
    k, scale = cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]
    specs.update({
        "moe_norm": centred(d),
        "router": dense(d, e_all),
        "router_bias": ((e_all,), e_all, 0, ("normal", 0.05)),
        "w_gate": ((e_held, d, f), e_all, e0, ("normal", d ** -0.5)),
        "w_up": ((e_held, d, f), e_all, e0, ("normal", d ** -0.5)),
        # k gates of about scale / k each: their squares sum to scale^2 / k
        "w_down": ((e_held, f, d), e_all, e0, ("normal", (k / scale ** 2 / f) ** 0.5)),
        "shared.w_gate": dense(d, fs), "shared.w_up": dense(d, fs), "shared.w_down": dense(fs, d),
    })
    return specs


def _raw_weights(seed: int, cfg: dict, part, through: Optional[Callable] = None,
                 names: Optional[tuple] = None) -> dict:
    """{name: float32 array} of one part (:func:`weight_specs`) as the seed's
    law gives it, the router's columns in the law's own order; or of its
    ``names`` only; ``through`` is applied to every matrix (a control's
    lower precision). A matrix is rounded to bfloat16's values here, by
    arithmetic on the bits (``deepseek_v32.py`` has why: on a TPU the compiler
    drops ``make_tensor``'s own pair of conversions)."""
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


def part_weights(seed: int, cfg: dict, part, through: Optional[Callable] = None,
                 names: Optional[tuple] = None) -> dict:
    """:func:`_raw_weights` with an expert layer's router and its bias in the
    order :func:`placement` gives their columns: what the program, the
    reference and the probes all hold."""
    out = _raw_weights(seed, cfg, part, through, names)
    if "router" in out or "router_bias" in out:
        order = placement(seed, cfg)[part]
        for name in ("router", "router_bias"):
            if name in out:
                out[name] = out[name][..., order]
    return out


def program(cfg: dict, mix: dict):
    """The configuration file as the program's own configuration."""
    import jax.numpy as jnp
    from tpu_tfrecord.models import lm

    plan, yarn = layer_plan(cfg), cfg["rope_scaling"]
    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_pattern=tuple(mixer for mixer, _ in plan), ffn_pattern=tuple(ffn for _, ffn in plan),
        kda_heads=cfg["linear_num_value_heads"], gdn_key_heads=cfg["linear_num_key_heads"],
        kda_head_dim=cfg["linear_key_head_dim"], conv_taps=cfg["linear_conv_kernel_dim"],
        n_heads=cfg["num_attention_heads"], qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        kv_rank=cfg["kv_lora_rank"], q_rank=cfg["q_lora_rank"], attn_gate=bool(cfg["gated_attention"]),
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=(float(yarn["factor"]), float(yarn["original_max_position_embeddings"]),
                      float(yarn["beta_fast"]), float(yarn["beta_slow"])),
        d_dense=cfg["intermediate_size"], n_experts=cfg["n_routed_experts"],
        experts_held=cfg["n_routed_experts_held"], held_offset=cfg.get("held_offset", 0),
        top_k=cfg["num_experts_per_tok"], d_expert=cfg["moe_intermediate_size"],
        n_shared=cfg["n_shared_experts"], routed_scale=float(cfg["routed_scaling_factor"]),
        router_bias=True, norm_eps=cfg["rms_norm_eps"], centred_norms=True, branch_norms=True,
        swiglu_limit=float(cfg["swiglu_limit"]), max_len=mix["row_tokens"], dtype=jnp.bfloat16,
        **cfg.get("program", {}),
    )


def program_params(seed: int, cfg: dict) -> dict:
    """The program's parameter tree on the device: matrices bfloat16 (the
    values the reference holds in float32; the router too, which the program
    widens to float32 before it multiplies), vectors and taps float32; the
    routers' columns in :func:`placement`'s order (found here, with this very
    tree, the first time a seed is asked for)."""
    import jax.numpy as jnp

    def tree(part):
        out = {}
        for name in weight_specs(cfg, part):  # one tensor in float32 at a time
            w = _raw_weights(seed, cfg, part, names=(name,))[name]
            w = w if w.ndim < 2 or name.startswith("conv_") else w.astype(jnp.bfloat16)
            if "." in name:
                group, leaf = name.split(".")
                out.setdefault(group, {})[leaf] = w
            else:
                out[name] = w
        return out

    params = {**tree("embed"), **tree("head"),
              "layers": [tree(i) for i in range(cfg["num_hidden_layers"])]}
    key = _placed_key(seed, cfg)
    if key not in _PLACED:
        _PLACED[key] = _place(seed, cfg, params)  # leaves the routers as it places them
        return params
    for i, order in _PLACED[key].items():
        layer = params["layers"][i]
        layer["router"], layer["router_bias"] = layer["router"][:, order], layer["router_bias"][order]
    return params


# ---------------------------------------------------------------------------
# Which experts this chip holds: the deployment's placement by observed load
# ---------------------------------------------------------------------------

_PLACED: Dict[tuple, dict] = {}


def _placed_key(seed: int, cfg: dict) -> tuple:
    import json

    said = {k: v for k, v in cfg.items() if k != "observed"}  # the loop's note of a window
    return int(seed), json.dumps(said, sort_keys=True, default=repr)


def placement(seed: int, cfg: dict) -> dict:
    """{expert layer: order [E]}: column j of the router (and of its bias) that
    is run is column ``order[j]`` of the seed's law.

    ``deepseek_v32.placement``'s reasons hold here (a served model's experts
    lie where the observed loads put them; weights from a seed have no such
    history): under Zipf's hot tokens the first id alone is a tenth of a
    document, every one of its visits to the same 8 experts, and which hot
    experts a seed's first 16 columns held moved that cell's step by 0.9% from
    seed to seed. So one seeded row of the traffic's law, as long as a step
    (``placement.row_tokens``), goes through the program layer by layer, the
    visits to all 256 experts (one group) are counted from the router's own
    choices, and this chip is given the ``n_routed_experts_held`` that
    ``deepseek_v32.pick_experts`` names: each visited 1 to ``placement.cap``
    times (three quarters of the expert loop's tile, times the real share of
    the observed row: a step's own rows may bring a third more before an
    expert needs a second tile), their visits nearest the even share; the others lie on the 15 chips beside it. Only
    names change hands: every token's routing, a function of the scores
    whatever their order, is what it was."""
    import jax

    key = _placed_key(seed, cfg)
    if key not in _PLACED:
        # the program as it is run, whoever asks first: inside the reference's
        # ``default_matmul_precision("highest")`` the kernels' bfloat16 products
        # would be asked for in float32
        with jax.default_matmul_precision(None):
            program_params(seed, cfg)  # builds the tree the placement is observed with, drops it
    return _PLACED[key]


def observed_row(seed: int, cfg: dict, row_tokens: int):
    """(tokens, segment_ids) [1, row_tokens + 1] int32: documents of the
    traffic's own law (``data/token_docs.py``: lengths, Zipf ranks and the
    seed's bijection) from a shard number no data set has, each with its end
    id, every one of the 64 drawn that still fits put behind the ones before
    it (``deepseek_v32.observed_row`` stops at the first that does not: with
    lengths of 16 to 8,192 that left half of some seeds' rows empty)."""
    from benchmark.data import token_docs

    flat, offsets = token_docs.shard_docs(seed, 0x504C41, 64, cfg)
    tokens = np.zeros((1, row_tokens + 1), np.int32)
    segs = np.zeros((1, row_tokens + 1), np.int32)
    at = nth = 0
    for a, b in zip(offsets[:-1], offsets[1:]):
        if at + (b - a) + 1 > row_tokens + 1:
            continue
        nth += 1
        tokens[0, at: at + b - a] = flat[a:b]
        segs[0, at: at + b - a + 1] = nth
        at += b - a + 1
    return tokens, segs


def _place(seed: int, cfg: dict, params: dict) -> dict:
    """:func:`placement`'s orders, observed with ``params`` (the program's own
    tree in the law's order; its routers are left as placed).

    The observed row walks the program's layers one at a time (the program of
    ONE layer, handed the hidden state so far as if it were an embedding and
    the row ``0 1 2 ..`` as its tokens), since a layer's visits depend on what
    the layers before it hold. Every position is a sampled one, so a layer's
    step returns what the router chose for each token among all 256 (two runs
    a layer: one to count, one as placed, whose output goes on to the next:
    what the absent experts would add is left out, so the output is the
    placement's). The row is an argument of those programs, never a constant
    of theirs: every seed finds them in the compile cache."""
    import dataclasses
    import json
    import time

    import jax
    import jax.numpy as jnp
    from tpu_tfrecord.models import lm

    plan = layer_plan(cfg)
    layers = [i for i, (_, ffn) in enumerate(plan) if ffn == "moe"]
    e_all, held, e0 = cfg["n_routed_experts"], cfg["n_routed_experts_held"], cfg.get("held_offset", 0)
    everyone = np.arange(e_all)
    if not layers or held >= e_all:
        return {i: everyone for i in layers}
    t0 = time.perf_counter()
    row_tokens = cfg["placement"]["row_tokens"]
    pcfg = program(cfg, {"row_tokens": row_tokens})
    tokens, segs = observed_row(seed, cfg, row_tokens)
    real = segs[0, :-1] != 0
    cap = max(1, int(cfg["placement"]["cap"] * real.sum() // row_tokens))   # of a row as full as this one
    walk, segs = jnp.arange(row_tokens + 1, dtype=jnp.int32)[None] % row_tokens, jnp.asarray(segs)
    every = jnp.arange(row_tokens, dtype=jnp.int32)[None]

    def run(cut):
        def one(layer, x, walk, segs, every):
            out, _, _, probes = lm.pattern_hidden({"embed": x, "layers": [layer]}, walk, segs, cut, every)
            return out[0], probes["router"]["experts"][0, 0] if "router" in probes else None
        program_of_one = jax.jit(one)
        return lambda layer, x: program_of_one(layer, x, walk, segs, every)

    one_layer = {kind: run(dataclasses.replace(pcfg, layer_pattern=(kind[0],), ffn_pattern=(kind[1],)))
                 for kind in set(plan)}
    raw = {i: _raw_weights(seed, cfg, i, names=("router", "router_bias")) for i in layers}

    def put(i, order):
        params["layers"][i]["router"] = raw[i]["router"][:, order].astype(jnp.bfloat16)
        params["layers"][i]["router_bias"] = raw[i]["router_bias"][order]

    def with_held(names):
        """The law's order with the experts ``names`` in the held columns."""
        rest = np.setdiff1d(everyone, names)
        return np.concatenate([rest[:e0], names, rest[e0:]])

    tile = pcfg.expert_tile
    share = round(int(real.sum()) * cfg["num_experts_per_tok"] * held / e_all)
    x = params["embed"][jnp.asarray(tokens[0, :-1])]
    orders, said = {}, []
    for i, kind in enumerate(plan):
        layer = params["layers"][i]
        if kind[1] == "moe":  # the routers lie in the law's order: a column's number is an expert's name
            chosen = np.asarray(one_layer[kind](layer, x)[1])
            loads = np.bincount(chosen[real].ravel(), minlength=e_all)
            mine = everyone[pick_experts(loads, held, cap, share)]
            orders[i] = with_held(mine)
            put(i, orders[i])
            said.append({"layer": i, "visits": int(loads[mine].sum()), "most": int(loads[mine].max()),
                         "tiles": int((-(-loads[mine] // tile)).sum()),
                         "all_visits": int(loads.sum()), "all_most": int(loads.max()),
                         "all_sorted_every_16th": np.sort(loads)[::16].tolist()})
        if i < layers[-1]:
            x = one_layer[kind](layer, x)[0]
    print("[placement] " + json.dumps({"seconds": time.perf_counter() - t0, "row_tokens": int(real.sum()),
                                       "cap": cap, "layers": said}, sort_keys=True), flush=True)
    return orders


# ---------------------------------------------------------------------------
# The plain reference (a copy of tpu_tfrecord/models/gdn_reference.py;
# tests/test_gdn_lm.py holds the two to each other line for line)
# ---------------------------------------------------------------------------
# --- reference: begin ---


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


# --- reference: end ---


def reference_weights(seed: int, cfg: dict, through: Optional[Callable] = None) -> Callable:
    """``weights(part)`` for :func:`reference_score` from the seed, a part at a
    time (the placement found now, if no one has asked for it yet, not in
    the middle of a document's layers)."""
    placement(seed, cfg)
    return lambda part: part_weights(seed, cfg, part, through)


def walk_head(q, k, v, log_decay, beta, scale) -> np.ndarray:
    """One value head's recurrence over one document, token by token from an
    empty state, in float64 on the host: q, k, v [n, d], log_decay and beta
    [n] (one decay a token) -> o [n, d]."""
    q, k, v, log_decay, beta = (np.asarray(a, np.float64) for a in (q, k, v, log_decay, beta))
    state, out = np.zeros((q.shape[1], v.shape[1])), np.empty_like(v)
    for t in range(len(q)):
        state *= np.exp(log_decay[t])
        state += np.outer(k[t], beta[t] * (v[t] - k[t] @ state))
        out[t] = q[t] @ state
    return out * scale


def probe_numbers(cfg: dict, seed: int, scans: list, routed: list) -> dict:
    """Two layers held to float64 ON THEIR OWN INPUTS, where the end-to-end
    numbers cannot tell a layer's precision from the bfloat16 activations
    around it. Both are plain numpy on the host: only the router's weights and
    bias, the seed's, come from the device.

    ``scan_state_gap``   a document's probe of the recurrence (``q``, ``k``,
        ``v``, ``o`` [n, d], ``log_decay``, ``beta`` [n]: one value head of the
        first delta-net layer and the key head it reads, as the program or a
        control computed it): ``o`` against :func:`walk_head` over the same
        inputs; root mean square over all documents, relative.
    ``router_gate_gap``  a document's ``u``, ``experts``, ``gates``
        [n_expert_layers, s, ..] at its sampled positions: the gates, bias and
        scale and all, against the float64 router's on the same ``u`` (the 8
        largest of ``sigmoid(u W_r) + b``, gates from the scores alone, times
        2.5), as the largest difference over the 256 experts; the 90th
        percentile over positions and layers (a maximum would hang on one
        near-tie)."""
    scale, top_k = cfg["linear_key_head_dim"] ** -0.5, cfg["num_experts_per_tok"]
    err = norm = 0.0
    for scan in scans:
        if "o" not in scan:
            continue
        want = walk_head(*(scan[name] for name in ("q", "k", "v", "log_decay", "beta")), scale)
        err += float(((np.asarray(scan["o"], np.float64) - want) ** 2).sum())
        norm += float((want ** 2).sum())
    gaps = []
    layers = [i for i, (_, ffn) in enumerate(layer_plan(cfg)) if ffn == "moe"]
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
    return {"scan_state_gap": float(np.sqrt(err / norm)) if norm else 0.0,
            "router_gate_gap": float(np.percentile(np.concatenate(gaps), 90.0)) if gaps else 0.0}


# ---------------------------------------------------------------------------
# What a step needs
# ---------------------------------------------------------------------------


def scan_needs(cfg: dict, tokens: float) -> dict:
    """What the delta-net recurrence of ONE layer asks for ``tokens`` tokens,
    whatever form a kernel takes: a token and value head decays a 128 x 128
    state, reads it against k, adds a rank-one update and reads it against q
    (1 + 2 + 2 + 2 operations an element of the state); the operands as the
    mechanism has them: q and k at the KEY heads and v at the value heads in
    bfloat16, one float32 decay and one beta a value head and token, the
    float32 output."""
    hk, h, dh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    return {"flops": tokens * h * 7.0 * dh * dh,
            "bytes": tokens * (2 * hk * dh * 2.0 + h * dh * 2.0 + 2 * h * 4.0 + h * dh * 4.0)}


def needs(cfg: dict, batch: int, loop: str) -> dict:
    """The least a scoring step asks of the chip, for the roofline shares:
    {"flops", "bytes", "scopes": {scope: {"flops", "bytes"}}}.

    What the mathematics asks, not what the program does: the recurrence at
    its token-by-token count (:func:`scan_needs`), latent attention over each
    document's own triangle at (128 + 64) + 128 products a pair and head, the
    rotary key read as the one head it is; the experts by the visits the batch
    makes, no work for pads, every weight read once a step, activations in
    bfloat16 once in and once out of a layer, the head's logits never stored.
    What the seed's rows held is the loop's to say: ``cfg["observed"]`` =
    {"tokens": scored positions a step, "triangle": sum over a step's
    documents of n (n + 1) / 2, "visits": visits to held experts a step and
    expert layer}."""
    seen = cfg["observed"]
    t, tri, visits = float(seen["tokens"]), float(seen["triangle"]), float(seen["visits"])
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    dn, dr, dv, rank, q_rank = (cfg[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                                 "v_head_dim", "kv_lora_rank", "q_lora_rank"))
    hk, hv, dh = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    f, wide, shared = cfg["moe_intermediate_size"], cfg["intermediate_size"], cfg["n_shared_experts"]
    plan = layer_plan(cfg)
    n_gdn, n_mla = sum(m == "gdn" for m, _ in plan), sum(m == "mla" for m, _ in plan)
    n_dense, n_moe = sum(ffn == "dense" for _, ffn in plan), sum(ffn == "moe" for _, ffn in plan)
    act = 2.0 * t * d * 2.0                                # a layer's rows in and out, bf16
    gdn_w = d * (2 * hk * dh + 2 * hv * dh) + 2 * d * hv + hv * dh * d
    mla_w = (d * q_rank + q_rank * h * (dn + dr) + d * (rank + dr) + rank * h * (dn + dv)
             + d * h * dv + h * dv * d)
    conv_cols = 2 * hk * dh + hv * dh
    scan = scan_needs(cfg, t)
    scopes = {
        "tfr.embed": {"flops": 0.0, "bytes": act + 4.0 * t},
        "tfr.gdn_proj": {"flops": n_gdn * 2.0 * t * gdn_w, "bytes": n_gdn * (2.0 * gdn_w + act)},
        "tfr.gdn_conv": {"flops": n_gdn * 2.0 * t * conv_cols * cfg["linear_conv_kernel_dim"],
                         "bytes": n_gdn * 2.0 * t * conv_cols * 2.0},
        "tfr.gdn_scan": {"flops": n_gdn * scan["flops"], "bytes": n_gdn * scan["bytes"]},
        "tfr.mla_proj": {"flops": n_mla * 2.0 * t * mla_w, "bytes": n_mla * (2.0 * mla_w + act)},
        # q, k_nope, one k_pe and v in, the heads' values out, bf16
        "tfr.mla_attn": {"flops": n_mla * 2.0 * tri * h * (dn + dr + dv),
                         "bytes": n_mla * 2.0 * t * (h * (dn + dr) + h * dn + dr + 2 * h * dv)},
        "tfr.dense_ffn": {"flops": n_dense * t * 6.0 * d * wide,
                          "bytes": n_dense * (3 * d * wide * 2.0 + act)},
        "tfr.moe_route": {"flops": n_moe * 2.0 * t * d * cfg["n_routed_experts"],
                          "bytes": n_moe * (2.0 * d * cfg["n_routed_experts"] + t * d * 2.0)},
        "tfr.moe_experts": {"flops": n_moe * visits * 6.0 * d * f,
                            "bytes": n_moe * (cfg["n_routed_experts_held"] * 3 * d * f * 2.0
                                              + 2.0 * visits * d * 2.0)},
        "tfr.moe_shared": {"flops": n_moe * t * 6.0 * d * f * shared,
                           "bytes": n_moe * (3 * d * f * shared * 2.0 + act)},
        "tfr.lm_head": {"flops": 2.0 * t * d * v, "bytes": 2.0 * d * v + t * d * 2.0 + 4.0 * t},
    }
    return {"flops": sum(s["flops"] for s in scopes.values()),
            "bytes": sum(s["bytes"] for s in scopes.values()), "scopes": scopes}
