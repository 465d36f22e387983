"""DeepSeek-V3.2-Exp for the benchmark: the weights from ``--seed``, the
program built for a configuration file, the plain reference, the probes of
the router and of the indexer's selection, and what a step needs.

Nothing here except :func:`program` and :func:`_place` (which observes the
program) imports ``tpu_tfrecord.models``; the
tensor law, the norms, the gated unit, the expert loop's pieces and the
reference's buckets are ``solar_open2.py``'s, the layer order and the head
``kimi_vl_lm.py``'s, imported. The reference takes the seed and the
generator's documents, never anything the program has made.

The model (``configs/deepseek_v32_exp_ep16.json`` has the source, what is
``assumed`` and what is ``left_out``: the multi-token-prediction module),
for ONE document of tokens ``t_0 .. t_n``, pre-norm residual, weighted
RMSNorm eps 1e-6:

    x      = embed[t_0 .. t_{n-1}]
    layer  : x += DSA(RMSNorm(x));  x += Dense(RMSNorm(x)) in layer 0, MoE(RMSNorm(x)) after it
    dsa    : c_q = RMSNorm_q(u W_qa) [1536]; q = c_q W_qb -> 128 heads of [128 | 64];
             [c | k_pe] = u W_kva -> 512 + 64; [k_nope | v] = RMSNorm_kv(c) W_kvb -> 128 x
             (128 + 128); rope(q_pe), rope(k_pe) by the token's index in ITS document under
             YaRN (theta 10,000 over 64; factor 40 over 4,096, beta 32 / 1: the frequencies
             of index 10 to 23 blend towards 1/40 of themselves), k_pe one head shared by all
    indexer: q^I = c_q W^I_q -> 64 heads of 128; k^I = LayerNorm(u W^I_k) [128], ONE a token;
             rope on the first 64 columns of both; w = u W^I_w / sqrt(64) / sqrt(128);
             I(t, s) = sum_j w(t, j) relu(q^I(t, j) . k^I(s)), s <= t;
             S(t) = the keys with I(t, s) >= the 2,048th largest (all where t < 2,048)
    att    : softmax over S(t) of ([q_nope | q_pe] . [k_nope | k_pe]) 192^-1/2
             (0.1 ln 40 + 1)^2, times v; y = att W_o
    dense  : W_down(silu(W_gate u) * W_up u), width 18,432
    moe    : s = sigmoid(u W_r) over the 256 experts; 8 runs of 32, a run's score the sum of
             its 2 largest s + b; the 4 best runs stay; the 8 largest s + b inside them;
             gates s_e / sum of the 8 chosen s, times 2.5; shared(u) of width 2,048 + sum of
             gate_e * expert_e(u) over the chosen experts HELD HERE (16 of 256)
    score  : log_softmax(head(RMSNorm(x)))[t_1 .. t_n] over the 16,160 ids held here

The program computes this in bfloat16 with float32 norms, router, rotary
angles, softmax, logits and index scores, over packed rows with positions
that restart at every document; the reference in float32 throughout
(``jax.default_matmul_precision("highest")``), each document alone from
position 0, one head's full scores at a time, the selection by a sort of
every query's row, every expert by a loop, the head's logits 1,024 rows at a
time, one layer's weights on the device at a time. Both hold the same
weights: pointwise functions of the seed, rounded to bfloat16, the router's
columns of the held experts' group in the order :func:`placement` observes
at set-up (which 16 of the group's 32 this chip holds: the deployment's
placement by load, so that every seed's step has the same tiles of the
expert loop to compute).
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional

import numpy as np

from benchmark.models.kimi_vl_lm import HEAD_ROWS, ffn_kinds, ref_head_block  # noqa: F401
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
    h, rank, q_rank = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["q_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]

    def dense(m, n, gain=1.0):
        return ((m, n), m, 0, ("normal", (gain / m) ** 0.5))

    specs = {
        "attn_norm": ((d,), d, 0, ("about_one", 0.1)),
        "wq_a": dense(d, q_rank), "q_norm": ((q_rank,), q_rank, 0, ("about_one", 0.1)),
        "wq_b": dense(q_rank, h * (dn + dr)), "wkv_a": dense(d, rank + dr),
        "kv_norm": ((rank,), rank, 0, ("about_one", 0.1)),
        "wkv_b": dense(rank, h * (dn + dv)), "wo": dense(h * dv, d),
        "wq_idx": dense(q_rank, hi * di), "wk_idx": dense(d, di),
        "k_idx_norm": ((di,), di, 0, ("about_one", 0.1)),
        "k_idx_bias": ((di,), di, 0, ("normal", 0.1)),
        "w_idx": dense(d, hi),
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


def _raw_weights(seed: int, cfg: dict, part, through: Optional[Callable] = None,
                 names: Optional[tuple] = None) -> dict:
    """{name: float32 array} of one part (:func:`weight_specs`) as the seed's
    law gives it, the router's columns in the law's own order; or of its
    ``names`` only; ``through`` is applied to every matrix (a control's
    lower precision). A matrix is rounded to bfloat16's values here, by
    arithmetic on the bits: on a TPU the compiler drops ``make_tensor``'s own
    pair of conversions (they only lose precision), and the reference and
    the float64 probes would hold float32 values where the program, which
    does round, holds their bfloat16 neighbours (the router's probe read
    1.7e-4 against the program for that, where float32 reads 2e-7)."""
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

    yarn = cfg["rope_scaling"]
    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_pattern=("mla",) * cfg["num_hidden_layers"], ffn_pattern=tuple(ffn_kinds(cfg)),
        n_heads=cfg["num_attention_heads"], qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        kv_rank=cfg["kv_lora_rank"], q_rank=cfg["q_lora_rank"], rope_theta=float(cfg["rope_theta"]),
        rope_scaling=(float(yarn["factor"]), float(yarn["original_max_position_embeddings"]),
                      float(yarn["beta_fast"]), float(yarn["beta_slow"])),
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"], d_dense=cfg["intermediate_size"],
        n_experts=cfg["n_routed_experts"], experts_held=cfg["n_routed_experts_held"],
        held_offset=cfg.get("held_offset", 0), top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], n_shared=cfg["n_shared_experts"],
        routed_scale=float(cfg["routed_scaling_factor"]), router_bias=True,
        n_group=cfg["n_group"], topk_group=cfg["topk_group"], norm_eps=cfg["rms_norm_eps"],
        max_len=mix["row_tokens"], dtype=jnp.bfloat16, **cfg.get("program", {}),
    )


def program_params(seed: int, cfg: dict) -> dict:
    """The program's parameter tree on the device: matrices bfloat16 (the
    values the reference holds in float32; the router too, which the program
    widens to float32 before it multiplies), vectors float32; the routers'
    columns in :func:`placement`'s order (found here, with this very tree,
    the first time a seed is asked for)."""
    import jax.numpy as jnp

    def tree(part):
        out = {}
        for name in weight_specs(cfg, part):  # one tensor in float32 at a time
            w = _raw_weights(seed, cfg, part, names=(name,))[name]
            w = w if w.ndim < 2 else w.astype(jnp.bfloat16)
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

    A served model's experts are placed on its chips by the loads observed
    (the family's own balancer moves and doubles experts until every chip of
    a group sees its share of the visits); weights from a seed have no such
    history, and under Zipf's few hot tokens the 16 columns that happen to lie
    first drew 6,011 to 10,894 visits a layer by seed and 16 to 22 tiles of
    the expert loop, which the step's time followed (PERF.md section 2). So
    the placement is made here, as a deployment makes it: one seeded row of the
    traffic's law goes through the program layer by layer, the visits to every
    expert of the held experts' routing group are counted, and this chip is
    given the ``n_routed_experts_held`` of them that :func:`pick_experts`
    names; the group's other experts lie on the chip beside it. Only the
    names of one group's experts change hands: the routing of every token, a
    function of the group's scores whatever their order, is what it was."""
    import jax

    key = _placed_key(seed, cfg)
    if key not in _PLACED:
        # the program as it is run, whoever asks first: inside the reference's
        # ``default_matmul_precision("highest")`` the selection kernel's bfloat16
        # products would be asked for in float32, which the chip's compiler refuses
        with jax.default_matmul_precision(None):
            program_params(seed, cfg)  # builds the tree the placement is observed with, drops it
    return _PLACED[key]


def pick_experts(loads, held: int, cap: int, target: int) -> list:
    """``held`` of the candidates whose ``loads`` (visits on the observed row)
    are 1 to ``cap`` (no expert of this chip then needs a second tile of the
    expert loop, nor none), their sum the nearest to ``target`` that ``held``
    such candidates reach, the lower of two equally near; of the sets that
    reach it the one made of the latest candidates. Where fewer than ``held``
    are that light: those, then the lightest of the rest."""
    loads = [int(n) for n in loads]
    light = [c for c, n in enumerate(loads) if 1 <= n <= cap]
    if len(light) < held:
        rest = sorted((c for c in range(len(loads)) if c not in light),
                      key=lambda c: (loads[c] == 0, loads[c], c))
        return sorted(light + rest[: held - len(light)])
    most = sum(sorted(loads[c] for c in light)[-held:])
    # reach[i, n, s]: n of the first i light candidates sum to s
    reach = np.zeros((len(light) + 1, held + 1, most + 1), bool)
    reach[0, 0, 0] = True
    for i, c in enumerate(light):
        reach[i + 1] = reach[i]
        reach[i + 1, 1:, loads[c]:] |= reach[i, :-1, : most + 1 - loads[c]]
    sums = np.flatnonzero(reach[-1, held])
    s = int(sums[np.argmin(np.abs(sums - target))])
    chosen, n = [], held
    for i in reversed(range(len(light))):
        c = light[i]
        if n and s >= loads[c] and reach[i, n - 1, s - loads[c]]:
            chosen.append(c)
            n, s = n - 1, s - loads[c]
    return sorted(chosen)


def observed_row(seed: int, cfg: dict, row_tokens: int):
    """(tokens, segment_ids) [1, row_tokens + 1] int32: documents of the
    traffic's own law (``data/token_docs.py``: lengths, Zipf ranks and the
    seed's bijection) from a shard number no data set has, packed in the order
    drawn while they fit, each with its end id."""
    from benchmark.data import token_docs

    flat, offsets = token_docs.shard_docs(seed, 0x504C41, 64, cfg)
    tokens = np.zeros((1, row_tokens + 1), np.int32)
    segs = np.zeros((1, row_tokens + 1), np.int32)
    at = 0
    for nth, (a, b) in enumerate(zip(offsets[:-1], offsets[1:])):
        if at + (b - a) + 1 > row_tokens + 1:
            break
        tokens[0, at: at + b - a] = flat[a:b]
        segs[0, at: at + b - a + 1] = nth + 1
        at += b - a + 1
    return tokens, segs


def _place(seed: int, cfg: dict, params: dict) -> dict:
    """:func:`placement`'s orders, observed with ``params`` (the program's own
    tree in the law's order; its routers are left as placed).

    The observed row walks the program's layers one at a time (the program of
    ONE layer, handed the hidden state so far as if it were an embedding and
    the row ``0 1 2 ..`` as its tokens), since a layer's visits depend on what
    the layers before it hold. A layer's step returns the visits to the
    experts HELD, so the group's experts are put in the held columns
    ``n_routed_experts_held`` at a time (two rounds in the cell), then the
    layer is run as placed and its output goes on to the next."""
    import dataclasses
    import json
    import time

    import jax
    import jax.numpy as jnp
    from tpu_tfrecord.models import lm

    kinds = ffn_kinds(cfg)
    layers = [i for i, ffn in enumerate(kinds) if ffn == "moe"]
    e_all, held, e0 = cfg["n_routed_experts"], cfg["n_routed_experts_held"], cfg.get("held_offset", 0)
    size = e_all // cfg.get("n_group", 1)
    lo = e0 // size * size
    same = np.arange(e_all)
    if not layers or held >= size or e0 + held > lo + size:
        return {i: same for i in layers}
    t0 = time.perf_counter()
    row_tokens = cfg["doc_length"]["max"]
    pcfg = program(cfg, {"row_tokens": row_tokens})
    tokens, segs = observed_row(seed, cfg, row_tokens)
    real = int((segs[0, :-1] != 0).sum())
    walk, segs = jnp.arange(row_tokens + 1, dtype=jnp.int32)[None] % row_tokens, jnp.asarray(segs)
    one_layer = {ffn: jax.jit(lambda layer, x, cut=dataclasses.replace(
        pcfg, layer_pattern=("mla",), ffn_pattern=(ffn,)): lm.pattern_hidden(
            {"embed": x, "layers": [layer]}, walk, segs, cut)[:2]) for ffn in set(kinds)}
    group = np.arange(lo, lo + size)
    raw = {i: _raw_weights(seed, cfg, i, names=("router", "router_bias")) for i in layers}

    def put(i, order):
        params["layers"][i]["router"] = raw[i]["router"][:, order].astype(jnp.bfloat16)
        params["layers"][i]["router_bias"] = raw[i]["router_bias"][order]

    def with_held(names):
        """The law's order with the group's experts ``names`` in the held columns."""
        order, rest, at = same.copy(), np.setdiff1d(group, names), e0 - lo
        order[lo: lo + size] = np.concatenate([rest[:at], names, rest[at:]])
        return order

    tile = pcfg.expert_tile
    share = round(real * cfg["num_experts_per_tok"] * held / e_all)
    x = params["embed"][jnp.asarray(tokens[0, :-1])]
    orders, said = {}, []
    for i, ffn in enumerate(kinds):
        layer = params["layers"][i]
        if ffn == "moe":
            loads = np.zeros(size, np.int64)
            for r0 in range(0, size, held):
                names = group[r0: r0 + held]
                names = np.concatenate([names, group[: held - len(names)]])  # the last round, filled up
                put(i, with_held(names))
                loads[names - lo] = np.asarray(one_layer[ffn](layer, x)[1])[0]
            mine = group[pick_experts(loads, held, tile - tile // 16, share)]
            orders[i] = with_held(mine)
            put(i, orders[i])
            said.append({"layer": i, "visits": int(loads[mine - lo].sum()),
                         "most": int(loads[mine - lo].max()),
                         "tiles": int((-(-loads[mine - lo] // tile)).sum()),
                         "group_visits": int(loads.sum()), "group_most": int(loads.max())})
        if i < layers[-1]:
            x = one_layer[ffn](layer, x)[0][0]
    print("[placement] " + json.dumps({"seconds": time.perf_counter() - t0, "row_tokens": real,
                                       "layers": said}, sort_keys=True), flush=True)
    return orders


# ---------------------------------------------------------------------------
# The plain reference (a copy of tpu_tfrecord/models/dsa_reference.py;
# tests/test_dsa_lm.py holds the two to each other line for line)
# ---------------------------------------------------------------------------
# --- reference: begin ---


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


# --- reference: end ---


def reference_weights(seed: int, cfg: dict, through: Optional[Callable] = None) -> Callable:
    """``weights(part)`` for :func:`reference_score` from the seed, a part at a
    time (the placement found now, if no one has asked for it yet, not in
    the middle of a document's layers)."""
    placement(seed, cfg)
    return lambda part: part_weights(seed, cfg, part, through)


def _float64_router(u, router, bias, cfg):
    """(chosen [n, k], gates [n, k]) of the group-limited router in float64."""
    k, groups, stay = cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"]
    scores = 1.0 / (1.0 + np.exp(-(u @ router)))
    runs = (scores + bias).reshape(len(u), groups, -1)
    run_score = np.sort(runs, axis=-1)[..., -2:].sum(axis=-1)
    stays = np.zeros(run_score.shape, bool)
    np.put_along_axis(stays, np.argsort(-run_score, axis=1, kind="stable")[:, :stay], True, axis=1)
    picking = np.where(stays[:, :, None], runs, -np.inf).reshape(scores.shape)
    chosen = np.argsort(-picking, axis=1, kind="stable")[:, :k]
    top = np.take_along_axis(scores, chosen, axis=1)
    return scores, chosen, top / top.sum(axis=1, keepdims=True) * cfg["routed_scaling_factor"]


def probe_numbers(cfg: dict, seed: int, scans: list, routed: list) -> dict:
    """The router and the indexer's selection held to float64 ON THEIR OWN
    INPUTS, where the end-to-end numbers cannot tell their precision from the
    bfloat16 activations around them. Plain numpy on the host: only the
    router's weights and bias, the seed's, come from the device.

    ``router_gate_gap``  a document's ``u``, ``experts``, ``gates``
        [n_expert_layers, s, ..] at its sampled positions: the gates, group
        limit, bias and scale and all, against the float64 router's on the
        same ``u``, as the largest difference over the 256 experts; the 90th
        percentile over positions and layers (a maximum would hang on one
        near-tie).

    ``index_select_gap``  the first expert layer's selection at the sampled
        positions: ``I(t, .)`` recomputed in float64 from the very ``q_index``,
        ``w_index`` (``routed``) and ``k_index`` (``scans``) the selection was
        made from, and the float64 threshold (the ``index_topk``-th largest
        candidate). Over the candidates where the kept keys and float64's
        differ, the largest distance of the float64 score from the float64
        threshold, over the row's root-mean-square score; the maximum over
        the positions. A sound run reads float32's rounding of a sum of 64
        terms; scores in bfloat16 read a hundred thousand times that; another
        number of keys, or none left out, reads the spread of a row's scores.

    ``index_keys_short``  sampled queries that kept fewer than
        min(position + 1, ``index_topk``) of their candidates, or any key
        that is no candidate (after it, or outside its document): limit 0."""
    top_k, gaps = cfg["num_experts_per_tok"], []
    layers = [i for i, ffn in enumerate(ffn_kinds(cfg)) if ffn == "moe"]
    for nth, layer in enumerate(layers):
        u = np.concatenate([np.asarray(r["u"][nth], np.float64) for r in routed])
        if not len(u):
            continue
        experts = np.concatenate([r["experts"][nth] for r in routed])
        got = np.concatenate([np.asarray(r["gates"][nth], np.float64) for r in routed])
        w = part_weights(seed, cfg, layer, names=("router", "router_bias"))
        scores, chosen, gates = _float64_router(u, np.asarray(w["router"], np.float64),
                                                np.asarray(w["router_bias"], np.float64), cfg)
        dense, at = np.zeros((2,) + scores.shape), np.arange(len(u))[:, None]
        dense[0, at, experts] = got
        dense[1, at, chosen] = gates
        gaps.append(np.abs(dense[0] - dense[1]).max(axis=1))
    topk, worst, short = cfg["index_topk"], 0.0, 0
    for scan, r in zip(scans, routed):
        if "k_index" not in scan or "q_index" not in r:
            continue
        keys = np.asarray(scan["k_index"], np.float64)
        for q, w, kept, pos, start in zip(r["q_index"][0], r["w_index"][0], r["kept"][0],
                                          r["index_pos"][0], r["index_start"][0]):
            pos, start, kept = int(pos), int(start), np.asarray(kept) != 0
            mine = kept[start:start + pos + 1]
            scores = np.asarray(w, np.float64) @ np.maximum(
                np.asarray(q, np.float64) @ keys[:pos + 1].T, 0.0)
            threshold = np.sort(scores)[-topk] if pos + 1 > topk else -np.inf
            differ = mine != (scores >= threshold)
            if differ.any():
                rms = max(float(np.sqrt(np.mean(scores ** 2))), 1e-30)
                worst = max(worst, float(np.abs(scores[differ] - max(threshold, scores.min())).max()) / rms)
            short += int(mine.sum() < min(pos + 1, topk) or kept.sum() != mine.sum())
    return {"router_gate_gap": float(np.percentile(np.concatenate(gaps), 90.0)) if gaps else 0.0,
            "index_select_gap": worst, "index_keys_short": float(short)}


# ---------------------------------------------------------------------------
# What a step needs
# ---------------------------------------------------------------------------


def selected_pairs(tokens: float, triangle: float, topk: int) -> float:
    """The (query, key) pairs a step's selection keeps, from the step's scored
    positions and its documents' triangles: exact where the step's documents
    are of one length n (then triangle / tokens = (n + 1) / 2; the cell's
    are), and that length's count times the documents otherwise. A query
    keeps min(position + 1, ``topk``) keys; ties are not counted."""
    n = 2.0 * triangle / max(tokens, 1.0) - 1.0
    per_doc = n * (n + 1.0) / 2.0 if n <= topk else topk * (topk + 1.0) / 2.0 + (n - topk) * topk
    return per_doc * tokens / max(n, 1.0)


def needs(cfg: dict, batch: int, loop: str) -> dict:
    """The least a scoring step asks of the chip, for the roofline shares:
    {"flops", "bytes", "scopes": {scope: {"flops", "bytes"}}}.

    What the mathematics asks, not what the program does: index scores over
    EVERY causal pair of each document at 64 heads of 128 (no exact
    selection can skip a pair) and the mask out as bits; attention over the
    SELECTED pairs only at (128 + 64) + 128 products a pair and head (a
    kernel that computes every causal pair and masks reads about 23% here),
    the rotary key read as the one head it is; the experts by the visits
    the batch makes, no work for pads, every weight read once a step,
    activations in bfloat16 once in and once out of a layer, the head's
    logits never stored. No share for the threshold alone: fused with the
    scores it moves no bytes to divide by. What the seed's rows held is the
    loop's to say: ``cfg["observed"]`` = {"tokens": scored positions a step,
    "triangle": sum over a step's documents of n (n + 1) / 2, "visits":
    visits to held experts a step and expert layer}."""
    seen = cfg["observed"]
    t, tri, visits = float(seen["tokens"]), float(seen["triangle"]), float(seen["visits"])
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    dn, dr, dv, rank, q_rank = (cfg[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                                 "v_head_dim", "kv_lora_rank", "q_lora_rank"))
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    f, wide, shared = cfg["moe_intermediate_size"], cfg["intermediate_size"], cfg["n_shared_experts"]
    kinds = ffn_kinds(cfg)
    n_layers, n_dense, n_moe = len(kinds), kinds.count("dense"), kinds.count("moe")
    act = 2.0 * t * d * 2.0                                # a layer's rows in and out, bf16
    mla_w = d * q_rank + q_rank * h * (dn + dr) + d * (rank + dr) + rank * h * (dn + dv) + h * dv * d
    dsa_w = q_rank * hi * di + d * di + d * hi
    picked = selected_pairs(t, tri, cfg["index_topk"])
    index_in = t * (hi * di * 2.0 + di * 2.0 + hi * 4.0)  # q^I and k^I bf16, w float32
    scopes = {
        "tfr.embed": {"flops": 0.0, "bytes": act + 4.0 * t},
        "tfr.mla_proj": {"flops": n_layers * 2.0 * t * mla_w, "bytes": n_layers * (2.0 * mla_w + act)},
        # the normed input and the query latent in, q^I, k^I and w out
        "tfr.dsa_proj": {"flops": n_layers * 2.0 * t * dsa_w,
                         "bytes": n_layers * (2.0 * dsa_w + t * (d + q_rank) * 2.0 + index_in)},
        "tfr.dsa_index": {"flops": n_layers * 2.0 * tri * hi * di,
                          "bytes": n_layers * (index_in + tri / 8.0)},
        # q, k_nope, one k_pe, v and the selection's bits in, the heads' values out, bf16
        "tfr.mla_attn": {"flops": n_layers * 2.0 * picked * h * (dn + dr + dv),
                         "bytes": n_layers * (2.0 * t * (h * (dn + dr) + h * dn + dr + 2 * h * dv)
                                              + tri / 8.0)},
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
