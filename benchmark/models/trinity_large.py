"""Trinity-Large-Preview for the benchmark: the weights from ``--seed``, the
program built for a configuration file, the plain reference, the probes of
the router and of one head's windowed attention, and what a step needs.

Nothing here except :func:`program` and :func:`_place` (which observes the
program) imports ``tpu_tfrecord.models``; the tensor law, the norms, the
gated unit and the reference's buckets are ``solar_open2.py``'s, the rotary
turn, the biased router's expert layer and the head ``kimi_vl_lm.py``'s, the
observed row and the choice among loads ``deepseek_v32.py``'s, imported. The
reference takes the seed and the generator's documents, never anything the
program has made.

The model (``configs/trinity_large_ep8.json`` has the source, the cut and
what is ``assumed``), for ONE document of tokens ``t_0 .. t_n``, with ``x``
the residual stream, weighted RMSNorm eps 1e-5, positions counted inside the
document:

    x0     = embed[t_0 .. t_{n-1}] * sqrt(3072)                         (mup_enabled)
    mixer  : u = rms(x; w_in); q = u Wq -> 48 heads of 128; k = u Wk, v = u Wv -> 8 heads of
             128; g = u Wg -> 48 x 128; q = rms(q; w_qn), k = rms(k; w_kn) over the 128 of a
             head, one weight for all heads; in a SLIDING layer rotary(q), rotary(k) over all
             128 columns (theta 10,000, pairs (i, i + 64)); a(t) = softmax_s(q(t) . k(s) /
             sqrt(128)) v(s) over s <= t and, in a sliding layer, t - s < 4,096 (4,096 keys,
             the query's own among them); a FULL layer has no positions and no window;
             y = (a * sigmoid(g)) Wo;  x = x + rms(y; w_post_attn)
    dense  : W_down(silu(W_gate u2) * W_up u2), u2 = rms(x; w_pre_mlp), width 12,288 (layer 5)
    moe    : s = sigmoid(u2 W_r) in float32 over the 256 experts; the 4 largest of s + b (one
             group: no group limit); gates s_e / sum of the 4 chosen s, times 2.448;
             shared(u2) of width 3,072 + sum of gate_e * expert_e(u2) over the chosen experts
             HELD HERE (32 of 256)
    either : x = x + rms(m; w_post_mlp)                                  (sandwich norms)
    score  : log_softmax(head(rms(x; w_final)))[t_1 .. t_n] over the 25,024 ids held here

The program computes this in bfloat16 with float32 norms, router, rotary
angles, softmax and logits, over packed rows with positions that restart at
every document; the reference in float32 throughout
(``jax.default_matmul_precision("highest")``), each document alone from
position 0, the mask written out (1,024 queries of one key-value head against
every key at a time), every expert by a loop, the head's logits 1,024 rows at
a time, one layer's weights on the device at a time. Both hold the same
weights: pointwise functions of the seed, rounded to bfloat16, the routers'
columns in the order :func:`placement` observes at set-up (which 32 of the 256
this chip holds: the deployment's placement by load, so that every seed's
step has the same tiles of the expert loop to compute).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from benchmark.models.deepseek_v32 import observed_row, pick_experts
from benchmark.models.kimi_vl_lm import HEAD_ROWS, ref_head_block, ref_moe_biased, ref_rope  # noqa: F401
from benchmark.models.solar_open2 import (  # noqa: F401
    _bucket, _jitted, make_tensor, ref_ffn, ref_norm, ref_round, through_int8)

# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------


def weight_specs(cfg: dict, part) -> Dict[str, tuple]:
    """{name: (shape held here, uncut leading size, first row held, law)} of one
    part: ``"embed"``, ``"head"`` or a layer's number (``solar_open2.py``'s
    laws; the embedding's rows of variance 1 / hidden: unit rows after the
    scale, ``assumed.init``)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    if part == "embed":
        return {"embed": ((v, d), v, 0, ("normal", d ** -0.5))}
    if part == "head":
        return {"head": ((d, v), d, 0, ("normal", d ** -0.5)),
                "final_norm": ((d,), d, 0, ("about_one", 0.1))}
    dh = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * dh, cfg["num_key_value_heads"] * dh

    def dense(m, n, gain=1.0):
        return ((m, n), m, 0, ("normal", (gain / m) ** 0.5))

    def gain(n):
        return ((n,), n, 0, ("about_one", 0.1))

    specs = {
        "attn_norm": gain(d), "wq": dense(d, hq), "wk": dense(d, hkv), "wv": dense(d, hkv),
        "wg": dense(d, hq), "wo": dense(hq, d), "q_norm": gain(dh), "k_norm": gain(dh),
        "post_attn_norm": gain(d), "post_ffn_norm": gain(d),
    }
    if layer_plan(cfg)[part][1] == "dense":
        wide = cfg["intermediate_size"]
        specs.update({"ffn_norm": gain(d), "dense.w_gate": dense(d, wide),
                      "dense.w_up": dense(d, wide), "dense.w_down": dense(wide, d)})
        return specs
    f, fs = cfg["moe_intermediate_size"], cfg["moe_intermediate_size"] * cfg["num_shared_experts"]
    e_all, e_held, e0 = cfg["num_experts"], cfg["n_routed_experts_held"], cfg.get("held_offset", 0)
    k, scale = cfg["num_experts_per_tok"], cfg["route_scale"]
    specs.update({
        "moe_norm": gain(d),
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

    plan = layer_plan(cfg)
    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_pattern=tuple("swa" if sliding else "gqa" for sliding, _ in plan),
        ffn_pattern=tuple(ffn for _, ffn in plan), n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"], qk_norm=True, branch_norms=True,
        embed_scale=bool(cfg["mup_enabled"]), rope_theta=float(cfg["rope_theta"]),
        d_dense=cfg["intermediate_size"], n_experts=cfg["num_experts"],
        experts_held=cfg["n_routed_experts_held"], held_offset=cfg.get("held_offset", 0),
        top_k=cfg["num_experts_per_tok"], d_expert=cfg["moe_intermediate_size"],
        n_shared=cfg["num_shared_experts"], routed_scale=float(cfg["route_scale"]),
        router_bias=True, norm_eps=cfg["rms_norm_eps"], max_len=mix["row_tokens"],
        dtype=jnp.bfloat16, **cfg.get("program", {}),
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

    ``deepseek_v32.placement``'s reasons hold here (a served model's experts
    lie where the observed loads put them; weights from a seed have no such
    history): under Zipf's hot tokens the first id alone is 9.7% of a document,
    3,178 visits to each of its 4 experts where a held expert's even share is
    512, and which hot experts a seed's first 32 columns held would move the
    expert loop by tens of percent from seed to seed. So one seeded row of the
    traffic's law goes through the program layer by layer, the visits to all
    256 experts (one group) are counted, and this chip is given the
    ``n_routed_experts_held`` that ``deepseek_v32.pick_experts`` names: none
    past its tile, their visits nearest the even share; the others lie on the
    7 chips beside it. Only names change hands: every token's routing, a
    function of the scores whatever their order, is what it was."""
    import jax

    key = _placed_key(seed, cfg)
    if key not in _PLACED:
        # the program as it is run, whoever asks first: inside the reference's
        # ``default_matmul_precision("highest")`` the kernels' bfloat16 products
        # would be asked for in float32
        with jax.default_matmul_precision(None):
            program_params(seed, cfg)  # builds the tree the placement is observed with, drops it
    return _PLACED[key]


def _place(seed: int, cfg: dict, params: dict) -> dict:
    """:func:`placement`'s orders, observed with ``params`` (the program's own
    tree in the law's order; its routers are left as placed).

    The observed row walks the program's layers one at a time (the program of
    ONE layer, handed the hidden state so far as if it were an embedding and
    the row ``0 1 2 ..`` as its tokens), since a layer's visits depend on what
    the layers before it hold. A layer's step returns the visits to the
    experts HELD, so the experts are put in the held columns
    ``n_routed_experts_held`` at a time (eight rounds in the cell), then the
    layer is run as placed and its output goes on to the next."""
    import dataclasses
    import json
    import time

    import jax
    import jax.numpy as jnp
    from tpu_tfrecord.models import lm

    plan = layer_plan(cfg)
    layers = [i for i, (_, ffn) in enumerate(plan) if ffn == "moe"]
    e_all, held, e0 = cfg["num_experts"], cfg["n_routed_experts_held"], cfg.get("held_offset", 0)
    everyone = np.arange(e_all)
    if not layers or held >= e_all:
        return {i: everyone for i in layers}
    t0 = time.perf_counter()
    row_tokens = cfg["doc_length"]["max"]
    pcfg = program(cfg, {"row_tokens": row_tokens})
    tokens, segs = observed_row(seed, cfg, row_tokens)
    real = int((segs[0, :-1] != 0).sum())
    walk, segs = jnp.arange(row_tokens + 1, dtype=jnp.int32)[None] % row_tokens, jnp.asarray(segs)
    one_layer = {kind: jax.jit(lambda layer, x, cut=dataclasses.replace(
        pcfg, layer_pattern=("swa" if kind[0] else "gqa",), ffn_pattern=(kind[1],),
        embed_scale=False): lm.pattern_hidden({"embed": x, "layers": [layer]}, walk, segs, cut)[:2])
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
    share = round(real * cfg["num_experts_per_tok"] * held / e_all)
    x = params["embed"][jnp.asarray(tokens[0, :-1])]
    if pcfg.embed_scale:  # as the program scales it
        x = (x.astype(jnp.float32) * pcfg.d_model ** 0.5).astype(x.dtype)
    orders, said = {}, []
    for i, kind in enumerate(plan):
        layer = params["layers"][i]
        if kind[1] == "moe":
            loads = np.zeros(e_all, np.int64)
            for r0 in range(0, e_all, held):
                names = everyone[r0: r0 + held]
                names = np.concatenate([names, everyone[: held - len(names)]])  # the last round, filled up
                put(i, with_held(names))
                loads[names] = np.asarray(one_layer[kind](layer, x)[1])[0]
            mine = everyone[pick_experts(loads, held, tile - tile // 16, share)]
            orders[i] = with_held(mine)
            put(i, orders[i])
            said.append({"layer": i, "visits": int(loads[mine].sum()), "most": int(loads[mine].max()),
                         "tiles": int((-(-loads[mine] // tile)).sum()),
                         "all_visits": int(loads.sum()), "all_most": int(loads.max()),
                         "all_sorted_every_16th": np.sort(loads)[::16].tolist()})
        if i < layers[-1]:
            x = one_layer[kind](layer, x)[0][0]
    print("[placement] " + json.dumps({"seconds": time.perf_counter() - t0, "row_tokens": real,
                                       "layers": said}, sort_keys=True), flush=True)
    return orders


# ---------------------------------------------------------------------------
# The plain reference (a copy of tpu_tfrecord/models/swa_reference.py;
# tests/test_swa_lm.py holds the two to each other line for line)
# ---------------------------------------------------------------------------
# --- reference: begin ---


def layer_plan(cfg: dict) -> List[Tuple[bool, str]]:
    """[(sliding, "dense" | "moe")] of the layers here: the published
    ``layer_types`` from ``first_layer`` on, the first ``num_dense_layers`` dense."""
    first = cfg.get("first_layer", 0)
    kinds = cfg["layer_types"][first: first + cfg["num_hidden_layers"]]
    return [(kind == "sliding_attention", "dense" if i < cfg["num_dense_layers"] else "moe")
            for i, kind in enumerate(kinds)]


QUERY_ROWS = 1024  # queries whose scores exist at once: 6 heads x 1,024 x 32,768 float32 are 805 MB


def ref_window_attention(q, k, v, window=None):
    """softmax(q . k / sqrt(d)) v over the keys s <= t and, with ``window``,
    t - s < window. q [n, g, r, d] (g key-value heads, each serving r query
    heads), k, v [n, g, d] -> [n, g, r, d]; a block of ``QUERY_ROWS`` queries
    of one key-value head at a time, against every key or, with a window,
    against the run of keys that holds every key its queries may see (the
    mask is written out over that run by the keys' own positions)."""
    import jax
    import jax.numpy as jnp

    n, _, r, d = q.shape
    rows = min(QUERY_ROWS, n)
    run = n if window is None else min(n, -(-(window - 1) // rows) * rows + rows)

    def one_group(group):
        qg, kg, vg = group                                     # [n, r, d], [n, d], [n, d]

        def one_block(block):
            qb, t = block                                      # [rows, r, d], [rows]
            first = jnp.clip(t[-1] + 1 - run, 0, n - run)      # the run ends with the block's last query
            at = first + jnp.arange(run)
            keys, values = (jax.lax.dynamic_slice_in_dim(a, first, run) for a in (kg, vg))
            seen = at[None, :] <= t[:, None]
            if window is not None:
                seen = seen & (t[:, None] - at[None, :] < window)
            scores = jnp.einsum("ihd,jd->hij", qb, keys) * d ** -0.5
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("hij,jd->ihd", probs, values)

        return jax.lax.map(one_block, (qg.reshape(n // rows, rows, r, d),
                                       jnp.arange(n).reshape(n // rows, rows))).reshape(n, r, d)

    by_group = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731  [n, g, ..] -> [g, n, ..]
    return by_group(jax.lax.map(one_group, (by_group(q), by_group(k), by_group(v))))


def ref_swa(p, u, cfg, sliding, lower=None, probe_head=None):
    """The softmax layer on one document u [n, D], ``sliding`` (a window and
    rotary positions) or full (neither): (y before the branch's norm, the
    record of ``probe_head``'s attention or None: ``q``, ``att`` [n, dh] and
    its key-value head's ``k``, ``v`` [n, dh], as the attention was given
    and gave them). ``lower`` names a control's departures: ``window`` (another
    number of keys; None: every key), ``no_rotary``, ``rotary_on_full``,
    ``no_qk_norm``, ``angle_dtype``."""
    import jax
    import jax.numpy as jnp

    lower = lower or {}
    n, eps, theta = u.shape[0], cfg["rms_norm_eps"], float(cfg["rope_theta"])
    h, g, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (u @ p["wq"]).reshape(n, h, dh)
    k = (u @ p["wk"]).reshape(n, g, dh)
    v = (u @ p["wv"]).reshape(n, g, dh)
    if not lower.get("no_qk_norm"):
        q, k = ref_norm(q, p["q_norm"], eps), ref_norm(k, p["k_norm"], eps)
    if lower.get("rotary_on_full") if not sliding else not lower.get("no_rotary"):
        at = jnp.arange(n)
        q = ref_rope(q, at, theta, lower.get("angle_dtype"))
        k = ref_rope(k, at, theta, lower.get("angle_dtype"))
    window = lower.get("window", cfg["sliding_window"]) if sliding else None
    att = ref_window_attention(q.reshape(n, g, h // g, dh), k, v, window).reshape(n, h, dh)
    record = None
    if probe_head is not None:
        held = probe_head // (h // g)
        record = {"q": q[:, probe_head], "k": k[:, held], "v": v[:, held], "att": att[:, probe_head]}
    return (att.reshape(n, h * dh) * jax.nn.sigmoid(u @ p["wg"])) @ p["wo"], record


def ref_join(x, y, weight, cfg, lower=None):
    """x + rms(y; weight): the sandwich's second norm, on the branch
    (``lower["no_branch_norms"]``: x + y, a control)."""
    return x + (y if (lower or {}).get("no_branch_norms") else ref_norm(y, weight, cfg["rms_norm_eps"]))


def ref_sandwich_front(sliding, ffn, p, x, cfg, lower=None, probe_head=None):
    """The mixer's branch joined to one document's x [n, D], then what the
    layer's feed-forward part needs: a dense layer is finished here (x, None,
    record), an expert layer hands back (x, rms(x; w_pre_mlp), record) for
    ``ref_moe_biased``, whose output :func:`ref_join` joins."""
    eps = cfg["rms_norm_eps"]
    y, record = ref_swa(p, ref_norm(x, p["attn_norm"], eps), cfg, sliding, lower, probe_head)
    x = ref_join(x, y, p["post_attn_norm"], cfg, lower)
    if ffn == "dense":
        y = ref_ffn(ref_norm(x, p["ffn_norm"], eps), p["dense.w_gate"], p["dense.w_up"],
                    p["dense.w_down"])
        return ref_join(x, y, p["post_ffn_norm"], cfg, lower), None, record
    return x, ref_norm(x, p["moe_norm"], eps), record


def reference_score(cfg: dict, docs: list, weights: Callable, logits_at: Optional[list] = None,
                    lower: Optional[dict] = None, probe_head: int = 0) -> dict:
    """Each document scored alone, in float32 at the highest matmul precision.

    docs: int arrays ``t_0 .. t_n`` (the end-of-document id included);
    ``weights(part)`` -> that part's float32 tensors (one part is held at a
    time); ``logits_at[i]``: positions of document i whose full logits to keep.
    Returns {"logprob": [log p(t_1..t_n)] a document, "logits": [[len(at), V]]
    a document, "dropped": visits a ``capacity`` control dropped, "router":
    a document's {"u", "experts", "gates"} [n_expert_layers, len(at), ..] at
    ``logits_at`` (each expert layer's router input and what it chose) and,
    of head ``probe_head`` of the FIRST sliding layer with a leading axis of
    1, "q_swa", "att_swa" [1, len(at), dh] and "swa_pos" (= the positions),
    "scan": a document's {"k_swa", "v_swa"} [n, dh] of that head's key-value
    head}. ``lower`` names a control's departures: :func:`ref_swa`'s,
    ``no_branch_norms``, ``no_embed_scale``, ``router_dtype``, ``capacity``."""
    import json

    import jax
    import jax.numpy as jnp

    lower = lower or {}
    plan = layer_plan(cfg)
    where = [np.asarray(a, np.int64) for a in (logits_at or [[]] * len(docs))]
    # the biased router's expert layer reads the DeepSeek-V3 family's names
    moe_cfg = {**cfg, "routed_scaling_factor": cfg["route_scale"]}
    with jax.default_matmul_precision("highest"):
        embed = weights("embed")["embed"]
        scale = 1.0 if lower.get("no_embed_scale") else float(cfg["hidden_size"]) ** 0.5
        xs = []
        for doc in docs:
            ids = np.zeros(_bucket(len(doc) - 1), np.int32)
            ids[: len(doc) - 1] = doc[:-1]
            xs.append(embed[ids] * scale)
        del embed
        out = {"logprob": [], "logits": [], "dropped": 0, "scan": [{} for _ in docs],
               "router": [{"u": [], "experts": [], "gates": []} for _ in docs]}
        mixer = {k: v for k, v in lower.items() if k in (
            "window", "no_rotary", "rotary_on_full", "no_qk_norm", "angle_dtype", "no_branch_norms")}
        same = json.dumps(cfg, sort_keys=True, default=repr)  # programs are kept by what cfg says
        said = json.dumps(mixer, sort_keys=True, default=repr)
        sliding_layers = [i for i, (sliding, _) in enumerate(plan) if sliding]
        probed = sliding_layers[0] if sliding_layers else None
        front = {(kind, probe): _jitted(
            lambda p, x, kind=kind, probe=probe: ref_sandwich_front(*kind, p, x, cfg, mixer, probe),
            key=("sandwich_front", same, kind, said, probe))
            for kind in set(plan) for probe in (None, probe_head)}
        join = _jitted(lambda x, y, w: ref_join(x, y, w, cfg, mixer), key=("sandwich_join", same, said))
        for i, kind in enumerate(plan):
            p = weights(i)
            for j, x in enumerate(xs):
                xs[j], u, record = front[kind, probe_head if i == probed else None](p, x)
                if record is not None:
                    n = len(docs[j]) - 1
                    out["scan"][j] = {"k_swa": np.asarray(record["k"])[:n],
                                      "v_swa": np.asarray(record["v"])[:n]}
                    out["router"][j]["window"] = {
                        "q_swa": np.asarray(record["q"][where[j]])[None],
                        "att_swa": np.asarray(record["att"][where[j]])[None],
                        "swa_pos": where[j].astype(np.int32)[None]}
                del record
                if u is None:
                    continue
                y, lost, (chosen, gates) = ref_moe_biased(
                    p, u, moe_cfg, lower.get("router_dtype"), lower.get("capacity"))
                xs[j], out["dropped"] = join(xs[j], y, p["post_ffn_norm"]), out["dropped"] + lost
                for name, a in (("u", u), ("experts", chosen), ("gates", gates)):
                    out["router"][j][name].append(np.asarray(a)[where[j]])
            del p
        p = weights("head")
        head = _jitted(lambda p, x, t: ref_head_block(p, x, t, cfg), key=("sandwich_head", same))
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
            window = out["router"][j].pop("window", {})
            out["router"][j] = {**{k: np.stack(v) for k, v in out["router"][j].items()}, **window}
    return out


# --- reference: end ---


def reference_weights(seed: int, cfg: dict, through: Optional[Callable] = None) -> Callable:
    """``weights(part)`` for :func:`reference_score` from the seed, a part at a
    time (the placement found now, if no one has asked for it yet, not in
    the middle of a document's layers)."""
    placement(seed, cfg)
    return lambda part: part_weights(seed, cfg, part, through)


def attend_keys(q, keys, values, pos: int, count: int) -> np.ndarray:
    """One query's softmax attention over its own key and the ``count - 1``
    before it, float64 on the host: q [dh], keys, values [n, dh] of its
    document, ``pos`` its index there."""
    lo = max(0, pos + 1 - count)
    scores = keys[lo: pos + 1] @ q * len(q) ** -0.5
    weights = np.exp(scores - scores.max())
    return weights @ values[lo: pos + 1] / weights.sum()


def probe_numbers(cfg: dict, seed: int, scans: list, routed: list) -> dict:
    """The router and one head's windowed attention held to float64 ON THEIR
    OWN INPUTS, where the end-to-end numbers cannot tell a layer's precision,
    or one key more or less in a window of 4,096, from the bfloat16
    activations around it. Plain numpy on the host: only the router's weights
    and bias, the seed's, come from the device.

    ``router_gate_gap``  a document's ``u``, ``experts``, ``gates``
        [n_expert_layers, s, ..] at its sampled positions: the gates, bias
        and scale and all, against the float64 router's on the same ``u``
        (the 4 largest of ``sigmoid(u W_r) + b``, gates from the scores
        alone, times 2.448), as the largest difference over the 256 experts;
        the 90th percentile over positions and layers (a maximum would hang
        on one near-tie).

    ``window_attn_gap``  one head of the first sliding layer at the sampled
        positions: its output ``att_swa`` against float64 attention over the
        very ``q_swa`` (``routed``), ``k_swa`` and ``v_swa`` (``scans``) the
        call was given, over the query's own key and the 4,095 before it;
        root mean square over all sampled positions, relative.

    ``window_keys_wrong``  sampled queries whose output another count of
        keys explains at least twice as well as the window's does (distance
        to float64 attention over one key fewer, one key more, or every key
        of the document before the query, under half the distance to the
        window's): limit 0. A query with fewer keys behind it than the window
        holds cannot tell them apart and counts for nothing."""
    top_k, gaps = cfg["num_experts_per_tok"], []
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
        gates = top / top.sum(axis=1, keepdims=True) * cfg["route_scale"]
        dense, at = np.zeros((2,) + scores.shape), np.arange(len(u))[:, None]
        dense[0, at, experts] = got
        dense[1, at, chosen] = gates
        gaps.append(np.abs(dense[0] - dense[1]).max(axis=1))
    window, err, norm, wrong = cfg["sliding_window"], 0.0, 0.0, 0
    for scan, r in zip(scans, routed):
        if "k_swa" not in scan or "q_swa" not in r:
            continue
        keys, values = (np.asarray(scan[k], np.float64) for k in ("k_swa", "v_swa"))
        for q, got, pos in zip(np.asarray(r["q_swa"][0], np.float64),
                               np.asarray(r["att_swa"][0], np.float64), r["swa_pos"][0]):
            pos = int(pos)
            want = attend_keys(q, keys, values, pos, window)
            off = float(((got - want) ** 2).sum())
            err, norm = err + off, norm + float((want ** 2).sum())
            others = {min(pos + 1, count) for count in (window - 1, window + 1, pos + 1)
                      if count >= 1} - {min(pos + 1, window)}
            wrong += any(float(((got - attend_keys(q, keys, values, pos, count)) ** 2).sum()) < off / 4.0
                         for count in others)
    return {"router_gate_gap": float(np.percentile(np.concatenate(gaps), 90.0)) if gaps else 0.0,
            "window_attn_gap": float(np.sqrt(err / norm)) if norm else 0.0,
            "window_keys_wrong": float(wrong)}


# ---------------------------------------------------------------------------
# What a step needs
# ---------------------------------------------------------------------------


def window_pairs(tokens: float, triangle: float, window: int) -> float:
    """The (query, key) pairs a step's sliding layer holds inside its window,
    from the step's scored positions and its documents' triangles: exact
    where the step's documents are of one length n (then triangle / tokens =
    (n + 1) / 2; the cell's are), and that length's count times the documents
    otherwise. A query sees min(position + 1, ``window``) keys."""
    n = 2.0 * triangle / max(tokens, 1.0) - 1.0
    per_doc = n * (n + 1.0) / 2.0 if n <= window else window * (window + 1.0) / 2.0 + (n - window) * window
    return per_doc * tokens / max(n, 1.0)


def needs(cfg: dict, batch: int, loop: str) -> dict:
    """The least a scoring step asks of the chip, for the roofline shares:
    {"flops", "bytes", "scopes": {scope: {"flops", "bytes"}}}.

    What the mathematics asks, not what the program does: a sliding layer's
    attention over the pairs INSIDE the window only (125,831,168 of a
    32,768-token document's 536,887,296) at 128 + 128 products a pair and
    query head, a full layer's over each document's own triangle, keys and
    values read as the 8 heads they are; the experts by the visits the batch
    makes, no work for pads, every weight read once a step, activations in
    bfloat16 once in and once out of a layer, the head's logits never stored.
    What the seed's rows held is the loop's to say: ``cfg["observed"]`` =
    {"tokens": scored positions a step, "triangle": sum over a step's
    documents of n (n + 1) / 2, "visits": visits to held experts a step and
    expert layer}."""
    seen = cfg["observed"]
    t, tri, visits = float(seen["tokens"]), float(seen["triangle"]), float(seen["visits"])
    d, v, dh = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, wide, shared = cfg["moe_intermediate_size"], cfg["intermediate_size"], cfg["num_shared_experts"]
    plan = layer_plan(cfg)
    n_swa, n_full = sum(sliding for sliding, _ in plan), sum(not sliding for sliding, _ in plan)
    n_dense, n_moe = sum(ffn == "dense" for _, ffn in plan), sum(ffn == "moe" for _, ffn in plan)
    act = 2.0 * t * d * 2.0                                # a layer's rows in and out, bf16
    mixer_w = d * (2 * h * dh + 2 * hkv * dh) + h * dh * d
    banded = window_pairs(t, tri, cfg["sliding_window"])
    heads_io = 2.0 * t * (2 * h * dh + 2 * hkv * dh)       # q in and a out, k and v in, bf16
    scopes = {
        "tfr.embed": {"flops": 0.0, "bytes": act + 4.0 * t},
        "tfr.swa_proj": {"flops": n_swa * 2.0 * t * mixer_w, "bytes": n_swa * (2.0 * mixer_w + act)},
        "tfr.swa_attn": {"flops": n_swa * 4.0 * banded * h * dh, "bytes": n_swa * heads_io},
        "tfr.gqa": {"flops": n_full * (2.0 * t * mixer_w + 4.0 * tri * h * dh),
                    "bytes": n_full * (2.0 * mixer_w + act)},
        "tfr.dense_ffn": {"flops": n_dense * t * 6.0 * d * wide,
                          "bytes": n_dense * (3 * d * wide * 2.0 + act)},
        "tfr.moe_route": {"flops": n_moe * 2.0 * t * d * cfg["num_experts"],
                          "bytes": n_moe * (2.0 * d * cfg["num_experts"] + t * d * 2.0)},
        "tfr.moe_experts": {"flops": n_moe * visits * 6.0 * d * f,
                            "bytes": n_moe * (cfg["n_routed_experts_held"] * 3 * d * f * 2.0
                                              + 2.0 * visits * d * 2.0)},
        "tfr.moe_shared": {"flops": n_moe * t * 6.0 * d * f * shared,
                           "bytes": n_moe * (3 * d * f * shared * 2.0 + act)},
        "tfr.lm_head": {"flops": 2.0 * t * d * v, "bytes": 2.0 * d * v + t * d * 2.0 + 4.0 * t},
    }
    return {"flops": sum(s["flops"] for s in scopes.values()),
            "bytes": sum(s["bytes"] for s in scopes.values()), "scopes": scopes}
