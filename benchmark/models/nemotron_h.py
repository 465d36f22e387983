"""Nemotron-H (the causal tower of Nemotron-Labs-TwoTower-30B-A3B) for the
benchmark: the weights from ``--seed``, the program built for a configuration
file, the plain reference, the probes of one head's recurrence and of the
router, and what a step needs.

Nothing here except :func:`program` and :func:`_place` (which observes the
program) imports ``tpu_tfrecord.models``; the tensor law, the plain norm, the
convolution and the reference's buckets are ``solar_open2.py``'s, the biased
router and the head's block ``kimi_vl_lm.py``'s, the softmax layer's attention (a block of queries
of one key-value head at a time) ``trinity_large.py``'s, the observed row
and the placement's key ``gigachat35.py``'s and the choice among loads ``deepseek_v32.py``'s,
imported. The reference takes the seed and the generator's documents, never
anything the program has made.

The model (``configs/nemotron_twotower_ep2.json`` has the source, the cut,
what is ``assumed`` with the reading taken, and what is ``left_out``: the
denoising tower and the block-diffusion decoding), for ONE document of tokens
``t_0 .. t_n``, with ``x`` the residual stream and ``N(x; w) = x / rms(x) * w``
(eps 1e-5). Each layer is ONE pre-normed branch, ``x = x + B(N(x; w))``, its
kind a letter of ``hybrid_override_pattern``:

    x0  = embed[t_0 .. t_{n-1}]                                          no positions
    M   : [z | xBC | dt] = u W_in, 4096 + 6144 + 64 columns; xBC = silu(conv4(xBC) + b_c), causal,
          nothing before the document; [xs | B | C] = xBC: xs -> 64 heads of 64, B, C -> 8 groups
          of 128, head h reads group h // 8; dt_t = softplus(dt_t + dt_bias_h); a_t = exp(dt_t A_h),
          A_h = -exp(A_log_h): ONE decay a head and token; S_t = a_t S_{t-1} + dt_t xs_t B_t^T, a
          [64 x 128] float32 state a head, S = 0 before the document; y_t = S_t C_t + D_h xs_t;
          y = Ng(y * silu(z); w_g), an RMSNorm over each group's 512 channels, the gate BEFORE it;
          B(x) = y W_out
    *   : q = u Wq -> 32 heads of 128; k, v = u Wk, u Wv -> 2 heads of 128; query head h reads
          key-value head h // 16; softmax over s <= t of q_t . k_s 128^-1/2, times v; no positions,
          no gate, no Q/K norm; B(x) = att Wo
    E   : f(u; Wu, Wd) = relu(u Wu)^2 Wd (two matrices: NOT a gated unit); s = sigmoid(u Wr) in
          float32 over the 128 experts; the 6 largest of s + b; gates s_e / sum of the 6 chosen s,
          times 2.5; B(x) = f(u; shared, width 3,712) + sum of gate_e f(u; expert e, width 1,856)
          over the chosen experts HELD HERE (64 of 128)
    out : log_softmax(head(N(x; w_final)))[t_1 .. t_n] over the 65,536 ids held here

The program computes this in bfloat16 with float32 norms, router, softmax,
step, decay, state and logits, over packed rows whose taps and state restart
at every document; the reference in float32 throughout
(``jax.default_matmul_precision("highest")``), each document alone from an
empty state, the recurrence token by token with B and C copied to their
group's heads (what the program never writes), a block of queries of one
key-value head at a time, every expert by a loop, the head's logits 1,024
rows at a time, one layer's weights on the device at a time. Both hold the
same weights: pointwise functions of the seed, rounded to bfloat16, the
routers' columns in the order :func:`placement` observes at set-up (which 64
of the 128 this chip holds: the deployment's placement by load).

Departures from the published description, each also under ``assumed`` in the
configuration file: the state-space layer's inner width is heads x head size
(4,096; ``expand`` 2 would give 5,376); ``W_in``'s columns lie ``[z | x | B |
C | dt]``; ``time_step_limit`` (0, none) clamps nothing; the softmax layers
take no positions (``rope_theta`` and ``partial_rotary_factor`` are carried
and unused); ``relu2`` is ``relu(.)^2`` in routed and shared experts alike;
``rescale_prenorm_residual`` is an initialisation and nothing here;
``chunk_size`` is a kernel's and moves no result.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from benchmark.models.deepseek_v32 import pick_experts
from benchmark.models.gigachat35 import _placed_key, observed_row
from benchmark.models.kimi_vl_lm import HEAD_ROWS, ref_head_block, ref_route_biased
from benchmark.models.solar_open2 import (  # noqa: F401
    _bucket, _jitted, _room, make_tensor, ref_conv, ref_norm, ref_round, through_int8)
from benchmark.models.trinity_large import ref_window_attention

KINDS = {"M": ("ssm", "none"), "*": ("gqa", "none"), "E": ("none", "moe")}

# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------


def ssm_widths(cfg: dict) -> Tuple[int, int, int, int, int]:
    """(heads, a head's channels, the state, groups of B and C, inner width)."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return h, p, cfg["ssm_state_size"], cfg["n_groups"], h * p


def weight_specs(cfg: dict, part, gated: bool = False) -> Dict[str, tuple]:
    """{name: (shape held here, uncut leading size, first row held, law)} of one
    part: ``"embed"``, ``"head"`` or a layer's number (``solar_open2.py``'s
    laws). ``gated`` adds a gate matrix beside every expert's way up: what the
    control ``gated_experts`` puts in the experts' place."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    if part == "embed":
        return {"embed": ((v, d), v, 0, ("normal", 1.0))}
    if part == "head":
        return {"head": ((d, v), d, 0, ("normal", d ** -0.5)),
                "final_norm": ((d,), d, 0, ("about_one", 0.1))}

    def dense(m, n, gain=1.0):
        return ((m, n), m, 0, ("normal", (gain / m) ** 0.5))

    mixer, ffn = layer_plan(cfg)[part]
    if mixer == "ssm":
        h, _, n, g, inner = ssm_widths(cfg)
        taps, conv = cfg["conv_kernel"], inner + 2 * g * n
        return {
            "attn_norm": ((d,), d, 0, ("about_one", 0.1)), "w_in": dense(d, 2 * inner + 2 * g * n + h),
            "conv_x": ((taps, conv), taps, 0, ("taps", 0.5)), "conv_bias": ((conv,), conv, 0, ("normal", 0.25)),
            # A_h = -exp(a_log) uniform in 1-16, D = 1, dt_bias the inverse softplus of a step
            # log-uniform in time_step_min .. time_step_max: as Mamba-2 draws them
            "a_log": ((h,), h, 0, ("log_between", 1.0, 16.0)), "d_skip": ((h,), h, 0, ("about_one", 0.0)),
            "dt_bias": ((h,), h, 0, ("rate_bias", cfg["time_step_min"], cfg["time_step_max"])),
            "o_norm": ((inner,), inner, 0, ("about_one", 0.1)), "wo": dense(inner, d),
        }
    if mixer == "gqa":
        hq = cfg["num_attention_heads"] * cfg["head_dim"]
        hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
        return {"attn_norm": ((d,), d, 0, ("about_one", 0.1)), "wq": dense(d, hq), "wk": dense(d, hkv),
                "wv": dense(d, hkv), "wo": dense(hq, d)}
    f, fs = cfg["moe_intermediate_size"], cfg["moe_shared_expert_intermediate_size"]
    e_all, e_held, e0 = cfg["n_routed_experts"], cfg["n_routed_experts_held"], cfg.get("held_offset", 0)
    k, scale = cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]
    specs = {
        "moe_norm": ((d,), d, 0, ("about_one", 0.1)),
        "router": dense(d, e_all),
        "router_bias": ((e_all,), e_all, 0, ("normal", 0.05)),
        "w_up": ((e_held, d, f), e_all, e0, ("normal", d ** -0.5)),
        # relu(n)^2 of a unit normal has a mean square of 1.5; k gates of about scale / k each
        "w_down": ((e_held, f, d), e_all, e0, ("normal", (k / scale ** 2 / f / 1.5) ** 0.5)),
        "shared.w_up": dense(d, fs), "shared.w_down": dense(fs, d, 1 / 1.5),
    }
    if gated:
        specs.update({"w_gate": ((e_held, d, f), e_all, e0, ("normal", d ** -0.5)),
                      "shared.w_gate": dense(d, fs)})
    return specs


def _raw_weights(seed: int, cfg: dict, part, through: Optional[Callable] = None,
                 names: Optional[tuple] = None, gated: bool = False) -> dict:
    """{name: float32 array} of one part (:func:`weight_specs`) as the seed's
    law gives it, the router's columns in the law's own order; or of its
    ``names`` only; ``through`` is applied to every matrix (a control's lower
    precision). A matrix is rounded to bfloat16's values here, by arithmetic
    on the bits (``deepseek_v32.py`` has why)."""
    import jax.numpy as jnp

    rounded = _jitted(ref_round, static_argnums=1)
    out = {}
    for name, (shape, _, first, law) in weight_specs(cfg, part, gated).items():
        if names is not None and name not in names:
            continue
        w = make_tensor(seed, f"{part}.{name}", tuple(shape), first, law)
        if w.ndim >= 2:
            w = rounded(w, jnp.bfloat16)
        out[name] = through(w) if through is not None and w.ndim >= 2 else w
    return out


def part_weights(seed: int, cfg: dict, part, through: Optional[Callable] = None,
                 names: Optional[tuple] = None, gated: bool = False) -> dict:
    """:func:`_raw_weights` with an expert layer's router and its bias in the
    order :func:`placement` gives their columns: what the program, the
    reference and the probes all hold."""
    out = _raw_weights(seed, cfg, part, through, names, gated)
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
    h, p, n, g, _ = ssm_widths(cfg)
    return lm.PatternLMConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layer_pattern=tuple(mixer for mixer, _ in plan), ffn_pattern=tuple(ffn for _, ffn in plan),
        kda_heads=h, kda_head_dim=p, ssm_state=n, ssm_groups=g, conv_taps=cfg["conv_kernel"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], gqa_gate=False,
        n_experts=cfg["n_routed_experts"], experts_held=cfg["n_routed_experts_held"],
        held_offset=cfg.get("held_offset", 0), top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"], n_shared=cfg["n_shared_experts"],
        d_shared=cfg["moe_shared_expert_intermediate_size"], expert_unit="relu2",
        routed_scale=float(cfg["routed_scaling_factor"]), router_bias=True,
        norm_eps=cfg["layer_norm_epsilon"], max_len=mix["row_tokens"], dtype=jnp.bfloat16,
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


def placement(seed: int, cfg: dict) -> dict:
    """{expert layer: order [E]}: column j of the router (and of its bias) that
    is run is column ``order[j]`` of the seed's law.

    ``gigachat35.placement``'s reasons hold here, with half the experts held
    instead of a sixteenth: one seeded row of the traffic's law, as long as a
    step (``placement.row_tokens``), goes through the program layer by layer,
    the visits to all 128 experts are counted from the router's own choices,
    and this chip is given the ``n_routed_experts_held`` whose visits are each
    1 to ``placement.cap`` (times the real share of the observed row: Zipf's
    hottest experts, three to five times the mean, lie on the chip beside it)
    and sum nearest the even share (``deepseek_v32.pick_experts``, on loads
    counted in ``placement.grain``s of visits: with 64 to choose of 128 and
    sums near 49,152 its table of reachable sums would not fit otherwise).
    Only names change hands: every token's routing, a function of the scores
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


def _place(seed: int, cfg: dict, params: dict) -> dict:
    """:func:`placement`'s orders, observed with ``params`` (the program's own
    tree in the law's order; its routers are left as placed), as
    ``gigachat35._place`` observes them: the observed row walks the program's
    layers one at a time (the program of ONE layer, handed the hidden state so
    far as if it were an embedding and the row ``0 1 2 ..`` as its tokens);
    every position is a sampled one, so an expert layer's step returns what
    the router chose for each token among all 128 (two runs: one to count,
    one as placed, whose output goes on). The row is an argument of those
    programs, never a constant of theirs: every seed finds them in the
    compile cache."""
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
    row_tokens, grain = cfg["placement"]["row_tokens"], cfg["placement"]["grain"]
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
            mine = everyone[pick_experts(-(-loads // grain), held, cap // grain, share // grain)]
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
# The plain reference
# ---------------------------------------------------------------------------
# --- reference: begin ---


def layer_plan(cfg: dict) -> List[Tuple[str, str]]:
    """[(mixer, feed-forward part)] of the layers here, one of the two
    ``"none"``: the letters ``first_layer`` .. ``first_layer +
    num_hidden_layers`` of ``hybrid_override_pattern`` (M a state-space layer,
    * a softmax layer, E an expert layer)."""
    first = cfg.get("first_layer", 0)
    return [KINDS[letter] for letter in cfg["hybrid_override_pattern"][first: first + cfg["num_hidden_layers"]]]


def ref_scan(x, b, c, dt, log_decay, state0=None, state_dtype=None):
    """The state-space recurrence token by token over ONE document: x [n, h,
    p], b, c [n, h, s] (a group's, copied to its heads), dt, log_decay [n, h]
    -> (y [n, h, p], the last state [h, p, s]). ``state_dtype`` keeps the
    state in a lower precision (a control)."""
    import jax
    import jax.numpy as jnp

    keep = (lambda s: ref_round(s, state_dtype)) if state_dtype else (lambda s: s)

    def token(state, xs):
        x_t, b_t, c_t, dt_t, g_t = xs
        state = keep(state * jnp.exp(g_t)[:, None, None]
                     + jnp.einsum("hp,hs->hps", x_t * dt_t[:, None], b_t))
        return state, jnp.einsum("hps,hs->hp", state, c_t)

    first = jnp.zeros(x.shape[1:] + b.shape[2:], jnp.float32) if state0 is None else state0
    last, y = jax.lax.scan(token, first, (x, b, c, dt, log_decay))
    return y, last


def ref_ssm(p, u, cfg, lower=None, carried=None, probe_head=None):
    """The state-space layer on one document u [n, D]: (y, what a document
    that followed would be handed if state and taps outlived this one: (the
    last state [H, P, N], the last three rows of the convolution's input),
    probe). With ``probe_head`` what the recurrence was given and gave for it:
    ``x``, ``o`` [n, P], its group's ``b``, ``c`` [n, N], ``dt``,
    ``log_decay`` [n]. ``carried``: such a pair from the document before (the
    controls ``carried_state`` and ``carried_taps`` hand over one half each).
    ``lower`` names a control's departures: ``state_dtype``, ``group_off``
    (head h reads group h mod G), ``no_conv_bias``, ``no_skip`` (D = 0),
    ``norm_before_gate``, ``no_dt_bias``."""
    import jax
    import jax.numpy as jnp

    lower = lower or {}
    n, eps = u.shape[0], cfg["layer_norm_epsilon"]
    h, ph, s, g, inner = ssm_widths(cfg)
    taps = p["conv_x"].shape[0]
    state0, tail = carried if carried is not None else (None, None)
    z, xbc, dt = jnp.split(u @ p["w_in"], [inner, 2 * inner + 2 * g * s], axis=1)
    before = jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype) if tail is None else tail
    mixed = ref_conv(jnp.concatenate([before, xbc]), p["conv_x"])[taps - 1:]
    if not lower.get("no_conv_bias"):
        mixed = mixed + p["conv_bias"]
    mixed = jax.nn.silu(mixed)
    x = mixed[:, :inner].reshape(n, h, ph)
    reads = jnp.arange(h) % g if lower.get("group_off") else jnp.arange(h) // (h // g)
    b = mixed[:, inner:inner + g * s].reshape(n, g, s)[:, reads]         # a group's B once a head
    c = mixed[:, inner + g * s:].reshape(n, g, s)[:, reads]
    dt = jax.nn.softplus(dt if lower.get("no_dt_bias") else dt + p["dt_bias"])
    log_decay = -jnp.exp(p["a_log"]) * dt
    o, last = ref_scan(x, b, c, dt, log_decay, state0, lower.get("state_dtype"))
    probe = None
    if probe_head is not None:
        probe = {"x": x[:, probe_head], "b": b[:, probe_head], "c": c[:, probe_head],
                 "dt": dt[:, probe_head], "log_decay": log_decay[:, probe_head], "o": o[:, probe_head]}
    y = o if lower.get("no_skip") else o + x * p["d_skip"][:, None]
    gate = jax.nn.silu(z).reshape(n, g, inner // g)
    y = y.reshape(n, g, inner // g)
    rms = lambda a: a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps)  # noqa: E731
    y = rms(y) * gate if lower.get("norm_before_gate") else rms(y * gate)
    return (y.reshape(n, inner) * p["o_norm"]) @ p["wo"], (last, xbc[n - (taps - 1):]), probe


def ref_attention(p, u, cfg, lower=None):
    """The softmax layer on one document u [n, D]: grouped causal attention
    without positions, gate or Q/K norm (``lower["attn_gate_on"]``: the output
    under a sigmoid gate of the layer's input, through the query matrix for
    want of a gate's own: a control)."""
    import jax

    n, h, g, dh = u.shape[0], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (u @ p["wq"]).reshape(n, g, h // g, dh)
    att = ref_window_attention(q, (u @ p["wk"]).reshape(n, g, dh), (u @ p["wv"]).reshape(n, g, dh))
    att = att.reshape(n, h * dh)
    if (lower or {}).get("attn_gate_on"):
        att = att * jax.nn.sigmoid(u @ p["wq"])
    return att @ p["wo"]


def ref_unit(u, w_up, w_down, w_gate=None, squared=True):
    """An expert's unit: ``relu(u w_up)^2 w_down``, two matrices (controls:
    ``squared`` False leaves the square out; with ``w_gate`` a SiLU-gated unit
    of the same width stands in its place)."""
    import jax

    if w_gate is not None:
        return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down
    hidden = jax.nn.relu(u @ w_up)
    return (hidden * hidden if squared else hidden) @ w_down


def _unit_front(router, bias, w_up, w_down, w_gate, u, cfg_items, router_dtype, squared):
    cfg = dict(cfg_items)
    chosen, gates = ref_route_biased(u, router, bias, cfg, router_dtype)
    return chosen, gates, ref_unit(u, w_up, w_down, w_gate, squared)


def _unit_expert_part(y, u, at, gate, w_up, w_down, w_gate, e, squared):
    """y + gate * expert_e(u[at]) laid down at ``at`` (an index past the end
    reads zeros and writes nothing)."""
    import jax.numpy as jnp

    part = ref_unit(jnp.take(u, at, axis=0, mode="fill", fill_value=0.0), w_up[e], w_down[e],
                    None if w_gate is None else w_gate[e], squared)
    return y.at[at].add(gate[:, None] * part, mode="drop")


def ref_moe_unit(p, u, cfg, router_dtype=None, capacity=None, squared=True):
    """The expert layer on one document: routing by ``ref_route_biased``
    (sigmoid scores, the bias picks, one group), the shared expert, plus every
    HELD expert's part, expert by expert, each over the tokens that chose it
    (picked on the host); where ``p`` holds ``w_gate`` every unit is the gated
    one (a control), ``squared`` False leaves the square out (another), and
    ``capacity`` drops an expert's visits beyond that many (a third). Returns
    (y, visits dropped, (chosen, gates))."""
    import jax.numpy as jnp

    n, e0, held = u.shape[0], cfg.get("held_offset", 0), cfg["n_routed_experts_held"]
    static = (("num_experts_per_tok", cfg["num_experts_per_tok"]),
              ("routed_scaling_factor", cfg["routed_scaling_factor"]))
    front = _jitted(_unit_front, static_argnums=(6, 7, 8))
    routing = front(p["router"], p["router_bias"], p["shared.w_up"], p["shared.w_down"],
                    p.get("shared.w_gate"), u, static,
                    jnp.dtype(router_dtype).name if router_dtype else None, squared)
    chosen, gates, y = np.asarray(routing[0]), np.asarray(routing[1]), routing[2]
    part = _jitted(_unit_expert_part, static_argnums=8)
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
        y = part(y, u, at, gate, p["w_up"], p["w_down"], p.get("w_gate"), np.int32(e), squared)
    return y, dropped, routing[:2]


def ref_branch(kind, p, x, cfg, lower=None, carried=None, probe_head=None):
    """One layer's ONE branch on a document's x [n, D]. A mixer's layer is
    finished here: (x + B(N(x)), None, what a state-space layer would hand on,
    its probe); an expert layer hands back (x, N(x; w), None, None) for
    :func:`ref_moe_unit`, whose output the caller adds."""
    mixer, _ = kind
    if mixer == "none":
        return x, ref_norm(x, p["moe_norm"], cfg["layer_norm_epsilon"]), None, None
    u = ref_norm(x, p["attn_norm"], cfg["layer_norm_epsilon"])
    if mixer == "gqa":
        return x + ref_attention(p, u, cfg, lower), None, None, None
    y, handed, probe = ref_ssm(p, u, cfg, lower, carried, probe_head)
    return x + y, None, handed, probe


def reference_score(cfg: dict, docs: list, weights: Callable, logits_at: Optional[list] = None,
                    lower: Optional[dict] = None, carry: Optional[str] = None,
                    probe_head: Optional[int] = None) -> dict:
    """Each document scored alone, in float32 at the highest matmul precision.

    docs: int arrays ``t_0 .. t_n`` (the end-of-document id included);
    ``weights(part)`` -> that part's float32 tensors (one part is held at a
    time); ``logits_at[i]``: positions of document i whose full logits to keep.
    Returns {"logprob": [log p(t_1..t_n)] a document, "logits": [[len(at), V]]
    a document, "dropped": visits a ``capacity`` control dropped, "router":
    a document's {"u", "experts", "gates"} [n_expert_layers, len(at), ..] at
    ``logits_at``: each expert layer's router input and what it chose,
    "scan": with ``probe_head`` a document's :func:`ref_ssm` probe of the
    first state-space layer, else {}}. ``lower`` names a control's departures:
    :func:`ref_ssm`'s, ``attn_gate_on``, ``relu_not_squared``,
    ``router_dtype``, ``capacity``; ``carry`` plants the fault of something
    that outlives its document: ``"state"``: each state-space layer starts a
    document from the last one's final state; ``"taps"``: its convolution
    reads the last one's last three tokens."""
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
        squared = not lower.pop("relu_not_squared", False)
        same = json.dumps(cfg, sort_keys=True, default=repr)  # programs are kept by what cfg says
        said = json.dumps(lower, sort_keys=True, default=repr)
        ssm_layers = [i for i, (mixer, _) in enumerate(plan) if mixer == "ssm"]
        probed = ssm_layers[0] if probe_head is not None and ssm_layers else None

        branch = {(kind, probe): _jitted(
            lambda p, x, c, kind=kind, probe=probe: ref_branch(kind, p, x, cfg, lower, c, probe),
            key=("nemotron_branch", same, kind, said, probe))
            for kind in set(plan) for probe in (None, probe_head)}
        for i, kind in enumerate(plan):
            p = weights(i)
            handed = None
            for j, x in enumerate(xs):
                n = len(docs[j]) - 1
                carried = None
                if carry and handed is not None:  # what the padded document before left: a fault either way
                    carried = (handed[0], None) if carry == "state" else (None, handed[1])
                xs[j], u, handed, scan = branch[kind, probe_head if i == probed else None](p, x, carried)
                if scan is not None:
                    out["scan"][j] = {name: np.asarray(a)[:n] for name, a in scan.items()}
                if u is None:
                    continue
                y, lost, (chosen, gates) = ref_moe_unit(p, u, cfg, router_dtype, capacity, squared)
                xs[j], out["dropped"] = xs[j] + y, out["dropped"] + lost
                for name, a in (("u", u), ("experts", chosen), ("gates", gates)):
                    out["router"][j][name].append(np.asarray(a)[where[j]])
            del p
        p = weights("head")
        eps = {"rms_norm_eps": cfg["layer_norm_epsilon"]}  # the name the sibling's head reads it under
        head = _jitted(lambda p, x, t: ref_head_block(p, x, t, eps), key=("nemotron_head", same))
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


def reference_weights(seed: int, cfg: dict, through: Optional[Callable] = None,
                      gated: bool = False) -> Callable:
    """``weights(part)`` for :func:`reference_score` from the seed, a part at a
    time (the placement found now, if no one has asked for it yet, not in
    the middle of a document's layers); ``gated``: with a gate matrix beside
    every expert's way up (the control ``gated_experts``)."""
    placement(seed, cfg)
    return lambda part: part_weights(seed, cfg, part, through, gated=gated)


def walk_head(x, b, c, dt, log_decay) -> np.ndarray:
    """One head's recurrence over one document, token by token from an empty
    state, in float64 on the host: x [n, p], b, c [n, s], dt and log_decay
    [n] -> y [n, p] (without the skip)."""
    x, b, c, dt, log_decay = (np.asarray(a, np.float64) for a in (x, b, c, dt, log_decay))
    state, out = np.zeros((x.shape[1], b.shape[1])), np.empty_like(x)
    for t in range(len(x)):
        state *= np.exp(log_decay[t])
        state += np.outer(x[t] * dt[t], b[t])
        out[t] = state @ c[t]
    return out


def probe_numbers(cfg: dict, seed: int, scans: list, routed: list) -> dict:
    """Two layers held to float64 ON THEIR OWN INPUTS, where the end-to-end
    numbers cannot tell a layer's precision from the bfloat16 activations
    around it. Both are plain numpy on the host: only the router's weights and
    bias, the seed's, come from the device.

    ``scan_state_gap``   a document's probe of the recurrence (``x``, ``o``
        [n, p], ``b``, ``c`` [n, s], ``dt``, ``log_decay`` [n]: one seeded head
        of the first state-space layer and the group it reads, as the program
        or a control computed it): ``o`` against :func:`walk_head` over the
        same inputs; root mean square over all documents, relative.
    ``router_gate_gap``  a document's ``u``, ``experts``, ``gates``
        [n_expert_layers, s, ..] at its sampled positions: the gates, bias and
        scale and all, against the float64 router's on the same ``u`` (the 6
        largest of ``sigmoid(u W_r) + b``, gates from the scores alone, times
        2.5), as the largest difference over the 128 experts; the 90th
        percentile over positions and layers (a maximum would hang on one
        near-tie)."""
    top_k = cfg["num_experts_per_tok"]
    err = norm = 0.0
    for scan in scans:
        if "o" not in scan:
            continue
        want = walk_head(*(scan[name] for name in ("x", "b", "c", "dt", "log_decay")))
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
    """What the state-space recurrence of ONE layer asks for ``tokens`` tokens,
    whatever form a kernel takes: a token and head decays a 64 x 128 state,
    adds a rank-one update and reads it against C (1 + 2 + 2 operations an
    element of the state); the operands as the mechanism has them: x at its
    heads and B and C at their 8 GROUPS in bfloat16, one float32 step and one
    float32 decay a head and token, the float32 output."""
    h, p, s, g, inner = ssm_widths(cfg)
    return {"flops": tokens * h * 5.0 * p * s,
            "bytes": tokens * (inner * 2.0 + 2 * g * s * 2.0 + 2 * h * 4.0 + inner * 4.0)}


def expert_needs(cfg: dict, visits: float) -> dict:
    """What the held experts of ONE layer ask for ``visits`` visits: TWO
    matrices a visit (up, down; no gate), every held expert's pair read once,
    a visit's row in and its result out in bfloat16."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"flops": visits * 4.0 * d * f,
            "bytes": cfg["n_routed_experts_held"] * 2 * d * f * 2.0 + 2.0 * visits * d * 2.0}


def needs(cfg: dict, batch: int, loop: str) -> dict:
    """The least a scoring step asks of the chip, for the roofline shares:
    {"flops", "bytes", "scopes": {scope: {"flops", "bytes"}}}.

    What the mathematics asks, not what the program does: the recurrence at
    its token-by-token count (:func:`scan_needs`), attention over each
    document's own triangle at 128 + 128 products a pair and query head, the
    experts by the visits the batch makes at two matrices a visit
    (:func:`expert_needs`), no work for pads, every weight read once a step,
    activations in bfloat16 once in and once out of a layer, the head's logits
    never stored. What the seed's rows held is the loop's to say:
    ``cfg["observed"]`` = {"tokens": scored positions a step, "triangle": sum
    over a step's documents of n (n + 1) / 2, "visits": visits to held experts
    a step and expert layer}."""
    seen = cfg["observed"]
    t, tri, visits = float(seen["tokens"]), float(seen["triangle"]), float(seen["visits"])
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hq, hkv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    h, _, s, g, inner = ssm_widths(cfg)
    fs = cfg["moe_shared_expert_intermediate_size"]
    plan = layer_plan(cfg)
    n_ssm, n_gqa = sum(m == "ssm" for m, _ in plan), sum(m == "gqa" for m, _ in plan)
    n_moe = sum(ffn == "moe" for _, ffn in plan)
    act = 2.0 * t * d * 2.0                                # a layer's rows in and out, bf16
    ssm_w = d * (2 * inner + 2 * g * s + h) + inner * d
    gqa_w = d * (hq * dh + 2 * hkv * dh) + hq * dh * d
    conv_cols = inner + 2 * g * s
    scan, experts = scan_needs(cfg, t), expert_needs(cfg, visits)
    scopes = {
        "tfr.embed": {"flops": 0.0, "bytes": act + 4.0 * t},
        "tfr.ssm_proj": {"flops": n_ssm * 2.0 * t * ssm_w, "bytes": n_ssm * (2.0 * ssm_w + act)},
        "tfr.ssm_conv": {"flops": n_ssm * 2.0 * t * conv_cols * cfg["conv_kernel"],
                         "bytes": n_ssm * 2.0 * t * conv_cols * 2.0},
        "tfr.ssm_scan": {"flops": n_ssm * scan["flops"], "bytes": n_ssm * scan["bytes"]},
        "tfr.gqa": {"flops": n_gqa * (2.0 * t * gqa_w + 4.0 * tri * hq * dh),
                    "bytes": n_gqa * (2.0 * gqa_w + act)},
        "tfr.moe_route": {"flops": n_moe * 2.0 * t * d * cfg["n_routed_experts"],
                          "bytes": n_moe * (2.0 * d * cfg["n_routed_experts"] + t * d * 2.0)},
        "tfr.moe_experts": {"flops": n_moe * experts["flops"], "bytes": n_moe * experts["bytes"]},
        "tfr.moe_shared": {"flops": n_moe * t * 4.0 * d * fs, "bytes": n_moe * (2 * d * fs * 2.0 + act)},
        "tfr.lm_head": {"flops": 2.0 * t * d * v, "bytes": 2.0 * d * v + t * d * 2.0 + 4.0 * t},
    }
    return {"flops": sum(scope["flops"] for scope in scopes.values()),
            "bytes": sum(scope["bytes"] for scope in scopes.values()), "scopes": scopes}
