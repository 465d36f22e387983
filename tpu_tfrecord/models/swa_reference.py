"""The plain reference of the sandwich-norm decoder with window and full
attention mixed (``models.lm.score`` with ``swa`` and ``gqa`` mixers,
``qk_norm``, ``branch_norms``, ``embed_scale``, leading dense layers and a
biased router): the forward pass in straightforward ``jax.numpy`` and
float32, with no kernels, no packing and no ``segment_ids`` — each document
scored alone from position 0, the mask written out (a block of queries of
one key-value head against every key at a time, so that 32,768 tokens fit),
every held expert by a loop, the head's logits a block of rows at a time.
It shares norms, the gated unit and the buckets with ``pattern_reference``
and the rotary turn, the biased router's expert layer and the head with
``mla_reference``, and calls nothing else in ``tpu_tfrecord.models``;
``benchmark/models/trinity_large.py`` carries a copy (the benchmark's files
stand alone), and ``tests/test_swa_lm.py`` holds the two to each other line
for line.

It reads a configuration with the published names of the AFMoE family
(``layer_types``, ``sliding_window``, ``num_dense_layers``, ``num_experts``,
``route_scale``, ...; ``first_layer``: which published layer the first one
here is; ``n_routed_experts_held`` + ``held_offset``: the share of the
experts computed here) and flat weight names (``shared.w_gate``,
``dense.w_gate``). For ONE document, with ``x`` the residual stream:

    x0     = embed[tokens] * sqrt(hidden)
    mixer  : u = rms(x; w_in); q, k, v, g = u Wq, u Wk, u Wv, u Wg;
             q = rms(q; w_qn), k = rms(k; w_kn) over a head's columns, one
             weight for all heads; in a sliding layer rope(q), rope(k) over the
             whole head by the token's index in the document; softmax(q . k
             / sqrt(head), s <= t, in a sliding layer t - s < window) v;
             y = (att * sigmoid(g)) Wo;  x = x + rms(y; w_post_attn)
    dense  : W_down(silu(W_gate u2) * W_up u2), u2 = rms(x; w_pre_mlp)
    moe    : s = sigmoid(u2 W_r) over all experts; the top-k of s + b; gates
             s_e / sum of the chosen s, times ``route_scale``; shared(u2) +
             sum of gate_e expert_e(u2) over the chosen experts held
    either : x = x + rms(m; w_post_mlp)

Departures from the published modelling code: the rotary pairs are (i, i +
head/2) as written there for this family (no interleaving); norm gains are
seeded about one where "depth-scaled" names how trained gains were
initialised; the router's bias is whatever the weights hold.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

from tpu_tfrecord.models.mla_reference import HEAD_ROWS, ref_head_block, ref_moe_biased, ref_rope
from tpu_tfrecord.models.pattern_reference import _bucket, _jitted, ref_ffn, ref_norm


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
