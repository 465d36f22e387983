"""deepseek_v32.needs() against FLOPs and bytes worked by hand for one small
shape, the published shape against the arithmetic of ISSUE 33, and the
configuration file against the catalog's entry."""

import json
import os

from benchmark import run as bench_run
from benchmark.models import deepseek_v32 as model

CFG = {
    "hidden_size": 8, "vocab_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 2, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 4,
    "kv_lora_rank": 6, "q_lora_rank": 5, "index_n_heads": 3, "index_head_dim": 4, "index_topk": 3,
    "intermediate_size": 12, "moe_intermediate_size": 3, "n_routed_experts": 10,
    "n_routed_experts_held": 5, "n_shared_experts": 1,
    # a step of 12 scored positions in two documents of 6, 9 visits to held experts a layer
    "observed": {"tokens": 12.0, "triangle": 2 * 6 * 7 / 2, "visits": 9.0},
}


def test_a_step_by_hand():
    t, tri, visits, d = 12, 42, 9, 8
    picked = 2 * (1 + 2 + 3 + 3 + 3 + 3)                  # a query keeps min(position + 1, 3) keys
    assert model.selected_pairs(t, tri, 3) == picked
    act = 2 * t * d * 2                                   # a layer's rows in and out, bf16
    mla_w = d * 5 + 5 * 2 * 6 + d * (6 + 2) + 6 * 2 * 8 + 2 * 4 * d   # q down, q up, latent, expansion, out
    dsa_w = 5 * 3 * 4 + d * 4 + d * 3                     # W^I_q, W^I_k, W^I_w
    index_in = t * (3 * 4 * 2 + 4 * 2 + 3 * 4)            # q^I and k^I bf16, w float32
    want = {
        "tfr.embed": (0, act + 4 * t),
        "tfr.mla_proj": (3 * 2 * t * mla_w, 3 * (2 * mla_w + act)),
        "tfr.dsa_proj": (3 * 2 * t * dsa_w, 3 * (2 * dsa_w + t * (d + 5) * 2 + index_in)),
        # every causal pair at 3 heads of 4; the mask out as bits
        "tfr.dsa_index": (3 * 2 * tri * 3 * 4, 3 * (index_in + tri / 8)),
        # a kept pair and head: 6 products for the score, 4 for the value
        "tfr.mla_attn": (3 * 2 * picked * 2 * (6 + 4), 3 * (2 * t * (12 + 8 + 2 + 8 + 8) + tri / 8)),
        "tfr.dense_ffn": (t * 6 * d * 12, 3 * d * 12 * 2 + act),
        "tfr.moe_route": (2 * 2 * t * d * 10, 2 * (2 * d * 10 + t * d * 2)),
        "tfr.moe_experts": (2 * visits * 6 * d * 3, 2 * (5 * 3 * d * 3 * 2 + 2 * visits * d * 2)),
        "tfr.moe_shared": (2 * t * 6 * d * 3, 2 * (3 * d * 3 * 2 + act)),
        "tfr.lm_head": (2 * t * d * 32, 2 * d * 32 + t * d * 2 + 4 * t),
    }
    got = model.needs(CFG, 1, "score_docs")
    assert {k: (v["flops"], v["bytes"]) for k, v in got["scopes"].items()} == {
        k: (float(f), float(b)) for k, (f, b) in want.items()}
    assert got["flops"] == sum(f for f, _ in want.values())
    assert got["bytes"] == sum(b for _, b in want.values())


def test_short_documents_keep_every_pair():
    assert model.selected_pairs(10.0, 2 * 5 * 6 / 2, 2048) == 30.0


def published():
    with open(os.path.join(bench_run.HERE, "configs", "deepseek_v32_exp_ep16.json")) as f:
        return json.load(f)


def count(cfg, part, only=None):
    total = 0
    for name, (shape, *_) in model.weight_specs(cfg, part).items():
        if only is None or name in only:
            n = 1
            for s in shape:
                n *= s
            total += n
    return total


def test_the_published_shape_is_what_the_issue_counted():
    cfg = published()
    index = ("wq_idx", "wk_idx", "k_idx_norm", "k_idx_bias", "w_idx")
    mixer = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
    assert count(cfg, 0, mixer) == 187_114_496 and count(cfg, 0, index) == 13_959_424
    assert count(cfg, 0, ("ffn_norm", "dense.w_gate", "dense.w_up", "dense.w_down")) == 396_368_896
    assert count(cfg, 1, ("w_gate",)) * 3 == 16 * 44_040_192
    assert count(cfg, 1, ("moe_norm", "router", "router_bias", "shared.w_gate", "shared.w_up",
                          "shared.w_down")) == 45_882_624
    assert count(cfg, 0) == 597_442_816 and count(cfg, 1) == 951_599_616
    assert count(cfg, "embed") + count(cfg, "head") == 231_676_928
    whole = sum(count(cfg, part) for part in ["embed", "head", *range(cfg["num_hidden_layers"])])
    assert whole == 4_635_518_208 and 2 * whole == 9_271_036_416
    assert model.ffn_kinds(cfg) == ["dense"] + ["moe"] * 4
    # the cell's step: one document of 16,384 tokens
    cfg["observed"] = {"tokens": 16384.0, "triangle": 16384 * 16385 / 2, "visits": 8192.0}
    scopes = model.needs(cfg, 1, "score_docs")["scopes"]
    assert round(scopes["tfr.dsa_index"]["flops"] / 1e12, 1) == 11.0
    assert round(scopes["tfr.mla_attn"]["flops"] / 5e12, 2) == 2.58
    assert round(scopes["tfr.mla_proj"]["flops"] / 1e12, 1) == 30.7


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` of DeepSeek-V3.2-Exp, key for key; the cut is
    the depth, the leading dense layers and the vocabulary."""
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu",
        "hidden_size": 7168, "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
        "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 163840,
        "model_type": "deepseek_v32", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
        "n_group": 8, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 128, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                         "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
        "v_head_dim": 128, "vocab_size": 129280,
    }
    cfg = published()
    differs = {k for k, v in catalog.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "first_k_dense_replace", "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                                "n_routed_experts": 256, "vocab_size": 129280}
    assert set(cfg["reduced"]) == {"num_hidden_layers", "first_k_dense_replace",
                                   "n_routed_experts_held", "vocab_size", "dataset"}
    assert cfg["left_out"] == ["multi_token_prediction"]
    assert cfg["n_routed_experts_held"] == 16 and cfg["held_offset"] == 0
    assert cfg["doc_length"]["min"] == cfg["doc_length"]["max"] == 16384


def test_the_cell_is_the_issues():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["deepseek_v32_exp_ep16.score"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek_v32_exp_ep16", "score_docs_dsa", 1)
    mix, older = (bench_run.load_json("traffic", name + ".json")
                  for name in ("score_docs_dsa", "score_docs"))
    changed = {k for k in older if k not in ("limits", "limit_reasons", "rehearsal", "arrivals")
               and mix[k] != older[k]}
    assert changed == {"batch", "row_tokens", "docs_per_shard", "warmup_steps", "verify_batches",
                       "trace_seconds"}
    assert (mix["batch"], mix["row_tokens"], mix["shards"], mix["docs_per_shard"]) == (1, 16384, 8, 64)
    assert (mix["warmup_steps"], mix["verify_batches"], mix["trace_seconds"]) == (2, 2, 10.0)
    assert set(mix) == set(older) and "scan_state_gap" not in mix["limits"]
    assert set(mix["limits"]) - set(mix["limit_reasons"]) <= {
        "repeat_gap", "tokens_altered", "docs_missing", "docs_doubled", "segments_wrong",
        "moe_visits_dropped", "steps_not_finite", "index_keys_short"}


def test_the_placement_is_one_groups_names_and_every_holder_has_it():
    """At rehearsal size: :func:`placement` reorders the held experts' routing
    group and nothing else, the same order on every call; the program's tree,
    ``part_weights`` (the reference's and the probes') hold the router in that
    order; on the observed row the experts held are visited as it counted:
    none over a tile where the group has enough light ones."""
    import jax.numpy as jnp
    import numpy as np
    from tpu_tfrecord.models import lm

    cfg = bench_run.at_rehearsal_size(published())
    seed, size = 2 ** 31 + 77, cfg["n_routed_experts"] // cfg["n_group"]
    params = model.program_params(seed, cfg)
    orders = model.placement(seed, {**cfg, "observed": {"tokens": 1.0}})   # the loop's note aside
    assert sorted(orders) == [1, 2]
    pcfg = model.program(cfg, {"row_tokens": cfg["doc_length"]["max"]})
    tokens, segs = model.observed_row(seed, cfg, cfg["doc_length"]["max"])
    assert (tokens[segs == 0] == 0).all() and (segs[0, :-1] != 0).sum() > 64
    import jax

    visits = np.asarray(jax.jit(lambda p, t, s: lm.pattern_hidden(p, t, s, pcfg)[1])(
        params, jnp.asarray(tokens), jnp.asarray(segs)))       # as the placement observed them
    for nth, (i, order) in enumerate(sorted(orders.items())):
        assert sorted(order) == list(range(cfg["n_routed_experts"]))
        assert (order[size:] == np.arange(size, cfg["n_routed_experts"])).all()
        raw = model._raw_weights(seed, cfg, i, names=("router", "router_bias"))
        placed = model.part_weights(seed, cfg, i, names=("router", "router_bias"))
        np.testing.assert_array_equal(np.asarray(placed["router"]), np.asarray(raw["router"])[:, order])
        np.testing.assert_array_equal(np.asarray(placed["router_bias"]),
                                      np.asarray(raw["router_bias"])[order])
        np.testing.assert_array_equal(np.asarray(params["layers"][i]["router"], np.float32),
                                      np.asarray(placed["router"]))
        tile = pcfg.expert_tile
        assert visits[nth].min() >= 1 and visits[nth].max() <= tile - tile // 16, visits[nth]
    again = model.program_params(seed, cfg)
    for i in orders:
        np.testing.assert_array_equal(np.asarray(again["layers"][i]["router"], np.float32),
                                      np.asarray(params["layers"][i]["router"], np.float32))


def test_the_placement_runs_the_program_as_it_is_run_whoever_asks_first(monkeypatch):
    """Asked for from inside the reference's ``default_matmul_precision("highest")``
    (a controls run does), the placement still traces the program without it:
    on the chip the selection kernel's bfloat16 products cannot be compiled in
    float32 precision. And it finds the order it finds when asked outside."""
    import jax
    import numpy as np
    from tpu_tfrecord.models import lm

    cfg = bench_run.at_rehearsal_size(published())
    seed, seen, plain = 2 ** 31 + 78, [], lm.pattern_hidden

    def watched(*args, **kw):
        seen.append(jax.config.jax_default_matmul_precision)
        return plain(*args, **kw)

    monkeypatch.setattr(lm, "pattern_hidden", watched)
    model._PLACED.clear()
    with jax.default_matmul_precision("highest"):
        weights = model.reference_weights(seed, cfg)
        inside = {i: np.array(o) for i, o in model.placement(seed, cfg).items()}
        assert weights(1)["router"].shape == (cfg["hidden_size"], cfg["n_routed_experts"])
    assert seen and set(seen) == {None}
    model._PLACED.clear()
    outside = model.placement(seed, cfg)
    assert sorted(inside) == sorted(outside) == [1, 2]
    for i in inside:
        np.testing.assert_array_equal(inside[i], outside[i])
