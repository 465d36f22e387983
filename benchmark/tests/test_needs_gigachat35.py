"""gigachat35.needs() against FLOPs and bytes worked by hand for one small
shape, the published shape against the arithmetic of ISSUE 41 (held against
``lm.pattern_param_shapes`` too), the recurrence's count and operand bytes,
the configuration file against the catalog's entry, and the placement."""

import json
import os

from benchmark import run as bench_run
from benchmark.models import gigachat35 as model

CFG = {
    "hidden_size": 8, "vocab_size": 32, "num_hidden_layers": 4, "first_k_dense_replace": 1,
    "first_layer": 2, "full_attention_layers": [3, 7], "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 2, "linear_conv_kernel_dim": 4,
    "num_attention_heads": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2,
    "kv_lora_rank": 4, "q_lora_rank": 6, "intermediate_size": 12, "moe_intermediate_size": 3,
    "n_routed_experts": 10, "n_routed_experts_held": 5, "n_shared_experts": 1,
    # a step of 12 scored positions in two documents of 6, 9 visits to held experts a layer
    "observed": {"tokens": 12.0, "triangle": 2 * 6 * 7 / 2, "visits": 9.0},
}


def test_a_step_by_hand():
    t, tri, visits, d = 12, 42, 9, 8
    assert model.layer_plan(CFG) == [("gdn", "dense"), ("mla", "moe"), ("gdn", "moe"), ("gdn", "moe")]
    act = 2 * t * d * 2                                   # a layer's rows in and out, bf16
    gdn_w = d * (4 + 4 + 8 + 8) + 2 * d * 4 + 8 * d       # Wq, Wk (2 heads), Wv, Wz (4), w_a, w_b, Wo
    mla_w = d * 6 + 6 * 4 * 4 + d * (4 + 2) + 4 * 4 * 4 + d * 8 + 8 * d   # Wqa, Wqb, Wkva, Wkvb, Wg, Wo
    conv_cols = 4 + 4 + 8
    # a token and value head: 7 operations an element of a 2 x 2 state; q, k at 2 heads and v at 4
    # in bf16, a float32 decay and beta a value head, the float32 output
    scan = (t * 4 * 7 * 2 * 2, t * (2 * 2 * 2 * 2 + 4 * 2 * 2 + 2 * 4 * 4 + 4 * 2 * 4))
    assert model.scan_needs(CFG, t) == {"flops": float(scan[0]), "bytes": float(scan[1])}
    want = {
        "tfr.embed": (0, act + 4 * t),
        "tfr.gdn_proj": (3 * 2 * t * gdn_w, 3 * (2 * gdn_w + act)),
        "tfr.gdn_conv": (3 * 2 * t * conv_cols * 4, 3 * 2 * t * conv_cols * 2),
        "tfr.gdn_scan": (3 * scan[0], 3 * scan[1]),
        "tfr.mla_proj": (2 * t * mla_w, 2 * mla_w + act),
        # a causal pair and head: 2 + 2 products for the score, 2 for the value
        "tfr.mla_attn": (2 * tri * 4 * 6, 2 * t * (4 * 4 + 4 * 2 + 2 + 2 * 4 * 2)),
        "tfr.dense_ffn": (t * 6 * d * 12, 3 * d * 12 * 2 + act),
        "tfr.moe_route": (3 * 2 * t * d * 10, 3 * (2 * d * 10 + t * d * 2)),
        "tfr.moe_experts": (3 * visits * 6 * d * 3, 3 * (5 * 3 * d * 3 * 2 + 2 * visits * d * 2)),
        "tfr.moe_shared": (3 * t * 6 * d * 3, 3 * (3 * d * 3 * 2 + act)),
        "tfr.lm_head": (2 * t * d * 32, 2 * d * 32 + t * d * 2 + 4 * t),
    }
    got = model.needs(CFG, 2, "score_docs")
    assert {k: (v["flops"], v["bytes"]) for k, v in got["scopes"].items()} == {
        k: (float(f), float(b)) for k, (f, b) in want.items()}
    assert got["flops"] == sum(f for f, _ in want.values())
    assert got["bytes"] == sum(b for _, b in want.values())


def published():
    with open(os.path.join(bench_run.HERE, "configs", "gigachat35_ep16.json")) as f:
        return json.load(f)


def count(cfg, part, only=None, matrices=False):
    total = 0
    for name, (shape, *_) in model.weight_specs(cfg, part).items():
        if (only is None or name in only) and (len(shape) >= 2 or not matrices) \
                and not (matrices and name.startswith("conv_")):
            n = 1
            for s in shape:
                n *= s
            total += n
    return total


def test_the_published_shape_is_what_the_issue_counted():
    cfg = published()
    assert model.layer_plan(cfg) == [("gdn", "dense"), ("mla", "moe"), ("gdn", "moe"), ("gdn", "moe"),
                                     ("gdn", "moe")]
    assert count(cfg, 0, ("wq", "wk", "wv", "wz", "w_a", "w_beta", "wo")) == 235_798_528
    assert count(cfg, 1, ("wq_a", "wq_b", "wkv_a", "wkv_b", "wg", "wo")) == 159_842_304
    assert count(cfg, 0, ("dense.w_gate", "dense.w_up", "dense.w_down")) == 396_361_728
    assert count(cfg, 1, ("w_gate",)) * 3 == 16 * 44_040_192
    assert count(cfg, 1, ("w_gate", "w_up", "w_down", "shared.w_gate", "shared.w_up", "shared.w_down",
                          "router")) == 750_518_272
    assert 256 * 44_040_192 == 11_274_289_152                # an expert layer WHOLE: 22.5 GB
    assert count(cfg, "embed") + count(cfg, "head", ("head",)) == 229_834_752
    parts = ["embed", "head", *range(cfg["num_hidden_layers"])]
    whole = sum(count(cfg, part, matrices=True) for part in parts)
    assert whole == 4_731_305_984 and 2 * whole == 9_462_611_968
    # on top, in float32: the taps (4 x (2 x 4,096 + 8,192) a delta-net layer), the norms, the
    # decays' vectors and the routers' biases: what param_bytes adds to the matrices
    taps = 4 * 4 * (2 * 4096 + 8192)
    vectors = sum(count(cfg, part) for part in parts) - whole - taps
    assert taps == 262_144 and vectors == 154_624
    assert 2 * whole + 4 * (taps + vectors) == 9_464_279_040
    # the cell's step: 16,384 tokens, the even share of the visits, the recurrence a layer
    assert 16384 * 8 * 16 // 256 == 8_192
    one = model.scan_needs(cfg, 16384.0)
    assert one["flops"] == 16384 * 64 * 7 * 128 * 128 == 120_259_084_288
    # q and k at 32 heads, v at 64, bfloat16; a decay and a beta [64] float32; the output float32
    assert one["bytes"] == 16384 * (2 * 32 * 128 * 2 + 64 * 128 * 2 + 2 * 64 * 4 + 64 * 128 * 4) == 1_082_130_432
    # what the broadcasts would add: q, k copied to 64 heads and a decay of v's shape, all float32
    assert 16384 * 64 * 128 * 4 * 4 == 2_147_483_648
    cfg["observed"] = {"tokens": 16384.0, "triangle": 16384 * 16385 / 2, "visits": 8192.0}
    scopes = model.needs(cfg, 2, "score_docs")["scopes"]
    assert scopes["tfr.gdn_scan"] == {"flops": 4 * one["flops"], "bytes": 4 * one["bytes"]}
    assert round(scopes["tfr.gdn_proj"]["flops"] / 1e12, 1) == 30.9
    assert round(scopes["tfr.dense_ffn"]["flops"] / 1e12, 1) == 13.0
    assert round(scopes["tfr.moe_shared"]["flops"] / 1e12, 1) == 5.8
    assert round(scopes["tfr.mla_proj"]["flops"] / 1e12, 1) == 5.2
    assert round(scopes["tfr.lm_head"]["flops"] / 1e12, 1) == 3.8
    assert round(scopes["tfr.moe_experts"]["flops"] / 1e12, 1) == 2.9


def test_the_programs_parameters_are_the_counted_ones():
    """``lm.pattern_param_shapes`` of the program the file builds, tensor for tensor."""
    from tpu_tfrecord.models import lm

    cfg = published()
    pcfg = model.program(cfg, {"row_tokens": 8192})
    assert pcfg.layer_pattern == ("gdn", "mla", "gdn", "gdn", "gdn")
    assert (pcfg.kda_heads, pcfg.gdn_key_heads, pcfg.kda_head_dim) == (64, 32, 128)
    assert pcfg.centred_norms and pcfg.branch_norms and pcfg.attn_gate and pcfg.swiglu_limit == 10.0
    shapes = lm.pattern_param_shapes(pcfg)
    assert shapes["embed"][0] == (16032, 7168) and shapes["head"][0] == (7168, 16032)
    for i, layer in enumerate(shapes["layers"]):
        mine = {}
        for name, leaf in layer.items():
            if lm._is_shape(leaf):
                mine[name] = leaf[0]
            else:
                mine.update({f"{name}.{k}": v[0] for k, v in leaf.items()})
        assert mine == {name: tuple(spec[0]) for name, spec in model.weight_specs(cfg, i).items()}, i


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` of GigaChat3.5-432B-A28B, key for key; the cut is
    the depth, the leading dense layers and the vocabulary."""
    catalog = {
        "vocab_size": 128256, "max_position_embeddings": 262144, "hidden_size": 7168,
        "intermediate_size": 18432, "moe_intermediate_size": 2048, "num_hidden_layers": 40,
        "nextn_is_sparse": False, "num_attention_heads": 64, "n_shared_experts": 1,
        "n_routed_experts": 256, "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128, "qk_head_dim": 192,
        "n_group": 1, "topk_group": 1, "num_experts_per_tok": 8, "first_k_dense_replace": 3,
        "norm_topk_prob": True, "rope_interleave": True, "num_key_value_heads": 64, "hidden_act": "silu",
        "rms_norm_eps": 1e-06, "rope_theta": 100000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 32768, "type": "yarn"},
        "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post",
        "layernorm_gating_weight": 2, "gated_attention": True, "use_shared_expert_sigmoid": False,
        "use_mla_scaling_factor": True, "linear_attention_type": "GigaChat35GatedDeltaNet",
        "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39], "linear_key_head_dim": 128,
        "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32,
        "linear_num_value_heads": 64, "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
        "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-06, "swiglu_limit": 10,
        "tie_word_embeddings": False, "num_nextn_predict_layers": 2, "model_type": "gigachat3_5",
        "tf_legacy_loss": False,
    }
    cfg = published()
    differs = {k for k, v in catalog.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "first_k_dense_replace", "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 40, "first_k_dense_replace": 3,
                                "n_routed_experts": 256, "vocab_size": 128256}
    assert set(cfg["reduced"]) == {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts_held",
                                   "vocab_size", "dataset"}
    assert cfg["left_out"] == ["multi_token_prediction"] and cfg["first_layer"] == 2
    assert cfg["n_routed_experts_held"] == 16 and cfg["held_offset"] == 0
    assert cfg["vocab_size"] * 8 == 128256
    assert (cfg["doc_length"]["mu"], cfg["doc_length"]["sigma"], cfg["doc_length"]["min"],
            cfg["doc_length"]["max"]) == (6.5, 1.2, 16, 8192)
    assert {"norm_gain", "sandwich_norms", "router", "attention_gate", "mla_scaling", "delta_net",
            "decay_laws", "swiglu_limit", "rotary_pairs", "expert_placement", "init"} <= set(cfg["assumed"])
    assert "deployment" in cfg and "precision" in cfg and "16 chips share each layer" in cfg["deployment"]


def test_the_cell_is_the_issues():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["gigachat35_ep16.score"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("gigachat35_ep16", "score_docs_gdn", 1)
    assert len(bench["workloads"]) >= 7 and len(bench["configs"]) >= 6
    entry = {c["name"]: c for c in bench["configs"]}["gigachat35_ep16"]
    assert entry["source"] == "https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/main/config.json"
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts_held",
                                "vocab_size", "dataset"]
    mix, older = (bench_run.load_json("traffic", name + ".json") for name in ("score_docs_gdn", "score_docs"))
    changed = {k for k in older if k not in ("limits", "limit_reasons", "rehearsal") and mix[k] != older[k]}
    assert changed <= {"verify_batches"} and set(mix) == set(older)
    assert (mix["batch"], mix["row_tokens"], mix["shards"], mix["docs_per_shard"]) == (2, 8192, 8, 1024)
    assert (mix["packing"], mix["reader_batch"], mix["in_flight"], mix["warmup_steps"]) == ("best_fit", 16, 2, 3)
    assert mix["trace_seconds"] == 8.0
    assert {"scan_state_gap", "router_gate_gap", "boundary_median_gap"} <= set(mix["limits"])
    assert set(mix["limits"]) - set(mix["limit_reasons"]) <= {
        "repeat_gap", "tokens_altered", "docs_missing", "docs_doubled", "segments_wrong",
        "moe_visits_dropped", "steps_not_finite"}
    # the gdn metrics name this mix and no other; the three shared lists grew by this cell alone
    named = {m["name"]: m for m in bench["per_layer"]}
    for name in ("step_unscoped_pct", "h2d_blocked_pct", "h2d_ms"):
        assert named[name]["workloads"][-1] == "gigachat35_ep16.score"
    mine = [m for m in bench["per_layer"] if m.get("workloads") == ["gigachat35_ep16.score"]]
    assert {m["name"] for m in mine} == {
        "step_ms.gdn", "roofline_pct.gdn_scan", "kernel_layers.gdn", "step_ms.mla.gdn",
        "step_ms.dense_ffn.gdn", "step_ms.moe_route.gdn", "step_ms.moe_experts.gdn", "step_ms.lm_head.gdn",
        "step_ms.all_once.gdn", "roofline_pct.mla_attn.gdn", "roofline_pct.moe_experts.gdn",
        "pack_tokens_busy_pct.gdn", "decode_blocked_pct.docs.gdn", "pack_blocked_pct.docs.gdn"}
    for m in mine:
        spec = bench_run.load_json("layer_metrics", m["name"] + ".json")
        assert spec["mixes"] == ["score_docs_gdn"] and spec["unit"] == m["unit"] and spec["layer"] == m["layer"]


def test_the_placement_is_a_renaming_and_every_holder_has_it():
    """At rehearsal size: :func:`placement` reorders the router's columns and
    nothing else, the same order on every call; the program's tree and
    ``part_weights`` (the reference's and the probes') hold the router in that
    order; on the observed row no expert held is visited past the cap where
    there are enough light ones."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from tpu_tfrecord.models import lm

    cfg = bench_run.at_rehearsal_size(published())
    seed = 2 ** 31 + 77
    params = model.program_params(seed, cfg)
    orders = model.placement(seed, {**cfg, "observed": {"tokens": 1.0}})   # the loop's note aside
    assert sorted(orders) == [1, 2, 3, 4]
    row_tokens = cfg["placement"]["row_tokens"]
    pcfg = model.program(cfg, {"row_tokens": row_tokens})
    tokens, segs = model.observed_row(seed, cfg, row_tokens)
    visits = np.asarray(jax.jit(lambda p, t, s: lm.pattern_hidden(p, t, s, pcfg)[1])(
        params, jnp.asarray(tokens), jnp.asarray(segs)))       # as the placement observed them
    for nth, (i, order) in enumerate(sorted(orders.items())):
        assert sorted(order) == list(range(cfg["n_routed_experts"]))
        raw = model._raw_weights(seed, cfg, i, names=("router", "router_bias"))
        placed = model.part_weights(seed, cfg, i, names=("router", "router_bias"))
        np.testing.assert_array_equal(np.asarray(placed["router"]), np.asarray(raw["router"])[:, order])
        np.testing.assert_array_equal(np.asarray(placed["router_bias"]),
                                      np.asarray(raw["router_bias"])[order])
        np.testing.assert_array_equal(np.asarray(params["layers"][i]["router"], np.float32),
                                      np.asarray(placed["router"]))
        assert 1 <= visits[nth].min() and visits[nth].max() <= cfg["placement"]["cap"], visits[nth]
    again = model.program_params(seed, cfg)
    for i in orders:
        np.testing.assert_array_equal(np.asarray(again["layers"][i]["router"], np.float32),
                                      np.asarray(params["layers"][i]["router"], np.float32))
