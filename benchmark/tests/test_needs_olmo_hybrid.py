"""olmo_hybrid.needs() against FLOPs and bytes worked by hand for one small
shape, the published shape against the arithmetic of ISSUE 49 to the digit
(held against ``lm.pattern_param_shapes`` too), the recurrence's count and
operand bytes at the PUBLISHED widths whatever a kernel pads, and the
configuration file against the catalog's entry."""

import json
import os

from benchmark import run as bench_run
from benchmark.models import olmo_hybrid as model

CFG = {
    "hidden_size": 8, "vocab_size": 32, "num_hidden_layers": 4, "first_layer": 0, "intermediate_size": 12,
    "layer_types": ["linear_attention", "linear_attention", "linear_attention", "full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 4, "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 3, "linear_value_head_dim": 6, "linear_conv_kernel_dim": 4,
    # a step of 12 scored positions in two documents of 6
    "observed": {"tokens": 12.0, "triangle": 2 * 6 * 7 / 2},
}


def test_a_step_by_hand():
    t, tri, d = 12, 42, 8
    assert model.layer_plan(CFG) == ["gdn", "gdn", "gdn", "gqa"] and model.head_dim(CFG) == 2
    act = 2 * t * d * 2                                   # a layer's rows in and out, bf16
    keys, values = 2 * 3, 2 * 6
    gdn_w = d * (2 * keys + 2 * values) + 2 * d * 2 + values * d     # Wq Wk | Wv Wz | w_a w_b | Wo
    gqa_w = d * (8 + 8 + 8) + 8 * d
    # a token and head: 7 operations an element of a 3 x 6 state; q, k at 3 and v at 6 channels a head in
    # bf16, a float32 decay and beta a head, the float32 output at 6
    scan = (t * 2 * 7 * 3 * 6, t * (2 * keys * 2 + values * 2 + 2 * 2 * 4 + values * 4))
    assert model.scan_needs(CFG, t) == {"flops": float(scan[0]), "bytes": float(scan[1])}
    want = {
        "tfr.embed": (0, act + 4 * t),
        "tfr.gdn_proj": (3 * 2 * t * gdn_w, 3 * (2 * gdn_w + act)),
        "tfr.gdn_conv": (3 * 2 * t * (2 * keys + values) * 4, 3 * 2 * t * (2 * keys + values) * 2),
        "tfr.gdn_scan": (3 * scan[0], 3 * scan[1]),
        # a causal pair and head: 2 products for the score, 2 for the value, 2 channels each
        "tfr.gqa": (2 * t * gqa_w + 4 * tri * 4 * 2, 2 * gqa_w + act),
        "tfr.dense_ffn": (4 * t * 6 * d * 12, 4 * (3 * d * 12 * 2 + act)),
        "tfr.lm_head": (2 * t * d * 32, 2 * d * 32 + t * d * 2 + 4 * t),
    }
    got = model.needs(CFG, 2, "score_docs_dense")
    assert {k: (v["flops"], v["bytes"]) for k, v in got["scopes"].items()} == {
        k: (float(f), float(b)) for k, (f, b) in want.items()}
    assert got["flops"] == sum(f for f, _ in want.values())
    assert got["bytes"] == sum(b for _, b in want.values())


def published():
    with open(os.path.join(bench_run.HERE, "configs", "olmo_hybrid_7b_pp4.json")) as f:
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
    assert model.layer_plan(cfg) == ["gdn", "gdn", "gdn", "gqa"] * 2
    assert model.widths(cfg) == (30, 30, 96, 192) and model.head_dim(cfg) == 128
    ffn = ("dense.w_gate", "dense.w_up", "dense.w_down")
    assert count(cfg, 0, ("wq", "wk")) == 2 * 3840 * 2880 and count(cfg, 0, ("wv", "wz", "wo")) == 3 * 3840 * 5760
    assert count(cfg, 0, ("w_a", "w_beta")) == 2 * 3840 * 30
    assert count(cfg, 0, ("wq", "wk", "wv", "wz", "wo", "w_a", "w_beta")) == 88_704_000    # a delta-net mixer
    assert count(cfg, 0, ffn) == 3 * 3840 * 11008 == 126_812_160
    assert count(cfg, 3, ("wq", "wk", "wv", "wo")) == 4 * 3840 ** 2 == 58_982_400           # a full mixer
    mixer = ("wq", "wk", "wv", "wz", "wo", "w_a", "w_beta")
    counted = {"gdn": count(cfg, 0, mixer + ffn), "gqa": count(cfg, 3, mixer + ffn),
               "ends": count(cfg, "embed") + count(cfg, "head", ("head",))}
    assert counted == {"gdn": 215_516_160, "gqa": 185_794_560, "ends": 770_703_360}
    assert 3 * counted["gdn"] + counted["gqa"] == 832_343_040                                # a period
    counted["all"] = 6 * counted["gdn"] + 2 * counted["gqa"] + counted["ends"]
    assert counted["all"] == 2 * 832_343_040 + 770_703_360 == 2_435_389_440                  # this chip: 4.87 GB
    assert 8 * 832_343_040 + 770_703_360 == 7_429_447_680                                    # the model whole
    assert round((24 * counted["gdn"] + 8 * counted["gqa"]) / 32 / 1e6, 1) == 208.1          # the catalog's "about 208M" a layer
    # in float32 beside them: the taps, the norms' gains, A, dt_bias
    parts = ["embed", "head", *range(cfg["num_hidden_layers"])]
    whole = sum(count(cfg, part) for part in parts)
    small = sum(count(cfg, part, [n for n, (shape, *_) in model.weight_specs(cfg, part).items()
                                  if len(shape) < 2 or n.startswith("conv_")]) for part in parts)
    assert small == 6 * (4 * (2 * 2880 + 5760) + 2 * 30 + 192 + 2 * 3840) + 2 * (4 * 3840) + 3840 == 358_632
    assert whole - small == counted["all"] and 2 * counted["all"] + 4 * small == 4_872_213_408    # param_bytes
    # the cell's step: 16,384 tokens through one delta-net layer's recurrence at the PUBLISHED widths
    one = model.scan_needs(cfg, 16384.0)
    assert one["flops"] == 16384 * 30 * 7 * 96 * 192 == 63_417_876_480
    # q and k at 30 heads of 96 and v at 30 of 192 in bfloat16; a decay and a beta [30] float32; o float32
    assert one["bytes"] == 16384 * (2 * 2880 * 2 + 5760 * 2 + 2 * 30 * 4 + 5760 * 4) == 758_906_880
    # what the kernel's padded tiles would count instead (128 and 256 lanes): never the roofline's
    assert 16384 * 30 * 7 * 128 * 256 == 112_742_891_520
    cfg["observed"] = {"tokens": 16384.0, "triangle": 16384 * 16385 / 2}
    scopes = model.needs(cfg, 2, "score_docs_dense")["scopes"]
    assert scopes["tfr.gdn_scan"] == {"flops": 6 * one["flops"], "bytes": 6 * one["bytes"]}
    assert round(scopes["tfr.gdn_proj"]["flops"] / 1e12, 1) == 17.4
    assert round(scopes["tfr.dense_ffn"]["flops"] / 1e12, 1) == 33.2
    assert round(scopes["tfr.lm_head"]["flops"] / 1e12, 1) == 12.6
    assert round(scopes["tfr.gdn_scan"]["flops"] / 1e12, 1) == 0.4
    assert "tfr.moe_experts" not in scopes and "tfr.moe_route" not in scopes


def test_the_programs_parameters_are_the_counted_ones():
    """``lm.pattern_param_shapes`` of the program the file builds, tensor for tensor."""
    from tpu_tfrecord.models import lm

    cfg = published()
    pcfg = model.program(cfg, {"row_tokens": 8192})
    assert pcfg.layer_pattern == ("gdn", "gdn", "gdn", "gqa") * 2 and pcfg.ffn_pattern == ("dense",) * 8
    assert (pcfg.kda_heads, pcfg.gdn_key_heads, pcfg.kda_head_dim, pcfg.gdn_value_dim) == (30, 30, 96, 192)
    assert (pcfg.n_heads, pcfg.n_kv_heads, pcfg.head_dim, pcfg.gqa_gate) == (30, 30, 128, False)
    assert pcfg.qk_norm_whole and not pcfg.qk_norm and pcfg.branch_norms and not pcfg.pre_norms
    assert pcfg.gdn_neg_eigval and pcfg.gdn_gate == "silu" and pcfg.d_dense == 11008 and pcfg.kda_chunk == 64
    shapes = lm.pattern_param_shapes(pcfg)
    assert shapes["embed"][0] == (100352, 3840) and shapes["head"][0] == (3840, 100352)
    for i, layer in enumerate(shapes["layers"]):
        mine = {}
        for name, leaf in layer.items():
            if lm._is_shape(leaf):
                mine[name] = leaf[0]
            else:
                mine.update({f"{name}.{k}": v[0] for k, v in leaf.items()})
        assert mine == {name: tuple(spec[0]) for name, spec in model.weight_specs(cfg, i).items()}, i


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` of Olmo-Hybrid-7B, key for key; the cut is the depth alone."""
    period = ["linear_attention", "linear_attention", "linear_attention", "full_attention"]
    catalog = {
        "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
        "num_hidden_layers": 32, "num_attention_heads": 30, "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False, "rms_norm_eps": 1e-06,
        "tie_word_embeddings": False, "layer_types": period * 8, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    }
    cfg = published()
    differs = {k for k, v in catalog.items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers"} and cfg["num_hidden_layers"] == 8
    assert differs | {"dataset"} == set(cfg["reduced"])
    assert cfg["published"] == {"num_hidden_layers": 32} and cfg["left_out"] == [] and cfg["left_out_why"]
    for item in ("norm_placement", "qk_norm", "no_positions", "delta_net", "head_gate", "decay_laws"):
        assert item in cfg["assumed"], item
    for control in ("pre_norm_gdn", "per_head_qk_norm", "no_qk_norm", "rotary_on_full", "sigmoid_gate",
                    "beta_times_1", "scale_by_dv"):
        assert any(control in said for said in cfg["assumed"].values()), control
    assert {"deployment", "precision", "guarantees", "doc_length", "token_law", "program", "rehearsal"} <= set(cfg)
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "olmo_hybrid_7b_pp4")
    assert entry["reduced"] == list(cfg["reduced"]) == ["num_hidden_layers", "dataset"]
    assert entry["source"] in cfg["source"] and entry["file"] == "benchmark/configs/olmo_hybrid_7b_pp4.json"
    cell = next(w for w in bench["workloads"] if w["config"] == "olmo_hybrid_7b_pp4")
    assert cell == {**cell, "name": "olmo_hybrid_7b_pp4.score", "traffic": "score_docs_dense", "chips": 1}
