"""nemotron_h.needs() against FLOPs and bytes worked by hand for one small
shape, the published shape against the arithmetic of ISSUE 46 (held against
``lm.pattern_param_shapes`` too), the recurrence's count and operand bytes,
two matrices a visit, and the configuration file against the catalog's entry."""

import json
import os

from benchmark import run as bench_run
from benchmark.models import nemotron_h as model

CFG = {
    "hidden_size": 8, "vocab_size": 32, "num_hidden_layers": 4, "first_layer": 0,
    "hybrid_override_pattern": "ME*M", "mamba_num_heads": 4, "mamba_head_dim": 2, "ssm_state_size": 3,
    "n_groups": 2, "conv_kernel": 4, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
    "moe_intermediate_size": 3, "moe_shared_expert_intermediate_size": 6, "n_routed_experts": 10,
    "n_routed_experts_held": 5, "n_shared_experts": 1,
    # a step of 12 scored positions in two documents of 6, 9 visits to held experts a layer
    "observed": {"tokens": 12.0, "triangle": 2 * 6 * 7 / 2, "visits": 9.0},
}


def test_a_step_by_hand():
    t, tri, visits, d = 12, 42, 9, 8
    assert model.layer_plan(CFG) == [("ssm", "none"), ("none", "moe"), ("gqa", "none"), ("ssm", "none")]
    act = 2 * t * d * 2                                   # a layer's rows in and out, bf16
    inner, bc = 4 * 2, 2 * 3
    ssm_w = d * (2 * inner + 2 * bc + 4) + inner * d      # W_in [z | x | B | C | dt], W_out
    gqa_w = d * (8 + 4 + 4) + 8 * d                       # Wq (4 heads of 2), Wk, Wv (2 heads), Wo
    # a token and head: 5 operations an element of a 2 x 3 state; x at 4 heads and B, C at 2 GROUPS
    # in bf16, a float32 step and decay a head, the float32 output
    scan = (t * 4 * 5 * 2 * 3, t * (inner * 2 + 2 * bc * 2 + 2 * 4 * 4 + inner * 4))
    assert model.scan_needs(CFG, t) == {"flops": float(scan[0]), "bytes": float(scan[1])}
    # TWO matrices a visit: up and down
    experts = (visits * 4 * d * 3, 5 * 2 * d * 3 * 2 + 2 * visits * d * 2)
    assert model.expert_needs(CFG, visits) == {"flops": float(experts[0]), "bytes": float(experts[1])}
    want = {
        "tfr.embed": (0, act + 4 * t),
        "tfr.ssm_proj": (2 * 2 * t * ssm_w, 2 * (2 * ssm_w + act)),
        "tfr.ssm_conv": (2 * 2 * t * (inner + 2 * bc) * 4, 2 * 2 * t * (inner + 2 * bc) * 2),
        "tfr.ssm_scan": (2 * scan[0], 2 * scan[1]),
        # a causal pair and query head: 2 products for the score, 2 for the value
        "tfr.gqa": (2 * t * gqa_w + 4 * tri * 4 * 2, 2 * gqa_w + act),
        "tfr.moe_route": (2 * t * d * 10, 2 * d * 10 + t * d * 2),
        "tfr.moe_experts": experts,
        "tfr.moe_shared": (t * 4 * d * 6, 2 * d * 6 * 2 + act),
        "tfr.lm_head": (2 * t * d * 32, 2 * d * 32 + t * d * 2 + 4 * t),
    }
    got = model.needs(CFG, 2, "score_docs")
    assert {k: (v["flops"], v["bytes"]) for k, v in got["scopes"].items()} == {
        k: (float(f), float(b)) for k, (f, b) in want.items()}
    assert got["flops"] == sum(f for f, _ in want.values())
    assert got["bytes"] == sum(b for _, b in want.values())


def published():
    with open(os.path.join(bench_run.HERE, "configs", "nemotron_twotower_ep2.json")) as f:
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
    plan = model.layer_plan(cfg)
    assert "".join({"ssm": "M", "gqa": "*", "none": "E"}[mixer] for mixer, _ in plan) == "MEMEM*EMEMEM*"
    assert [ffn for _, ffn in plan] == ["moe" if mixer == "none" else "none" for mixer, _ in plan]
    assert count(cfg, 0, ("w_in",)) == 2688 * 10_304 and count(cfg, 0, ("wo",)) == 4096 * 2688
    assert count(cfg, 0, ("conv_x", "conv_bias")) == 5 * 6144 and count(cfg, 0, ("a_log", "d_skip", "dt_bias")) == 3 * 64
    assert count(cfg, 0) == 38_744_896                    # a state-space layer
    assert count(cfg, 5) == 23_399_040                    # a softmax layer: Wq, Wo 2688 x 4096, Wk, Wv 2688 x 256
    assert count(cfg, 1, ("w_up", "w_down")) == 64 * 9_977_856 and 2 * 2688 * 1856 == 9_977_856
    assert count(cfg, 1, ("shared.w_up", "shared.w_down")) == 19_955_712
    assert count(cfg, 1) == 658_885_376                   # an expert layer with 64 held
    assert 128 * 9_977_856 + 19_955_712 + 2688 * 128 + 128 + 2688 == 1_297_468_160    # an expert layer WHOLE
    assert count(cfg, "embed") + count(cfg, "head", ("head",)) == 352_321_536
    parts = ["embed", "head", *range(cfg["num_hidden_layers"])]
    whole = sum(count(cfg, part) for part in parts)
    assert whole == 3_926_018_560                         # layers 0-12 + embedding + head + final norm
    # the model whole: 23 state-space, 23 expert and 6 softmax layers, both halves of the vocabulary
    assert 23 * 38_744_896 + 23 * 1_297_468_160 + 6 * 23_399_040 + 2 * 352_321_536 + 2688 == 31_577_940_288
    # in float32: the taps and biases, the norms' gains, A, D and dt_bias, the routers' biases
    small = sum(count(cfg, part, [n for n, (shape, *_) in model.weight_specs(cfg, part).items()
                                  if len(shape) < 2 or n.startswith("conv_")]) for part in parts)
    assert small == 6 * (5 * 6144 + 3 * 64 + 4096 + 2688) + 2 * 2688 + 5 * (128 + 2688) + 2688 == 248_320
    assert 2 * (whole - small) + 4 * small == 7_852_533_760    # param_bytes: 7.85 GB
    # the cell's step: 16,384 tokens, the even share of the visits, the recurrence a layer
    assert 16384 * 6 * 64 // 128 == 49_152 and 49_152 // 64 == 768
    assert 98_304 * 2688 * 2 == 528_482_304               # the read-back buffer's worst case
    one = model.scan_needs(cfg, 16384.0)
    assert one["flops"] == 16384 * 64 * 5 * 64 * 128 == 42_949_672_960
    # x at 64 heads of 64 and B, C at 8 groups of 128 in bfloat16; a step and a decay [64] float32; y float32
    assert one["bytes"] == 16384 * (4096 * 2 + 2 * 1024 * 2 + 2 * 64 * 4 + 4096 * 4) == 478_150_656
    # what a 64-head copy of B and C would add, in bfloat16: 0.54 GB where the mechanism has 0.07
    assert 16384 * 2 * 64 * 128 * 2 == 536_870_912 and 16384 * 2 * 8 * 128 * 2 == 67_108_864
    assert model.expert_needs(cfg, 49152.0)["flops"] == 49152 * 4 * 2688 * 1856
    cfg["observed"] = {"tokens": 16384.0, "triangle": 16384 * 16385 / 2, "visits": 49152.0}
    scopes = model.needs(cfg, 2, "score_docs")["scopes"]
    assert scopes["tfr.ssm_scan"] == {"flops": 6 * one["flops"], "bytes": 6 * one["bytes"]}
    assert round(scopes["tfr.ssm_proj"]["flops"] / 1e12, 1) == 7.6
    assert round(scopes["tfr.lm_head"]["flops"] / 1e12, 1) == 5.8
    assert round(scopes["tfr.moe_experts"]["flops"] / 1e12, 1) == 4.9
    assert round(scopes["tfr.moe_shared"]["flops"] / 1e12, 1) == 3.3
    assert round(scopes["tfr.ssm_scan"]["flops"] / 1e12, 1) == 0.3


def test_the_programs_parameters_are_the_counted_ones():
    """``lm.pattern_param_shapes`` of the program the file builds, tensor for tensor."""
    from tpu_tfrecord.models import lm

    cfg = published()
    pcfg = model.program(cfg, {"row_tokens": 8192})
    assert pcfg.layer_pattern == ("ssm", "none", "ssm", "none", "ssm", "gqa", "none", "ssm", "none", "ssm",
                                  "none", "ssm", "gqa")
    assert pcfg.ffn_pattern == tuple("moe" if kind == "none" else "none" for kind in pcfg.layer_pattern)
    assert (pcfg.kda_heads, pcfg.kda_head_dim, pcfg.ssm_state, pcfg.ssm_groups) == (64, 64, 128, 8)
    assert (pcfg.n_heads, pcfg.n_kv_heads, pcfg.head_dim, pcfg.gqa_gate) == (32, 2, 128, False)
    assert (pcfg.expert_unit, pcfg.d_shared, pcfg.experts_held, pcfg.n_experts) == ("relu2", 3712, 64, 128)
    assert pcfg.kda_chunk == 128 and pcfg.router_bias and not pcfg.branch_norms
    shapes = lm.pattern_param_shapes(pcfg)
    assert shapes["embed"][0] == (65536, 2688) and shapes["head"][0] == (2688, 65536)
    for i, layer in enumerate(shapes["layers"]):
        mine = {}
        for name, leaf in layer.items():
            if lm._is_shape(leaf):
                mine[name] = leaf[0]
            else:
                mine.update({f"{name}.{k}": v[0] for k, v in leaf.items()})
        assert mine == {name: tuple(spec[0]) for name, spec in model.weight_specs(cfg, i).items()}, i


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` of Nemotron-Labs-TwoTower-30B-A3B-Base-BF16, key
    for key; the cut is the depth and the vocabulary (and the experts held,
    under a key of its own)."""
    catalog = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 2688, "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8, "n_routed_experts": 128,
        "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_limit": [0, None],
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072,
    }
    cfg = published()
    differs = {k for k, v in catalog.items() if cfg.get(k) != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs | {"n_routed_experts_held", "dataset"} == set(cfg["reduced"])
    assert {k: catalog[k] for k in cfg["published"] if k in catalog} == {
        k: v for k, v in cfg["published"].items() if k in catalog}
    assert len(catalog["hybrid_override_pattern"]) == 52
    assert [catalog["hybrid_override_pattern"].count(c) for c in "ME*"] == [23, 23, 6]
    assert cfg["left_out"] == ["denoising_tower", "block_diffusion_decoding"] and cfg["left_out_why"]
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "nemotron_twotower_ep2")
    assert entry["reduced"] == list(cfg["reduced"]) and entry["source"] in cfg["source"]
