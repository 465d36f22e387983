"""kimi_vl_lm.needs() against FLOPs and bytes worked by hand for one small
shape, and the published shape against the arithmetic of ISSUE 31."""

import json
import os

from benchmark import run as bench_run
from benchmark.models import kimi_vl_lm as model

CFG = {
    "hidden_size": 8, "vocab_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 2, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 4,
    "kv_lora_rank": 6, "intermediate_size": 12, "moe_intermediate_size": 3,
    "n_routed_experts": 10, "n_routed_experts_held": 10, "n_shared_experts": 2,
    # a step of 10 scored positions in documents of 6 and 4, 30 visits to held experts a layer
    "observed": {"tokens": 10.0, "triangle": 6 * 7 / 2 + 4 * 5 / 2, "visits": 30.0},
}


def test_a_step_by_hand():
    t, tri, visits, d = 10, 31, 30, 8
    act = 2 * t * d * 2                                   # a layer's rows in and out, bf16
    mla_w = d * 2 * 6 + d * (6 + 2) + 6 * 2 * 8 + 2 * 4 * d   # q, latent + rotary key, expansion, out
    want = {
        "tfr.embed": (0, act + 4 * t),
        "tfr.mla_proj": (3 * 2 * t * mla_w, 3 * (2 * mla_w + act)),
        # a pair and head: 6 products for the score, 4 for the value; q 12, k_nope 8, k_pe 2, v 8, out 8 a token
        "tfr.mla_attn": (3 * 2 * tri * 2 * (6 + 4), 3 * 2 * t * (12 + 8 + 2 + 8 + 8)),
        "tfr.dense_ffn": (t * 6 * d * 12, 3 * d * 12 * 2 + act),
        "tfr.moe_route": (2 * 2 * t * d * 10, 2 * (4 * d * 10 + t * d * 2)),
        "tfr.moe_experts": (2 * visits * 6 * d * 3, 2 * (10 * 3 * d * 3 * 2 + 2 * visits * d * 2)),
        "tfr.moe_shared": (2 * t * 6 * d * 3 * 2, 2 * (3 * d * 3 * 2 * 2 + act)),
        "tfr.lm_head": (2 * t * d * 32, 2 * d * 32 + t * d * 2 + 4 * t),
    }
    got = model.needs(CFG, 2, "score_docs")
    assert {k: (v["flops"], v["bytes"]) for k, v in got["scopes"].items()} == {
        k: (float(f), float(b)) for k, (f, b) in want.items()}
    assert got["flops"] == sum(f for f, _ in want.values())
    assert got["bytes"] == sum(b for _, b in want.values())


def published():
    with open(os.path.join(bench_run.HERE, "configs", "kimi_vl_a3b_lm.json")) as f:
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
    mixer = ("wq", "wkv_a", "wkv_b", "wo")
    assert count(cfg, 0, mixer) == 13_762_560
    assert count(cfg, 0, ("dense.w_gate", "dense.w_up", "dense.w_down")) + 13_762_560 == 82_968_576
    matrices = lambda name: not name.endswith("norm") and name != "router_bias"  # noqa: E731
    layer = sum(count(cfg, 1, (n,)) for n in model.weight_specs(cfg, 1) if matrices(n))
    assert layer == 584_843_264
    assert count(cfg, "embed") + count(cfg, "head", ("head",)) == 671_088_640
    whole = sum(count(cfg, part) for part in ["embed", "head", *range(cfg["num_hidden_layers"])])
    # ISSUE 31 counted the matrices: 4,263,116,800; norms and the router's bias are 34,688 more
    assert whole == 4_263_116_800 + 34_688
    assert model.ffn_kinds(cfg) == ["dense"] + ["moe"] * 6


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` of Kimi-VL-A3B-Instruct, key for key; only the depth is cut."""
    catalog = {
        "vocab_size": 163840, "max_position_embeddings": 131072, "hidden_size": 2048,
        "intermediate_size": 11264, "moe_intermediate_size": 1408, "num_hidden_layers": 27,
        "num_attention_heads": 16, "n_shared_experts": 2, "n_routed_experts": 64, "ep_size": 1,
        "routed_scaling_factor": 2.446, "kv_lora_rank": 512, "q_lora_rank": None,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "num_experts_per_tok": 6,
        "moe_layer_freq": 1, "first_k_dense_replace": 1, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "seq_aux": True, "num_key_value_heads": 16,
        "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 800000, "rope_scaling": None,
        "attention_bias": False, "tie_word_embeddings": False,
    }
    cfg = published()
    differs = {k for k, v in catalog.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} and cfg["published"]["num_hidden_layers"] == 27
    assert set(cfg["reduced"]) == {"num_hidden_layers", "dataset"}
    assert cfg["left_out"] == ["vision_tower", "multi_modal_projector"]
    assert cfg["n_routed_experts_held"] == cfg["n_routed_experts"] and cfg["held_offset"] == 0
