"""solar_open2.needs() against FLOPs and bytes worked by hand for one small shape."""

from benchmark.models import solar_open2 as model

CFG = {
    "hidden_size": 8, "vocab_size": 32, "num_hidden_layers": 2, "gqa_layers": [0],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
    "linear_attn_config": {"num_heads": 2, "head_dim": 4, "short_conv_kernel_size": 4},
    "moe_intermediate_size": 3, "n_routed_experts": 10, "n_routed_experts_held": 5,
    "n_shared_experts": 1,
    # a step of 10 scored positions in documents of 6 and 4, 7 visits to held experts a layer
    "observed": {"tokens": 10.0, "triangle": 6 * 7 / 2 + 4 * 5 / 2, "visits": 7.0},
}


def test_a_step_by_hand():
    t, tri, visits, d, r = 10, 31, 7, 8, model.GATE_RANK
    act = 2 * t * d * 2                                   # a layer's rows in and out, bf16
    gqa_w = d * (2 * 8 + 2 * 4) + 8 * d                   # q, gate, k, v, out
    kda_w = 4 * d * 8 + 2 * (d * r + r * 8) + d * 2       # q, k, v, out, two rank-r gates, beta
    want = {
        "tfr.embed": (0, act + 4 * t),
        "tfr.gqa": (2 * t * gqa_w + 4 * tri * 4 * 2, 2 * gqa_w + act),
        "tfr.kda_proj": (2 * t * kda_w, 2 * kda_w + act),
        "tfr.kda_conv": (2 * t * 3 * 8 * 4, 2 * t * 3 * 8 * 2),
        "tfr.kda_scan": (t * 2 * 7 * 16, t * 2 * (3 * 4 * 2 + 4 * 4 + 4 + 4 * 4)),
        "tfr.moe_route": (2 * 2 * t * d * 10, 2 * (4 * d * 10 + t * d * 2)),
        "tfr.moe_experts": (2 * visits * 6 * d * 3, 2 * (5 * 3 * d * 3 * 2 + 2 * visits * d * 2)),
        "tfr.moe_shared": (2 * t * 6 * d * 3, 2 * (3 * d * 3 * 2 + act)),
        "tfr.lm_head": (2 * t * d * 32, 2 * d * 32 + t * d * 2 + 4 * t),
    }
    got = model.needs(CFG, 2, "score_docs")
    assert {k: (v["flops"], v["bytes"]) for k, v in got["scopes"].items()} == {
        k: (float(f), float(b)) for k, v in want.items() for f, b in [v]}
    assert got["flops"] == sum(f for f, _ in want.values())
    assert got["bytes"] == sum(b for _, b in want.values())


def test_the_published_shape_is_what_the_issue_counted():
    """One period at the published widths: 3,308 M parameters on the chip."""
    import json
    import os

    from benchmark import run as bench_run

    with open(os.path.join(bench_run.HERE, "configs", "solar_open2_ep8.json")) as f:
        cfg = json.load(f)
    count = 0
    for part in ["embed", "head", *range(cfg["num_hidden_layers"])]:
        for shape, *_ in model.weight_specs(cfg, part).values():
            n = 1
            for s in shape:
                n *= s
            count += n
    assert round(count / 1e6) == 3308
    assert model.layer_kinds(cfg) == ["gqa", "kda", "kda", "kda"]
