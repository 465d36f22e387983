"""trinity_large.needs() against FLOPs and bytes worked by hand for one small
shape, the published shape against the arithmetic of ISSUE 39 (held against
``lm.pattern_param_shapes`` too), and the configuration file against the
catalog's entry."""

import json
import os

from benchmark import run as bench_run
from benchmark.models import trinity_large as model

CFG = {
    "hidden_size": 8, "vocab_size": 32, "num_hidden_layers": 4, "num_dense_layers": 1,
    "first_layer": 1, "layer_types": ["sliding_attention"] * 3 + ["full_attention"] * 2,
    "sliding_window": 3, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
    "intermediate_size": 12, "moe_intermediate_size": 3, "num_experts": 10,
    "n_routed_experts_held": 5, "num_shared_experts": 1,
    # a step of 12 scored positions in two documents of 6, 9 visits to held experts a layer
    "observed": {"tokens": 12.0, "triangle": 2 * 6 * 7 / 2, "visits": 9.0},
}


def test_a_step_by_hand():
    t, tri, visits, d = 12, 42, 9, 8
    banded = 2 * (1 + 2 + 3 + 3 + 3 + 3)                  # a query sees min(position + 1, 3) keys
    assert model.window_pairs(t, tri, 3) == banded
    assert model.layer_plan(CFG) == [(True, "dense"), (True, "moe"), (False, "moe"), (False, "moe")]
    act = 2 * t * d * 2                                   # a layer's rows in and out, bf16
    mixer_w = d * (8 + 4 + 4 + 8) + 8 * d                 # Wq, Wk, Wv, Wg, Wo
    heads_io = 2 * t * (2 * 8 + 2 * 4)                    # q in and a out, k and v in, bf16
    want = {
        "tfr.embed": (0, act + 4 * t),
        "tfr.swa_proj": (2 * 2 * t * mixer_w, 2 * (2 * mixer_w + act)),
        # a pair inside the window and query head: 2 products for the score, 2 for the value
        "tfr.swa_attn": (2 * 4 * banded * 4 * 2, 2 * heads_io),
        "tfr.gqa": (2 * (2 * t * mixer_w + 4 * tri * 4 * 2), 2 * (2 * mixer_w + act)),
        "tfr.dense_ffn": (t * 6 * d * 12, 3 * d * 12 * 2 + act),
        "tfr.moe_route": (3 * 2 * t * d * 10, 3 * (2 * d * 10 + t * d * 2)),
        "tfr.moe_experts": (3 * visits * 6 * d * 3, 3 * (5 * 3 * d * 3 * 2 + 2 * visits * d * 2)),
        "tfr.moe_shared": (3 * t * 6 * d * 3, 3 * (3 * d * 3 * 2 + act)),
        "tfr.lm_head": (2 * t * d * 32, 2 * d * 32 + t * d * 2 + 4 * t),
    }
    got = model.needs(CFG, 1, "score_docs")
    assert {k: (v["flops"], v["bytes"]) for k, v in got["scopes"].items()} == {
        k: (float(f), float(b)) for k, (f, b) in want.items()}
    assert got["flops"] == sum(f for f, _ in want.values())
    assert got["bytes"] == sum(b for _, b in want.values())


def test_short_documents_keep_every_pair():
    assert model.window_pairs(10.0, 2 * 5 * 6 / 2, 4096) == 30.0


def published():
    with open(os.path.join(bench_run.HERE, "configs", "trinity_large_ep8.json")) as f:
        return json.load(f)


def count(cfg, part, only=None, matrices=False):
    total = 0
    for name, (shape, *_) in model.weight_specs(cfg, part).items():
        if (only is None or name in only) and (len(shape) >= 2 or not matrices):
            n = 1
            for s in shape:
                n *= s
            total += n
    return total


def test_the_published_shape_is_what_the_issue_counted():
    cfg = published()
    assert count(cfg, 0, ("wq", "wk", "wv", "wg", "wo")) == 62_914_560
    assert count(cfg, 0, ("dense.w_gate", "dense.w_up", "dense.w_down")) == 113_246_208
    assert count(cfg, 1, ("w_gate",)) * 3 == 32 * 28_311_552
    assert count(cfg, 1, ("router", "shared.w_gate", "shared.w_up", "shared.w_down")) == 29_097_984
    assert 62_914_560 + 256 * 28_311_552 + 29_097_984 == 7_339_769_856   # an expert layer WHOLE: 14.7 GB
    assert count(cfg, 0, matrices=True) == 176_160_768 and count(cfg, 1, matrices=True) == 997_982_208
    assert count(cfg, "embed") + count(cfg, "head", ("head",)) == 153_747_456
    parts = ["embed", "head", *range(cfg["num_hidden_layers"])]
    whole = sum(count(cfg, part, matrices=True) for part in parts)
    assert whole == 4_321_837_056 and 2 * whole == 8_643_674_112
    # norms and the router's bias on top: what param_bytes adds to the matrices
    assert sum(count(cfg, part) for part in parts) - whole == 5 * (4 * 3072 + 2 * 128) + 4 * 256 + 3072
    assert model.layer_plan(cfg) == [(True, "dense"), (True, "moe"), (False, "moe"),
                                     (True, "moe"), (True, "moe")]
    # the cell's step: one document of 32,768 tokens, the even share of the visits
    assert model.window_pairs(32768.0, 32768 * 32769 / 2, 4096) == 125_831_168
    assert 32768 * 32769 // 2 == 536_887_296 and 32768 * 4 * 32 // 256 == 16_384
    cfg["observed"] = {"tokens": 32768.0, "triangle": 32768 * 32769 / 2, "visits": 16384.0}
    scopes = model.needs(cfg, 1, "score_docs")["scopes"]
    assert scopes["tfr.swa_attn"]["flops"] == 4 * 125_831_168 * 48 * 512
    assert round(scopes["tfr.swa_attn"]["flops"] / 1e12, 1) == 12.4
    assert round(scopes["tfr.gqa"]["flops"] / 1e12, 1) == 17.3       # 13.2 of attention, 4.1 of projections
    assert round(scopes["tfr.swa_proj"]["flops"] / 1e12, 1) == 16.5
    assert round(scopes["tfr.moe_experts"]["flops"] / 1e12, 1) == 3.7


def test_the_programs_parameters_are_the_counted_ones():
    """``lm.pattern_param_shapes`` of the program the file builds, tensor for tensor."""
    from tpu_tfrecord.models import lm

    cfg = published()
    shapes = lm.pattern_param_shapes(model.program(cfg, {"row_tokens": 32768}))
    assert shapes["embed"][0] == (25024, 3072) and shapes["head"][0] == (3072, 25024)
    for i, layer in enumerate(shapes["layers"]):
        mine = {}
        for name, leaf in layer.items():
            if lm._is_shape(leaf):
                mine[name] = leaf[0]
            else:
                mine.update({f"{name}.{k}": v[0] for k, v in leaf.items()})
        assert mine == {name: tuple(spec[0]) for name, spec in model.weight_specs(cfg, i).items()}, i


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` of Trinity-Large-Preview, key for key; the cut is
    the depth, the leading dense layers and the vocabulary."""
    period = ["sliding_attention"] * 3 + ["full_attention"]
    catalog = {
        "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 3072,
        "intermediate_size": 12288, "layer_types": period * 15, "load_balance_coeff": 5e-05,
        "max_position_embeddings": 262144, "model_type": "afmoe", "moe_intermediate_size": 3072,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 48, "num_dense_layers": 6,
        "num_expert_groups": 1, "num_experts": 256, "num_experts_per_tok": 4,
        "num_hidden_layers": 60, "num_key_value_heads": 8, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid", "sliding_window": 4096,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
    }
    cfg = published()
    differs = {k for k, v in catalog.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_dense_layers", "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 60, "num_dense_layers": 6, "num_experts": 256,
                                "vocab_size": 200192}
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_dense_layers", "n_routed_experts_held",
                                   "vocab_size", "dataset"}
    assert cfg["left_out"] == [] and cfg["first_layer"] == 5
    assert cfg["n_routed_experts_held"] == 32 and cfg["held_offset"] == 0
    assert cfg["doc_length"]["min"] == cfg["doc_length"]["max"] == 32768
    assert {"qk_norm", "positions", "attention_gate", "sandwich_norms", "embedding_scale", "window",
            "router_bias", "expert_placement", "init"} <= set(cfg["assumed"])


def test_the_cell_is_the_issues():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["trinity_large_ep8.score"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity_large_ep8", "score_docs_swa", 1)
    assert len(bench["workloads"]) == 6 and len(bench["configs"]) == 5
    mix, older = (bench_run.load_json("traffic", name + ".json")
                  for name in ("score_docs_swa", "score_docs_dsa"))
    changed = {k for k in older if k not in ("limits", "limit_reasons", "rehearsal", "arrivals")
               and mix[k] != older[k]}
    assert changed == {"row_tokens", "docs_per_shard", "verify_batches"}
    assert (mix["batch"], mix["row_tokens"], mix["shards"], mix["docs_per_shard"]) == (1, 32768, 8, 32)
    assert (mix["packing"], mix["reader_batch"], mix["in_flight"]) == ("best_fit", 16, 2)
    assert set(mix) == set(older) and "scan_state_gap" not in mix["limits"]
    assert {"window_attn_gap", "window_keys_wrong"} <= set(mix["limits"])
    assert set(mix["limits"]) - set(mix["limit_reasons"]) <= {
        "repeat_gap", "tokens_altered", "docs_missing", "docs_doubled", "segments_wrong",
        "moe_visits_dropped", "steps_not_finite", "window_keys_wrong"}


def test_the_placement_is_a_renaming_and_every_holder_has_it():
    """At rehearsal size: :func:`placement` reorders the router's columns and
    nothing else, the same order on every call; the program's tree and
    ``part_weights`` (the reference's and the probes') hold the router in that
    order; on the observed row no expert held is visited past its tile where
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
    pcfg = model.program(cfg, {"row_tokens": cfg["doc_length"]["max"]})
    tokens, segs = model.observed_row(seed, cfg, cfg["doc_length"]["max"])
    visits = np.asarray(jax.jit(lambda p, t, s: lm.pattern_hidden(p, t, s, pcfg)[1])(
        params, jnp.asarray(tokens), jnp.asarray(segs)))       # as the placement observed them
    for nth, (i, order) in enumerate(sorted(orders.items())):
        assert sorted(order) == list(range(cfg["num_experts"]))
        raw = model._raw_weights(seed, cfg, i, names=("router", "router_bias"))
        placed = model.part_weights(seed, cfg, i, names=("router", "router_bias"))
        np.testing.assert_array_equal(np.asarray(placed["router"]), np.asarray(raw["router"])[:, order])
        np.testing.assert_array_equal(np.asarray(placed["router_bias"]),
                                      np.asarray(raw["router_bias"])[order])
        np.testing.assert_array_equal(np.asarray(params["layers"][i]["router"], np.float32),
                                      np.asarray(placed["router"]))
        tile = pcfg.expert_tile
        assert visits[nth].max() <= tile - tile // 16, visits[nth]
    again = model.program_params(seed, cfg)
    for i in orders:
        np.testing.assert_array_equal(np.asarray(again["layers"][i]["router"], np.float32),
                                      np.asarray(params["layers"][i]["router"], np.float32))
