"""sdar_moe.needs() against FLOPs and bytes worked by hand for one small shape,
the published shape against the arithmetic of ISSUE 53 (held against
``lm.pattern_param_shapes``: the guide's share test is trivial here, the group
that divides a layer being 1, and this count stands in its place), and the
configuration file against the catalog's entry."""

import json
import os

import numpy as np

from benchmark import run as bench_run
from benchmark.models import sdar_moe as model

CFG = {
    "hidden_size": 8, "vocab_size": 32, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 2, "moe_intermediate_size": 3, "num_experts": 10,
    "n_routed_experts_held": 10, "num_experts_per_tok": 2,
    # a step of two documents of 6 and 5 tokens, 7 of them masked, 44 visits a layer
    "observed": {"tokens": 11.0, "pairs": 0.0, "masked": 7.0, "visits": 44.0},
}


def test_the_pairs_the_mask_allows():
    # blocks of 4 over 6 tokens: positions 0-3 see to 4, positions 4-5 to 6; the noised stream as many
    assert model.seen_pairs(6, 4) == 2 * (4 * 4 + 2 * 6) and model.seen_pairs(5, 4) == 2 * (4 * 4 + 5)
    assert model.seen_pairs(1, 4) == 2 and model.seen_pairs(4, 4) == 2 * 16
    # against the mask written out
    for n in (1, 3, 4, 9, 17):
        at = np.tile(np.arange(n), 2)
        noised = np.arange(2 * n) >= n
        seen = np.asarray(model.block_mask(at, noised, at >= 0, at, noised, at >= 0, 4))
        assert int(seen.sum()) == model.seen_pairs(n, 4), n
        assert not seen[:n, n:].any()                     # no clean query sees a noised key
    # one whole document of 8,192: each stream over its own mask, not the square
    assert model.seen_pairs(8192, 4) == 2 * (8192 * 8193 // 2 + 8192 * 3 // 2) == 67_141_632


def test_a_step_by_hand():
    cfg = {**CFG, "observed": {**CFG["observed"], "pairs": float(model.seen_pairs(6, 4) + model.seen_pairs(5, 4))}}
    t, pairs, masked, visits, d = 2 * 11, 56 + 42, 7, 44, 8
    act = 2 * t * d * 2                                   # a layer's rows in and out, bf16, BOTH streams
    mixer_w = d * (8 + 4 + 4) + 8 * d                     # Wq, Wk, Wv, Wo: no gate
    heads_io = 2 * t * (2 * 8 + 2 * 4)                    # q in and a out, k and v in, bf16
    want = {
        "tfr.embed": (0, act + 4 * t),
        "tfr.bda_proj": (3 * 2 * t * mixer_w, 3 * (2 * mixer_w + act)),
        # a pair the mask allows and query head: 2 products for the score, 2 for the value
        "tfr.bda_attn": (3 * 4 * pairs * 4 * 2, 3 * heads_io),
        "tfr.moe_route": (3 * 2 * t * d * 10, 3 * (2 * d * 10 + t * d * 2)),
        "tfr.moe_experts": (3 * visits * 6 * d * 3, 3 * (10 * 3 * d * 3 * 2 + 2 * visits * d * 2)),
        # the head over the masked positions alone
        "tfr.lm_head": (2 * masked * d * 32, 2 * d * 32 + masked * d * 2 + 4 * masked),
    }
    got = model.needs(cfg, 1, "score_docs_bd")
    assert {k: (v["flops"], v["bytes"]) for k, v in got["scopes"].items()} == {
        k: (float(f), float(b)) for k, (f, b) in want.items()}
    assert got["flops"] == sum(f for f, _ in want.values())
    assert got["bytes"] == sum(b for _, b in want.values())


def published():
    with open(os.path.join(bench_run.HERE, "configs", "sdar_30b_a3b_pp8.json")) as f:
        return json.load(f)


def count(cfg, part, only=None, matrices=False):
    total = 0
    for name, (shape, *_) in model.weight_specs(cfg, part).items():
        if (only is None or name in only) and (len(shape) >= 2 or not matrices):
            total += int(np.prod(shape))
    return total


def test_the_published_shape_is_what_the_issue_counted():
    cfg = published()
    assert count(cfg, 0, ("wq", "wk", "wv", "wo")) == 18_874_368
    assert count(cfg, 0, ("router",)) == 262_144
    assert count(cfg, 0, ("w_gate", "w_up", "w_down")) == 128 * 4_718_592 == 603_979_776
    assert count(cfg, 0, matrices=True) == 623_116_288            # a layer WHOLE: 1.246 GB in bfloat16
    assert count(cfg, "embed") + count(cfg, "head", ("head",)) == 622_329_856
    parts = ["embed", "head", *range(cfg["num_hidden_layers"])]
    whole = sum(count(cfg, part, matrices=True) for part in parts)
    assert whole == 6 * 623_116_288 + 622_329_856 == 4_361_027_584 and 2 * whole == 8_722_055_168
    # the float32 vectors on top: what param_bytes adds to the matrices
    assert sum(count(cfg, part) for part in parts) - whole == 6 * (2 * 2048 + 2 * 128) + 2048
    # the model whole: 48 layers and one embedding and head
    assert 48 * 623_116_288 + 622_329_856 == 30_531_911_680
    # the cell's step: the even share of 131,072 visits a layer
    assert 2 * 8192 * 8 == 131_072 and 131_072 // 128 == 1024
    cfg["observed"] = {"tokens": 8192.0, "pairs": 2.0 * 12e6, "masked": 4096.0, "visits": 131072.0}
    scopes = model.needs(cfg, 1, "score_docs_bd")["scopes"]
    assert scopes["tfr.moe_experts"]["flops"] == 6 * 131072 * 6 * 2048 * 768        # 1.24 TFLOP a layer
    assert round(scopes["tfr.moe_experts"]["flops"] / 1e12, 2) == 7.42
    assert round(scopes["tfr.bda_proj"]["flops"] / 1e12, 2) == 3.71
    assert round(scopes["tfr.lm_head"]["flops"] / 1e12, 2) == 2.55     # half the row's positions
    assert round(scopes["tfr.bda_attn"]["flops"] / 1e12, 2) == round(6 * 4 * 24e6 * 32 * 128 / 1e12, 2) == 2.36


def test_the_programs_parameters_are_the_counted_ones():
    """``lm.pattern_param_shapes`` of the program the file builds, tensor for
    tensor, and their sum: six layers, the embedding and the head."""
    from tpu_tfrecord.models import lm

    cfg = published()
    mix = bench_run.load_json("traffic", "score_docs_bd.json")
    shapes = lm.pattern_param_shapes(model.program(cfg, mix))
    assert shapes["embed"][0] == (151936, 2048) and shapes["head"][0] == (2048, 151936)
    total = int(np.prod(shapes["embed"][0])) + int(np.prod(shapes["head"][0]))
    for i, layer in enumerate(shapes["layers"]):
        mine = {name: leaf[0] for name, leaf in layer.items()}
        assert mine == {name: tuple(spec[0]) for name, spec in model.weight_specs(cfg, i).items()}, i
        total += sum(int(np.prod(shape)) for shape in mine.values() if len(shape) >= 2)
    assert len(shapes["layers"]) == 6 and total == 4_361_027_584


def test_every_number_of_the_catalogs_config_is_in_the_file():
    """The catalog's ``config`` of SDAR-30B-A3B-Chat, key for key; the cut is the depth."""
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936,
    }
    cfg = published()
    differs = {k for k, v in catalog.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} and cfg["num_hidden_layers"] == 6
    assert cfg["published"] == {"num_hidden_layers": 48}
    assert set(cfg["reduced"]) == {"num_hidden_layers", "dataset"}
    assert cfg["left_out"] == ["decode_loop", "confidence_remasking", "finalised_block_cache"]
    assert cfg["n_routed_experts_held"] == cfg["num_experts"] == 128 and cfg["held_offset"] == 0
    assert {"block_length", "noise_law", "mask_id", "no_shift", "qk_norm", "rotary", "router", "init"} <= set(
        cfg["assumed"])
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}["sdar_30b_a3b_pp8"]
    assert entry["reduced"] == ["num_hidden_layers", "dataset"] and entry["source"] == cfg["source"]
    assert entry["source"].startswith("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")


def test_the_cell_is_the_issues():
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}["sdar_30b_a3b_pp8.score"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("sdar_30b_a3b_pp8", "score_docs_bd", 1)
    mix, older = (bench_run.load_json("traffic", name + ".json") for name in ("score_docs_bd", "score_docs"))
    changed = {k for k in older if k not in ("limits", "limit_reasons", "rehearsal", "arrivals")
               and mix[k] != older[k]}
    assert changed == {"loop", "batch"} and set(mix) - set(older) == {"block_length", "noise", "mask_id"}
    assert (mix["loop"], mix["batch"], mix["row_tokens"], mix["block_length"], mix["mask_id"]) == (
        "score_docs_bd", 1, 8192, 4, 151935)
    assert mix["mask_id"] == published()["vocab_size"] - 1
    assert (mix["shards"], mix["docs_per_shard"], mix["packing"], mix["reader_batch"], mix["prefetch"],
            mix["in_flight"], mix["warmup_steps"], mix["verify_batches"], mix["logit_samples"],
            mix["trace_seconds"]) == (8, 1024, "best_fit", 16, 4, 2, 3, 3, 32, 8.0)
    assert {"bda_attn_gap", "bda_keys_wrong", "noise_off_law", "router_gate_gap", "doc_score_gap",
            "boundary_median_gap"} <= set(mix["limits"]) and "scan_state_gap" not in mix["limits"]
    assert set(mix["limits"]) - set(mix["limit_reasons"]) <= {
        "repeat_gap", "tokens_altered", "docs_missing", "docs_doubled", "segments_wrong",
        "moe_visits_dropped", "steps_not_finite", "bda_keys_wrong"}


def test_the_documents_never_hold_the_mask_id():
    from benchmark.data import token_docs, token_docs_bd

    cfg = bench_run.at_rehearsal_size(published())
    mix = bench_run.at_rehearsal_size(bench_run.load_json("traffic", "score_docs_bd.json"))
    flat, offsets = token_docs_bd.shard_docs(7, 0, 400, cfg, mix)
    assert flat.min() >= 1 and flat.max() == mix["mask_id"] - 1 == 510     # every id but 0 and the last
    plain, _ = token_docs.shard_docs(7, 0, 400, cfg)
    assert plain.max() == 511 and len(offsets) == 401                      # the plain law reaches the last id
    try:
        token_docs_bd.without_mask_id(cfg, {**mix, "mask_id": 5})
    except ValueError as e:
        assert "last" in str(e)
    else:
        raise AssertionError("a mask id inside the documents' range was taken")
