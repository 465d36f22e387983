"""The controls of ``sdar_30b_a3b_pp8.score``: the plain reference put in the
program's place, computed one precision below what the configuration states or
with a part of the mechanism broken, and compared with the float32 reference by
the loop's own numbers, on the documents' own noised copies as the feed makes
them. Each has to come out as not correct.

    causal_clean        a clean query sees no key after it: no sight inside its block
    own_clean_seen      a noised query sees the clean copy of its own block too
                        (the leak that flatters every score)
    blocks_from_row     blocks counted from one token before a document's first, as
                        a count from the row's start makes them wherever a document
                        does not start on a whole block
    carried_positions   the noised stream's positions go on from the clean stream's
                        last (counted across the row, not in the document)
    shifted_targets     position i scored against token i + 1 (no shift)
    sigmoid_router      the router's scores by a sigmoid (a softmax over all 128)
    bf16_router         the router's logits, softmax and gates in bfloat16 (float32)
    bf16_softmax        attention's scores, exponentials and weights in bfloat16 (float32)
    no_qk_norm          q and k enter the scores as projected (an RMSNorm a head)
    int8_weights        every matrix through int8's 255 levels (bfloat16 weights)
    dropped_visits      an expert takes no more visits from a document than its even
                        share, as a capacity would have it (no visit dropped)
    noise_ignores_t     a feed that masks every token with probability 1/2 whatever
                        its block's level says (the count is right, the law is not)

Judged as ``controls_docs.py`` judges Solar's: the numbers go through
``window.judge`` against the cell's own limits. ``test_controls_sdar.py`` does
that at a size a test run can hold; on the chip, at the cell's own widths and
limits, over one row's worth of a seed's documents
(benchmark/TOKEN_DOCS_SDAR.md has the readings; the exit code is the number of
controls that passed as correct):

    python3 -m benchmark.tests.controls_sdar --seeds 1
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from benchmark.data import token_docs_bd
from benchmark.loops import score_docs_bd
from benchmark.tests.controls import load_cell
from benchmark.tests.controls_docs import judged

CELL = "sdar_30b_a3b_pp8.score"
CONTROLS = ("causal_clean", "own_clean_seen", "blocks_from_row", "carried_positions", "shifted_targets",
            "sigmoid_router", "bf16_router", "bf16_softmax", "no_qk_norm", "int8_weights", "dropped_visits",
            "noise_ignores_t")


def step_documents(cfg: dict, mix: dict, seed: int, tokens: int) -> tuple:
    """(documents with their end id, their noised copies, their levels) of the
    seed's first shard: ``controls_docs.step_documents``'s choice (the longest
    of the first 64 that fits half of ``tokens``, then the others in the order
    written while they fit), noised by the feed's own packer."""
    from tpu_tfrecord.tpu.ingest import TokenPacker

    flat, offsets = token_docs_bd.shard_docs(seed, 0, mix["docs_per_shard"], cfg, mix)
    docs = [flat[a:b].astype(np.int32) for a, b in zip(offsets[:64], offsets[1:65])]
    first = max((d for d in docs if len(d) + 1 <= max(tokens // 2, 2)), key=len, default=docs[0])
    picked, total = [first], len(first) + 1
    for doc in docs:
        if doc is not first and total + len(doc) + 1 <= tokens:
            picked.append(doc)
            total += len(doc) + 1
    packer = TokenPacker(1, max(len(d) for d in picked) + 1, packing="first_fit",
                         noise=(mix["block_length"], mix["mask_id"], int(seed)))
    rows = []
    for doc in picked:  # a row a document: cut out again below
        packer.feed_docs([doc])
        packer.flush()
        rows.append(packer.pop())
    cut = lambda row, name, n: row[name][0, :n]  # noqa: E731
    return ([cut(r, "tokens", len(d) + 1) for r, d in zip(rows, picked)],
            [cut(r, "noised", len(d) + 1) for r, d in zip(rows, picked)],
            [cut(r, "noise_level", len(d) + 1) for r, d in zip(rows, picked)])


def control_numbers(model, cfg: dict, mix: dict, seed: int, names=None, tokens=None) -> dict:
    """{control: the loop's numbers, reference-with-the-departure against
    reference, the departure's own router and attention held to float64, and
    the feed's noise against its law}."""
    import jax.numpy as jnp

    block, mask_id = mix["block_length"], mix["mask_id"]
    docs, copies, levels = step_documents(cfg, mix, seed, tokens or mix["batch"] * mix["row_tokens"])
    masked = [c == mask_id for c in copies]
    rng = np.random.default_rng([int(seed), 0x43544C])
    at = [sorted(rng.choice(len(d), size=min(8, len(d)), replace=False).tolist()) for d in docs]
    plain = model.reference_weights(seed, cfg)
    longest = max(len(d) for d in docs)
    departures = {
        "causal_clean": dict(lower={"causal_clean": True}),
        "own_clean_seen": dict(lower={"own_clean_seen": True}),
        "blocks_from_row": dict(lower={"block_origin": 1}),
        "carried_positions": dict(lower={"carried_positions": True}),
        "shifted_targets": dict(lower={"shifted_targets": True}),
        "sigmoid_router": dict(lower={"sigmoid_router": True}),
        "bf16_router": dict(lower={"router_dtype": jnp.bfloat16}),
        "bf16_softmax": dict(lower={"softmax_dtype": jnp.bfloat16}),
        "no_qk_norm": dict(lower={"no_qk_norm": True}),
        "int8_weights": dict(weights=model.reference_weights(seed, cfg, model.through_int8)),
        # both streams' tokens visit: twice a document's length, 8 each, over 128
        "dropped_visits": dict(lower={"capacity": max(
            1, 2 * longest * cfg["num_experts_per_tok"] // cfg["num_experts"])}),
        "noise_ignores_t": dict(),
    }

    def feed(noised):  # the documents as one kept step's rows: what noise_numbers reads
        return [{"segment_ids": np.ones((1, len(d)), np.int32), "noised": z[None], "noise_level": t[None]}
                for d, z, t in zip(docs, noised, levels)]

    def score(weights=plain, **kw):
        return model.reference_score(cfg, docs, weights, at, noised=copies, block_length=block, **kw)

    want = score()
    out = {}
    for name in names or CONTROLS:
        kw = dict(departures[name])
        got = score(**kw)
        noised = copies
        if name == "noise_ignores_t":
            flat = np.random.default_rng([int(seed), 0x464C4154])
            noised = [np.where(flat.random(len(d)) < 0.5, mask_id, d).astype(np.int32) for d in docs]
        out[name] = {
            # as if packed into one row in this order: all but the first follow another
            **score_docs_bd.gaps(got["logprob"], want["logprob"], masked, levels,
                                 np.concatenate(got["logits"]), np.concatenate(want["logits"]),
                                 [i > 0 for i in range(len(docs))]),
            **model.probe_numbers(cfg, seed, got["scan"], got["router"], block),
            **score_docs_bd.noise_numbers(feed(noised), mask_id, block),
            "moe_visits_dropped": float(got["dropped"]),
        }
    return out


def main(argv=None) -> int:
    import importlib

    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*")
    ap.add_argument("--tokens", type=int)
    args = ap.parse_args(argv)
    import jax

    cfg, mix = load_cell(CELL)
    model = importlib.import_module("benchmark.models." + cfg["model"])
    passed = 0
    for seed in args.seeds:
        for name, numbers in control_numbers(model, cfg, mix, seed, args.controls,
                                             args.tokens).items():
            correct, outside = judged(numbers, mix["limits"])
            passed += int(correct)
            print("[control] " + json.dumps(
                {"workload": CELL, "seed": seed, "control": name, "correct": correct,
                 "platform": jax.devices()[0].platform, "numbers": numbers, "outside": outside}),
                flush=True)
    return passed


if __name__ == "__main__":
    raise SystemExit(main())
