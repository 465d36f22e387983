"""The controls of ``deepseek_v32_exp_ep16.score``: the plain reference put in
the program's place, computed one precision below what the configuration
states or with a part of the mathematics left out, and compared with the
float32 reference by the loop's own numbers. Each has to come out as not
correct.

    no_selection       every key of the document at or before the query is
                       attended (a query attends its 2,048 best)
    topk_half          a query keeps 1,024 keys (2,048)
    bf16_index_scores  the index products, the weighted terms and their running
                       sum rounded to bfloat16 (float32)
    no_yarn            plain rotary frequencies and softmax scale (YaRN's)
    no_group_limit     the 8 experts chosen among all 256 (inside the 4 best groups)
    bf16_router        the router's scores, bias, group scores and gates in bfloat16 (float32)
    bf16_rotary        the rotary angles computed in bfloat16 (float32)
    int8_weights       every matrix through int8's 255 levels (bfloat16 weights)
    dropped_visits     an expert takes no more visits from a document than its
                       even share, as a capacity would have it (no visit dropped)

Judged as ``controls_docs.py`` judges Solar's: the numbers go through
``window.judge`` against the cell's own limits. ``test_controls_dsv32.py``
does that at a size a test run can hold; on the chip, at the cell's own
widths and limits, over one 16,384-token document a seed
(benchmark/TOKEN_DOCS_DSV32.md has the readings; the exit code is the number
of controls that passed as correct):

    python3 -m benchmark.tests.controls_dsv32 --seeds 1 2
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from benchmark.loops import score_docs
from benchmark.tests.controls import load_cell
from benchmark.tests.controls_docs import even_share, judged, step_documents

CELL = "deepseek_v32_exp_ep16.score"
CONTROLS = ("no_selection", "topk_half", "bf16_index_scores", "no_yarn", "no_group_limit",
            "bf16_router", "bf16_rotary", "int8_weights", "dropped_visits")


def control_numbers(model, cfg: dict, mix: dict, seed: int, names=None, tokens=None) -> dict:
    """{control: the loop's numbers, reference-with-the-departure against
    reference, and the departure's own router and selection held to float64}."""
    import jax.numpy as jnp

    docs = step_documents(cfg, mix, seed, tokens or mix["batch"] * mix["row_tokens"])
    rng = np.random.default_rng([int(seed), 0x43544C])
    at = [sorted(rng.choice(len(d) - 1, size=min(8, len(d) - 1), replace=False).tolist())
          for d in docs]
    plain = model.reference_weights(seed, cfg)
    departures = {
        "no_selection": dict(lower={"no_selection": True}),
        "topk_half": dict(lower={"index_topk": cfg["index_topk"] // 2}),
        "bf16_index_scores": dict(lower={"index_dtype": jnp.bfloat16}),
        "no_yarn": dict(lower={"no_yarn": True}),
        "no_group_limit": dict(lower={"no_group_limit": True}),
        "bf16_router": dict(lower={"router_dtype": jnp.bfloat16}),
        "bf16_rotary": dict(lower={"angle_dtype": jnp.bfloat16}),
        "int8_weights": dict(weights=model.reference_weights(seed, cfg, model.through_int8)),
        "dropped_visits": dict(lower={"capacity": even_share(cfg, docs)}),
    }
    want = model.reference_score(cfg, docs, plain, at)
    out = {}
    for name in names or CONTROLS:
        kw = dict(departures[name])
        got = model.reference_score(cfg, docs, kw.pop("weights", plain), at, **kw)
        out[name] = {
            # as if packed into one row in this order: all but the first follow another
            **score_docs.gaps(got["logprob"], want["logprob"], np.concatenate(got["logits"]),
                              np.concatenate(want["logits"]), [i > 0 for i in range(len(docs))]),
            **model.probe_numbers(cfg, seed, got["scan"], got["router"]),
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
