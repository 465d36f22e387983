"""The controls of ``trinity_large_ep8.score``: the plain reference put in the
program's place, computed one precision below what the configuration states or
with a part of the mathematics left out, and compared with the float32
reference by the loop's own numbers. Each has to come out as not correct.

    no_window        a sliding layer's query attends every key of its document
                     before it (its own and the 4,095 before it)
    window_4097      one key more (off by one)
    rotary_on_full   the full layers turn q and k too (no positions there)
    no_rotary        the sliding layers turn nothing (rotary over the whole head)
    no_qk_norm       q and k enter the scores as projected (an RMSNorm a head)
    no_branch_norms  x + branch (x + rms(branch): the sandwich's second norm)
    no_embed_scale   the embedding's rows as stored (times sqrt(3072))
    int8_weights     every matrix through int8's 255 levels (bfloat16 weights)
    bf16_router      the router's scores, bias and gates in bfloat16 (float32)
    bf16_rotary      the rotary angles computed in bfloat16 (float32)
    dropped_visits   an expert takes no more visits from a document than its
                     even share, as a capacity would have it (no visit dropped)

Judged as ``controls_docs.py`` judges Solar's: the numbers go through
``window.judge`` against the cell's own limits. ``test_controls_trinity.py``
does that at a size a test run can hold; on the chip, at the cell's own
widths and limits, over one 32,768-token document a seed
(benchmark/TOKEN_DOCS_TRINITY.md has the readings; the exit code is the number
of controls that passed as correct):

    python3 -m benchmark.tests.controls_trinity --seeds 1
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from benchmark.loops import score_docs
from benchmark.tests.controls import load_cell
from benchmark.tests.controls_docs import even_share, judged, step_documents

CELL = "trinity_large_ep8.score"
CONTROLS = ("no_window", "window_4097", "rotary_on_full", "no_rotary", "no_qk_norm",
            "no_branch_norms", "no_embed_scale", "int8_weights", "bf16_router", "bf16_rotary",
            "dropped_visits")


def control_numbers(model, cfg: dict, mix: dict, seed: int, names=None, tokens=None) -> dict:
    """{control: the loop's numbers, reference-with-the-departure against
    reference, and the departure's own router and window held to float64}."""
    import jax.numpy as jnp

    docs = step_documents(cfg, mix, seed, tokens or mix["batch"] * mix["row_tokens"])
    rng = np.random.default_rng([int(seed), 0x43544C])
    at = [sorted(rng.choice(len(d) - 1, size=min(8, len(d) - 1), replace=False).tolist())
          for d in docs]
    plain = model.reference_weights(seed, cfg)
    departures = {
        "no_window": dict(lower={"window": None}),
        "window_4097": dict(lower={"window": cfg["sliding_window"] + 1}),
        "rotary_on_full": dict(lower={"rotary_on_full": True}),
        "no_rotary": dict(lower={"no_rotary": True}),
        "no_qk_norm": dict(lower={"no_qk_norm": True}),
        "no_branch_norms": dict(lower={"no_branch_norms": True}),
        "no_embed_scale": dict(lower={"no_embed_scale": True}),
        "int8_weights": dict(weights=model.reference_weights(seed, cfg, model.through_int8)),
        "bf16_router": dict(lower={"router_dtype": jnp.bfloat16}),
        "bf16_rotary": dict(lower={"angle_dtype": jnp.bfloat16}),
        "dropped_visits": dict(lower={"capacity": even_share(
            {**cfg, "n_routed_experts": cfg["num_experts"]}, docs)}),
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
