"""The controls of ``olmo_hybrid_7b_pp4.score``: the plain reference put in the
program's place, computed one precision below what the configuration states,
with the OTHER reading of an item the configuration ``assumed``, or with a
fault planted, and compared with the float32 reference by the loop's own
numbers. Each has to come out as not correct.

    carried_state      a delta-net layer starts a document from the last
                       document's final state (S = 0 before its first token)
    carried_taps       its convolutions read the last document's last three
                       tokens (nothing before the document)
    bf16_state         the recurrent state kept in bfloat16 (float32)
    int8_weights       every matrix through int8's 255 levels (bfloat16 weights)
    pre_norm_gdn       a delta-net layer's mixer pre-normed, x + M(N(x; w_1))
                       (normed on its way out alone: x + N(M(x); w_1))
    per_head_qk_norm   the Q/K norm over each head's 128 channels (over the
                       whole projection of 3,840)
    no_qk_norm         no Q/K norm
    rotary_on_full     the full-attention heads turned by their positions,
                       theta 500,000 (no positions)
    sigmoid_gate       2 sigmoid(z) on a head's normed output (silu(z))
    beta_times_1       beta in (0, 1): no negative eigenvalue (2 sigmoid in (0, 2))
    scale_by_dv        q times 192^-1/2, the value width's (96^-1/2, the key width's)

Judged as ``controls_docs.py`` judges Solar's: the numbers go through
``window.judge`` against the cell's own limits. ``test_controls_olmo_hybrid.py``
does that at a size a test run can hold; on the chip, at the cell's own widths
and limits, over some of a seed's documents
(benchmark/TOKEN_DOCS_OLMO_HYBRID.md has the readings; the exit code is the
number of controls that passed as correct):

    python3 -m benchmark.tests.controls_olmo_hybrid --seeds 1 --tokens 4096
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from benchmark.loops import score_docs
from benchmark.tests.controls import load_cell
from benchmark.tests.controls_docs import judged, step_documents

CELL = "olmo_hybrid_7b_pp4.score"
CONTROLS = ("carried_state", "carried_taps", "bf16_state", "int8_weights", "pre_norm_gdn", "per_head_qk_norm",
            "no_qk_norm", "rotary_on_full", "sigmoid_gate", "beta_times_1", "scale_by_dv")


def control_numbers(model, cfg: dict, mix: dict, seed: int, names=None, tokens=None) -> dict:
    """{control: the loop's numbers, reference-with-the-departure against
    reference, and the departure's own recurrence held to float64}."""
    import jax.numpy as jnp

    docs = step_documents(cfg, mix, seed, tokens or mix["batch"] * mix["row_tokens"])
    rng = np.random.default_rng([int(seed), 0x43544C])
    at = [sorted(rng.choice(len(d) - 1, size=min(4, len(d) - 1), replace=False).tolist())
          for d in docs]
    plain = model.reference_weights(seed, cfg)
    head = int(rng.integers(cfg["linear_num_value_heads"]))
    departures = {
        "carried_state": dict(carry="state"),
        "carried_taps": dict(carry="taps"),
        "bf16_state": dict(lower={"state_dtype": jnp.bfloat16}),
        "int8_weights": dict(weights=model.reference_weights(seed, cfg, model.through_int8)),
        **{name: dict(lower={name: True}) for name in CONTROLS[4:]},
    }
    want = model.reference_score(cfg, docs, plain, at)
    out = {}
    for name in names or CONTROLS:
        kw = dict(departures[name])
        got = model.reference_score(cfg, docs, kw.pop("weights", plain), at, probe_head=head, **kw)
        out[name] = {
            # as if packed into one row in this order: all but the first follow another
            **score_docs.gaps(got["logprob"], want["logprob"], np.concatenate(got["logits"]),
                              np.concatenate(want["logits"]), [i > 0 for i in range(len(docs))]),
            **model.probe_numbers(cfg, seed, got["scan"], got["router"]),
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
