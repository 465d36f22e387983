"""The controls of ``nemotron_twotower_ep2.score``: the plain reference put in
the program's place, computed one precision below what the configuration
states or with a part of the mathematics left out or planted wrong, and
compared with the float32 reference by the loop's own numbers. Each has to
come out as not correct.

    carried_state      a state-space layer starts a document from the last
                       document's final state (S = 0 before its first token)
    carried_taps       its convolution reads the last document's last three
                       tokens (nothing before the document)
    bf16_state         the recurrent state kept in bfloat16 (float32)
    group_off          head h reads B and C of group h mod 8 (h // 8)
    no_conv_bias       the convolution without its bias
    no_skip            y_t = S_t C_t (+ D_h x_t)
    norm_before_gate   Ng(y) * silu(z) (the gate BEFORE the norm)
    no_dt_bias         dt = softplus(dt) (softplus(dt + dt_bias))
    relu_not_squared   an expert's unit relu(u W_up) W_down (relu(.)^2)
    gated_experts      a SiLU-gated unit of the same width, its gate matrix
                       from the seed, in every expert's place (two matrices)
    attn_gate_on       the softmax layer's output under a sigmoid gate (none)
    int8_weights       every matrix through int8's 255 levels (bfloat16 weights)
    bf16_router        the router's scores, bias and gates in bfloat16 (float32)
    dropped_visits     an expert takes no more visits from a document than its
                       even share, as a capacity would have it (no visit dropped)

Judged as ``controls_docs.py`` judges Solar's: the numbers go through
``window.judge`` against the cell's own limits. ``test_controls_nemotron.py``
does that at a size a test run can hold; on the chip, at the cell's own
widths and limits, over a step's worth of a seed's documents
(benchmark/TOKEN_DOCS_NEMOTRON.md has the readings; the exit code is the
number of controls that passed as correct):

    python3 -m benchmark.tests.controls_nemotron --seeds 1
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from benchmark.loops import score_docs
from benchmark.tests.controls import load_cell
from benchmark.tests.controls_docs import even_share, judged, step_documents

CELL = "nemotron_twotower_ep2.score"
CONTROLS = ("carried_state", "carried_taps", "bf16_state", "group_off", "no_conv_bias", "no_skip",
            "norm_before_gate", "no_dt_bias", "relu_not_squared", "gated_experts", "attn_gate_on",
            "int8_weights", "bf16_router", "dropped_visits")


def control_numbers(model, cfg: dict, mix: dict, seed: int, names=None, tokens=None) -> dict:
    """{control: the loop's numbers, reference-with-the-departure against
    reference, and the departure's own recurrence and router held to float64}."""
    import jax.numpy as jnp

    docs = step_documents(cfg, mix, seed, tokens or mix["batch"] * mix["row_tokens"])
    rng = np.random.default_rng([int(seed), 0x43544C])
    at = [sorted(rng.choice(len(d) - 1, size=min(4, len(d) - 1), replace=False).tolist())
          for d in docs]
    plain = model.reference_weights(seed, cfg)
    head = int(rng.integers(cfg["mamba_num_heads"]))
    departures = {
        "carried_state": dict(carry="state"),
        "carried_taps": dict(carry="taps"),
        "bf16_state": dict(lower={"state_dtype": jnp.bfloat16}),
        "group_off": dict(lower={"group_off": True}),
        "no_conv_bias": dict(lower={"no_conv_bias": True}),
        "no_skip": dict(lower={"no_skip": True}),
        "norm_before_gate": dict(lower={"norm_before_gate": True}),
        "no_dt_bias": dict(lower={"no_dt_bias": True}),
        "relu_not_squared": dict(lower={"relu_not_squared": True}),
        "gated_experts": dict(weights=model.reference_weights(seed, cfg, gated=True)),
        "attn_gate_on": dict(lower={"attn_gate_on": True}),
        "int8_weights": dict(weights=model.reference_weights(seed, cfg, model.through_int8)),
        "bf16_router": dict(lower={"router_dtype": jnp.bfloat16}),
        "dropped_visits": dict(lower={"capacity": even_share(cfg, docs)}),
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
