"""The controls of ``gigachat35_ep16.score``: the plain reference put in the
program's place, computed one precision below what the configuration states or
with a part of the mathematics left out or planted wrong, and compared with
the float32 reference by the loop's own numbers. Each has to come out as not
correct.

    carried_state      a delta-net layer starts a document from the last
                       document's final state (S = 0 before its first token)
    bf16_state         the recurrent state kept in bfloat16 (float32)
    per_key_head_off   value head h reads key head h mod 32 (h // 2)
    decay_per_channel  the decay's rate times a fixed per-channel factor
                       (one decay a head and token)
    beta_times_2       a beta in (0, 2) (in (0, 1))
    no_attn_gate       latent attention's output as it is (times sigmoid(u Wg))
    no_yarn            plain rotary frequencies and softmax scale (YaRN's)
    no_branch_norms    x + branch (x + N(branch): the sandwich's second norm)
    plain_norm_gain    a norm's gain read as 1 + w (2 sigmoid(w))
    int8_weights       every matrix through int8's 255 levels (bfloat16 weights)
    bf16_router        the router's scores, bias and gates in bfloat16 (float32)
    bf16_rotary        the rotary angles computed in bfloat16 (float32)
    dropped_visits     an expert takes no more visits from a document than its
                       even share, as a capacity would have it (no visit dropped)

Judged as ``controls_docs.py`` judges Solar's: the numbers go through
``window.judge`` against the cell's own limits. ``test_controls_gigachat35.py``
does that at a size a test run can hold; on the chip, at the cell's own
widths and limits, over a step's worth of a seed's documents
(benchmark/TOKEN_DOCS_GIGACHAT35.md has the readings; the exit code is the
number of controls that passed as correct):

    python3 -m benchmark.tests.controls_gigachat35 --seeds 1
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from benchmark.loops import score_docs
from benchmark.tests.controls import load_cell
from benchmark.tests.controls_docs import even_share, judged, step_documents

CELL = "gigachat35_ep16.score"
CONTROLS = ("carried_state", "bf16_state", "per_key_head_off", "decay_per_channel", "beta_times_2",
            "no_attn_gate", "no_yarn", "no_branch_norms", "plain_norm_gain", "int8_weights",
            "bf16_router", "bf16_rotary", "dropped_visits")


def control_numbers(model, cfg: dict, mix: dict, seed: int, names=None, tokens=None) -> dict:
    """{control: the loop's numbers, reference-with-the-departure against
    reference, and the departure's own recurrence and router held to float64}."""
    import jax.numpy as jnp

    docs = step_documents(cfg, mix, seed, tokens or mix["batch"] * mix["row_tokens"])
    rng = np.random.default_rng([int(seed), 0x43544C])
    at = [sorted(rng.choice(len(d) - 1, size=min(4, len(d) - 1), replace=False).tolist())
          for d in docs]
    plain = model.reference_weights(seed, cfg)
    head = int(rng.integers(cfg["linear_num_value_heads"]))
    departures = {
        "carried_state": dict(carry_state=True),
        "bf16_state": dict(lower={"state_dtype": jnp.bfloat16}),
        "per_key_head_off": dict(lower={"per_key_head_off": True}),
        "decay_per_channel": dict(lower={"decay_per_channel": True}),
        "beta_times_2": dict(lower={"beta_times_2": True}),
        "no_attn_gate": dict(lower={"no_attn_gate": True}),
        "no_yarn": dict(lower={"no_yarn": True}),
        "no_branch_norms": dict(lower={"no_branch_norms": True}),
        "plain_norm_gain": dict(lower={"plain_norm_gain": True}),
        "int8_weights": dict(weights=model.reference_weights(seed, cfg, model.through_int8)),
        "bf16_router": dict(lower={"router_dtype": jnp.bfloat16}),
        "bf16_rotary": dict(lower={"angle_dtype": jnp.bfloat16}),
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
