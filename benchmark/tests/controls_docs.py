"""The controls of the token-document cells: the plain reference put in the
program's place, computed one precision below what the configuration
states or with a fault planted, and compared with the float32 reference by
the loop's own numbers. Each has to come out as not correct.

    int8_weights    every matrix through int8's 255 levels (the configuration
                    states bfloat16 weights)
    bf16_state      the delta rule's recurrent state kept in bfloat16 (float32)
    bf16_router     the router's scores computed in bfloat16 (float32)
    carried_state   a delta-rule layer starts a document from the last
                    document's final state (S = 0 before a document's first token)
    dropped_visits  an expert takes no more visits from a document than its
                    even share, as a capacity would have it (no visit dropped)

Every control is judged as a run is: its numbers go through
``window.judge`` against the cell's own limits (``judged``), and a control
that comes out ``correct`` is a failure of the comparison.
``test_controls_docs.py`` does that at a size a test run can hold, with the
mix's rehearsal limits. On the chip, at the cell's own widths and limits,
over a step's worth of a seed's documents (PERF.md has the readings; the
exit code is the number of controls that passed as correct):

    python3 -m benchmark.tests.controls_docs --workload solar_open2_ep8.score --seeds 1 2
"""

from __future__ import annotations

import argparse
import json

import numpy as np

from benchmark.data import token_docs
from benchmark.harness import window
from benchmark.loops import score_docs
from benchmark.tests.controls import load_cell


def step_documents(cfg: dict, mix: dict, seed: int, tokens: int) -> list:
    """Documents of the seed's first shard, end id appended: the longest of the
    first 64 that fits half of ``tokens``, then the others in the order
    written while they fit, so that long and short ones are always in."""
    flat, offsets = token_docs.shard_docs(seed, 0, mix["docs_per_shard"], cfg)
    docs = [np.append(flat[a:b], 0).astype(np.int32)
            for a, b in zip(offsets[:64], offsets[1:65])]
    first = max((d for d in docs if len(d) <= max(tokens // 2, 2)), key=len, default=docs[0])
    picked, total = [first], len(first)
    for doc in docs:
        if doc is not first and total + len(doc) <= tokens:
            picked.append(doc)
            total += len(doc)
    return picked


def even_share(cfg: dict, docs: list) -> int:
    """Visits an expert gets from the longest document if the router spreads them evenly."""
    longest = max(len(d) for d in docs)
    return max(1, longest * cfg["num_experts_per_tok"] // cfg["n_routed_experts"])


def control_numbers(model, cfg: dict, mix: dict, seed: int, names=None, tokens=None) -> dict:
    """{control: the loop's numbers, reference-with-the-departure against
    reference, and the departure's own probes held to float64}."""
    import jax.numpy as jnp

    docs = step_documents(cfg, mix, seed, tokens or mix["batch"] * mix["row_tokens"])
    rng = np.random.default_rng([int(seed), 0x43544C])
    at = [sorted(rng.choice(len(d) - 1, size=min(4, len(d) - 1), replace=False).tolist())
          for d in docs]
    plain = model.reference_weights(seed, cfg)
    head = int(rng.integers(cfg["linear_attn_config"]["num_heads"]))
    departures = {
        "int8_weights": dict(weights=model.reference_weights(seed, cfg, model.through_int8)),
        "bf16_state": dict(lower={"state_dtype": jnp.bfloat16}),
        "bf16_router": dict(lower={"router_dtype": jnp.bfloat16}),
        "carried_state": dict(carry_state=True),
        "dropped_visits": dict(lower={"capacity": even_share(cfg, docs)}),
    }
    want = model.reference_score(cfg, docs, plain, at)
    out = {}
    for name in names or departures:
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


class Lines:
    """What ``window.judge`` asks of a run's environment."""

    def __init__(self):
        self.compared = {}

    def info(self, what: str, **fields) -> None:
        print(f"[{what}] " + json.dumps(fields, sort_keys=True), flush=True)


def judged(numbers: dict, limits: dict) -> tuple:
    """(``correct`` as a run would be judged on these numbers, the numbers
    outside their limits)."""
    env = Lines()
    correct = window.judge(env, numbers, limits)
    return correct, sorted(k for k, (v, limit) in env.compared.items() if not v <= limit)


def main(argv=None) -> int:
    import importlib

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*")
    ap.add_argument("--tokens", type=int)
    args = ap.parse_args(argv)
    import jax

    cfg, mix = load_cell(args.workload)
    model = importlib.import_module("benchmark.models." + cfg["model"])
    passed = 0
    for seed in args.seeds:
        for name, numbers in control_numbers(model, cfg, mix, seed, args.controls,
                                             args.tokens).items():
            correct, outside = judged(numbers, mix["limits"])
            passed += int(correct)
            print("[control] " + json.dumps(
                {"workload": args.workload, "seed": seed, "control": name, "correct": correct,
                 "platform": jax.devices()[0].platform, "numbers": numbers, "outside": outside}),
                flush=True)
    return passed


if __name__ == "__main__":
    raise SystemExit(main())
