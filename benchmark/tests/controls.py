"""The controls: the plain reference put in the program's place, computed
one precision below what the configuration states, and compared with the
reference by the loop's own numbers. Each has to come out as not correct.

    train  tables (and with them the row gradients) kept in bfloat16 where
           the configuration states float32
    score  rows rounded to int8's 255 levels where the configuration states
           bfloat16 activations (a table stored in bfloat16 scores the same
           bit for bit: ``forward`` rounds every row to bfloat16 itself)

``test_controls.py`` runs them at a size a test run can hold. On the chip,
at the cell's own size (PERF.md has the readings):

    python3 -m benchmark.tests.controls --workload criteo_mlperf.train --seeds 1 2 3
    python3 -m benchmark.tests.controls --workload criteo_mlperf.train --seeds 1 2 3 --fault half_batch

The second line plants the fault the losses are held against (part of a
batch left out) in the reference and reads how far the losses move.
"""

from __future__ import annotations

import argparse
import json
import os

import numpy as np

from benchmark.data import criteo
from benchmark.loops import score as score_loop
from benchmark.loops import train as train_loop
from benchmark.models import dlrm

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def expected_rows(cfg: dict, seed: int, rows: int) -> np.ndarray:
    cols = criteo.shard_columns(seed, 0, rows, cfg["cardinalities"],
                                    cfg["key_law_exponent"], cfg["label_positive_rate"])
    return criteo.expected_rows(*cols)


def train_control(cfg: dict, mix: dict, seed: int, table_dtype="bfloat16") -> dict:
    batch, steps = mix["batch"], mix["verify_steps"]
    expected = expected_rows(cfg, seed, steps * batch)
    todo = train_loop.plan(cfg, mix, dlrm, seed, expected)
    views = [
        train_loop.reference_view(
            dlrm.reference_train(cfg, seed, expected, steps, batch, table_dtype=dt),
            todo["touched"], todo["init_untouched"])
        for dt in (table_dtype, "float32")
    ]
    return train_loop.numbers(cfg, dlrm.init_mlps(seed, cfg), *views)[0]


def score_control(cfg: dict, mix: dict, seed: int, table_dtype="int8") -> dict:
    batch = mix["batch"]
    expected = expected_rows(cfg, seed, mix["verify_batches"] * batch)
    got = dlrm.reference_score(cfg, seed, expected, batch, table_dtype=table_dtype)
    want = dlrm.reference_score(cfg, seed, expected, batch)
    return score_loop.logit_gaps(got, want)


def half_batch_fault(cfg: dict, mix: dict, seed: int) -> dict:
    """The fault the loss is held against: part of a batch left out. The
    reference on batches whose second half repeats the first (the mean is
    then over half the rows), compared with the reference by its losses."""
    batch, steps = mix["batch"], mix["verify_steps"]
    expected = expected_rows(cfg, seed, steps * batch)
    halved = expected.reshape(steps, batch, -1).copy()
    halved[:, batch // 2:] = halved[:, : batch // 2]
    got, want = (
        dlrm.reference_train(cfg, seed, rows, steps, batch)["losses"]
        for rows in (halved.reshape(expected.shape), expected)
    )
    return {f"loss_gap_step{s + 1}": abs(g - w) / abs(w) for s, (g, w) in enumerate(zip(got, want))}


CONTROLS = {"train": train_control, "score": score_control}
FAULTS = {"half_batch": half_batch_fault}


def load_cell(workload: str):
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(os.path.dirname(HERE), config["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    return cfg, mix


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), help="a planted fault instead of the control")
    args = ap.parse_args(argv)
    import jax

    cfg, mix = load_cell(args.workload)
    for seed in args.seeds:
        read = FAULTS[args.fault] if args.fault else CONTROLS[mix["loop"]]
        numbers = read(cfg, mix, seed)
        print(f"[{args.fault or 'control'}] " + json.dumps(
            {"workload": args.workload, "seed": seed, "platform": jax.devices()[0].platform,
             "numbers": numbers, "limits": {k: mix["limits"][k] for k in numbers}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
