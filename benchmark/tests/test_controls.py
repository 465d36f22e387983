"""The controls at a size a test run can hold: each must fail one of its
cell's limits, and the reference in its own place must pass them all."""

import pytest

from benchmark import run as bench_run
from benchmark.tests import controls

SEEDS = (11, 2 ** 31 + 12, 13)


def small_cell(workload):
    """The cell at its files' own rehearsal sizes, its limits left the cell's."""
    cfg, mix = controls.load_cell(workload)
    sizes = {k: v for k, v in mix["rehearsal"].items() if k != "limits"}
    return bench_run.at_rehearsal_size(cfg), {**mix, **sizes}


@pytest.mark.parametrize("workload", ["criteo_mlperf.train", "criteo_mlperf.score"])
@pytest.mark.parametrize("seed", SEEDS)
def test_the_control_is_not_correct(workload, seed):
    cfg, mix = small_cell(workload)
    numbers = controls.CONTROLS[mix["loop"]](cfg, mix, seed)
    outside = {k: v for k, v in numbers.items() if not v <= mix["limits"][k]}
    assert outside, f"the control passed every limit: {numbers}"


@pytest.mark.parametrize("workload,same", [
    ("criteo_mlperf.train", "float32"), ("criteo_mlperf.score", "bfloat16"),
])
def test_the_stated_precision_is_correct(workload, same):
    """float32 tables for float32 tables; and rows through bfloat16 where
    the activations are bfloat16 anyway: no number moves at all."""
    cfg, mix = small_cell(workload)
    numbers = controls.CONTROLS[mix["loop"]](cfg, mix, SEEDS[0], table_dtype=same)
    assert all(v == 0.0 for v in numbers.values()), numbers


@pytest.mark.parametrize("seed", SEEDS)
def test_half_a_batch_left_out_moves_the_loss(seed):
    cfg, mix = small_cell("criteo_mlperf.train")
    limits = dict(mix["limits"], **mix["rehearsal"]["limits"])
    numbers = controls.half_batch_fault(cfg, mix, seed)
    assert numbers["loss_gap_step1"] > limits["loss_gap_step1"], numbers
