"""CPU rehearsals of ``kimi_vl_a3b_lm.score`` (``--rehearse``: the widths,
the vocabulary and the rows cut to what a CPU walks in seconds): the result
line, and ``correct`` turning false when what this configuration added is
broken underneath: keys that keep the row's positions, a router that
forgets its bias, a mask that lets a document see its neighbour, an expert
visit reported dropped."""

import pytest

from benchmark.tests.test_rehearsal import rehearse
from benchmark.tests.test_rehearsal_docs import compared

CELL = "kimi_vl_a3b_lm.score"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_result_line(capsys, trace):
    rc, result, earlier = rehearse(capsys, CELL, trace)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["metrics"] == {} and result["rehearsal"] is True
    numbers = compared(earlier)
    assert {"logprob_median_gap", "logit_rms_gap", "boundary_median_gap", "repeat_gap",
            "docs_missing", "router_gate_gap", "moe_visits_dropped"} <= set(numbers)
    assert "scan_state_gap" not in numbers and all(c["ok"] for c in numbers.values())
    if trace == "1":
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def broken(capsys, monkeypatch, target, name, replacement):
    monkeypatch.setattr(target, name, replacement)
    _, result, earlier = rehearse(capsys, CELL)
    assert result["correct"] is False
    return compared(earlier)


def test_keys_that_keep_the_rows_positions_are_not_correct(capsys, monkeypatch):
    """The restart applied to the queries alone: every second rotary call
    (the keys') is given the index in the row."""
    import jax.numpy as jnp
    from tpu_tfrecord.models import lm

    sound, calls = lm.rotary, []

    def one_sided(x, positions, theta):
        calls.append(1)
        if len(calls) % 2:  # mla_mixer turns the keys first
            positions = jnp.broadcast_to(jnp.arange(positions.shape[1]), positions.shape)
        return sound(x, positions, theta)

    numbers = broken(capsys, monkeypatch, lm, "rotary", one_sided)
    assert not numbers["boundary_median_gap"]["ok"] and numbers["repeat_gap"]["ok"]


def test_a_router_that_forgets_its_bias_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import moe

    sound = moe.route_top_k
    numbers = broken(capsys, monkeypatch, moe, "route_top_k",
                     lambda x, router, top_k, scale=1.0, bias=None: sound(x, router, top_k, scale))
    assert not numbers["router_gate_gap"]["ok"]


def test_a_document_that_sees_its_neighbour_is_not_correct(capsys, monkeypatch):
    import jax.numpy as jnp
    from tpu_tfrecord.models import lm

    sound = lm._attend
    numbers = broken(capsys, monkeypatch, lm, "_attend",
                     lambda q, k, v, segments, block: sound(q, k, v, jnp.ones_like(segments), block))
    assert not numbers["boundary_median_gap"]["ok"]


def test_a_dropped_visit_is_not_correct(capsys, monkeypatch):
    """The expert loop stops one tile short: the counter says so."""
    import jax
    from tpu_tfrecord.models import moe

    sound = jax.lax.fori_loop
    monkeypatch.setattr(moe.jax.lax, "fori_loop",
                        lambda lower, upper, body, init: sound(lower, upper - 1, body, init))
    try:
        _, result, earlier = rehearse(capsys, CELL)
    finally:
        monkeypatch.undo()
    numbers = compared(earlier)
    assert result["correct"] is False and numbers["moe_visits_dropped"]["value"] > 0
