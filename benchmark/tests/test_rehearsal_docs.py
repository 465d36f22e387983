"""CPU rehearsals of ``solar_open2_ep8.score`` (``--rehearse``: the widths,
the vocabulary and the rows cut to what a CPU walks in seconds): the result
line, and ``correct`` turning false when the timed path is broken
underneath: a token altered on its way to the device, a document left out,
a state that outlives its document, taps that reach across a boundary, an
expert visit reported dropped, an altered answer."""

import json

import numpy as np
import pytest

from benchmark.tests.test_rehearsal import rehearse

CELL = "solar_open2_ep8.score"
GAPS = ("boundary_median_gap", "logprob_median_gap", "logprob_p90_gap", "logprob_rms_gap",
        "doc_score_gap", "logit_rms_gap", "scan_state_gap", "router_gate_gap")


def compared(earlier):
    return {c["number"]: c for c in (json.loads(x.split(" ", 1)[1]) for x in earlier
                                     if x.startswith("[compare]"))}


@pytest.mark.parametrize("trace", ["0", "1"])
def test_result_line(capsys, trace):
    rc, result, earlier = rehearse(capsys, CELL, trace)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["metrics"] == {} and result["rehearsal"] is True
    numbers = compared(earlier)
    assert {"logprob_median_gap", "logit_rms_gap", "repeat_gap", "docs_missing", "scan_state_gap",
            "router_gate_gap", "moe_visits_dropped"} <= set(numbers)
    assert all(c["ok"] for c in numbers.values())
    packed = [json.loads(x.split(" ", 1)[1]) for x in earlier if x.startswith("[packed]")][0]
    assert 0.5 < packed["pack_density"] <= 1.0 and packed["documents"] > 0
    if trace == "1":
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def broken(capsys, monkeypatch, target, name, replacement):
    monkeypatch.setattr(target, name, replacement)
    _, result, earlier = rehearse(capsys, CELL)
    assert result["correct"] is False
    return compared(earlier)


def test_an_altered_token_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.tpu.ingest import TokenPacker

    sound, calls = TokenPacker.pop, []

    def corrupting(self):
        batch = sound(self)
        if batch is not None:
            calls.append(1)
            if len(calls) == 3:
                batch["tokens"][1, 2] += 1
        return batch

    numbers = broken(capsys, monkeypatch, TokenPacker, "pop", corrupting)
    assert numbers["tokens_altered"]["value"] == 1.0 and numbers["docs_missing"]["value"] == 1.0


def test_a_document_left_out_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.tpu.ingest import TokenPacker

    sound = TokenPacker.feed_docs

    def forgetful(self, docs):
        docs = list(docs)
        return sound(self, docs[1:] if len(docs) > 1 else docs)

    numbers = broken(capsys, monkeypatch, TokenPacker, "feed_docs", forgetful)
    assert numbers["docs_missing"]["value"] > 0 and numbers["tokens_altered"]["value"] == 0


def test_a_state_that_outlives_its_document_is_not_correct(capsys, monkeypatch):
    """The recurrence told that a row is one document: taps still stop at
    the boundary, the state does not."""
    import jax.numpy as jnp
    from tpu_tfrecord.models import linear_attn

    sound = linear_attn.delta_rule_chunked

    def carried(q, k, v, log_decay, beta, segments, **kw):
        return sound(q, k, v, log_decay, beta, jnp.ones_like(segments), **kw)

    numbers = broken(capsys, monkeypatch, linear_attn, "delta_rule_chunked", carried)
    moved = [k for k, c in numbers.items() if not c["ok"]]
    assert "scan_state_gap" in moved and set(moved) <= set(GAPS) and numbers["repeat_gap"]["ok"]


def test_taps_that_cross_a_boundary_are_not_correct(capsys, monkeypatch):
    import jax.numpy as jnp
    from tpu_tfrecord.models import linear_attn

    sound = linear_attn.short_conv
    monkeypatch.setattr(linear_attn, "short_conv",
                        lambda x, taps, segments: sound(x, taps, jnp.ones_like(segments)))
    _, result, earlier = rehearse(capsys, CELL)
    numbers = compared(earlier)
    assert result["correct"] is False and not numbers["boundary_median_gap"]["ok"]


def test_a_dropped_visit_is_not_correct(capsys, monkeypatch):
    """The expert loop stops one tile short: the counter says so."""
    import jax
    from tpu_tfrecord.models import moe

    sound = jax.lax.fori_loop

    def short(lower, upper, body, init):
        return sound(lower, upper - 1, body, init)

    monkeypatch.setattr(moe.jax.lax, "fori_loop", short)
    try:
        _, result, earlier = rehearse(capsys, CELL)
    finally:
        monkeypatch.undo()
    numbers = compared(earlier)
    assert result["correct"] is False and numbers["moe_visits_dropped"]["value"] > 0


def test_an_altered_answer_is_not_correct(capsys, monkeypatch):
    from tpu_tfrecord.models import lm

    sound = lm.score

    def altered(*args):
        out = sound(*args)
        return {**out, "logprob": out["logprob"] * 1.05}

    numbers = broken(capsys, monkeypatch, lm, "score", altered)
    assert not numbers["logprob_median_gap"]["ok"]
