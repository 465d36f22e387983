"""readers/scope_once.py: a loop's own event is left out, its body's
operations are counted, and where nothing loops the sums are
``trace_scope``'s; readers/scope_roofline.py divides by that time."""

import pytest

from benchmark.readers import scope_once, scope_roofline, trace_scope as ts
from benchmark.tests.test_trace_scope import FakeEnv, ctx_for, hand_made_xspace

MS = 1e6  # ns


def looped_planes():
    """One step: a fusion, a ``while`` of 30 ms around two passes of a
    10 ms and a 5 ms operation (a nested ``while`` around the 5 ms one),
    then a fusion of no scope that follows the loop at once."""
    events = {
        1: {"display_name": "fusion.1", "tf_op": "jit(f)/tfr.kda_proj/dot:"},
        2: {"display_name": "while.7", "tf_op": "jit(f)/tfr.kda_scan/while:"},
        3: {"display_name": "fusion.2", "tf_op": "jit(f)/tfr.kda_scan/while/body/dot:"},
        4: {"display_name": "while.8", "tf_op": "jit(f)/tfr.kda_scan/while/body/while:"},
        5: {"display_name": "fusion.3", "tf_op": "jit(f)/tfr.kda_scan/while/body/while/body/mul:"},
        6: {"display_name": "copy.4"},
        # a loop that ran nothing is an operation like any other
        7: {"display_name": "while.9", "tf_op": "jit(f)/tfr.moe_experts/while:"},
    }
    ops = [(1, 0.0, 4 * MS), (2, 4 * MS, 30 * MS),
           (3, 4 * MS, 10 * MS), (4, 14 * MS, 5 * MS), (5, 14 * MS, 5 * MS),
           (3, 19 * MS, 10 * MS), (4, 29 * MS, 5 * MS), (5, 29 * MS, 5 * MS),
           (6, 34 * MS, 1 * MS), (7, 35 * MS, 0.5 * MS), (1, 35.5 * MS, 4 * MS)]
    return {"/device:TPU:0": {"events": events, "lines": {"XLA Ops": ops}}}


def test_a_loops_own_event_is_left_out_and_its_body_counted():
    by_scope, left_out = scope_once.seconds_once(looped_planes())
    assert by_scope == pytest.approx({"tfr.kda_proj": 0.008, "tfr.kda_scan": 0.030,
                                      None: 0.001, "tfr.moe_experts": 0.0005})
    assert left_out == pytest.approx({"while.7": 0.030, "while.8": 0.010})
    # trace_scope counts the loops twice and thrice
    twice, _ = ts.scoped_seconds(looped_planes(), {})
    assert twice["tfr.kda_scan"] == pytest.approx(0.070)
    # the operations counted once add up to the chip's busy time
    from benchmark.harness.trace_reduce import union_seconds
    ops = looped_planes()["/device:TPU:0"]["lines"]["XLA Ops"]
    assert sum(by_scope.values()) == pytest.approx(union_seconds((s, s + d) for _, s, d in ops) / 1e9)


def test_where_nothing_loops_the_sums_are_trace_scopes(tmp_path):
    ctx = ctx_for(tmp_path, hand_made_xspace(), steps=2)
    assert scope_once.read(ctx, scopes=["tfr.table_scatter"]) == pytest.approx(10.0)
    assert scope_once.read(ctx, scopes=["tfr.gather", "tfr.interaction"]) == pytest.approx(5.0)
    assert scope_once.read(ctx, quantity="all") == pytest.approx(15.5)
    assert [what for what, _ in ctx["env"].lines] == ["scopes_once"]  # parsed once
    with pytest.raises(ValueError):
        scope_once.read(ctx, quantity="no_such")


def test_nothing_to_read_is_none(tmp_path):
    plain = hand_made_xspace().replace(b"tfr.", b"xyz.")  # the parent: no scope anywhere
    ctx = ctx_for(tmp_path, plain, steps=2)
    assert scope_once.read(ctx, scopes=["tfr.kda_scan"]) is None
    assert scope_once.read(ctx, quantity="all") is None
    ctx["peaks"] = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    assert scope_roofline.read(ctx, scope="tfr.kda_scan") is None
    empty = {"env": FakeEnv(str(tmp_path / "nowhere")), "trace": {"steps": 2}, "peaks": None}
    assert scope_once.read(empty, scopes=["tfr.gqa"]) is None
    assert scope_roofline.read(empty, scope="tfr.gqa") is None


def test_a_share_of_the_roofline_divides_by_the_time_counted_once(tmp_path):
    class Model:
        @staticmethod
        def needs(cfg, batch, loop):
            return {"scopes": {"tfr.table_scatter": {"flops": 2e9, "bytes": 1e8}}}

    ctx = ctx_for(tmp_path, hand_made_xspace(), steps=2)
    env = ctx["env"]
    env.model, env.cfg, env.mix = Model, {}, {"batch": 2, "loop": "score_docs"}
    ctx["peaks"] = {"flops_per_s": 1e12, "bytes_per_s": 1e11}
    # 2 ms by FLOPs, 1 ms by bytes, 10 ms a step under the scope
    assert scope_roofline.read(ctx, scope="tfr.table_scatter") == pytest.approx(20.0)
    assert scope_roofline.read(ctx, scope="tfr.gather") is None  # needs() names no such scope
