"""trace_reduce.py on a trace worked by hand and on a recorded one.

``recorded_trace.json`` is the first three steps (972 operations) of
``criteo_mlperf.train``'s first traced run on a TPU v5 lite (PR 23), as
``trace_reduce.load_planes`` read them: the device's ``XLA Ops``, ``XLA
Modules`` and ``Steps`` lines and the loop thread's spans, times in ns from
the cut's start, operation names cut to 60 characters.
"""

import json
import os

import numpy as np

from benchmark.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_by_hand():
    planes = {
        "/device:TPU:0": {
            "XLA Ops": [("a", 0.0, 4e9), ("b", 2e9, 4e9), ("a", 10e9, 1e9), ("c", 20e9, 2e9)],
            "XLA Modules": [("jit_step", 0.0, 6e9), ("jit_split", 10e9, 1e9), ("jit_step", 20e9, 2e9)],
        },
        "/host:CPU": {"python3": [("observe", 5e9, 6e9), ("wait_batch", 12e9, 7e9),
                                  ("produce", 0.0, 30e9)]},
    }
    out = tr.reduce_planes(planes, window_s=25.0)
    assert out["events"] == 4 and out["chips"] == 1
    assert out["op_s"] == 11.0                      # 4 + 4 + 1 + 2
    assert out["busy_s"] == 9.0                     # [0, 6] + [10, 11] + [20, 22]
    assert out["steps"] == 2                        # jit_step has most device time
    assert out["top_ops"] == [["a", 5.0], ["b", 4.0], ["c", 2.0]]
    # gaps [6, 10] under observe and [11, 20] under wait_batch, longest first;
    # 'produce' is another thread's span and names no gap
    assert out["idle_gaps"] == [["wait_batch", 9.0], ["observe", 4.0]]


def test_union_and_gaps():
    assert tr.union_seconds([(0, 1), (1, 2), (5, 6), (0.5, 1.5)]) == 3.0
    assert tr.gaps_between([(5, 6), (0, 2), (1, 3)]) == [(3, 5)]
    assert tr.union_seconds([]) == 0.0


def test_on_the_recorded_trace():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        planes = json.load(f)
    ops = np.asarray([[s, s + d] for _, s, d in planes["/device:TPU:0"]["XLA Ops"]])
    window_s = 0.233254272
    out = tr.reduce_planes(planes, window_s)
    assert out["events"] == 972 and out["steps"] == 3
    assert abs(out["op_s"] - (ops[:, 1] - ops[:, 0]).sum() / 1e9) < 1e-12
    # the union, counted another way: between consecutive boundaries the
    # chip is busy where more operations have started than ended
    cuts = np.unique(ops)
    mids = (cuts[1:] + cuts[:-1]) / 2
    open_ = (ops[:, 0][None, :] <= mids[:, None]) & (mids[:, None] < ops[:, 1][None, :])
    busy = ((cuts[1:] - cuts[:-1]) * open_.any(axis=1)).sum() / 1e9
    assert abs(out["busy_s"] - busy) < 1e-9
    assert 0.2323 < out["busy_s"] < 0.2326        # three steps of 77.5 ms
    by_name = {}
    for name, _, d in planes["/device:TPU:0"]["XLA Ops"]:
        by_name[name] = by_name.get(name, 0.0) + d / 1e9
    assert out["top_ops"][0][0].startswith("%fusion.12 = f32[13631488,128]")
    assert all(abs(by_name[n] - s) < 1e-12 for n, s in out["top_ops"])
    assert [s for _, s in out["top_ops"]] == sorted(by_name.values(), reverse=True)[:10]
    # the longest gap is the 0.72 ms between split_wire and the first step
    assert abs(out["idle_gaps"][0][1] - 0.000723) < 2e-6
    assert all(name in tr.LOOP_SPANS + ("no_span",) for name, _ in out["idle_gaps"])
