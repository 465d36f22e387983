"""What both loops share: timed AOT compiles, the ingest check, the step
loop with two steps in flight, and the numbers a comparison prints.

The step loop is chip_smoke.py's ``phase_ingest_train`` loop (commit
0c9422d) without its bookkeeping: next wire batch -> split -> step, and the
completion of step n seen by blocking on an output of step n - in_flight.
"""

from __future__ import annotations

import time
from collections import deque

import numpy as np


def timed_compile(env, name: str, jitted, *args):
    """AOT-compile for ``args``; the seconds (a warm persistent cache shows
    here) and the compiler's memory estimate go on information lines."""
    lowered = jitted.lower(*args)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    env.info("compile", program=name, seconds=time.perf_counter() - t0)
    mem = compiled.memory_analysis()
    if mem is not None:
        env.info("memory_estimate", program=name,
                 argument_bytes=int(mem.argument_size_in_bytes),
                 temp_bytes=int(mem.temp_size_in_bytes),
                 alias_bytes=int(mem.alias_size_in_bytes))
    return compiled


def check_ingest(env, feed, split_c, expected: np.ndarray, rows_per_table: int) -> dict:
    """One epoch through reader -> pack -> wire -> DeviceIterator -> split,
    every batch fetched back: the label and dense lanes of the wire and the
    unpacked indices must equal the generator's expected rows, row for row."""
    from benchmark.harness import criteo_io

    keep, at, bad_rows, batches = criteo_io.KEEP, 0, 0, 0
    for gb in feed:
        got_head = np.asarray(gb["wire"])[:, :keep]
        got_cat = np.asarray(split_c(gb)["cat"])
        want = expected[at: at + got_head.shape[0]]
        if want.shape[0] != got_head.shape[0]:
            bad_rows += got_head.shape[0]
        else:
            ok = (got_head == want[:, :keep]).all(axis=1) & (
                got_cat == want[:, keep:] % rows_per_table
            ).all(axis=1)
            bad_rows += int((~ok).sum())
        at += got_head.shape[0]
        batches += 1
    return {"rows_read": at, "rows_written": int(expected.shape[0]),
            "rows_altered": bad_rows, "batches": batches}


def compile_and_check_ingest(env, split_j, step_name: str, step_j, *state, rows_per_table: int):
    """Compile the cell's two programs on the first batch of a one-epoch
    feed, then walk that epoch through :func:`check_ingest`.
    Returns (split_c, step_c, ingest)."""
    from benchmark.harness.feed import Feed

    feed = Feed(env.data_dir, env.mix, env.mesh, num_epochs=1)
    try:
        first = next(feed)
        split_c = timed_compile(env, "split_wire", split_j, first)
        step_c = timed_compile(env, step_name, step_j, *state, split_c(first))

        def rewound():
            yield first
            yield from feed

        ingest = check_ingest(env, rewound(), split_c, env.expected, rows_per_table)
    finally:
        feed.close()
    env.info("ingest", **ingest)
    return split_c, step_c, ingest


class StepLoop:
    """Drives ``one_step(wire batch) -> a device array to observe`` from a
    feed, keeping ``in_flight`` steps dispatched beyond the one observed.
    Each observed completion keeps the seconds its call of :meth:`step`
    spent waiting for the batch, dispatching and observing, so that a long
    gap between two completions can be laid at one of the three."""

    def __init__(self, feed, one_step, observe, spans, in_flight: int):
        self.feed, self.one_step, self.observe = feed, one_step, observe
        self.spans, self.in_flight = spans, in_flight
        self.pending = deque()
        self.done_at = []  # perf_counter at each observed completion
        self.spent = []  # (wait, dispatch, observe) seconds before each of them

    def _observe_one(self, t_wait: float = 0.0, t_dispatch: float = 0.0):
        t0 = time.perf_counter()
        with self.spans.span("observe"):
            self.observe(self.pending.popleft())
        t1 = time.perf_counter()
        self.done_at.append(t1)
        self.spent.append((t_wait, t_dispatch, t1 - t0))

    def step(self):
        t0 = time.perf_counter()
        with self.spans.span("wait_batch"):
            gb = next(self.feed)
        t1 = time.perf_counter()
        self.pending.append(self.one_step(gb))
        if len(self.pending) > self.in_flight:
            self._observe_one(t1 - t0, time.perf_counter() - t1)

    def drain(self):
        while self.pending:
            self._observe_one()

    def run_for(self, seconds: float) -> dict:
        """Dispatch steps for ``seconds``, then wait for every one of them:
        the window ends when the last step's output is ready."""
        self.drain()
        first = len(self.done_at)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.step()
        self.drain()
        t1 = time.perf_counter()
        spent = np.asarray(self.spent[first:]).reshape(-1, 3)
        return {"t0": t0, "t1": t1, "steps": len(spent),
                "gaps_s": np.diff(np.asarray(self.done_at[first:])),
                "spent_s": spent[1:], "waited_s": float(spent[:, 0].sum())}


def gap(got, want) -> float:
    """max|got - want| over max|want|."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def rms_gap(got, want, base=None) -> float:
    """rms(got - want) over rms(want - base); ``base`` defaults to zero."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    moved = want if base is None else want - np.asarray(base, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / max(np.sqrt(np.mean(moved ** 2)), 1e-30))


def norm_gap_worst_leaf(got: dict, want: dict) -> tuple:
    """Per leaf ``|norm_got - norm_want| / max(norm_want, median leaf's
    norm_want)``; returns (worst, its leaf). Both are {leaf name: norm}."""
    floor = float(np.median(list(want.values())))
    worst, leaf = 0.0, ""
    for name, w in want.items():
        g = abs(got[name] - w) / max(w, floor, 1e-30)
        if g >= worst:
            worst, leaf = float(g), name
    return worst, leaf


def leaf_norms(after: dict, before: dict, scale: float = 1.0) -> dict:
    """{leaf: ||after - before|| * scale} over the MLP trees."""
    out = {}
    for tower in ("bottom", "top"):
        for i, (a, b) in enumerate(zip(after[tower], before[tower])):
            for k in ("w", "b"):
                d = np.asarray(a[k], np.float64) - np.asarray(b[k], np.float64)
                out[f"{tower}[{i}].{k}"] = float(np.sqrt((d * d).sum())) * scale
    return out


def judge(env, numbers: dict, limits: dict, notes: dict = None) -> bool:
    """Print each number beside its limit (and ``notes[name]``, the leaf a
    worst-leaf number came from); True if every one is inside."""
    ok, notes = True, notes or {}
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        inside = bool(np.isfinite(value)) and value <= limits[name]
        extra = {"worst_leaf": notes[name]} if name in notes else {}
        env.info("compare", number=name, value=value, limit=limits[name], ok=inside, **extra)
        env.compared[name] = [value, limits[name]]
        ok = ok and inside
    return ok
