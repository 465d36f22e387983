"""Shared train-loop harness for the examples.

The three trainers (train_dlrm, train_longdoc, train_lm) share one loop
shape: duty-cycled wait/step windows with a one-deep device pipeline
(block on step N-1's loss inside the busy window while the host prepares
batch N+1), checkpoint cadence, an end-of-run summary with the gauge-safe
stage-throughput snapshot, and fingerprint-tolerant resume. That shape
lives here ONCE; each example keeps only its data/model specifics.

Since ISSUE 13 the loop is also the TRAINING FLIGHT RECORDER: every step
is decomposed into disjoint wall-clock phases (``train.data_wait`` /
``train.h2d`` / ``train.compute`` / ``train.ckpt`` — Metrics stages with
latency histograms, plus ``train.step`` per-step latency and a
``train.steps`` counter), each step carries a ``train.step`` span (Chrome
trace) and a ``tracing.trace`` annotation (xprof), windowed phase SHARES
land in ``train.share.<phase>`` gauges, and the windowed training verdict
(``input_bound`` / ``compute_bound`` / ``ckpt_bound`` —
telemetry.training_verdict) explains where the step went. A trainer that
spools (``trainer_spool``) is aggregated by the fleet doctor exactly like
a reader process, under the ``trainer`` role.

Examples run as scripts: each one inserts the repo root on sys.path and
places the compile cache (``tpu_tfrecord.compile_cache.enable()``) before
importing this module.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Callable, Dict, Iterator, Optional, Tuple

import jax

from tpu_tfrecord import checkpoint, compile_cache, telemetry
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.tracing import DutyCycle, trace


def resume_or_fresh(ds, ckpt_dir: str):
    """(iterator, resume_state): open ``ds.batches`` at the saved input
    position when one exists and still matches the dataset fingerprint; a
    state saved under a different dataset config starts fresh with a loud
    line rather than dying."""
    resume = checkpoint.load_state(ckpt_dir)
    print("resuming from", resume) if resume else print("fresh start")
    try:
        return ds.batches(resume), resume
    except ValueError as e:
        print(f"saved input state incompatible ({e}); starting fresh")
        return ds.batches(None), None


def state_saver(ckpt_dir: str):
    """(save_callback, saver) for ``run_train_loop``'s ``save=`` seam.

    The callback snapshots the LIVE iterator position on the caller's
    thread and hands the fsync-then-rename write to the background commit
    thread (checkpoint.AsyncStateSaver), so the ``ckpt`` step phase
    measures microseconds instead of disk latency. ``TFR_CKPT_MODE=sync``
    keeps the write inline — the measurement twin verify.sh's
    throttle legs compare against. Callers must ``saver.close()`` in a
    ``finally`` so the last commit drains (and any commit failure
    surfaces) before the process exits."""
    sync = os.environ.get("TFR_CKPT_MODE", "async") == "sync"
    saver = checkpoint.AsyncStateSaver(ckpt_dir, sync=sync)

    def save(step, live_it, _state):
        saver.save(live_it, step=step)

    return save, saver


def stage_throughput() -> dict:
    """records/sec per pipeline stage. Gauges share the snapshot namespace
    with a distinct {"gauge": v} shape, and pure event counters ride the
    ``records`` field with ~zero seconds (their "rate" is meaningless) —
    only entries with both records AND measured time are real stages."""
    return {
        k: round(v["records_per_sec"])
        for k, v in METRICS.snapshot().items()
        if v.get("records") and v.get("seconds")
    }


class StepPhases:
    """Per-step phase decomposition: the training half of the flight
    recorder (ISSUE 13).

    Each phase is a DISJOINT wall-clock partition of one loop iteration:

    - ``data_wait``: blocked in ``next(it)`` waiting on the input
      pipeline, MINUS any transfer seconds a DeviceIterator spent
      synchronously inside that call (its ``transfer_seconds`` counter is
      snapshotted around the wait) — so H2D cost never masquerades as
      input starvation.
    - ``h2d``: host batch assembly + device placement (``produce``), plus
      the DeviceIterator transfer seconds carved out of the wait above.
    - ``compute``: the device-step window (block on step N-1's loss +
      dispatch step N).
    - ``ckpt``: the checkpoint callback.

    Phase timings are BUFFERED per step and committed by ``end_step``:
    every phase lands in the Metrics registry as a ``train.<phase>``
    stage (seconds + per-step latency histogram), each completed step
    bumps the ``train.steps`` counter, feeds the ``train.step`` per-step
    latency stage, and records one ``train.step`` flight-recorder span
    covering the step's wall extent. A partial iteration that never
    completes — the loop's final ``next(it)`` that only DISCOVERS
    exhaustion — is dropped by ``abort_step``, so stage records, window
    shares, and span counts always agree exactly with ``train.steps``
    (the drained-pipeline wait of that last probe would otherwise bias
    short runs toward input_bound). Every ``window`` steps the WINDOWED
    phase shares are published as ``train.share.<phase>`` gauges (what
    the spool ships to the fleet, and what the verdict describes — the
    recent regime, not the lifetime average) plus a ``train.verdict``
    trace instant. Overhead: a few perf_counter pairs and one locked
    Metrics add per phase per step — noise next to any real train step.
    """

    PHASES = telemetry.TRAIN_PHASES

    def __init__(self, window: int = 16, metrics=None):
        if window < 1:
            raise ValueError("window must be >= 1")
        self.window = int(window)
        self.metrics = metrics or METRICS
        self.steps = 0
        self._totals = {p: 0.0 for p in self.PHASES}
        self._window_start = dict(self._totals)
        self._pending = {p: 0.0 for p in self.PHASES}
        self._pending_t0_ns: Optional[int] = None
        self._last_shares: Dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str, iterator=None):
        """Time one phase of the current step (buffered until
        ``end_step`` commits it). ``iterator`` (the wait phase passes the
        batch iterator) lets a DeviceIterator's inline transfer seconds
        be re-attributed from data_wait to h2d."""
        if self._pending_t0_ns is None:
            self._pending_t0_ns = time.perf_counter_ns()
        t0 = time.perf_counter()
        h0 = getattr(iterator, "transfer_seconds", 0.0) if iterator is not None else 0.0
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            inline_h2d = 0.0
            if iterator is not None:
                inline_h2d = getattr(iterator, "transfer_seconds", 0.0) - h0
                # never attribute more than the wall we actually waited
                # (a transfer thread may have progressed concurrently)
                inline_h2d = min(max(0.0, inline_h2d), dt)
                dt -= inline_h2d
            self._pending[name] += dt
            self._pending["h2d"] += inline_h2d

    def end_step(self) -> None:
        """Commit the buffered phases as one completed step: stage
        totals + latency histograms, the ``train.steps`` counter, the
        ``train.step`` whole-step latency, one ``train.step`` span, and
        the windowed shares/verdict refresh every ``window`` steps."""
        step_seconds = 0.0
        for name, dt in self._pending.items():
            if dt:
                self.metrics.add(
                    telemetry.TRAIN_STAGE_PREFIX + name,
                    records=1, seconds=dt, latency=dt,
                )
                self._totals[name] += dt
                step_seconds += dt
        self.steps += 1
        self.metrics.count("train.steps")
        self.metrics.add(
            "train.step", records=1, seconds=step_seconds,
            latency=step_seconds,
        )
        if self._pending_t0_ns is not None:
            telemetry.record_span(
                "train.step",
                self._pending_t0_ns,
                time.perf_counter_ns() - self._pending_t0_ns,
                step=self.steps,
            )
        self.abort_step()
        if self.steps % self.window == 0:
            self._refresh_window()

    def abort_step(self) -> None:
        """Drop the buffered partial step (the exhaustion-discovery
        iteration): nothing lands in the registry, so every published
        number keeps agreeing with ``train.steps``."""
        self._pending = {p: 0.0 for p in self.PHASES}
        self._pending_t0_ns = None

    def _refresh_window(self) -> None:
        deltas = {
            p: self._totals[p] - self._window_start[p] for p in self.PHASES
        }
        total = sum(deltas.values())
        if total > 0:
            self._last_shares = {p: deltas[p] / total for p in self.PHASES}
            for p, v in self._last_shares.items():
                self.metrics.gauge(
                    telemetry.TRAIN_SHARE_PREFIX + p, round(v, 4)
                )
            telemetry.instant(
                "train.verdict", verdict=self.verdict(), step=self.steps
            )
        self._window_start = dict(self._totals)

    def flush(self) -> None:
        """Publish the shares for a run that never completed one window
        (run_train_loop calls this at loop end). Once a full window HAS
        published, flush is a no-op: republishing a 1-2 step trailing
        remainder would overwrite the windowed gauges — and the spool's
        final snapshot, and the doctor's verdict — with single-step
        noise (one anomalous GC pause or shard-boundary wait)."""
        if self._last_shares:
            return
        if any(
            self._totals[p] > self._window_start[p] for p in self.PHASES
        ):
            self._refresh_window()

    def shares(self) -> Dict[str, float]:
        """The newest windowed shares; before the first full window (or
        for a run shorter than one window), the lifetime shares."""
        if self._last_shares:
            return dict(self._last_shares)
        total = sum(self._totals.values())
        if total <= 0:
            return {}
        return {p: v / total for p, v in self._totals.items()}

    def verdict(self) -> str:
        return telemetry.training_verdict(self.shares())


def fold_model_diagnostics(diag, metrics=None) -> Dict[str, float]:
    """In-jit model diagnostics (models.lm ``diagnostics=True`` output) ->
    the flight recorder: one gauge (last value) + one histogram
    observation (distribution over steps) per metric, so the spool ships
    them to the fleet doctor and ``doctor train`` can print the
    expert-imbalance / bubble lines. Returns the folded floats (the
    caller may log them).

    Gauges: ``moe.dropped_fraction``, ``moe.gate_entropy``,
    ``moe.expert_imbalance`` (max/mean of per-expert routed tokens — 1.0
    = perfectly balanced routing), ``pipeline.bubble_fraction``. Device
    scalars are fetched with float(): call AFTER the step's loss is
    already blocked on, so the fetch adds no sync point of its own."""
    metrics = metrics or METRICS
    out: Dict[str, float] = {}
    if not diag:
        return out
    import numpy as np

    # ONE transfer for the whole tiny pytree: per-field float() would pay
    # a dispatch fence each (measured at >10% step overhead on a small LM)
    host = jax.device_get(diag)
    if "expert_tokens" in host:
        tokens = np.asarray(host["expert_tokens"], dtype=float)
        mean = tokens.mean() if tokens.size else 0.0
        out["moe.expert_imbalance"] = (
            float(tokens.max() / mean) if mean > 0 else 0.0
        )
        out["moe.dropped_fraction"] = float(host["dropped_fraction"])
        out["moe.gate_entropy"] = float(host["gate_entropy"])
    if "bubble_fraction" in host:
        out["pipeline.bubble_fraction"] = float(host["bubble_fraction"])
        if float(host.get("virtual_stages", 1)) > 1:
            # the interleaved schedule's number, under its own name so a
            # dashboard can read V>1 runs against the 1F1B baseline
            out["pipeline.bubble_fraction_v"] = float(
                host["bubble_fraction"]
            )
    for name, v in out.items():
        metrics.gauge(name, v)
        metrics.observe(name, v)
    return out


def device_banner() -> str:
    """The devices JAX gave this process, as the trainers print it at
    start-up (chip_smoke.py reads ``platform=`` from it)."""
    d = jax.devices()[0]
    return (
        f"devices: platform={d.platform} kind={d.device_kind!r} "
        f"count={len(jax.devices())}"
    )


def report_mesh(mesh, metrics=None) -> Dict[str, int]:
    """Publish the trainer's mesh shape as ``train.mesh.<axis>`` gauges
    (axis name -> extent), so the spool ships the parallelism layout to
    the fleet and `tfrecord_doctor train` can print WHICH mesh a trainer
    is flying (a dp×fsdp×pp trainer and a pure-dp one look identical in
    phase shares; they are very different machines). Returns the shape
    dict (the caller may log it)."""
    metrics = metrics or METRICS
    shape = {
        name: int(size)
        for name, size in zip(mesh.axis_names, mesh.devices.shape)
    }
    for name, size in shape.items():
        metrics.gauge(f"train.mesh.{name}", size)
    return shape


def report_fsdp_param_bytes(params, metrics=None) -> int:
    """Per-device AT-REST param bytes of an fsdp-placed tree (sum of each
    leaf's local shard), published as the ``lm.fsdp_param_bytes`` gauge —
    the number the gather-on-use layout exists to shrink, shipped with
    the spool so the fleet doctor sees it next to the mesh shape."""
    import numpy as np

    metrics = metrics or METRICS
    per_dev = sum(
        int(np.prod(p.sharding.shard_shape(p.shape))) * p.dtype.itemsize
        for p in jax.tree.leaves(params)
    )
    metrics.gauge("lm.fsdp_param_bytes", per_dev)
    return per_dev


def trainer_spool(spool_dir: Optional[str] = None, interval_s=None):
    """Acquire this process's telemetry spool under the ``trainer`` role
    (None when no dir is configured). Falls back to the
    ``TFR_TRAIN_SPOOL_DIR`` env var so the no-argparse examples
    (train_dlrm, train_longdoc) spool without growing a CLI; pair with
    ``release_trainer_spool`` so a clean exit lands the ``final: true``
    goodbye snapshot (the aggregator then never flags the trainer dead).
    """
    spool_dir = spool_dir or os.environ.get("TFR_TRAIN_SPOOL_DIR")
    if not spool_dir:
        return None
    from tpu_tfrecord import fleet

    if interval_s is None:
        env = os.environ.get("TFR_TRAIN_SPOOL_INTERVAL_S")
        interval_s = float(env) if env else None
    return fleet.acquire_spool(spool_dir, role="trainer", interval_s=interval_s)


def release_trainer_spool(spool) -> None:
    """Release a ``trainer_spool`` handle (no-op for None)."""
    if spool is not None:
        from tpu_tfrecord import fleet

        fleet.release_spool(spool.spool_dir)


def log_setup_summary() -> Optional[dict]:
    """One structured line once the first step has been dispatched, when
    every program of the run has been built: what ``compile_cache``'s log
    says the start was spent on (seconds by phase with each moment once,
    cache hits and misses, the three programs that cost most). A slow
    start reads off it: ``backend`` seconds with misses is a cold compile
    cache, ``lower`` seconds are the kernels' lowerings (no cache holds
    them), ``trace`` seconds are Python. Silent where no entry point called
    ``compile_cache.enable()``."""
    found = compile_cache.summary()
    if found is not None:
        line = {"event": "setup", "hits": found["hits"], "misses": found["misses"],
                "seconds": {k: round(v, 3) for k, v in found["seconds"].items()},
                "largest": [{k: round(v, 3) if isinstance(v, float) else v for k, v in row.items()}
                            for row in found["programs"][:3]]}
        print("setup " + json.dumps(line, sort_keys=True), flush=True)
    return found


def run_train_loop(
    it,
    produce: Callable,
    step_fn: Callable,
    state: Tuple,
    *,
    save: Optional[Callable[[int, object, object], None]] = None,
    save_every: int = 8,
    log_every: int = 8,
    on_step: Optional[Callable[[int, object], None]] = None,
    max_steps: Optional[int] = None,
    phases: Optional[StepPhases] = None,
) -> Tuple[Tuple, int, DutyCycle]:
    """The shared duty-cycled loop.

    - ``it``: the dataset's batch iterator (supports next(it, None)).
    - ``produce(cb) -> global_batch``: host prep + device placement; runs
      inside the WAIT window — it covers everything the host does between
      steps, including blocking on the prefetch queue, so the duty cycle
      cannot inflate exactly when the input pipeline is the bottleneck.
    - ``step_fn(state, gb) -> (state, loss)``: the jitted update; the
      PREVIOUS loss is blocked inside the busy window (its device time)
      and the next step dispatches async — a one-deep pipeline where host
      prep of batch N+1 overlaps device compute of batch N.
    - ``save(step, it, state)``: checkpoint cadence (every ``save_every``
      steps, aligned with the log line); receives the live train state so
      model checkpoints never need to smuggle it out of the loop.
    - ``on_step(step, loss)``: per-step hook AFTER the loss is known
      (train_lm logs step/digest/loss lines through it).
    - ``phases``: the StepPhases recorder decomposing every step into
      ``train.*`` stages + the windowed training verdict. Always on (one
      is constructed when the caller passes none — pass your own to read
      shares()/verdict() after the run).

    After the first step one ``setup {...}`` line says what building the
    programs cost (:func:`log_setup_summary`).

    Every completed step records a ``train.step`` flight-recorder span
    (Chrome trace, when tracing is on — exactly one per counted step) and
    is wrapped in a ``tracing.trace`` xprof annotation, so profiler
    timelines carry explicit step markers.

    Returns (state, steps, duty).
    """
    step = 0
    duty = DutyCycle()
    rec = phases if phases is not None else StepPhases()
    prev_loss = None
    while max_steps is None or step < max_steps:
        with trace("train.step"):
            with duty.wait():
                with rec.phase("data_wait", iterator=it):
                    cb = next(it, None)
                with rec.phase("h2d"):
                    gb = produce(cb) if cb is not None else None
            with duty.step():
                with rec.phase("compute"):
                    if prev_loss is not None:
                        jax.block_until_ready(prev_loss)
                    if gb is not None:
                        state, prev_loss = step_fn(state, gb)
            if cb is None:
                # exhaustion discovery, not a step: the drained-pipeline
                # wait must not land in the phase stages or the shares
                rec.abort_step()
                break
            step += 1
            if step == 1:
                log_setup_summary()
            # blocking on THIS step's freshly dispatched loss (the
            # on_step/log paths) is device-step wall time: it must land
            # in the compute phase, or an instrumented run (--diagnostics
            # forces on_step) would report near-zero compute and misread
            # a compute-bound trainer as input_bound
            if on_step is not None and prev_loss is not None:
                with rec.phase("compute"):
                    jax.block_until_ready(prev_loss)
                on_step(step, prev_loss)
            if step % log_every == 0 and prev_loss is not None:
                with rec.phase("compute"):
                    jax.block_until_ready(prev_loss)
                print(f"step {step}  loss ~{float(prev_loss):.4f}", flush=True)
            if save is not None and step % save_every == 0:
                with rec.phase("ckpt"):
                    save(step, it, state)
            rec.end_step()
    if prev_loss is not None:
        jax.block_until_ready(prev_loss)
    rec.flush()  # a run shorter than one window still lands its shares
    return state, step, duty


def finish(
    ckpt_dir: Optional[str],
    step: int,
    batch_size: int,
    t0: float,
    duty: DutyCycle,
    clear_state: bool = True,
    stages: bool = False,
    phases: Optional[StepPhases] = None,
) -> None:
    """End-of-run bookkeeping shared by the examples: clear the input
    state when the epoch budget is exhausted (so the next run starts a
    fresh pass instead of resuming into an empty stream), print the
    examples/s line, the duty cycle, the train-phase shares + verdict
    (when a StepPhases recorder ran), and optionally the stage table."""
    if clear_state and ckpt_dir is not None:
        state_file = checkpoint.state_path(ckpt_dir)
        if os.path.exists(state_file):
            os.remove(state_file)
    dt = time.perf_counter() - t0
    print(f"done: {step} steps, {step * batch_size / dt:,.0f} examples/s")
    if duty.value() is not None:
        print(f"device duty cycle: {duty.value():.1%}")
    if phases is not None and phases.shares():
        shares = {k: round(v, 3) for k, v in phases.shares().items()}
        print(f"train phases: {shares}  verdict: {phases.verdict()}")
    if stages:
        print("stage throughput:", stage_throughput())
