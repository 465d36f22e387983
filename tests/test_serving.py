"""Overload-proof serving tier (ISSUE 18): continuous batching with
admission control, request deadlines, and chaos-certified degradation.

The load-bearing pins:
  - BYTE PARITY: N concurrent clients through one server produce exactly
    the bytes of N sequential `LMStream` runs — including with a
    mid-generation client disconnect and a deadline expiry in the batch
    (the per-slot isolation property makes slot position and neighbors
    irrelevant; tests/test_pipeline_stream.py pins that half).
  - ADMISSION: the bounded queue sheds EXACTLY the over-admission excess
    (`serve.rejected`, Retry-After hint), never silently queues, and a
    deadline is enforced at admission AND at every tick — an expired
    in-flight request frees its slot immediately and is never served
    late.
  - DEGRADATION: `faults.py` op="serve" chaos (slow_client /
    client_disconnect / burst) rides the same replayable ledger; a
    SIGKILLed replica under the scaler drains through the survivor and
    the `min_workers` floor refills it.
  - CHECKPOINT: the serving-side checkpoint read routes through the
    manifest-last restore path — a generation killed mid-commit (parked
    with the ckpt-chaos seam) is invisible, never half-read.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax

from tpu_tfrecord import elastic, faults, telemetry
from tpu_tfrecord import service_protocol as sp
from tpu_tfrecord.metrics import METRICS, Metrics
from tpu_tfrecord.models import lm
from tpu_tfrecord.serving import (
    DeadlineExpired,
    ServeClient,
    ServePolicy,
    ServeRejected,
    ServeServer,
    ServingEngine,
    sequential_reference,
)
from tpu_tfrecord.tpu import create_mesh

CFG = lm.LMConfig(
    vocab_size=96, d_model=32, n_heads=2, n_layers=4,
    max_len=16, n_micro=4, n_virtual=1,
)
MB = 4


@pytest.fixture(scope="module")
def model():
    """One tiny seeded LM + 2-stage pipe mesh shared by the module (the
    compiled per-tick step is the expensive part)."""
    params = lm.init_params(jax.random.key(0), CFG)
    mesh = create_mesh({"pipe": 2}, jax.devices()[:2])
    return params, CFG, mesh


def _windows(n, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(1, CFG.vocab_size, size=CFG.max_len).astype(np.int32)
        for _ in range(n)
    ]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------------
# Policy / verdict units
# ---------------------------------------------------------------------------


class TestServePolicy:
    def test_validation(self):
        with pytest.raises(ValueError, match="mb"):
            ServePolicy(mb=0)
        with pytest.raises(ValueError, match="max_queue"):
            ServePolicy(max_queue=0)
        with pytest.raises(ValueError, match="retry_after_s"):
            ServePolicy(retry_after_s=-1.0)

    def test_hint_scales_with_queue_pressure(self):
        pol = ServePolicy(mb=4, retry_after_s=0.1)
        assert pol.hint(0) == pytest.approx(0.1)
        assert pol.hint(8) > pol.hint(4) > pol.hint(0)


class TestServingVerdict:
    def test_no_data_is_unknown(self):
        assert telemetry.serving_verdict(None, 0, 250.0) == "unknown"

    def test_meeting_slo(self):
        assert telemetry.serving_verdict(100.0, 3, 250.0) == "meeting_slo"

    def test_missing_slo_with_full_queue_is_queue_bound(self):
        assert telemetry.serving_verdict(
            900.0, 8, 250.0, max_queue=16
        ) == "queue_bound"

    def test_missing_slo_with_empty_queue_is_compute_bound(self):
        assert telemetry.serving_verdict(
            900.0, 0, 250.0, max_queue=16
        ) == "compute_bound"


# ---------------------------------------------------------------------------
# Admission control (no engine thread: deterministic)
# ---------------------------------------------------------------------------


class TestAdmission:
    def _engine(self, model, **pol):
        params, cfg, mesh = model
        metrics = Metrics()
        clock = FakeClock()
        eng = ServingEngine(
            params, cfg, mesh,
            policy=ServePolicy(mb=MB, **pol), metrics=metrics, clock=clock,
        )
        return eng, metrics, clock

    def test_queue_full_shed_loudly_with_hint(self, model):
        eng, metrics, _ = self._engine(model, max_queue=3)
        ws = _windows(4, seed=1)
        for w in ws[:3]:
            eng.submit(w, 1)
        with pytest.raises(ServeRejected, match="queue full") as ei:
            eng.submit(ws[3], 1)
        assert ei.value.retry_after_s > 0
        assert metrics.counter("serve.rejected") == 1
        eng.stop()

    def test_draining_rejects_new_requests(self, model):
        eng, _, _ = self._engine(model)
        eng.drain()
        with pytest.raises(ServeRejected, match="draining"):
            eng.submit(_windows(1)[0], 1)

    def test_deadline_unmeetable_at_admission(self, model):
        eng, metrics, clock = self._engine(model)
        clock.advance(10.0)
        with pytest.raises(DeadlineExpired, match="admission"):
            eng.submit(_windows(1)[0], 1, deadline_s=0.0)
        assert metrics.counter("serve.deadline_expired") == 1
        eng.stop()

    def test_bad_request_shapes_rejected(self, model):
        eng, _, _ = self._engine(model)
        with pytest.raises(ValueError, match="window shape"):
            eng.submit(np.zeros(7, np.int32), 1)
        with pytest.raises(ValueError, match="n_new"):
            eng.submit(_windows(1)[0], 0)
        eng.stop()

    def test_overload_sheds_exactly_the_excess(self, model):
        """The chaos-acceptance half that needs no wall clock: a seeded
        burst of 10 against capacity 3 sheds exactly 7 (counted), every
        admitted request completes with the reference bytes, and ZERO
        admitted requests miss a deadline."""
        params, cfg, mesh = model
        eng, metrics, _ = self._engine(model, max_queue=3)
        ws = _windows(10, seed=2)
        admitted, shed = [], 0
        for w in ws:
            try:
                admitted.append((w, eng.submit(w, 2, deadline_s=60.0)))
            except ServeRejected:
                shed += 1
        assert len(admitted) == 3 and shed == 7
        assert metrics.counter("serve.rejected") == 7
        eng.run_until_idle()
        ref = sequential_reference(
            params, cfg, mesh, [(w, 2) for w, _ in admitted], MB
        )
        for (w, req), want in zip(admitted, ref):
            assert req.result(timeout=0) == want
        assert metrics.counter("serve.deadline_expired") == 0
        assert metrics.counter("serve.requests") == 3
        eng.stop()


# ---------------------------------------------------------------------------
# Engine byte parity: continuous batching == sequential runs
# ---------------------------------------------------------------------------


class TestEngineParity:
    def test_multiplexed_equals_sequential(self, model):
        """THE pin: mixed-length requests packed/refilled across ticks
        produce, bitwise, the tokens of one-at-a-time runs."""
        params, cfg, mesh = model
        metrics = Metrics()
        eng = ServingEngine(
            params, cfg, mesh, policy=ServePolicy(mb=MB, max_queue=32),
            metrics=metrics,
        )
        reqs = [(w, 1 + i % 3) for i, w in enumerate(_windows(7, seed=3))]
        handles = [eng.submit(w, n) for w, n in reqs]
        eng.run_until_idle()
        ref = sequential_reference(params, cfg, mesh, reqs, MB)
        for h, want in zip(handles, ref):
            assert h.result(timeout=0) == want
        assert metrics.counter("serve.requests") == 7
        eng.stop()

    def test_deadline_expiry_in_batch_frees_slot_without_perturbing(
        self, model
    ):
        """A deadline passing MID-GENERATION: the request is finished
        loudly (never served late), its slot frees on the next pack, and
        its neighbors' bytes are exactly the sequential reference."""
        params, cfg, mesh = model
        metrics = Metrics()
        clock = FakeClock()
        eng = ServingEngine(
            params, cfg, mesh, policy=ServePolicy(mb=MB, max_queue=32),
            metrics=metrics, clock=clock,
        )
        ws = _windows(4, seed=4)
        survivors = [eng.submit(w, 3) for w in ws[:3]]
        doomed = eng.submit(ws[3], 3, deadline_s=1.5)
        assert eng.step() == 4  # tick 1: all four get token 1
        clock.advance(2.0)      # the deadline passes while queued/continuing
        while eng.step() > 0:
            pass
        with pytest.raises(DeadlineExpired):
            doomed.result(timeout=0)
        assert len(doomed.out) < 3, "expired request must not be served late"
        assert metrics.counter("serve.deadline_expired") == 1
        ref = sequential_reference(
            params, cfg, mesh, [(w, 3) for w in ws[:3]], MB
        )
        for h, want in zip(survivors, ref):
            assert h.result(timeout=0) == want
        eng.stop()

    def test_cancel_frees_slot_without_perturbing(self, model):
        """Client abandonment (the engine half of a disconnect): cancel
        mid-generation, neighbors' bytes unchanged."""
        params, cfg, mesh = model
        eng = ServingEngine(
            params, cfg, mesh, policy=ServePolicy(mb=MB, max_queue=32),
            metrics=Metrics(),
        )
        ws = _windows(4, seed=5)
        keep = [eng.submit(w, 3) for w in ws[:3]]
        gone = eng.submit(ws[3], 3)
        assert eng.step() == 4
        eng.cancel(gone)
        eng.run_until_idle()
        with pytest.raises(ServeRejected, match="cancelled"):
            gone.result(timeout=0)
        ref = sequential_reference(
            params, cfg, mesh, [(w, 3) for w in ws[:3]], MB
        )
        for h, want in zip(keep, ref):
            assert h.result(timeout=0) == want
        eng.stop()


# ---------------------------------------------------------------------------
# Request-scoped tracing (ISSUE 20): root spans, children, exemplars
# ---------------------------------------------------------------------------


class TestRequestTracing:
    @pytest.fixture(autouse=True)
    def _recorder(self):
        """The span recorder is process-global: scrub it around every
        tracing test so neither direction leaks spans."""
        telemetry.RECORDER.clear()
        telemetry.enable()
        yield
        telemetry.disable()
        telemetry.RECORDER.clear()

    def test_one_root_span_per_admitted_request_exact_duration(self, model):
        """THE tracing pin: a seeded run shaped like the acceptance
        criterion (4 served requests, one cancel = client disconnect, one
        in-flight deadline expiry) yields EXACTLY one `serve.request`
        span per admitted request, with t0 = admission and duration =
        admission -> completion EXACTLY on the engine's fake clock; the
        latency histogram's tail exemplar resolves to one of those
        spans."""
        params, cfg, mesh = model
        metrics = Metrics()
        clock = FakeClock()
        eng = ServingEngine(
            params, cfg, mesh, policy=ServePolicy(mb=MB, max_queue=32),
            metrics=metrics, clock=clock,
        )
        ws = _windows(6, seed=20)
        finishes = {}

        def on_done(req):
            # the engine clock is frozen within a tick, so clock() here
            # IS the `now` _finish stamped into the span
            finishes[req.rid] = (req.status, clock())

        admitted = []
        for i, w in enumerate(ws[:4]):
            clock.advance(0.125)  # staggered admissions: distinct births
            admitted.append(eng.submit(w, 1 + i % 3, on_done=on_done))
        gone = eng.submit(ws[4], 3, on_done=on_done)
        doomed = eng.submit(ws[5], 3, deadline_s=1.5, on_done=on_done)
        admitted += [gone, doomed]
        eng.cancel(gone)        # disconnects before ever claiming a slot
        assert eng.step() == 4  # tick 1: the four serveable slots
        clock.advance(2.0)      # the in-flight deadline passes
        eng.run_until_idle()
        eng.stop()

        assert finishes[gone.rid][0] == "cancelled"
        assert finishes[doomed.rid][0] == "deadline_expired"
        spans = [
            s for s in telemetry.RECORDER.spans() if s[0] == "serve.request"
        ]
        assert len(spans) == len(admitted) == 6
        by_sid = {s[4]["span_id"]: s for s in spans}
        assert len(by_sid) == 6, "span ids must be unique per request"
        for req in admitted:
            _, t0_ns, dur_ns, _, attrs, ph = by_sid[req.span_id]
            status, done_t = finishes[req.rid]
            assert ph == "X"
            assert t0_ns == int(req.birth * 1e9)
            assert dur_ns == int((done_t - req.birth) * 1e9)
            assert attrs["status"] == status
            assert attrs["trace_id"] == req.trace_id
            assert attrs["rid"] == req.rid

        # every SERVED request carries a queue_wait child and >=1 tick
        # child parented under its span id; the refused two carry none
        children = {}
        for s in telemetry.RECORDER.spans():
            parent = (s[4] or {}).get("parent_span_id")
            if s[0] in ("serve.queue_wait", "serve.tick") and parent:
                children.setdefault(parent, set()).add(s[0])
        for req in admitted[:4]:
            assert children[req.span_id] == {
                "serve.queue_wait", "serve.tick"
            }
        assert gone.span_id not in children
        assert doomed.span_id not in children

        # the fleet-mergeable latency histogram's p99 exemplar names a
        # recorded request span, and its value is that span's duration
        merged = telemetry.Histogram.from_states(
            [metrics.hist_states()["serve.latency"]]
        )
        ex = merged.exemplar_at(0.99)
        assert ex is not None
        assert ex["span_id"] in by_sid
        root = by_sid[ex["span_id"]]
        assert root[4]["trace_id"] == ex["trace_id"]
        assert ex["value"] == pytest.approx(root[2] / 1e9)

    def test_admission_refusals_land_attributable_instants(self, model):
        """A refused request never gets a root span (it was never
        admitted) but its shed/expiry instant carries the trace identity,
        so it is still attributable in a merged timeline."""
        params, cfg, mesh = model
        clock = FakeClock()
        eng = ServingEngine(
            params, cfg, mesh, policy=ServePolicy(mb=MB, max_queue=1),
            metrics=Metrics(), clock=clock,
        )
        ws = _windows(3, seed=21)
        eng.submit(ws[0], 1)
        with pytest.raises(ServeRejected):
            eng.submit(ws[1], 1)
        with pytest.raises(DeadlineExpired):
            eng.submit(ws[2], 1, deadline_s=0.0)
        events = telemetry.RECORDER.spans()
        sheds = [s for s in events if s[0] == "serve.shed"]
        expiries = [s for s in events if s[0] == "serve.deadline_expired"]
        assert len(sheds) == 1
        assert sheds[0][5] == "i"
        assert sheds[0][4]["reason"] == "queue_full"
        assert sheds[0][4]["trace_id"] and sheds[0][4]["span_id"]
        assert len(expiries) == 1
        assert expiries[0][4]["at"] == "admission"
        eng.run_until_idle()
        requests = [
            s for s in telemetry.RECORDER.spans() if s[0] == "serve.request"
        ]
        assert len(requests) == 1  # only the admitted one
        eng.stop()

    def test_byte_parity_unchanged_with_tracing_enabled(self, model):
        """The serving parity pin holds verbatim with the recorder ON:
        tracing is observation, never perturbation."""
        params, cfg, mesh = model
        metrics = Metrics()
        eng = ServingEngine(
            params, cfg, mesh, policy=ServePolicy(mb=MB, max_queue=32),
            metrics=metrics,
        )
        reqs = [(w, 1 + i % 3) for i, w in enumerate(_windows(7, seed=3))]
        handles = [eng.submit(w, n) for w, n in reqs]
        eng.run_until_idle()
        ref = sequential_reference(params, cfg, mesh, reqs, MB)
        for h, want in zip(handles, ref):
            assert h.result(timeout=0) == want
        assert metrics.counter("serve.requests") == 7
        roots = [
            s for s in telemetry.RECORDER.spans() if s[0] == "serve.request"
        ]
        assert len(roots) == 7
        eng.stop()


# ---------------------------------------------------------------------------
# Socket tier: concurrent clients, disconnect chaos, drain
# ---------------------------------------------------------------------------


@pytest.fixture
def server(model):
    params, cfg, mesh = model
    metrics = Metrics()
    eng = ServingEngine(
        params, cfg, mesh, policy=ServePolicy(mb=MB, max_queue=32),
        metrics=metrics,
    )
    srv = ServeServer(eng, port=0).start()
    yield srv, metrics
    srv.stop()


class TestServeServer:
    def test_concurrent_clients_with_disconnect_byte_identical(
        self, model, server
    ):
        """The acceptance pin on the wire: 4 concurrent clients, one of
        them disconnecting mid-generation — the 3 survivors' bytes equal
        the sequential reference, the dropped slot frees (the engine
        drains to idle), and the loss is counted once."""
        params, cfg, mesh = model
        srv, metrics = server
        ws = _windows(4, seed=6)

        # the doomed client: raw socket, long request, hang up mid-run
        doomed = sp.connect(srv.addr, timeout=10.0)
        sp.send_msg(doomed, {
            "v": sp.PROTO_VERSION, "op": "generate", "req": 1,
            "tokens": ws[3].tolist(), "n_new": 500, "deadline_s": None,
        })
        deadline = time.monotonic() + 30
        while metrics.gauge_value("serve.in_flight", 0.0) < 1:
            assert time.monotonic() < deadline, "request never started"
            time.sleep(0.01)
        doomed.close()

        results: dict = {}

        def client(i):
            c = ServeClient([srv.addr])
            try:
                results[i] = c.generate(ws[i], n_new=3)
            finally:
                c.close()

        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        ref = sequential_reference(
            params, cfg, mesh, [(w, 3) for w in ws[:3]], MB
        )
        for i in range(3):
            assert results[i] == ref[i], f"client {i} diverged"
        # the abandoned slot freed: the engine drains to idle
        deadline = time.monotonic() + 30
        while True:
            rep = srv.engine.report()
            if rep["queue_depth"] == 0 and rep["in_flight"] == 0:
                break
            assert time.monotonic() < deadline, rep
            time.sleep(0.05)
        assert metrics.counter("serve.disconnects") == 1

    def test_injected_disconnect_chaos_is_survivable(self, model):
        """faults.py op='serve' client_disconnect on the reply seam: the
        victim's connection drops (counted), the client's RetryPolicy
        resends, and every byte still matches the reference — chaos is
        invisible to correctness."""
        params, cfg, mesh = model
        metrics = Metrics()
        plan = faults.FaultPlan([
            faults.FaultRule(op="serve", kind="client_disconnect",
                             path="reply:", times=1),
        ])
        eng = ServingEngine(
            params, cfg, mesh, policy=ServePolicy(mb=MB, max_queue=32),
            metrics=metrics,
        )
        srv = ServeServer(eng, port=0, fault_plan=plan).start()
        try:
            ws = _windows(3, seed=7)
            results: dict = {}

            def client(i):
                # the victim learns of the drop when its read times out (30 s by default)
                c = ServeClient([srv.addr], timeout_s=5.0)
                try:
                    results[i] = c.generate(ws[i], n_new=2)
                finally:
                    c.close()

            threads = [
                threading.Thread(target=client, args=(i,)) for i in range(3)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            ref = sequential_reference(
                params, cfg, mesh, [(w, 2) for w in ws], MB
            )
            for i in range(3):
                assert results[i] == ref[i]
            fired = [e for e in plan.ledger
                     if e["kind"] == "client_disconnect"]
            assert len(fired) == 1, "the injected disconnect never fired"
        finally:
            srv.stop()

    def test_status_reply_carries_report_fields(self, model, server):
        """The wire contract clients and the scaler read: status carries
        the full engine report (queue depth, verdict, counters)."""
        srv, _ = server
        sock = sp.connect(srv.addr, timeout=10.0)
        try:
            st = sp.request(sock, srv.addr, {
                "v": sp.PROTO_VERSION, "op": "status", "req": 1,
            })
            for key in ("queue_depth", "in_flight", "verdict", "mb",
                        "max_queue", "counters", "addr", "pid"):
                assert key in st, key
        finally:
            sock.close()

    def test_version_skew_rejected(self, model, server):
        srv, _ = server
        sock = sp.connect(srv.addr, timeout=10.0)
        try:
            rep = sp.request(sock, srv.addr, {
                "v": sp.PROTO_VERSION + 1, "op": "ping", "req": 1,
            })
            assert rep["ok"] is False and rep["error"] == "version_skew"
        finally:
            sock.close()

    def test_drain_finishes_in_flight_then_rejects(self, model, server):
        """Scale-down's goodbye: drain stops admission, finishes what was
        admitted, and flips the drained latch."""
        srv, _ = server
        # a request that meets the drained server as it closes ends by the read's timeout
        c = ServeClient([srv.addr], timeout_s=5.0)
        try:
            w = _windows(1, seed=9)[0]
            got = c.generate(w, n_new=2)
            assert len(got) == 2
            rep = c.drain()
            assert rep["ok"] and rep["draining"]
            assert srv.drained.wait(30)
            with pytest.raises((ServeRejected, ConnectionError)):
                c.generate(w, n_new=1)
        finally:
            c.close()


class TestOverloadReply:
    def test_shed_reply_carries_retry_after(self, model):
        """One queue slot, no engine thread: the second concurrent
        generate is shed on the wire with 'overloaded' + a positive
        Retry-After hint (the client backoff floor)."""
        params, cfg, mesh = model
        eng = ServingEngine(
            params, cfg, mesh, policy=ServePolicy(mb=MB, max_queue=1),
            metrics=Metrics(),
        )
        srv = ServeServer(eng, port=0)
        srv._accept_thread = threading.Thread(
            target=srv._accept_loop, daemon=True
        )
        srv._accept_thread.start()
        try:
            w = _windows(1, seed=10)[0].tolist()
            s1 = sp.connect(srv.addr, timeout=10.0)
            sp.send_msg(s1, {
                "v": sp.PROTO_VERSION, "op": "generate", "req": 1,
                "tokens": w, "n_new": 1, "deadline_s": None,
            })
            s2 = sp.connect(srv.addr, timeout=10.0)
            deadline = time.monotonic() + 10
            while True:  # wait for req 1 to occupy the one queue slot
                if srv.engine.report()["queue_depth"] >= 1:
                    break
                assert time.monotonic() < deadline
                time.sleep(0.01)
            rep = sp.request(s2, srv.addr, {
                "v": sp.PROTO_VERSION, "op": "generate", "req": 2,
                "tokens": w, "n_new": 1, "deadline_s": None,
            })
            assert rep["ok"] is False and rep["error"] == "overloaded"
            assert rep["retry_after_s"] > 0
            s1.close()
            s2.close()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# op="serve" fault vocabulary
# ---------------------------------------------------------------------------


class TestServeFaults:
    def test_serve_kinds_require_serve_op(self):
        for kind in faults.SERVE_ONLY_KINDS:
            with pytest.raises(ValueError, match="op='serve'"):
                faults.FaultRule(op="read", kind=kind, stall_ms=5,
                                 burst_n=1)

    def test_serve_op_rejects_foreign_kinds(self):
        with pytest.raises(ValueError, match="op='serve' supports"):
            faults.FaultRule(op="serve", kind="short_read", cap_bytes=1)

    def test_slow_client_requires_stall(self):
        with pytest.raises(ValueError, match="stall_ms"):
            faults.FaultRule(op="serve", kind="slow_client")

    def test_burst_requires_n(self):
        with pytest.raises(ValueError, match="burst_n"):
            faults.FaultRule(op="serve", kind="burst")

    def test_apply_serve_slow_client_stalls_and_ledgers(self):
        slept = []
        plan = faults.FaultPlan(
            [faults.FaultRule(op="serve", kind="slow_client",
                              stall_ms=40.0)],
            sleep=slept.append,
        )
        assert plan.apply_serve("reply:127.0.0.1:5") == 0
        assert slept == [0.04]
        assert plan.ledger[0]["kind"] == "slow_client"
        assert plan.ledger[0]["stall_ms"] == 40.0

    def test_apply_serve_disconnect_closes_socket_and_raises(self):
        import socket as _socket

        a, b = _socket.socketpair()
        try:
            plan = faults.FaultPlan([
                faults.FaultRule(op="serve", kind="client_disconnect"),
            ])
            with pytest.raises(faults.InjectedFault):
                plan.apply_serve("recv:peer", sock=a)
            assert a.fileno() == -1, "socket must be closed"
        finally:
            for s in (a, b):
                try:
                    s.close()
                except OSError:
                    pass

    def test_apply_serve_burst_returns_extra_request_count(self):
        plan = faults.FaultPlan([
            faults.FaultRule(op="serve", kind="burst", burst_n=5),
        ])
        assert plan.apply_serve("admit") == 5
        assert plan.apply_serve("admit") == 0  # times=1: fired out
        assert plan.ledger[0]["kind"] == "burst"

    def test_round_trips_through_json(self):
        plan = faults.FaultPlan([
            faults.FaultRule(op="serve", kind="slow_client", stall_ms=10,
                             path="reply:"),
            faults.FaultRule(op="serve", kind="burst", burst_n=3),
        ], seed=7)
        again = faults.FaultPlan.from_json(json.dumps(plan.to_json()))
        assert again.to_json() == plan.to_json()


# ---------------------------------------------------------------------------
# ServingScaler: queue_bound grows, idle drains, SIGKILL refills
# ---------------------------------------------------------------------------


class _FakeFleet:
    """In-memory replicas for the scaler state machine: spawn() mints an
    address; statuses are scripted per test."""

    def __init__(self):
        self.n = 0
        self.load = {}  # addr -> status dict overrides
        self.dead = set()
        self.draining = set()

    def spawn(self):
        self.n += 1
        addr = f"127.0.0.1:{9000 + self.n}"
        self.load[addr] = {}
        return addr

    def status(self, addr):
        if addr in self.dead:
            raise ConnectionError("SIGKILLed")
        base = {
            "queue_depth": 0, "in_flight": 0, "p99_ms": 50.0,
            "slo_p99_ms": 250.0, "max_queue": 16, "completed": 0,
            "draining": addr in self.draining,
        }
        base.update(self.load.get(addr, {}))
        return base

    def drain(self, addr):
        self.draining.add(addr)
        return {"ok": True, "draining": True}


def _scaler(fleet, **pol):
    clock = FakeClock()
    s = elastic.ServingScaler(
        fleet.spawn,
        policy=elastic.ScalerPolicy(
            min_workers=1, max_workers=4, hysteresis=2, cooldown_s=1.0,
            **pol,
        ),
        status_fn=fleet.status, drain_fn=fleet.drain, clock=clock,
    )
    return s, clock


class TestServingScaler:
    def test_grows_on_queue_bound_and_drains_on_idle(self):
        fleet = _FakeFleet()
        s, clock = _scaler(fleet)
        assert s.step()["reason"] == "below_min"  # empty fleet -> floor
        addr = s.replicas[0]
        # sustained overload: full queue + missed SLO -> queue_bound
        fleet.load[addr] = {
            "queue_depth": 12, "p99_ms": 900.0, "completed": 10,
        }
        grew = None
        for _ in range(6):
            clock.advance(2.0)
            fleet.load[addr]["completed"] += 5  # not idle
            grew = s.step()
            if grew:
                break
        assert grew and grew["action"] == "scale_up"
        assert grew["reason"] == "queue_bound"
        assert len(s.replicas) == 2
        # load vanishes: empty queues + zero completions -> idle -> drain
        for a in s.replicas:
            fleet.load[a] = {"queue_depth": 0, "completed": 50}
        shrank = None
        for _ in range(8):
            clock.advance(2.0)
            shrank = s.step() or shrank
        assert shrank and shrank["action"] == "scale_down"
        assert shrank["reason"] == "idle"
        assert fleet.draining, "the victim never got the drain RPC"

    def test_drained_replica_death_is_a_clean_goodbye(self):
        fleet = _FakeFleet()
        s, clock = _scaler(fleet)
        s.step()
        victim = fleet.spawn()
        s.replicas.append(victim)
        fleet.drain(victim)
        s._draining.add(victim)
        before = METRICS.counter("elastic.drains")
        lost = METRICS.counter("elastic.replicas_lost")
        fleet.dead.add(victim)  # drained replica exits on its own
        clock.advance(2.0)
        s.step()
        assert victim not in s.replicas
        assert METRICS.counter("elastic.drains") == before + 1
        assert METRICS.counter("elastic.replicas_lost") == lost

    def test_sigkill_refills_below_floor_bypassing_climber(self):
        """An UNDRAINED death is a kill: counted `elastic.replicas_lost`
        and refilled on the very next tick (no hysteresis wait)."""
        fleet = _FakeFleet()
        s, clock = _scaler(fleet)
        s.step()
        victim = s.replicas[0]
        lost = METRICS.counter("elastic.replicas_lost")
        fleet.dead.add(victim)
        clock.advance(2.0)
        decision = s.step()
        assert METRICS.counter("elastic.replicas_lost") == lost + 1
        assert decision is not None and decision["reason"] == "below_min"
        assert len(s.replicas) == 1 and s.replicas[0] != victim


# ---------------------------------------------------------------------------
# Subprocess chaos: SIGKILLed replica drains through the survivor
# ---------------------------------------------------------------------------


def _replica_env():
    return {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }


@pytest.mark.slow
class TestReplicaKillChaos:
    def test_sigkill_drains_through_survivor_and_scaler_refills(
        self, tmp_path
    ):
        """The acceptance scenario end-to-end with real processes: two
        seeded replicas, one SIGKILLed mid-fleet — the client walks the
        member list so its requests drain through the survivor with the
        reference bytes, and the scaler's next tick counts the loss and
        refills the floor."""
        spawner = elastic.ServingReplicaSpawner(
            extra_args=(
                "--stages", "1", "--layers", "2", "--d-model", "16",
                "--heads", "2", "--mb", "2", "--seed", "5",
            ),
            env=_replica_env(),
        )
        scaler = elastic.ServingScaler(
            spawner,
            policy=elastic.ScalerPolicy(
                min_workers=2, max_workers=3, hysteresis=2, cooldown_s=0.0,
            ),
        )
        try:
            scaler.step()  # below_min: 1st replica
            scaler.step()  # below_min: 2nd replica
            assert len(scaler.replicas) == 2
            addrs = list(scaler.replicas)

            cfg = lm.LMConfig(
                vocab_size=96, d_model=16, n_heads=2, n_layers=2,
                max_len=16, n_micro=2, n_virtual=1,
            )
            params = lm.init_params(jax.random.key(5), cfg)
            mesh = create_mesh({"pipe": 1}, jax.devices()[:1])
            ws = _windows(3, seed=11)
            ref = sequential_reference(
                params, cfg, mesh, [(w, 2) for w in ws], 2
            )

            c = ServeClient(addrs)
            try:
                assert c.generate(ws[0], 2) == ref[0]
                # SIGKILL the replica the client is currently pinned to:
                # the next request MUST rotate to the survivor
                victim_addr = c.addr
                victim = next(
                    p for p, a in zip(spawner.procs, addrs)
                    if a == victim_addr
                )
                os.kill(victim.pid, signal.SIGKILL)
                victim.wait(timeout=30)
                assert c.generate(ws[1], 2) == ref[1]
                assert c.generate(ws[2], 2) == ref[2]
            finally:
                c.close()

            lost = METRICS.counter("elastic.replicas_lost")
            decision = scaler.step()  # census the corpse, refill the floor
            assert METRICS.counter("elastic.replicas_lost") == lost + 1
            assert decision is not None and decision["reason"] == "below_min"
            assert len(scaler.replicas) == 2
            assert victim_addr not in scaler.replicas
        finally:
            scaler.stop()
            spawner.reap()


# ---------------------------------------------------------------------------
# Checkpoint chaos pin: serving load never half-reads a generation
# ---------------------------------------------------------------------------

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
EXAMPLES_DIR = os.path.join(os.path.dirname(TESTS_DIR), "examples")


@pytest.mark.slow
class TestServeCheckpointChaosPin:
    def test_load_skips_generation_killed_mid_commit(self, tmp_path):
        """Park the LMCheckpoint writer at pre_manifest on generation 8
        (generation 4 complete), SIGKILL it there, then run serve_lm's
        `load_checkpoint` against the wreckage: it must serve generation
        4 — the newest COMPLETE one — and never touch the manifest-less
        gen-8 carcass."""
        import ckpt_chaos_worker as worker

        d = str(tmp_path / "ckpt")
        mark = str(tmp_path / "mark")
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "TFR_CKPT_CHAOS_STAGE": "pre_manifest",
            "TFR_CKPT_CHAOS_MARK": mark,
            "TFR_CKPT_CHAOS_SKIP": "1",
        }
        p = subprocess.Popen(
            [sys.executable, os.path.join(TESTS_DIR, "ckpt_chaos_worker.py"),
             "lm", d, "--steps", "12", "--save-every", "4"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        try:
            deadline = time.time() + 120
            while not os.path.exists(mark):
                if p.poll() is not None:
                    out, err = p.communicate()
                    raise AssertionError(
                        f"worker exited before parking:\n{out}\n{err}"
                    )
                assert time.time() < deadline, "worker never parked"
                time.sleep(0.02)
        finally:
            if p.poll() is None:
                os.kill(p.pid, signal.SIGKILL)
            p.wait()

        # the wreckage the serving tier must survive: gen-4 complete,
        # gen-8 present but manifest-less (killed mid-commit)
        gens = sorted(n for n in os.listdir(d) if n.startswith("gen-"))
        assert "gen-00000004" in gens and "gen-00000008" in gens
        assert not os.path.exists(
            os.path.join(d, "gen-00000008", "MANIFEST.json")
        )

        sys.path.insert(0, EXAMPLES_DIR)
        try:
            import serve_lm
        finally:
            sys.path.remove(EXAMPLES_DIR)
        step, state = serve_lm.load_checkpoint(d, worker._init_state())
        assert step == 4, f"served step {step}, not the complete gen 4"
        # the restored bytes are exactly the step-4 state, not a blend
        want = worker._init_state()
        for s in range(1, 5):
            want = worker._update(want, s)
        assert worker._digest(
            {k: np.asarray(v) for k, v in state.items()}
        ) == worker._digest(want)
