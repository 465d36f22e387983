"""The host log (``tpu_tfrecord.tracing``): one call a site writes the
profiler's timeline and the process's ring, the ring is read back by
``host_events``, and the host watch writes ``host:pause`` and ``host:gc``.

No case asserts on the machine's clock: the watch under test takes its
clock, its wait and its readers from the test, which makes it late by hand.
"""

import gc
import logging
import os
import queue
import threading
import time

import pytest

from tpu_tfrecord import telemetry, tracing, vocabulary
from tpu_tfrecord.metrics import METRICS


@pytest.fixture
def ring(monkeypatch):
    """A ring of this test's own in the process's place, flight recorder off."""
    made = telemetry.SpanRecorder(capacity=64)
    monkeypatch.setattr(telemetry, "RECORDER", made)
    return made


class Annotations:
    """Stands in for ``jax.profiler``: remembers what was opened and closed."""

    def __init__(self):
        self.opened, self.closed = [], []

    def TraceAnnotation(self, name, **args):
        return _Annotation(self, name, args)


class _Annotation:
    def __init__(self, owner, name, args):
        self.owner, self.name, self.args = owner, name, dict(args)

    def __enter__(self):
        self.owner.opened.append(self.name)
        return self

    def set_metadata(self, **args):
        self.args.update(args)

    def __exit__(self, *exc):
        self.owner.closed.append((self.name, self.args))


@pytest.fixture
def profiler(monkeypatch):
    made = Annotations()
    monkeypatch.setattr(tracing, "_PROF", made)
    monkeypatch.setattr(tracing, "_PROF_CHECKED", True)
    return made


class Hands:
    """A clock a test moves: ``wait`` advances it by what the test queued
    (default: the interval asked for, a punctual wake)."""

    def __init__(self):
        self.now, self.naps = 1000.0, []

    def clock(self):
        return self.now

    def wait(self, interval):
        self.now += self.naps.pop(0) if self.naps else interval


def watch_with(hands, readers):
    return tracing.HostWatch(clock=hands.clock, wait=hands.wait, readers=readers)


# -- one call a site writes both clocks ----------------------------------------


def test_the_host_spans_are_the_names_with_a_colon():
    assert tracing.HOST_SPANS == {n for n in tracing.ANNOTATIONS if ":" in n}
    assert {"host:pause", "host:gc", "tfr:decode", "tfr:pack_tokens", "tfr:h2d_land",
            "tfr:blocked.batch", "tfr:starved.device"} <= tracing.HOST_SPANS
    assert not [n for n in tracing.HOST_SPANS if n.startswith("tfr.")]


@pytest.mark.parametrize("name", sorted(tracing.HOST_SPANS))
def test_a_host_span_lands_in_the_log_inside_the_callers_own_clock_pair(ring, profiler, name):
    t0 = time.perf_counter()
    with tracing.trace(name, shard="s") as tr:
        tr.set_metadata(rows=3)
    t1 = time.perf_counter()
    (rec,) = tracing.host_events()
    assert rec.name == name and t0 <= rec.begin <= rec.end <= t1
    assert rec.thread == threading.get_ident() and rec.args == {"shard": "s", "rows": 3}
    # and the same call fed the profiler's timeline, arguments and all
    assert profiler.opened == [name] and profiler.closed == [(name, {"shard": "s", "rows": 3})]


def test_with_no_profiler_at_all_the_ring_is_still_written(ring, monkeypatch):
    monkeypatch.setattr(tracing, "_PROF", None)
    monkeypatch.setattr(tracing, "_PROF_CHECKED", True)
    with tracing.trace("tfr:pack") as tr:
        tr.set_metadata(rows=8, bytes=64)
    (rec,) = tracing.host_events()
    assert (rec.name, rec.args) == ("tfr:pack", {"rows": 8, "bytes": 64})


def test_a_span_without_arguments_keeps_none(ring, profiler):
    with tracing.trace("tfr:h2d_land"):
        pass
    assert tracing.host_events()[0].args is None


def test_a_name_outside_the_host_spans_goes_to_the_profiler_alone(ring, profiler, monkeypatch):
    for name in ("tfr.write.encode", "train.step"):
        with tracing.trace(name, rows=1):
            pass
    assert profiler.opened == ["tfr.write.encode", "train.step"]
    assert len(ring) == 0 and tracing.host_events() == []
    monkeypatch.setattr(tracing, "_PROF", None)
    assert tracing.trace("train.step") is tracing._NULL_TRACE


def test_the_flight_recorder_off_still_records_the_host_spans_and_nothing_else(ring, profiler):
    assert not ring.enabled
    with tracing.trace("tfr:decode", shard="a"), telemetry.span("decode", shard="a") as sp:
        sp.set(rows=1)
    telemetry.instant("read.stall")
    telemetry.record_span("batch", 0, 10)
    assert [s[0] for s in ring.spans()] == ["tfr:decode"]


def test_the_flight_recorder_on_shares_the_ring_and_host_events_picks_its_own(ring, profiler):
    telemetry.enable()
    try:
        with telemetry.span("read", shard="a"):
            with tracing.trace("tfr:open", shard="a"):
                pass
        telemetry.instant("tfr:open")  # an instant is no span of the log
    finally:
        telemetry.disable()
    assert {s[0] for s in ring.spans()} == {"read", "tfr:open"} and len(ring) == 3
    assert [r.name for r in tracing.host_events()] == ["tfr:open"]


# -- the queues' hand-offs --------------------------------------------------------


def test_a_put_that_finds_room_and_a_get_that_finds_an_item_write_nothing(ring, profiler):
    q, stop = queue.Queue(maxsize=2), threading.Event()
    assert tracing.put_or_wait(q, "a", stop, "tfr:blocked.host")
    assert tracing.get_or_wait(q, stop, "tfr:starved.host") == "a"
    assert len(ring) == 0 and profiler.opened == []


@pytest.mark.parametrize("which", ["batch", "host", "device"])
def test_a_blocked_put_writes_one_record_however_many_polls_it_took(ring, profiler, which,
                                                                    monkeypatch):
    q, stop, polls = queue.Queue(maxsize=1), threading.Event(), []
    q.put("full")
    real_put = q.put

    def put(item, block=True, timeout=None):
        if not block:  # put_nowait: no room
            raise queue.Full
        polls.append(timeout)  # three polls find it full; then the consumer takes one
        if len(polls) == 3:
            q.get_nowait()
        if len(polls) < 4:
            raise queue.Full
        real_put(item, timeout=timeout)

    monkeypatch.setattr(q, "put", put)
    assert tracing.put_or_wait(q, "late", stop, f"tfr:blocked.{which}")
    assert len(polls) == 4
    assert [r.name for r in tracing.host_events()] == [f"tfr:blocked.{which}"]
    assert profiler.opened == [f"tfr:blocked.{which}"]


def test_a_starved_get_writes_one_record_and_a_stopped_one_closes_it(ring, profiler, monkeypatch):
    q, stop, polls = queue.Queue(), threading.Event(), []

    def get(block=True, timeout=None):
        if not block:  # get_nowait: nothing there
            raise queue.Empty
        polls.append(timeout)
        if len(polls) == 3:
            return "x"       # the third poll finds the producer's item
        if len(polls) == 5:
            stop.set()       # and the fifth is told to give up
        raise queue.Empty

    monkeypatch.setattr(q, "get", get)
    assert tracing.get_or_wait(q, stop, "tfr:starved.device") == "x"
    assert tracing.get_or_wait(q, stop, "tfr:starved.device") is tracing.STOPPED
    assert [r.name for r in tracing.host_events()] == ["tfr:starved.device"] * 2


# -- reading the ring back ----------------------------------------------------------


def test_host_events_are_cut_by_when_they_began_oldest_first(ring):
    for begin_s, dur_s, name in [(30, 5, "tfr:h2d"), (10, 25, "tfr:decode"), (20, 1, "tfr:pack")]:
        ring.log(name, int(begin_s * 1e9), int(dur_s * 1e9), None)
    assert [r.begin for r in tracing.host_events()] == [10.0, 20.0, 30.0]
    assert [r.name for r in tracing.host_events(since=20.0)] == ["tfr:pack", "tfr:h2d"]
    assert [r.name for r in tracing.host_events(until=30.0)] == ["tfr:decode", "tfr:pack"]
    # a record that began before the cut is out though it ends inside it
    assert [r.name for r in tracing.host_events(since=15.0, until=25.0)] == ["tfr:pack"]
    assert tracing.host_events(since=10.0, until=10.0) == []
    rec = tracing.host_events(since=10.0, until=11.0)[0]
    assert (rec.begin, rec.end) == (10.0, 35.0)


def test_the_ring_is_bounded_and_counts_what_it_drops(ring):
    assert tracing.host_log_dropped() == 0
    for i in range(ring.capacity + 10):
        ring.log("tfr:pack", i, 1, {"rows": i})
    assert len(ring) == ring.capacity and tracing.host_log_dropped() == 10
    kept = tracing.host_events()
    assert len(kept) == ring.capacity and kept[0].args == {"rows": 10}


def test_the_process_ring_holds_the_densest_cell_twice_over():
    # criteo_mlperf.score wrote 34,006 records in a run (my chip run, PR 51: PERF.md §6)
    assert telemetry.RECORDER.capacity == telemetry.RING_CAPACITY >= 2 * 34006


# -- the witness for the host's pauses ------------------------------------------------


def test_a_punctual_wake_writes_nothing(ring):
    hands = Hands()
    watch = watch_with(hands, {"steal_s": lambda: 0.0})
    for _ in range(10):
        assert watch.step() is None
    hands.naps = [tracing.HostWatch.INTERVAL_S + tracing.HostWatch.LATE_S - 1e-6]  # under the line
    assert watch.step() is None and len(ring) == 0


def test_a_late_wake_writes_host_pause_from_when_it_was_due_to_when_it_woke(ring):
    hands, totals = Hands(), {"steal_s": 5.0, "runqueue_s": 1.0}
    watch = watch_with(hands, {"steal_s": lambda: totals["steal_s"],
                               "runqueue_s": lambda: totals["runqueue_s"],
                               "pressure_io_s": lambda: None,  # this machine has no such file
                               "major_faults": lambda: (_ for _ in ()).throw(OSError("gone"))})
    due = hands.now + tracing.HostWatch.INTERVAL_S
    hands.naps, totals["steal_s"], totals["runqueue_s"] = [0.52], 5.4, 1.01
    written = watch.step()
    (rec,) = tracing.host_events()
    assert rec.name == "host:pause" and rec == written._replace(thread=rec.thread)
    assert rec.begin == pytest.approx(due) and rec.end == pytest.approx(hands.now)
    assert rec.args["late_s"] == pytest.approx(0.5)
    assert rec.args["steal_s"] == pytest.approx(0.4) and rec.args["runqueue_s"] == pytest.approx(0.01)
    # a file that is absent (or unreadable) leaves its field out
    assert "pressure_io_s" not in rec.args and "major_faults" not in rec.args
    assert rec.args["cause"] == "steal"
    assert METRICS.stage("host.pause").records >= 1


def test_the_fields_are_differences_against_a_snapshot_refreshed_each_quiet_second(ring):
    hands, total = Hands(), {"v": 0.0}
    watch = watch_with(hands, {"runqueue_s": lambda: total["v"]})
    total["v"] = 7.0                      # what piles up while nothing is late ...
    for _ in range(int(tracing.HostWatch.REFRESH_S / tracing.HostWatch.INTERVAL_S) + 2):
        watch.step()                      # ... is in the refreshed snapshot
    total["v"], hands.naps = 7.25, [0.3]
    assert watch.step().args["runqueue_s"] == pytest.approx(0.25)
    total["v"], hands.naps = 7.5, [0.3]   # and a pause refreshes it too
    assert watch.step().args["runqueue_s"] == pytest.approx(0.25)


@pytest.mark.parametrize("fields, gc_s, word", [
    ({"steal_s": 1.5, "runqueue_s": 0.2}, 0.0, "steal"),
    ({"steal_s": 0.1, "runqueue_s": 1.2, "pressure_cpu_s": 0.3}, 0.0, "runqueue"),
    ({"pressure_memory_s": 1.1, "major_faults": 900.0}, 0.0, "memory"),
    ({"pressure_io_s": 1.9, "pressure_memory_s": 1.1}, 0.0, "io"),
    ({"steal_s": 1.9}, 1.0, "gc"),
    ({"steal_s": 0.9, "runqueue_s": 0.9, "involuntary_switches": 5000.0}, 0.9, "unknown"),
])
def test_the_cause_is_the_largest_field_that_explains_half_the_pause(fields, gc_s, word):
    assert tracing.pause_cause(2.0, fields, gc_s) == word


def test_a_pause_a_collection_covers_is_the_collectors(ring):
    hands = Hands()
    watch = watch_with(hands, {"steal_s": lambda: 0.0})
    due = hands.now + tracing.HostWatch.INTERVAL_S
    ring.log("host:gc", int((due + 0.1) * 1e9), int(0.6 * 1e9), {"generation": 2, "collected": 0})
    hands.naps = [1.0]
    assert watch.step().args["cause"] == "gc"


@pytest.mark.parametrize("nap, warned", [(0.9, False), (1.0 + tracing.HostWatch.INTERVAL_S, True),
                                         (2.5, True)])
def test_a_pause_of_a_second_is_a_warning_and_one_of_less_is_not(ring, caplog, nap, warned):
    hands = Hands()
    watch = watch_with(hands, {"steal_s": lambda: 0.0, "major_faults": lambda: 3.0})
    hands.naps = [nap]
    with caplog.at_level(logging.WARNING, logger="tpu_tfrecord"):
        rec = watch.step()
    lines = [r.getMessage() for r in caplog.records if "tfrecord.host_pause" in r.getMessage()]
    assert len(lines) == (1 if warned else 0) and rec.name == "host:pause"
    if warned:
        assert '"cause": "unknown"' in lines[0] and '"major_faults": 0.0' in lines[0]
        assert f'"late_s": {round(nap - tracing.HostWatch.INTERVAL_S, 6)}' in lines[0]


def test_a_reader_that_raises_does_not_stop_the_watch(ring):
    hands = Hands()

    def broken():
        raise ValueError("a line this kernel writes otherwise")

    watch = watch_with(hands, {"pressure_cpu_s": broken, "steal_s": lambda: 1.0})
    hands.naps = [0.2]
    assert set(watch.step().args) == {"late_s", "steal_s", "cause"}


def test_the_operating_systems_readers_give_a_number_or_none():
    for field, reader in tracing.HOST_READERS.items():
        value = reader()
        assert value is None or value >= 0.0, field
    assert tracing._first_numbers("/proc/no/such/file") is None
    assert tracing._pressure_s("no_such_resource")() is None


# -- the collector ----------------------------------------------------------------------


def test_a_forced_collection_writes_host_gc_with_its_generation(ring, profiler):
    watch = watch_with(Hands(), {})
    watch.GC_RECORD_NS = 0  # every collection, however short on this machine
    gc.callbacks.append(watch._on_gc)
    try:
        t0 = time.perf_counter()
        gc.collect(1)
        gc.collect()
        t1 = time.perf_counter()
    finally:
        gc.callbacks.remove(watch._on_gc)
    forced = [r for r in tracing.host_events() if r.name == "host:gc"]
    assert [r.args["generation"] for r in forced][-2:] == [1, 2]
    assert all(t0 <= r.begin <= r.end <= t1 and r.args["collected"] >= 0 for r in forced[-2:])
    # an old generation's collection is on the profiler's timeline too
    assert ("host:gc", {"generation": 2}) in profiler.closed
    before = METRICS.stage("host.gc").records
    watch.fold_gc()
    assert METRICS.stage("host.gc").records - before == watch._gc_count >= 2
    watch.fold_gc()  # nothing new: nothing counted twice
    assert METRICS.stage("host.gc").records - before == watch._gc_count


def test_a_short_collection_is_counted_and_not_recorded(ring, profiler):
    watch = watch_with(Hands(), {})
    watch.GC_RECORD_NS = 10 ** 12
    watch._on_gc("start", {"generation": 0})
    watch._on_gc("stop", {"generation": 0, "collected": 4, "uncollectable": 0})
    assert watch._gc_count == 1 and len(ring) == 0
    assert profiler.opened == []  # a young collection opens no annotation
    watch._on_gc("stop", {"generation": 0, "collected": 0})  # a stop without its start
    assert watch._gc_count == 1


def test_watch_host_twice_is_one_thread_and_one_callback(ring):
    tracing.unwatch_host()
    callbacks = len(gc.callbacks)
    try:
        first = tracing.watch_host()
        assert tracing.watch_host() is first and tracing.watching()
        assert len(gc.callbacks) == callbacks + 1
        assert [t.name for t in threading.enumerate()].count("tfr-host-watch") == 1
        assert first._thread.daemon
    finally:
        tracing.unwatch_host()
    assert not tracing.watching() and len(gc.callbacks) == callbacks
    assert "tfr-host-watch" not in [t.name for t in threading.enumerate()]


# -- the names ----------------------------------------------------------------------------


@pytest.mark.parametrize("name, kind", [("host.gc", "stage"), ("host.pause", "stage"),
                                        ("host:gc", "span"), ("host:pause", "span"),
                                        ("tfr:decode", "span"), ("tfr:open", "span"),
                                        ("tfr:cache", "span")])
def test_every_new_name_is_in_the_vocabulary_and_in_readmes_block(name, kind):
    assert vocabulary.is_registered(name, kind)
    readme = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "README.md")
    with open(readme) as f:
        text = f.read()
    block = text[text.index(vocabulary.VOCABULARY_BEGIN):text.index(vocabulary.VOCABULARY_END)]
    assert f"`{name}`" in block


def test_every_host_span_is_a_span_of_the_vocabulary():
    assert tracing.HOST_SPANS <= set(vocabulary.SPANS)
