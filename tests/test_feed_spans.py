"""The feed chain and the DLRM step name themselves: ``tfr:*`` spans at every
hand-off (dataset -> HostPrefetcher -> DeviceIterator's transfer thread) and
``tfr.*`` scopes in ``forward`` / ``sparse_train_step``.

Most cases swap the profiler module behind ``tracing.trace`` for a recorder
(name, thread, start, end, arguments of every span); one runs under the real
``jax.profiler`` and reads the capture back.
"""

import functools
import glob
import os
import queue
import re
import threading
import time

import jax
import numpy as np
import optax
import pytest

import tpu_tfrecord.io as tfio
from tpu_tfrecord import tracing
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.models import (
    DLRMConfig, forward, init_params, make_synthetic_batch, sparse_opt_init,
    sparse_train_step,
)
from tpu_tfrecord.schema import FloatType, LongType, StructField, StructType
from tpu_tfrecord.tpu import (
    DeviceIterator, HostPrefetcher, create_mesh, host_batch_from_columnar, pack_mixed,
)

SCHEMA = StructType([StructField("uid", LongType()), StructField("score", FloatType())])
ROWS, BATCH = 512, 64
QUEUES = ("batch", "host", "device")


class Recorder:
    """Stands in for ``jax.profiler``: ``TraceAnnotation`` records itself."""

    def __init__(self):
        self.spans = []  # (name, thread, t0, t1, args); list.append is atomic

    def TraceAnnotation(self, name, **args):
        return _Span(self, name, args)

    def named(self, name):
        return [s for s in self.spans if s[0] == name]

    def names(self):
        return {s[0] for s in self.spans}


class _Span:
    def __init__(self, recorder, name, args):
        self.recorder, self.name, self.args = recorder, name, dict(args)

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def set_metadata(self, **args):
        self.args.update(args)

    def __exit__(self, *exc):
        self.recorder.spans.append(
            (self.name, threading.current_thread().name, self.t0, time.perf_counter(), self.args)
        )


@pytest.fixture
def recorder(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(tracing, "_PROF", rec)
    monkeypatch.setattr(tracing, "_PROF_CHECKED", True)
    return rec


@pytest.fixture
def data_dir(sandbox):
    out = str(sandbox / "feed")
    tfio.write([[i, i / 2.0] for i in range(ROWS)], SCHEMA, out, mode="overwrite")
    return out


class Chain:
    """TFRecordDataset -> host_batch_from_columnar on HostPrefetcher's thread
    -> DeviceIterator(transfer_thread=True), as the benchmark's feed builds it."""

    def __init__(self, data_dir, num_epochs, produce_sleep=0.0):
        self.ds = TFRecordDataset(data_dir, batch_size=BATCH, schema=SCHEMA,
                                  num_epochs=num_epochs, prefetch=2)
        self.batches = self.ds.batches()
        self.host_bytes = 0

        def host_batches():
            for cb in self.batches:
                time.sleep(produce_sleep)
                hb = host_batch_from_columnar(cb, self.ds.schema)
                self.host_bytes += sum(a.nbytes for a in hb.values())
                yield hb

        self.prefetcher = HostPrefetcher(host_batches())
        self.device = DeviceIterator(self.prefetcher, create_mesh(), transfer_thread=True)

    def close(self):
        self.device.close()
        self.prefetcher.close()
        self.batches.close()
        self.prefetcher._thread.join(timeout=5.0)
        assert not self.prefetcher._thread.is_alive()


def test_every_batch_is_named_once_with_its_rows_and_bytes(recorder, data_dir):
    chain = Chain(data_dir, num_epochs=1)
    try:
        got = sum(int(gb["uid"].shape[0]) for gb in chain.device)
    finally:
        chain.close()
    assert got == ROWS
    n_batches = ROWS // BATCH
    for name in ("tfr:pack", "tfr:h2d"):
        spans = recorder.named(name)
        assert len(spans) == n_batches, name
        assert sum(s[4]["rows"] for s in spans) == ROWS, name
        assert sum(s[4]["bytes"] for s in spans) == chain.host_bytes, name
    assert len(recorder.named("tfr:h2d_land")) == n_batches
    decodes = recorder.named("tfr:decode")
    assert sum(s[4]["rows"] for s in decodes) == ROWS
    files = glob.glob(os.path.join(data_dir, "**", "part-*"), recursive=True)
    on_disk = sum(os.path.getsize(f) for f in files)
    # the fused scan counts the frames it walked, the two-pass path the payloads
    assert sum(s[4]["bytes"] for s in decodes) in (on_disk, on_disk - 16 * ROWS)
    # each stage on a thread of its own: decode, pack, transfer
    threads = {name: {s[1] for s in recorder.named(name)}
               for name in ("tfr:decode", "tfr:pack", "tfr:h2d", "tfr:h2d_land")}
    assert all(len(t) == 1 for t in threads.values()), threads
    assert threads["tfr:h2d"] == threads["tfr:h2d_land"]
    assert len({*threads["tfr:decode"], *threads["tfr:pack"], *threads["tfr:h2d"]}) == 3
    assert recorder.names() <= set(tracing.ANNOTATIONS)


def test_a_slow_consumer_blocks_all_three_queues_one_span_a_put(recorder, data_dir):
    chain = Chain(data_dir, num_epochs=None)
    steps, nap = 6, 0.15  # a nap longer than the 0.1 s poll inside a blocked put
    try:
        next(chain.device)
        time.sleep(0.5)  # every queue fills behind the idle consumer
        t0 = time.perf_counter()
        for _ in range(steps):
            next(chain.device)
            time.sleep(nap)
        t1 = time.perf_counter()
    finally:
        chain.close()  # the puts still blocked give up and close their spans
    slept = t1 - t0
    for q in QUEUES:
        spans = recorder.named(f"tfr:blocked.{q}")
        inside = sum(max(0.0, min(s[3], t1) - max(s[2], t0)) for s in spans)
        assert abs(inside - slept) <= 0.2 * slept, (q, inside, slept)
        began = [s for s in spans if t0 <= s[2] < t1]
        assert 1 <= len(began) <= steps + 1, (q, len(began))
        # start-up aside, nobody waits on an empty queue
        assert not [s for s in recorder.named(f"tfr:starved.{q}") if s[2] >= t0], q
    assert recorder.names() <= set(tracing.ANNOTATIONS)


def test_a_slow_producer_starves_the_queues_after_it(recorder, data_dir):
    chain = Chain(data_dir, num_epochs=1, produce_sleep=0.03)
    try:
        t0 = time.perf_counter()
        n = sum(1 for _ in chain.device)
        waited = time.perf_counter() - t0
    finally:
        chain.close()
    assert n == ROWS // BATCH
    for q in ("host", "device"):
        starved = sum(s[3] - s[2] for s in recorder.named(f"tfr:starved.{q}"))
        assert abs(starved - waited) <= 0.25 * waited, (q, starved, waited)
        assert not recorder.named(f"tfr:blocked.{q}")
    assert recorder.names() <= set(tracing.ANNOTATIONS)


def test_a_consumer_that_arrives_before_the_first_chunk_is_starved(recorder, data_dir,
                                                                   monkeypatch):
    # the decode thread holds its first chunk back until the consumer waits: on a
    # loaded runner it can otherwise have a batch queued before next() is reached
    waiting, annotate = threading.Event(), recorder.TraceAnnotation

    def annotation(name, **args):
        if name == "tfr:starved.batch":
            waiting.set()
        elif name == "tfr:decode":
            assert waiting.wait(timeout=30.0)
        return annotate(name, **args)

    monkeypatch.setattr(recorder, "TraceAnnotation", annotation)
    ds = TFRecordDataset(data_dir, batch_size=BATCH, schema=SCHEMA, num_epochs=1)
    with ds.batches() as it:
        assert next(it).num_rows == BATCH  # asked for before anything is decoded
    (span,) = recorder.named("tfr:starved.batch")
    assert span[1] == threading.current_thread().name


def test_an_unblocked_hand_off_opens_no_span(recorder):
    q, stop = queue.Queue(maxsize=2), threading.Event()
    assert tracing.put_or_wait(q, "a", stop, "tfr:blocked.host")
    assert tracing.get_or_wait(q, stop, "tfr:starved.host") == "a"
    assert recorder.spans == []
    stop.set()
    assert not tracing.put_or_wait(q, "b", stop, "tfr:blocked.host")
    assert tracing.get_or_wait(q, stop, "tfr:starved.host") is tracing.STOPPED
    assert recorder.spans == [] and q.empty()


def test_a_blocked_put_gives_up_when_stopped(recorder):
    q, stop = queue.Queue(maxsize=1), threading.Event()
    q.put("full")
    threading.Timer(0.25, stop.set).start()
    assert not tracing.put_or_wait(q, "late", stop, "tfr:blocked.host")
    (span,) = recorder.spans  # 0.25 s of polling, one span
    assert span[0] == "tfr:blocked.host" and 0.2 <= span[3] - span[2] < 1.0


def test_pack_mixed_is_a_pack_span(recorder):
    arr = np.arange(8 * 40, dtype=np.int32).reshape(8, 40) % 1000
    out = pack_mixed(arr, 14, 20)
    (span,) = recorder.named("tfr:pack")
    assert span[4] == {"rows": 8, "bytes": out.nbytes}


def test_under_the_real_profiler_the_spans_land_on_the_host_plane(data_dir, tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        chain = Chain(data_dir, num_epochs=1)
        try:
            for gb in chain.device:
                pass
        finally:
            chain.close()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("tfr:"):
                    found.setdefault(ev.name, []).append({k: v for k, v in ev.stats})
    # arguments become the event's stats; the name stays bare
    for name in ("tfr:decode", "tfr:pack", "tfr:h2d"):
        assert sum(int(s["rows"]) for s in found[name]) == ROWS, name
        assert all(int(s["bytes"]) >= 0 for s in found[name]), name
    assert len(found["tfr:h2d_land"]) == ROWS // BATCH
    assert set(found) <= set(tracing.ANNOTATIONS)


FORWARD_SCOPES = {"tfr.bottom_mlp", "tfr.gather", "tfr.interaction", "tfr.top_mlp"}
STEP_SCOPES = FORWARD_SCOPES | {
    "tfr.dense_update", "tfr.dedup_sort", "tfr.segment_sum", "tfr.accum_update",
    "tfr.table_scatter"}


def _small_dlrm(embed_dim=16):
    cfg = DLRMConfig(vocab_size=64, embed_dim=embed_dim, bottom_mlp=(32, embed_dim),
                     top_mlp=(32, 1), interaction="dot")
    return cfg, init_params(jax.random.PRNGKey(0), cfg), make_synthetic_batch(cfg, 32)


@pytest.mark.parametrize("program,scopes,embed_dim", [
    ("forward", FORWARD_SCOPES, 16),
    ("sparse_train_step", STEP_SCOPES, 16),
    ("sparse_train_step", STEP_SCOPES, 128),    # the update in its loop over blocks of slots
])
def test_the_compiled_program_holds_every_scope(program, scopes, embed_dim):
    cfg, params, batch = _small_dlrm(embed_dim)
    if program == "forward":
        lowered = jax.jit(functools.partial(forward, cfg=cfg)).lower(params, batch)
    else:
        tx = optax.sgd(1e-3)
        lowered = jax.jit(functools.partial(sparse_train_step, cfg=cfg, tx=tx)).lower(
            params, sparse_opt_init(params, cfg, tx), batch)
    op_names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    held = {tok for name in op_names for tok in re.findall(r"tfr\.\w+", name)}
    assert held == scopes
    assert held <= set(tracing.ANNOTATIONS)
    # backward operations keep the forward scope inside transpose(jvp(...))
    if program == "sparse_train_step":
        assert any("transpose(jvp(tfr.top_mlp))" in name for name in op_names)


def test_forward_makes_no_value_of_the_tables_shape():
    """``forward`` reads the rows a batch names out of the float32 table; a
    cast, copy or view of all [F, V, D] of it (PR 24's trace: 70% of the
    score step) must not come back. Parameters are the table itself."""
    cfg, params, batch = _small_dlrm()
    hlo = jax.jit(functools.partial(forward, cfg=cfg)).lower(params, batch).compile().as_text()
    f, v, d = params["embeddings"].shape
    produced = re.findall(rf"^\s*(?:ROOT )?%?\S+ = \w+\[(?:1,)?{f},{v},{d}\]\S* (\w[\w-]*)\(",
                          hlo, flags=re.M)
    assert "parameter" in produced          # the pattern does see the table
    assert set(produced) == {"parameter"}, produced
