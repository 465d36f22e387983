#!/usr/bin/env python
"""End-to-end ingest benchmark: Criteo-like TFRecords -> device memory.

Measures the BASELINE.md north-star metric: tf.Example/sec/host sustained
into device HBM through the full pipeline — native frame scan + CRC, native
batch decode to columnar buffers (background prefetch thread, GIL released),
categorical hashing, global-array assembly on the device mesh, transfer
blocked to completion.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} where
vs_baseline is value / 1e6 (the reference publishes no numbers —
BASELINE.md: >=1M examples/sec/host target; >1.0 beats it).

Dataset: Criteo-shaped — int64 label, 13 int64 dense features, 26
categorical byte strings — TFR_BENCH_SHARDS shards (default 4) of
RECORDS_PER_SHARD records, generated once and cached (the cache key
includes the shard count, so changing TFR_BENCH_SHARDS regenerates
instead of silently benchmarking a stale dataset).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

N_SHARDS = int(os.environ.get("TFR_BENCH_SHARDS", 4))
RECORDS_PER_SHARD = int(os.environ.get("TFR_BENCH_RECORDS_PER_SHARD", 32768))
BATCH_SIZE = int(os.environ.get("TFR_BENCH_BATCH", 16384))
HASH_BUCKETS = 1 << 20
CAT_BITS = 20  # hash_buckets = 2**20 -> bucket indices carry 20 bits
WARMUP_BATCHES = 4
MEASURE_SECONDS = float(os.environ.get("TFR_BENCH_SECONDS", 6.0))
SUSTAIN_SECONDS = float(os.environ.get("TFR_BENCH_SUSTAIN", 8.0))


NUM_DENSE, NUM_CAT = 13, 26


def criteo_schema():
    """Write-side schema (inference parity: ints are LongType)."""
    from tpu_tfrecord.schema import LongType, StringType, StructField, StructType

    fields = [StructField("label", LongType(), nullable=False)]
    fields += [StructField(f"I{i}", LongType()) for i in range(1, 14)]
    fields += [StructField(f"C{i}", StringType()) for i in range(1, 27)]
    return StructType(fields)


def criteo_read_schema():
    """Read-side schema: IntegerType for the int features — the reference's
    IntegerType read path (Long.toInt truncation, TFRecordDeserializer
    IntegerType case) — so every device-bound column is int32 and the whole
    batch packs into ONE [B, 40] i32 matrix (one transfer dispatch)."""
    from tpu_tfrecord.schema import IntegerType, StringType, StructField, StructType

    fields = [StructField("label", IntegerType(), nullable=False)]
    fields += [StructField(f"I{i}", IntegerType()) for i in range(1, 14)]
    fields += [StructField(f"C{i}", StringType()) for i in range(1, 27)]
    return StructType(fields)


def criteo_reader_spec():
    """(hash_buckets, pack) of the Criteo reader: categorical hashing to
    2^20 buckets fused into the native decode, and ONE column group = one
    [B, 40] i32 host matrix = ONE device transfer (the consumer jit splits
    label/dense/cat on device, free under XLA fusion)."""
    hash_buckets = {f"C{i}": HASH_BUCKETS for i in range(1, NUM_CAT + 1)}
    pack = {
        "packed": ["label"]
        + [f"I{i}" for i in range(1, NUM_DENSE + 1)]
        + [f"C{i}" for i in range(1, NUM_CAT + 1)],
    }
    return hash_buckets, pack


def criteo_dlrm_config(vocab: int, top_mlp=(64, 1), **kw):
    """The Criteo cell's DLRM: 26 tables x ``vocab`` x 32, bottom 64-32,
    dot interaction (bf16 activations unless ``dtype`` is given)."""
    from tpu_tfrecord.models import DLRMConfig

    return DLRMConfig(
        num_dense=NUM_DENSE, num_categorical=NUM_CAT, vocab_size=vocab,
        embed_dim=32, bottom_mlp=(64, 32), top_mlp=top_mlp,
        interaction="dot", **kw,
    )


def split_wire(gb, vocab: int):
    """The consumer-side split of the bit-packed wire batch: label / 13
    dense / 26 categorical indices, the 20-bit unpack fused into the
    caller's jit (the train step is a separate program — its donated
    params preclude merging here). Dense ints get the standard Criteo
    log1p (examples/train_dlrm.py) so SGD steps stay finite; indices fold
    only when the table is smaller than the hashed space (CPU smoke runs
    shrink it)."""
    import jax.numpy as jnp

    from tpu_tfrecord.tpu import unpack_bits

    m = gb["wire"]
    cat = unpack_bits(m[:, 1 + NUM_DENSE:], NUM_CAT, CAT_BITS)
    return {
        "label": m[:, 0].astype(jnp.float32),
        "dense": jnp.log1p(m[:, 1:1 + NUM_DENSE].astype(jnp.float32)),
        "cat": cat % vocab if vocab < HASH_BUCKETS else cat,
    }


def ensure_dataset(data_dir: str) -> str:
    """Generate the benchmark dataset once; reuse across runs. The cache
    key (a subdirectory) includes the generation parameters, so changing
    TFR_BENCH_SHARDS / TFR_BENCH_RECORDS_PER_SHARD regenerates instead of
    silently measuring a stale dataset of the wrong shape."""
    from tpu_tfrecord import wire
    from tpu_tfrecord.options import RecordType
    from tpu_tfrecord.serde import TFRecordSerializer, encode_row

    data_dir = os.path.join(data_dir, f"s{N_SHARDS}r{RECORDS_PER_SHARD}")
    marker = os.path.join(data_dir, "_BENCH_READY")
    if os.path.exists(marker):
        return data_dir
    os.makedirs(data_dir, exist_ok=True)
    schema = criteo_schema()
    ser = TFRecordSerializer(schema)
    rng = np.random.default_rng(0)
    for s in range(N_SHARDS):
        ints = rng.integers(0, 1 << 31, size=(RECORDS_PER_SHARD, 13))
        labels = rng.integers(0, 2, size=RECORDS_PER_SHARD)
        cats = rng.integers(0, 16, size=(RECORDS_PER_SHARD, 26, 8), dtype=np.uint8) + 97

        def rows():
            for r in range(RECORDS_PER_SHARD):
                row = [int(labels[r])]
                row += [int(v) for v in ints[r]]
                row += [cats[r, c].tobytes().decode() for c in range(26)]
                yield encode_row(ser, RecordType.EXAMPLE, row)

        wire.write_records(
            os.path.join(data_dir, f"part-{s:05d}-bench.tfrecord"), rows()
        )
    with open(marker, "w") as fh:
        fh.write("ok")
    return data_dir


def _make_dataset(data_dir, schema, hash_buckets, pack, **kw):
    from tpu_tfrecord.io.dataset import TFRecordDataset

    return TFRecordDataset(
        data_dir,
        batch_size=BATCH_SIZE,
        schema=schema,
        prefetch=4,
        hash_buckets=hash_buckets,  # fused into native decode
        pack=pack,              # groups assembled in C++ as [B, K] matrices
        **kw,
    )


def _host_side_throughput(data_dir, schema, hash_buckets, pack, seconds=4.0, **ds_kw):
    """Device-free pipeline throughput: frame scan + CRC + decode + hash +
    pack to dense host batches, no device anywhere. Measured on EVERY run
    (before backend init), so the artifact always carries a device-free
    number to read the device phase against.
    ``ds_kw`` forwards extra dataset options (the stall-guard overhead
    probe runs this same loop with deadlines+watchdog enabled)."""
    from tpu_tfrecord.tpu import host_batch_from_columnar

    ds = _make_dataset(data_dir, schema, hash_buckets, pack, num_epochs=None, **ds_kw)
    it = ds.batches()
    try:
        for _ in range(2):  # warm the decode threads / entry-shape caches
            host_batch_from_columnar(
                next(it), ds.schema, hash_buckets=hash_buckets, pack=pack
            )
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            hb = host_batch_from_columnar(
                next(it), ds.schema, hash_buckets=hash_buckets, pack=pack
            )
            n += hb["packed"].shape[0]
        return n / (time.perf_counter() - t0)
    finally:
        it.close()


def _drop_page_cache(data_dir) -> None:
    """Evict the shards from the page cache (POSIX_FADV_DONTNEED; works on
    ext4 for clean pages without any privileges)."""
    for name in sorted(os.listdir(data_dir)):
        if not name.startswith("part-"):
            continue
        fd = os.open(os.path.join(data_dir, name), os.O_RDONLY)
        try:
            # fsync first: DONTNEED silently skips dirty pages, so a
            # just-generated dataset would otherwise measure warm.
            os.fsync(fd)
            os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
        finally:
            os.close(fd)


def _raw_disk_mbps(data_dir) -> float:
    """Serial cold read of the shards, 8MB blocks, no hints: the
    UNENGINEERED IO bound, disclosed next to cold_value so the pipeline
    number reads against the store's state during THIS run (the backing
    volume on this box swings 150 MB/s .. 2 GB/s between moments)."""
    _drop_page_cache(data_dir)
    buf = bytearray(8 << 20)
    t0 = time.perf_counter()
    nb = 0
    for name in sorted(os.listdir(data_dir)):
        if not name.startswith("part-"):
            continue
        with open(os.path.join(data_dir, name), "rb", buffering=0) as fh:
            while True:
                k = fh.readinto(buf)
                if not k:
                    break
                nb += k
    return nb / (time.perf_counter() - t0) / 1e6


def _cold_io_throughput(data_dir, schema, hash_buckets, pack) -> dict:
    """One full pass over the dataset right after dropping it from the page
    cache: the only number here that includes real disk IO (the main
    measurement loops over a cache-resident dataset — BASELINE.md configs[4]
    is about line-rate ingest of storage-resident data).

    Engineered (round 4): sliding posix_fadvise(WILLNEED) readahead inside
    the decode paths (io/dataset.py) keeps the kernel streaming ahead of
    the decoder, and ``num_workers`` shards decode/IO concurrently (IO
    waits release the GIL, so overlap is real even on this 1-core host).
    The raw serial disk rate is measured first and disclosed, so
    cold_value / cold_disk_bound_value tells IO-bound from decode-bound."""
    from tpu_tfrecord.tpu import host_batch_from_columnar

    disk_mbps = _raw_disk_mbps(data_dir)
    wire_bytes = sum(
        os.path.getsize(os.path.join(data_dir, n))
        for n in os.listdir(data_dir)
        if n.startswith("part-")
    )
    n_records = N_SHARDS * RECORDS_PER_SHARD
    bytes_per_example = wire_bytes / n_records
    workers = int(os.environ.get("TFR_BENCH_COLD_WORKERS", 2))
    readahead = int(os.environ.get("TFR_BENCH_COLD_READAHEAD", 64 << 20))
    _drop_page_cache(data_dir)
    ds = _make_dataset(
        data_dir, schema, hash_buckets, pack,
        num_epochs=1, num_workers=workers, readahead_bytes=readahead,
    )
    # Stage attribution (VERDICT r4 item 2): process CPU time vs wall tells
    # IO-stalled from CPU-bound; consumer-side wait/pack and the decode
    # stage's per-worker seconds (sums across threads, so it can exceed
    # wall when overlap works) localize where the wall time went; majflt ~ 0
    # proves the WILLNEED readahead turned cold reads into prefetched
    # (minor-fault) hits.
    import resource

    from tpu_tfrecord.metrics import METRICS

    d0 = METRICS.stage("decode").seconds
    r0 = resource.getrusage(resource.RUSAGE_SELF)
    t0 = time.perf_counter()
    n = 0
    wait_s = 0.0
    pack_s = 0.0
    with ds.batches() as it:
        while True:
            w0 = time.perf_counter()
            cb = next(it, None)
            wait_s += time.perf_counter() - w0
            if cb is None:
                break
            p0 = time.perf_counter()
            hb = host_batch_from_columnar(
                cb, ds.schema, hash_buckets=hash_buckets, pack=pack
            )
            pack_s += time.perf_counter() - p0
            n += hb["packed"].shape[0]
    wall = time.perf_counter() - t0
    r1 = resource.getrusage(resource.RUSAGE_SELF)
    decode_s = METRICS.stage("decode").seconds - d0
    cpu_s = (r1.ru_utime - r0.ru_utime) + (r1.ru_stime - r0.ru_stime)
    value = n / wall
    bound = disk_mbps * 1e6 / bytes_per_example  # ex/s if purely IO-bound
    # The raw-disk bound is unreachable when decode CPU alone exceeds the
    # disk's per-example time budget (a 1-core host decoding at ~0.8us/ex
    # cannot ingest a >2 GB/s stream at ~0.3us/ex). The corrected bound is
    # the binding constraint: min(disk rate, this run's measured CPU work
    # rate) — a multi-core host relaxes the CPU term toward the disk bound.
    cpu_bound = n / cpu_s if cpu_s > 0 else None
    eff_bound = min(bound, cpu_bound) if cpu_bound else bound
    return {
        "cold_value": round(value, 1),
        # serial no-hint read rate measured immediately before the pass
        "cold_disk_mbps": round(disk_mbps, 1),
        # that rate expressed in ex/s: the raw-disk bound cold_value reads
        # against (>1.0 cold_vs_disk_bound = the engineered path beat the
        # serial-read bound via readahead/overlap; <1.0 = decode-bound or
        # the store sped up/slowed down between the two measurements)
        "cold_disk_bound_value": round(bound, 1),
        "cold_vs_disk_bound": round(value / bound, 3) if bound else None,
        "cold_cpu_bound_value": round(cpu_bound, 1) if cpu_bound else None,
        "cold_vs_bound": round(value / eff_bound, 3) if eff_bound else None,
        "cold_stage_s": {
            "wall": round(wall, 3),
            "cpu": round(cpu_s, 3),
            "decode_workers": round(decode_s, 3),
            "consumer_wait": round(wait_s, 3),
            "consumer_pack": round(pack_s, 3),
        },
        "cold_majflt": r1.ru_majflt - r0.ru_majflt,
        "cold_wire_bytes_per_example": round(bytes_per_example, 1),
        "cold_workers": workers,
        "cold_readahead_mb": readahead >> 20,
    }


def _stall_guard_overhead(data_dir, schema, hash_buckets, pack) -> dict:
    """Bench guardrail for the stall-defense layer (ISSUE 3 acceptance:
    fault-free read throughput regresses < 2% with deadlines + watchdog
    enabled): the SAME device-free host loop measured with the guards off
    and on — generous deadlines that never fire, watchdog armed, parallel
    workers so the watchdog actually monitors something — interleaved
    A/B/A/B with best-of-each (this box's one-sided noise estimator, same
    argument as the main attempts loop). Reported as one JSON field:
    ``stall_guard_overhead_pct`` (negative = in the noise)."""
    import statistics

    seconds = float(os.environ.get("TFR_BENCH_STALL_SECONDS", 2.0))
    repeats = int(os.environ.get("TFR_BENCH_STALL_REPEATS", 3))
    guarded_kw = dict(
        read_deadline_ms=60_000.0,
        open_deadline_ms=60_000.0,
        watchdog_timeout_ms=60_000.0,
        num_workers=2,
    )
    base_kw = dict(num_workers=2)

    def run(kw):
        return _host_side_throughput(
            data_dir, schema, hash_buckets, pack, seconds=seconds, **kw
        )

    # Interleaved rounds, alternating B/G then G/B so drift in the shared
    # box's load hits both sides equally. Interference here is strictly
    # one-sided (other tenants only SLOW a run down), so the overhead
    # estimate compares the BEST of each side — the same min-of-repeats
    # argument the main attempts loop documents; the per-round paired
    # ratios are disclosed so a reader can see the noise floor (single
    # pairs swing +-5% on this box, far above the true overhead).
    base, guarded, pair_pct = [], [], []
    for r in range(repeats):
        if r % 2 == 0:
            b, g = run(base_kw), run(guarded_kw)
        else:
            g, b = run(guarded_kw), run(base_kw)
        base.append(b)
        guarded.append(g)
        pair_pct.append((1.0 - g / b) * 100.0)
    best_b, best_g = max(base), max(guarded)
    return {
        "stall_guard_baseline_eps": round(best_b, 1),
        "stall_guard_enabled_eps": round(best_g, 1),
        "stall_guard_overhead_pct": round((1.0 - best_g / best_b) * 100.0, 2),
        "stall_guard_pair_median_pct": round(statistics.median(pair_pct), 2),
        "stall_guard_pair_pcts": [round(p, 2) for p in pair_pct],
    }


def _tracing_overhead(data_dir, schema, hash_buckets, pack) -> dict:
    """Bench guardrail for the flight recorder (ISSUE 5 acceptance:
    ``trace="on"`` costs <= 2%, ``trace="off"`` is within noise of the
    pre-PR baseline): the SAME device-free host loop measured with tracing
    off and on, interleaved A/B with best-of-each (the box's one-sided
    noise estimator — same argument as the stall-guard probe). The traced
    runs also produce the ``telemetry`` block: per-stage latency quantiles
    from the always-on histograms plus the bound-ness verdict from the
    prefetch-occupancy gauge."""
    import statistics

    from tpu_tfrecord import telemetry as tm
    from tpu_tfrecord.metrics import METRICS

    seconds = float(os.environ.get("TFR_BENCH_TRACE_SECONDS", 2.0))
    repeats = int(os.environ.get("TFR_BENCH_TRACE_REPEATS", 3))
    # the earlier phases (cold pass, stall probe, warm-cache epochs) ran
    # under different configurations; their histogram observations would
    # blend into the reported quantiles, so the probe starts clean (every
    # later bench phase captures its own baselines, none reads cumulative
    # pre-probe state)
    METRICS.reset()

    def run(traced: bool):
        # the recorder is process-global: force the state per run (a
        # trace="off" dataset deliberately does not disable it)
        if traced:
            tm.RECORDER.clear()
            tm.enable()
        else:
            tm.disable()
        try:
            return _host_side_throughput(
                data_dir, schema, hash_buckets, pack, seconds=seconds,
                **({"trace": "on"} if traced else {}),
            )
        finally:
            tm.disable()

    base, traced, pair_pct = [], [], []
    for r in range(repeats):
        if r % 2 == 0:
            b, g = run(False), run(True)
        else:
            g, b = run(True), run(False)
        base.append(b)
        traced.append(g)
        pair_pct.append((1.0 - g / b) * 100.0)
    best_b, best_g = max(base), max(traced)

    # cluster-spool arm (ISSUE 7, same <=2% bar): the identical loop with
    # TRACE on AND the telemetry spool ticking into a scratch dir — the
    # full fleet-observed configuration a disaggregated worker would run
    # with. One interleaved pair against a fresh baseline (the spool is a
    # 1 Hz daemon-thread JSONL rewrite; it either costs ~nothing or the
    # number says so).
    import shutil
    import tempfile

    from tpu_tfrecord import fleet

    spool_dir = tempfile.mkdtemp(prefix="tfr_bench_spool_")
    try:

        def run_spooled():
            tm.RECORDER.clear()
            tm.enable()
            try:
                return _host_side_throughput(
                    data_dir, schema, hash_buckets, pack, seconds=seconds,
                    trace="on", telemetry_spool_dir=spool_dir,
                    telemetry_role="bench",
                )
            finally:
                tm.disable()

        # interleaved A/B, best-of-each — the same one-sided noise
        # estimator as the trace arm above
        b0, s0 = run(False), run_spooled()
        # the second spooled run's spool object rewrites the (same-pid)
        # spool file from scratch, so the aggregator only ever sees ITS
        # lines — count the writes over the same window so the two
        # corroborating fields below agree
        writes_before_s1 = METRICS.counter("fleet.spool_writes")
        s1, b1 = run_spooled(), run(False)
        spool_base, spool_on = max(b0, b1), max(s0, s1)
        fleet_snap = fleet.TelemetryAggregator(spool_dir).aggregate()
        spool_info = {
            "spool_baseline_eps": round(spool_base, 1),
            "spool_enabled_eps": round(spool_on, 1),
            "spool_overhead_pct": round(
                (1.0 - spool_on / spool_base) * 100.0, 2
            ),
            "spool_snapshots": sum(p.seq for p in fleet_snap.processes),
            "spool_writes_counted": METRICS.counter("fleet.spool_writes")
            - writes_before_s1,
        }
    finally:
        shutil.rmtree(spool_dir, ignore_errors=True)

    quantiles = tm.quantiles_ms(METRICS.quantiles())
    occ = METRICS.gauge_value(tm.OCCUPANCY_GAUGE)
    ctx = tm.current_context()
    out = {
        "tracing_baseline_eps": round(best_b, 1),
        "tracing_enabled_eps": round(best_g, 1),
        "tracing_overhead_pct": round((1.0 - best_g / best_b) * 100.0, 2),
        "tracing_pair_median_pct": round(statistics.median(pair_pct), 2),
        "tracing_pair_pcts": [round(p, 2) for p in pair_pct],
        **spool_info,
        "telemetry": {
            "quantiles": quantiles,
            "prefetch_occupancy": round(occ, 4) if occ is not None else None,
            "verdict": tm.boundness_verdict(occ),
            "spans_recorded": len(tm.RECORDER),
            "spans_dropped": tm.RECORDER.dropped,
            # identity stamp: correlates this artifact with pulse lines,
            # spool snapshots, and merged traces from the same run
            "proc": {"host": ctx.host, "pid": ctx.pid, "role": ctx.role,
                     "trace_id": ctx.trace_id},
        },
    }
    tm.RECORDER.clear()
    return out


def _warm_epoch_throughput(data_dir, schema, hash_buckets, pack) -> dict:
    """Columnar epoch cache (ISSUE 4): populate the cache with one full
    pass (decode + cache append), then measure the mmap-served warm-epoch
    rate with the SAME device-free loop host_side_value uses — so
    warm_epoch_value / host_side_value is the cache's speedup over the
    decode-bound path on this box (acceptance bar: >= 1.5x). The populate
    pass rate is disclosed too (it pays decode + cache-file writes)."""
    import shutil
    import tempfile

    from tpu_tfrecord.metrics import METRICS

    cache_dir = tempfile.mkdtemp(prefix="tfr_bench_cache_")
    kw = dict(cache="auto", cache_dir=cache_dir)
    try:
        b0 = METRICS.counter("cache.bytes_written")
        ds = _make_dataset(data_dir, schema, hash_buckets, pack, num_epochs=1, **kw)
        t0 = time.perf_counter()
        n = 0
        with ds.batches() as it:
            for cb in it:
                n += cb.num_rows
        populate_eps = n / (time.perf_counter() - t0)
        h0 = METRICS.counter("cache.hits")
        c0 = METRICS.counter("cache.corrupt_fallbacks")
        value = _host_side_throughput(
            data_dir, schema, hash_buckets, pack,
            seconds=float(os.environ.get("TFR_BENCH_WARM_SECONDS", 3.0)), **kw,
        )
        return {
            # cache-served epoch: decode replaced by mmap views + hash/pack
            "warm_epoch_value": round(value, 1),
            # the one-time population pass (decode + cache append)
            "warm_populate_value": round(populate_eps, 1),
            "warm_cache_hits": METRICS.counter("cache.hits") - h0,
            "warm_cache_corrupt_fallbacks": METRICS.counter("cache.corrupt_fallbacks") - c0,
            "warm_cache_bytes_written": METRICS.counter("cache.bytes_written") - b0,
        }
    finally:
        # unpin the probe entries' mmaps BEFORE deleting the dir, or the
        # deleted inodes' blocks stay allocated for the rest of the run
        from tpu_tfrecord.cache import release_registry

        release_registry(cache_dir)
        shutil.rmtree(cache_dir, ignore_errors=True)


# SEQ_* are env-overridable like the Criteo knobs; ensure_seq_dataset keys
# its cache directory on all four generation parameters, so changing any
# of them regenerates instead of silently benchmarking stale data
# (ADVICE: seq bench cache key).
SEQ_SHARDS = int(os.environ.get("TFR_BENCH_SEQ_SHARDS", 2))
SEQ_DOCS_PER_SHARD = int(os.environ.get("TFR_BENCH_SEQ_DOCS", 4096))
SEQ_MAX_LEN = int(os.environ.get("TFR_BENCH_SEQ_MAX_LEN", 64))
SEQ_DIM = int(os.environ.get("TFR_BENCH_SEQ_DIM", 16))
SEQ_BATCH = int(os.environ.get("TFR_BENCH_SEQ_BATCH", 1024))


def _remote_prefetch_probe() -> dict:
    """Disclosed evidence for the remote readahead path (VERDICT r4 item 3):
    stream one object through PrefetchReader over a simulated high-RTT link
    (every range request pays a fixed latency; requests on independent
    handles overlap, like real object-store GETs) vs a serial read loop
    paying one RTT per block. The pipelined rate approaching
    block_size*depth/RTT = the prefetcher saturates the link. Device-free,
    ~2s; memory-backed so no network variance. Correctness (byte equality,
    fault injection) is pinned in tests/test_fs.py — this records the
    NUMBER next to the headline."""
    try:
        import fsspec  # noqa: F401
    except ImportError:
        return {"remote_skipped": "fsspec unavailable"}
    import threading

    from tpu_tfrecord import fs as tfs

    rtt_s = float(os.environ.get("TFR_BENCH_REMOTE_RTT_S", 0.02))
    block = int(os.environ.get("TFR_BENCH_REMOTE_BLOCK", 2 << 20))
    depth = int(os.environ.get("TFR_BENCH_REMOTE_DEPTH", 4))
    nbytes = 32 << 20
    path = "memory://tfr-bench/remote.bin"
    fsys = tfs.filesystem_for(path)
    payload = np.random.default_rng(3).integers(0, 256, nbytes, np.uint8)
    with fsys.open(path, "wb") as fh:
        fh.write(payload.tobytes())

    io_lock = threading.Lock()

    class _LinkFile:
        def __init__(self, inner):
            self._inner = inner
            self._pos = 0

        def seek(self, pos, whence=0):
            self._pos = pos

        def read(self, size=-1):
            time.sleep(rtt_s)  # per-request RTT, outside the lock
            with io_lock:  # memory:// shares one cursor across handles
                self._inner.seek(self._pos)
                data = self._inner.read(size)
            self._pos += len(data)
            return data

        def close(self):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

    class _LinkFS:
        protocol = "simlink"  # independent handles: no serialization needed

        def __init__(self, fs):
            self._fs = fs

        def open(self, p, mode):
            # under io_lock: memory://'s _open seeks the SHARED file object
            # to 0, which must not interleave with another handle's
            # locked seek+read
            with io_lock:
                return _LinkFile(self._fs.open(p, mode))

        def __getattr__(self, name):
            return getattr(self._fs, name)

    link = _LinkFS(fsys)

    def drain_serial() -> float:
        # loop the KNOWN block count: a read-until-empty loop would pay one
        # extra RTT for the EOF probe that the pipelined path never issues,
        # biasing the speedup upward (~1/nblocks)
        t0 = time.perf_counter()
        with link.open(path, "rb") as fh:
            for _ in range((nbytes + block - 1) // block):
                fh.read(block)
        return nbytes / (time.perf_counter() - t0) / 1e6

    def drain_pipelined() -> float:
        t0 = time.perf_counter()
        with tfs.PrefetchReader(link, path, nbytes, block, depth) as fh:
            while fh.read(block):
                pass
        return nbytes / (time.perf_counter() - t0) / 1e6

    serial_mbps = drain_serial()
    pipe_mbps = drain_pipelined()
    fsys.remove(path)
    return {
        # simulated-link streaming rates (MB/s) and the pipelining win;
        # link ceiling = block*depth/RTT, serial floor = block/RTT
        "remote_sim_rtt_ms": rtt_s * 1e3,
        "remote_sim_serial_mbps": round(serial_mbps, 1),
        "remote_sim_pipelined_mbps": round(pipe_mbps, 1),
        "remote_sim_speedup": round(pipe_mbps / serial_mbps, 2),
        "remote_sim_link_ceiling_mbps": round(block * depth / rtt_s / 1e6, 1),
        "remote_prefetch_depth": depth,
    }


def _remote_http_probe() -> dict:
    """Real-network remote evidence (ISSUE 9 / ROADMAP #3): the depth
    sweep and the remote->cache->mmap number over a REAL threaded HTTP
    backend — genuinely independent TCP connections per block fetch, with
    a fixed server-side per-request latency as the simulated link RTT
    (the sim-link probe above plateaued at 76% of the depth-4 ceiling;
    this finds the knee on real sockets).

    - ``remote_http_depth_sweep``: MB/s streaming one object through
      PrefetchReader at depth 1/2/4/8; ``remote_http_knee_depth`` is the
      smallest depth within 85% of the best rate (the knee DISCLOSED,
      not assumed).
    - ``remote_http_cold_value`` / ``remote_http_cached_value``: ex/s of
      a full epoch over HTTP populating the columnar cache, then the
      same epoch served from the mmap cache (zero file GETs — the link
      paid once); ``remote_cold_vs_cached`` is the ratio.

    Device-free, runs pre-backend-init.
    """
    import shutil
    import tempfile

    import tpu_tfrecord.io as tfio
    from tpu_tfrecord import fs as tfs, httpfs
    from tpu_tfrecord.io.dataset import TFRecordDataset
    from tpu_tfrecord.metrics import METRICS
    from tpu_tfrecord.schema import (
        LongType, StringType, StructField, StructType,
    )

    rtt_s = float(os.environ.get("TFR_BENCH_HTTP_RTT_S", 0.008))
    block = int(os.environ.get("TFR_BENCH_HTTP_BLOCK", 1 << 20))
    nbytes = int(os.environ.get("TFR_BENCH_HTTP_BYTES", 16 << 20))
    depths = [1, 2, 4, 8]
    root = tempfile.mkdtemp(prefix="tfr_bench_http_")
    try:
        payload = np.random.default_rng(9).integers(0, 256, nbytes, np.uint8)
        with open(os.path.join(root, "sweep.bin"), "wb") as fh:
            fh.write(payload.tobytes())
        schema = StructType([
            StructField("id", LongType(), nullable=False),
            StructField("s", StringType()),
        ])
        ds_dir = os.path.join(root, "ds")
        n_rows = int(os.environ.get("TFR_BENCH_HTTP_ROWS", 120_000))
        per = n_rows // 4
        for s in range(4):
            tfio.write(
                [[i, f"v{i % 97}"] for i in range(s * per, (s + 1) * per)],
                schema, ds_dir, mode="append" if s else "overwrite",
            )
        with httpfs.serve_directory(root, latency_s=rtt_s) as srv:
            sweep_url = srv.url_for("sweep.bin")
            fsys = tfs.filesystem_for(sweep_url)
            sweep = {}
            saved = {
                k: os.environ.get(k)
                for k in ("TFR_REMOTE_BLOCK_BYTES", "TFR_REMOTE_PREFETCH_DEPTH")
            }
            try:
                os.environ["TFR_REMOTE_BLOCK_BYTES"] = str(block)
                for depth in depths:
                    os.environ["TFR_REMOTE_PREFETCH_DEPTH"] = str(depth)
                    t0 = time.perf_counter()
                    with tfs.open_for_read(fsys, sweep_url) as fh:
                        while fh.read(block):
                            pass
                    sweep[str(depth)] = round(
                        nbytes / (time.perf_counter() - t0) / 1e6, 1
                    )
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
            best = max(sweep.values())
            knee = next(
                d for d in depths if sweep[str(d)] >= 0.85 * best
            )

            def epoch_ex_s(**kw):
                ds = TFRecordDataset(
                    srv.url_for("ds"), batch_size=4096, schema=schema,
                    drop_remainder=False, **kw,
                )
                t0 = time.perf_counter()
                rows = 0
                with ds.batches() as it:
                    for cb in it:
                        rows += cb.num_rows
                return rows / (time.perf_counter() - t0)

            cache_dir = os.path.join(root, "cache")
            srv.set_latency(0.0)  # rate the pipeline, not the injected RTT
            hits0 = METRICS.counter("cache.hits")
            cold = epoch_ex_s(cache="auto", cache_dir=cache_dir)
            gets_cold = srv.file_get_count
            cached = epoch_ex_s(cache="auto", cache_dir=cache_dir)
            link_repaid = srv.file_get_count - gets_cold
            hits = METRICS.counter("cache.hits") - hits0
        return {
            # real-socket streaming rates per prefetch depth (MB/s at
            # rtt_ms of injected server latency) and the disclosed knee
            "remote_http_rtt_ms": rtt_s * 1e3,
            "remote_http_depth_sweep": sweep,
            "remote_http_knee_depth": knee,
            "remote_http_pipelined_mbps": best,
            # remote -> CachePopulator -> mmap, end to end: one epoch
            # paying the link + populating, then the same epoch from the
            # cache (file GETs during it disclosed — 0 = link paid once)
            "remote_http_cold_value": round(cold, 1),
            "remote_http_cached_value": round(cached, 1),
            "remote_cold_vs_cached": round(cached / cold, 2) if cold else None,
            "remote_http_cached_refetches": link_repaid,
            "remote_http_cache_hits": hits,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def seq_schema():
    from tpu_tfrecord.schema import (
        ArrayType, FloatType, LongType, StructField, StructType,
    )

    return StructType([
        StructField("label", LongType(), nullable=False),
        StructField("frames", ArrayType(ArrayType(FloatType()))),
    ])


def ensure_seq_dataset(data_dir: str) -> str:
    """Ragged SequenceExample dataset (long-doc shape: variable-length
    frame lists of SEQ_DIM floats); generated once and cached. The cache
    key includes the SEQ_* generation parameters — changing them must
    regenerate, not silently benchmark stale data of the wrong shape."""
    data_dir = os.path.join(
        data_dir,
        f"s{SEQ_SHARDS}d{SEQ_DOCS_PER_SHARD}l{SEQ_MAX_LEN}f{SEQ_DIM}",
    )
    if os.path.exists(os.path.join(data_dir, "_SUCCESS")):
        return data_dir
    from tpu_tfrecord.io.writer import DatasetWriter
    from tpu_tfrecord.options import TFRecordOptions

    rng = np.random.default_rng(7)
    rows = []
    for _ in range(SEQ_SHARDS * SEQ_DOCS_PER_SHARD):
        n = int(rng.integers(8, SEQ_MAX_LEN + 1))
        frames = rng.normal(size=(n, SEQ_DIM)).astype(np.float32)
        rows.append([int(n), [row.tolist() for row in frames]])
    writer = DatasetWriter(
        data_dir,
        seq_schema(),
        TFRecordOptions.from_map(recordType="SequenceExample"),
        mode="overwrite",
        max_records_per_file=SEQ_DOCS_PER_SHARD,
    )
    writer.write_rows(rows)
    return data_dir


def _seq_pipeline():
    """Dataset + host-side produce fn for the ragged² SequenceExample leg
    (decode 2-level FeatureLists, pad/bucket to dense [B, Lo, Li], cast
    frames to bfloat16 — fused in the native kernel, so the dense f32
    batch never materializes host-side). Shared by the device-free host
    leg and the device leg."""
    import ml_dtypes
    from tpu_tfrecord.io.dataset import TFRecordDataset
    from tpu_tfrecord.tpu import host_batch_from_columnar

    data_dir = ensure_seq_dataset(
        os.environ.get("TFR_BENCH_SEQ_DIR", "/tmp/tpu_tfrecord_bench_seq")
    )
    ds = TFRecordDataset(
        data_dir,
        batch_size=SEQ_BATCH,
        schema=seq_schema(),
        prefetch=4,
        num_epochs=None,
        recordType="SequenceExample",
    )
    pad_to = {"frames": (SEQ_MAX_LEN, SEQ_DIM)}
    cast = {"frames": ml_dtypes.bfloat16}

    def produce(cb):
        hb = host_batch_from_columnar(cb, ds.schema, pad_to=pad_to, cast=cast)
        return {
            "frames": hb["frames"],
            "frames_len": hb["frames_len"],
            "label": hb["label"],
        }

    return ds, produce


def _seq_host_throughput(seconds=2.0) -> dict:
    """Device-free seq leg: decode+pad+bf16 rate with no device anywhere.
    Runs BEFORE backend init, so ``seq_host_value`` lands in the artifact
    whatever the device phase does."""
    ds, produce = _seq_pipeline()
    with ds.batches() as it:
        for _ in range(2):
            produce(next(it))
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            produce(next(it))
            n += SEQ_BATCH
        value = n / (time.perf_counter() - t0)
    return {
        "seq_host_value": round(value, 1),
        "seq_shape": f"[{SEQ_BATCH}, {SEQ_MAX_LEN}, {SEQ_DIM}] ragged->padded",
        "seq_frames_dtype": "bfloat16",
    }


def _seq_device_throughput(mesh, sharding_3d, seconds=4.0) -> dict:
    """Secondary disclosed metric (verdict r3): the ragged² SequenceExample
    path end-to-end — decode, pad, bf16, transfer to the mesh, block.
    Reported as seq_value so the long-doc path's throughput is tracked
    round over round, not just unit-tested. (The device-free half of this
    leg is ``_seq_host_throughput``, measured pre-backend.)"""
    import jax

    from tpu_tfrecord.tpu import data_sharding

    ds, produce = _seq_pipeline()
    sharding_1d = data_sharding(mesh, ndim=1)
    with ds.batches() as it:

        def put(hb):
            gb = {
                "frames": jax.device_put(hb["frames"], sharding_3d),
                "frames_len": jax.device_put(hb["frames_len"], sharding_1d),
                "label": jax.device_put(hb["label"], sharding_1d),
            }
            jax.block_until_ready(gb)

        for _ in range(2):
            put(produce(next(it)))
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < seconds:
            put(produce(next(it)))
            n += SEQ_BATCH
        value = n / (time.perf_counter() - t0)
    per_ex = SEQ_MAX_LEN * SEQ_DIM * 2 + 8 + 4  # bf16 frames + i64 + i32
    return {
        "seq_value": round(value, 1),
        "seq_link_bytes_per_example": per_ex,
    }


def _autotune_probe(data_dir, schema, hash_buckets, pack) -> dict:
    """Closed-loop autotune convergence (ISSUE 6 acceptance): the SAME
    device-free host loop measured (a) with HAND-TUNED fixed knobs
    (workers=2/prefetch=4 on this 2-vCPU box; override with
    TFR_BENCH_AUTOTUNE_FIXED_WORKERS) and (b) starting from
    deliberately-wrong knobs (workers=1, prefetch=1) with
    ``autotune="on"``, where the controller must climb back at pulse
    boundaries. Reports the convergence trajectory (the controller's
    decision log), the final knob set, and autotune_vs_fixed. Both runs
    share the box state, and the registry is RESET between them — the
    metrics quantiles are process-global and cumulative, so without the
    reset the controller would derive thresholds from the fixed leg's
    (and earlier bench phases') latency regimes instead of its own."""
    from tpu_tfrecord.metrics import METRICS
    from tpu_tfrecord.tpu import host_batch_from_columnar

    seconds = float(os.environ.get("TFR_BENCH_AUTOTUNE_SECONDS", 4.0))
    interval = float(os.environ.get("TFR_BENCH_AUTOTUNE_INTERVAL", 0.25))
    fixed_workers = int(os.environ.get("TFR_BENCH_AUTOTUNE_FIXED_WORKERS", 2))
    fixed = _host_side_throughput(
        data_dir, schema, hash_buckets, pack, seconds=seconds,
        num_workers=fixed_workers,
    )
    METRICS.reset()
    ds = _make_dataset(
        data_dir, schema, hash_buckets, pack,
        num_epochs=None, num_workers=1,
        autotune="on", autotune_interval_s=interval,
    )
    ds.prefetch = 1  # deliberately-wrong starting depth (ctor set 4)
    it = ds.batches()
    try:
        for _ in range(2):
            host_batch_from_columnar(
                next(it), ds.schema, hash_buckets=hash_buckets, pack=pack
            )
        t0 = time.perf_counter()
        n = 0
        marks = []  # (elapsed, rows) after each batch: convergence evidence
        while time.perf_counter() - t0 < seconds:
            hb = host_batch_from_columnar(
                next(it), ds.schema, hash_buckets=hash_buckets, pack=pack
            )
            n += hb["packed"].shape[0]
            marks.append((time.perf_counter() - t0, n))
        tuned = n / (time.perf_counter() - t0)
        # converged rate: the tail half of the window — the head pays the
        # deliberate mis-configuration plus the controller's climb, which
        # the trajectory discloses; vs_fixed judges the CONVERGED regime
        half = seconds / 2.0
        head = next(((t, r) for t, r in marks if t >= half), None)
        tail_end = marks[-1] if marks else None
        converged = (
            (tail_end[1] - head[1]) / (tail_end[0] - head[0])
            if head and tail_end and tail_end[0] > head[0]
            else tuned
        )
        tuner = it.autotune
        return {
            "autotune": {
                "fixed_eps": round(fixed, 1),
                "autotune_eps": round(tuned, 1),
                "autotune_converged_eps": round(converged, 1),
                "vs_fixed": round(converged / fixed, 3) if fixed else None,
                "fixed_knobs": {"workers": fixed_workers, "prefetch": 4},
                "start_knobs": {"workers": 1, "prefetch": 1},
                "final_knobs": tuner.snapshot(),
                "trajectory": tuner.log[:64],
                "interval_s": interval,
            }
        }
    finally:
        it.close()


def _service_probe(data_dir, schema, hash_buckets, pack) -> dict:
    """Disaggregated data service leg (ISSUE 8): K decode-worker
    SUBPROCESSES (real processes — the consumer's GIL never pays for
    decode) leased by an in-process dispatcher feed ONE consumer running
    the SAME device-free host loop as host_side_value, so
    service_value / host_side_value reads directly as "what does moving
    decode off-host cost/buy on this box". Device-free by construction:
    runs in the pre-backend-init block. Workers inherit K from
    TFR_BENCH_SERVICE_WORKERS (default 2)."""
    import subprocess
    import sys as _sys

    from tpu_tfrecord import service
    from tpu_tfrecord.metrics import METRICS

    seconds = float(os.environ.get("TFR_BENCH_SERVICE_SECONDS", 4.0))
    n_workers = int(os.environ.get("TFR_BENCH_SERVICE_WORKERS", 2))
    d = service.ServiceDispatcher(lease_ttl_s=10.0).start()
    procs = []
    try:
        for _ in range(n_workers):
            procs.append(subprocess.Popen(
                [_sys.executable, "-m", "tpu_tfrecord.service", "worker",
                 "--dispatcher", d.addr],
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            ))
        deadline = time.perf_counter() + 60.0
        while time.perf_counter() < deadline:
            if len(d.status()["workers"]) >= n_workers:
                break
            time.sleep(0.05)
        registered = len(d.status()["workers"])
        before = METRICS.counter("service.fallbacks")
        value = _host_side_throughput(
            data_dir, schema, hash_buckets, pack, seconds=seconds,
            service=d.addr,
        )
        fallbacks = METRICS.counter("service.fallbacks") - before
        return {
            "service_value": round(value, 1),
            "service": {
                "workers": registered,
                "seconds": seconds,
                "fallbacks": fallbacks,  # >0 = some shards read locally:
                # the number above partly measured the fallback, not the
                # service — disclosed, not hidden
            },
        }
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        d.stop()


def _elastic_probe() -> dict:
    """Elastic decode fleet leg (ISSUE 12): worker count vs offered load.
    A dedicated small dataset is served through the data service while
    every worker-side read pays a seeded 10ms injected stall — one worker
    cannot keep the consumer fed, the consumer's spool says
    producer_bound, and the FleetScaler must GROW the fleet; when the
    consumer closes (load removed, its spool lands a final snapshot) the
    verdict goes idle and the scaler must DRAIN back toward the floor.
    Reports ``elastic_value`` (examples/s through the elastic fleet) plus
    the workers-vs-time load table and the scaler's decision trajectory.
    Device-free by construction: runs in the pre-backend-init block."""
    import tempfile

    import tpu_tfrecord.io as tfio
    from tpu_tfrecord import elastic, service
    from tpu_tfrecord.faults import FaultPlan, FaultRule, install_chaos
    from tpu_tfrecord.io.dataset import TFRecordDataset
    from tpu_tfrecord.metrics import METRICS
    from tpu_tfrecord.schema import LongType, StructField, StructType

    seconds = float(os.environ.get("TFR_BENCH_ELASTIC_SECONDS", 6.0))
    root = tempfile.mkdtemp(prefix="tfr_bench_elastic_")
    out_dir = os.path.join(root, "ds")
    schema = StructType([StructField("id", LongType(), nullable=False)])
    for s in range(6):
        tfio.write([[i] for i in range(s * 2000, (s + 1) * 2000)], schema,
                   out_dir, mode="append" if s else "overwrite")
    spool = os.path.join(root, "spool")
    ups0 = METRICS.counter("elastic.scale_ups")
    downs0 = METRICS.counter("elastic.scale_downs")
    drains0 = METRICS.counter("elastic.drains")
    d = service.ServiceDispatcher(lease_ttl_s=2.0).start()
    workers = []

    def spawn():
        workers.append(
            service.DecodeWorker(d.addr, drain_grace_s=0.2).start()
        )

    scaler = elastic.FleetScaler(
        d, spawn, spool_dir=spool,
        policy=elastic.ScalerPolicy(
            hysteresis=2, cooldown_s=0.5, min_workers=1, max_workers=3
        ),
        interval_s=0.25,
    ).start()
    plan = FaultPlan(
        [FaultRule(op="read", kind="stall", path="part-", times=None,
                   stall_ms=10)],
        seed=5,
    )
    samples = []  # (elapsed_s, active_workers): the load table
    n = 0
    try:
        with install_chaos(plan):
            ds = TFRecordDataset(
                out_dir, batch_size=256, schema=schema, num_epochs=None,
                service=d.addr, service_deadline_ms=15000,
                telemetry_spool_dir=spool, spool_interval_s=0.1,
            )
            t0 = time.perf_counter()
            with ds.batches() as it:
                for b in it:
                    n += b.num_rows
                    el = time.perf_counter() - t0
                    if not samples or el - samples[-1][0] >= 0.5:
                        samples.append((
                            round(el, 2),
                            int(METRICS.gauge_value("elastic.workers", 1) or 1),
                        ))
                    if el >= seconds:
                        break
            value = n / (time.perf_counter() - t0)
        plan.release()
        peak = max((w for _t, w in samples), default=1)
        # load removed: the consumer's spool said goodbye (final), the
        # verdict goes idle, and the fleet must shrink toward the floor
        deadline = time.perf_counter() + 10.0
        after = peak
        while time.perf_counter() < deadline:
            st = d.status()
            after = sum(
                1 for w in st["workers"]
                if w["alive"] and not w["draining"]
            )
            if after <= 1:
                break
            time.sleep(0.2)
        return {
            "elastic_value": round(value, 1),
            "elastic": {
                "seconds": seconds,
                "workers_start": 1,
                "workers_peak": peak,
                "workers_after_load_removed": after,
                "scale_ups": METRICS.counter("elastic.scale_ups") - ups0,
                "scale_downs": METRICS.counter("elastic.scale_downs") - downs0,
                "drains_completed": METRICS.counter("elastic.drains") - drains0,
                "load_table": samples,
                "trajectory": scaler.log[:32],
            },
        }
    finally:
        scaler.stop()
        for w in workers:
            w.stop()
        d.stop()


def _lease_throughput_probe() -> dict:
    """Aggregate lease throughput vs partition count K (ISSUE 17): the
    scale half of killing the dispatcher SPOF. K journaled dispatcher
    SUBPROCESSES (real process parallelism — the probe measures the
    service tier, not this process's GIL), one registered worker each,
    and a fixed pool of hammer threads driving route + shard_done pairs
    over persistent sockets — each thread a distinct tenant routed by
    the same ``PartitionMap`` consumers use, every pair two fsynced
    journal appends (the mutation path as deployed). Reports ops/s at
    K=1 and K=2 and whether aggregate throughput grew. Device-free:
    runs in the pre-backend block."""
    import subprocess
    import tempfile
    import threading

    from tpu_tfrecord import service
    from tpu_tfrecord import service_protocol as sp

    seconds = float(os.environ.get("TFR_BENCH_LEASE_SECONDS", 2.0))
    procs_n = int(os.environ.get("TFR_BENCH_LEASE_PROCS", 4))
    threads_n = int(os.environ.get("TFR_BENCH_LEASE_THREADS", 8))
    root = tempfile.mkdtemp(prefix="tfr_bench_lease_")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    pkg_parent = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = (
        pkg_parent + os.pathsep + env["PYTHONPATH"]
        if env.get("PYTHONPATH") else pkg_parent
    )

    # hammer CLIENTS are subprocesses too — client-side GIL must not be
    # what one measures when asking whether the SERVICE tier scales.
    # Each runs threads_n synchronous route+shard_done loops, one tenant
    # per thread, routed by the same PartitionMap consumers use, and
    # prints its completed-pair count.
    hammer_src = """
import json, sys, threading, time
from tpu_tfrecord import service
from tpu_tfrecord import service_protocol as sp

spec, proc_i, threads_n, start_at, stop_at = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]),
    float(sys.argv[4]), float(sys.argv[5]),
)
pmap = service.PartitionMap.parse(spec)
counts = [0] * threads_n

def hammer(ti):
    tenant = f"bench-tenant-{proc_i}-{ti}"
    addr = pmap.addrs(pmap.partition_for(tenant))[0]
    s = sp.connect(addr, timeout=10.0)
    try:
        s.settimeout(10.0)
        # sockets up, imports paid: wait for the fleet-wide start line so
        # interpreter startup never dilutes the measured window
        while time.time() < start_at:
            time.sleep(0.005)
        i = 0
        while time.time() < stop_at:
            path = f"/bench/{proc_i}/{ti}/shard-{i:06d}"
            base = {"proto": service.PROTO_VERSION, "tenant": tenant,
                    "job": tenant, "consumer": tenant, "path": path}
            r = sp.request(s, addr, {"op": "route", "shard_index": i,
                                     **base})
            if r.get("ok"):
                sp.request(s, addr, {"op": "shard_done",
                                     "worker_id": r["worker_id"], **base})
                counts[ti] += 1
            i += 1
    finally:
        s.close()

ths = [threading.Thread(target=hammer, args=(ti,))
       for ti in range(threads_n)]
for t in ths:
    t.start()
for t in ths:
    t.join()
print(json.dumps({"pairs": sum(counts)}), flush=True)
"""

    def run_k(k: int) -> float:
        procs = []
        addrs = []
        try:
            for i in range(k):
                p = subprocess.Popen(
                    [sys.executable, "-m", "tpu_tfrecord.service",
                     "dispatcher", "--partition", str(i), "--journal",
                     os.path.join(root, f"journal-k{k}-p{i}.json")],
                    stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                    text=True, env=env,
                )
                procs.append(p)
                ready = json.loads(p.stdout.readline())
                addrs.append(ready["addr"])
            spec = ",".join(addrs)
            for a in addrs:
                # one registered (never-fetched-from) worker per
                # partition so routes have something to grant
                s = sp.connect(a, timeout=5.0)
                try:
                    s.settimeout(5.0)
                    sp.request(s, a, {"op": "register_worker",
                                      "proto": service.PROTO_VERSION,
                                      "worker_id": f"bench-{a}",
                                      "addr": a, "pid": 0})
                finally:
                    s.close()
            # start line 2s out: every child is connected and waiting
            # before the window opens, so startup cost is outside it
            start_at = time.time() + 2.0
            stop_at = start_at + seconds
            hammers = [
                subprocess.Popen(
                    [sys.executable, "-c", hammer_src, spec, str(pi),
                     str(threads_n), str(start_at), str(stop_at)],
                    stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                    text=True, env=env,
                )
                for pi in range(procs_n)
            ]
            pairs = 0
            for h in hammers:
                out, _ = h.communicate(timeout=seconds * 10 + 30)
                pairs += json.loads(out)["pairs"]
            return pairs / seconds if seconds > 0 else 0.0
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=5.0)
                except Exception:  # noqa: BLE001 — shutdown safety net
                    p.kill()

    k1 = run_k(1)
    k2 = run_k(2)
    return {
        "lease_throughput_vs_k": {
            "client_procs": procs_n,
            "threads_per_proc": threads_n,
            "seconds": seconds,
            "k1_ops_s": round(k1, 1),
            "k2_ops_s": round(k2, 1),
            "speedup": round(k2 / k1, 3) if k1 else None,
            "grows": k2 > k1,
        }
    }


def _decode_scaling_trend(data_dir, schema, hash_buckets, pack) -> dict:
    """Workers -> ex/s sweep, committed to PARITY.md every round (ROADMAP
    #1 / VERDICT #8): one round's scaling sample is an anecdote; the
    appended table is the TREND multi-core extrapolations need. Each
    point is the same device-free host loop host_side_value uses, at
    num_workers = 1/2/4. Runs pre-backend."""
    secs = float(os.environ.get("TFR_BENCH_SCALING_SECONDS", 1.5))
    series = {}
    for w in (1, 2, 4):
        series[w] = round(_host_side_throughput(
            data_dir, schema, hash_buckets, pack, seconds=secs,
            num_workers=w,
        ), 1)
    try:
        _append_parity_scaling_row(series)
    except Exception as e:  # noqa: BLE001 — a malformed/hand-edited
        # PARITY.md must cost the trend row, never the bench artifact
        print(f"bench: PARITY.md decode-scaling append failed: {e}",
              file=sys.stderr, flush=True)
    return {"decode_scaling_ex_s": {str(k): v for k, v in series.items()}}


_PARITY_SCALING_HEADER = "## Decode-scaling trend (bench-appended)"


def _append_parity_scaling_row(series: dict, path: Optional[str] = None) -> None:
    """Append one round's workers->ex/s row under the trend table in
    PARITY.md (creating the section on first use). Rows are inserted at
    the end of the section, before any later section. ``path`` overrides
    the repo PARITY.md (test seam)."""
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    parity = path or os.path.join(here, "PARITY.md")
    rounds = [
        int(m.group(1))
        for name in os.listdir(here)
        for m in [re.match(r"BENCH_r(\d+)\.json$", name)]
        if m
    ]
    label = f"r{(max(rounds) + 1 if rounds else 1):02d}"
    date = time.strftime("%Y-%m-%d")
    row = (
        f"| {label} | {date} | {series[1]:.0f} | {series[2]:.0f} "
        f"| {series[4]:.0f} | {series[2] / series[1]:.2f}x "
        f"| {series[4] / series[1]:.2f}x |"
    )
    with open(parity) as fh:
        content = fh.read()
    if _PARITY_SCALING_HEADER not in content:
        block = (
            f"\n{_PARITY_SCALING_HEADER}\n\n"
            "One row per bench round (appended by `bench.py`, device-free,\n"
            "pre-backend): sustained decode throughput of the Criteo-shaped\n"
            "host loop at num_workers = 1/2/4 on the round's box. On the\n"
            "2-vCPU bench box ratios ~<=1 are the documented contention\n"
            "negative control (PARITY round 7); the trend is what multi-core\n"
            "extrapolations should be anchored to.\n\n"
            "| round | date | 1w ex/s | 2w ex/s | 4w ex/s | 2w/1w | 4w/1w |\n"
            "|---|---|---|---|---|---|---|\n"
            f"{row}\n"
        )
        content = content.rstrip("\n") + "\n" + block
    else:
        head, _, tail = content.partition(_PARITY_SCALING_HEADER)
        # the section runs to the next "## " heading (or EOF); the new
        # row lands right after the LAST table row, so trailing prose
        # (the basis-row footnote) stays below the table
        m = re.search(r"\n## ", tail)
        if m is None:
            section, rest = tail, ""
        else:
            section, rest = tail[: m.start()], tail[m.start():]
        lines = section.split("\n")
        # insert after the last table line of ANY kind — data row, the
        # "|---|" separator, or the header — so a table stripped down to
        # header+separator gets its new row BELOW the separator, never
        # wedged between header and separator
        rows = [i for i, line in enumerate(lines) if line.startswith("|")]
        if rows:
            lines.insert(rows[-1] + 1, row)
        else:
            # header survived a hand edit but the table didn't: rebuild
            # the table head in place rather than dying row-less
            lines.extend([
                "",
                "| round | date | 1w ex/s | 2w ex/s | 4w ex/s | 2w/1w | 4w/1w |",
                "|---|---|---|---|---|---|---|",
                row,
            ])
        content = head + _PARITY_SCALING_HEADER + "\n".join(lines) + rest
    with open(parity, "w") as fh:
        fh.write(content)


def _attach_regression_verdict(out: dict) -> None:
    """vs_previous + the FIRST-CLASS ``regression_verdict`` (ROADMAP #1):
    a banded-field drop is a loud top-level verdict plus a nonzero stderr
    line, never just a buried list a reader has to know to look for.
    Attached on every artifact path — success and both degraded shapes —
    so an rc!=0 round still self-flags."""
    vs_prev = _vs_previous(out)
    if vs_prev is not None:
        out["vs_previous"] = vs_prev
    regressions = (vs_prev or {}).get("regressions") or []
    out["regression_verdict"] = (
        "no_previous" if vs_prev is None
        else ("regression" if regressions else "ok")
    )
    if regressions:
        fields = vs_prev["fields"]
        print(
            "bench REGRESSION vs " + vs_prev["previous_round"] + ": "
            + ", ".join(
                f"{f} {fields[f]['previous']} -> {fields[f]['current']} "
                f"({fields[f]['delta_pct']:+}%)"
                for f in regressions
            ),
            file=sys.stderr, flush=True,
        )


def _model_parallel_child() -> None:
    """Subprocess body (CPU 8-device env forced by the parent): measure the
    model-parallel memory shape + a causal-LM train rate, print ONE JSON
    line. Device-free from the PARENT's point of view — the ambient
    backend is never touched."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import optax

    from tpu_tfrecord.models import lm, pipeline
    from tpu_tfrecord.tpu import create_mesh

    out = {}
    # --- pipeline memory shape at bench scale: what ONE device holds of
    # the microbatch stream, vs the old replicated-[M, mb, ...] layout
    s_axis, m, mb = 8, 32, (8, 128)
    mesh = create_mesh({"pipe": s_axis})
    rng = np.random.default_rng(0)
    params = {
        "w": jnp.asarray(
            rng.normal(size=(s_axis, mb[1], mb[1])) * 0.1, jnp.float32
        )
    }

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"])

    xs = jnp.zeros((m,) + mb, jnp.float32)
    xs_sh = jax.device_put(xs, pipeline.microbatch_sharding(mesh, ndim=xs))
    p_sh = jax.device_put(params, NamedSharding(mesh, P("pipe")))
    comp = (
        jax.jit(lambda p, xs: pipeline.pipeline_apply(stage_fn, p, xs, mesh))
        .lower(p_sh, xs_sh)
        .compile()
    )
    hlo = comp.as_text()
    mb_bytes = int(np.prod(mb)) * 4
    new_bytes = (m // s_axis) * mb_bytes       # the shard one device holds
    old_bytes = m * mb_bytes                   # the replicated layout held M
    ma = comp.memory_analysis()
    out["pipeline_input_bytes_per_device_old"] = old_bytes
    out["pipeline_input_bytes_per_device_new"] = new_bytes
    out["pipeline_input_shrink"] = round(old_bytes / new_bytes, 2)
    out["pipeline_shape"] = f"M={m} stages={s_axis} mb={list(mb)} f32"
    out["pipeline_hlo_pins"] = {
        "collective_permute": "collective-permute" in hlo,
        "all_gather": "all-gather" in hlo,       # must be False
        "all_reduce": "all-reduce" in hlo,       # must be False
    }
    if ma is not None:
        out["pipeline_compiled_arg_bytes_per_device"] = int(
            ma.argument_size_in_bytes
        )

    # --- causal-LM train rate: the examples/train_lm.py default shape
    # (dp×sp zigzag causal ring) on synthetic packed batches
    mesh2 = create_mesh({"data": 4, "seq": 2})
    cfg = lm.LMConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, max_len=64
    )
    lm_params = lm.init_params(jax.random.key(0), cfg)
    tx = optax.adam(3e-3)
    opt = tx.init(lm_params)
    step = jax.jit(
        functools.partial(
            lm.train_step, cfg=cfg, tx=tx, mesh=mesh2, data_axis="data",
            seq_axis="seq",
        ),
        donate_argnums=(0, 1),
    )
    toks = jnp.asarray(lm.make_synthetic_tokens(cfg, 32, seed=0))
    for _ in range(2):  # compile + warm
        lm_params, opt, loss = step(lm_params, opt, toks)
    jax.block_until_ready(loss)
    seconds = float(os.environ.get("TFR_BENCH_LM_SECONDS", 3.0))
    t0 = time.perf_counter()
    n = 0
    while time.perf_counter() - t0 < seconds:
        lm_params, opt, loss = step(lm_params, opt, toks)
        n += 1
    jax.block_until_ready(loss)
    out["lm_steps_per_s"] = round(n / (time.perf_counter() - t0), 2)
    out["lm_shape"] = "B=32 L=64 d=64 2L zigzag-ring dp4xsp2"

    # --- MULTICHIP partial (ROADMAP #4): per-device compiled-memory bytes
    # for the SAME LM step, from memory_analysis() via the shared
    # tests/hlo_util compiled handle, labeled with the backend — the
    # eventual real-device round records the same fields
    from tests.hlo_util import compiled_memory_bytes

    mem = compiled_memory_bytes(step, lm_params, opt, toks)
    if mem:
        out["lm_compiled_memory"] = mem

    # --- fsdp weight sharding (full GSPMD mesh, PR 19): per-device
    # at-rest bytes (params + opt state + inputs = compiled argument
    # bytes) for the SAME LM step under dp×fsdp vs pure dp — the number
    # the gather-on-use layout exists to shrink — plus the best-fit
    # packer's density on a ragged corpus (what segment-masked packing
    # buys over padding each document to L)
    from tpu_tfrecord.tpu import TokenPacker

    def _arg_bytes(mesh_axes, fsdp_axis):
        m = create_mesh(mesh_axes)
        p = lm.init_params(jax.random.key(0), cfg)
        p = jax.device_put(
            p, lm.param_shardings(m, p, fsdp_axis=fsdp_axis)
        )
        o = tx.init(p)
        t = jax.device_put(toks, NamedSharding(m, P("data", None)))
        s = jax.jit(
            functools.partial(
                lm.train_step, cfg=cfg, tx=tx, mesh=m,
                data_axis="data", fsdp_axis=fsdp_axis,
            )
        )
        ma_s = s.lower(p, o, t).compile().memory_analysis()
        return (
            int(ma_s.argument_size_in_bytes) if ma_s is not None else None
        )

    b_dp = _arg_bytes({"data": 8}, None)
    b_fsdp = _arg_bytes({"data": 2, "fsdp": 4}, "fsdp")
    if b_dp and b_fsdp:
        out["lm_dp_param_bytes_per_device"] = b_dp
        out["lm_fsdp_param_bytes_per_device"] = b_fsdp
        out["lm_fsdp_param_shrink"] = round(b_dp / b_fsdp, 2)
        out["lm_fsdp_shape"] = "dp2xfsdp4 vs dp8, same step"

    prng = np.random.default_rng(15)
    packer = TokenPacker(4, 32, packing="best_fit")
    packer.feed_docs(
        np.ones(int(s), np.int32)
        for s in prng.choice([2, 6, 10, 15, 16, 21, 25, 31], size=300)
    )
    while packer.pop() is not None:
        pass
    out["pack_density"] = round(packer.density(), 4)
    out["pack_shape"] = "B=4 L=32 best_fit ragged[2..31]x300"

    # --- training flight recorder (ISSUE 13): the REAL harness loop
    # (StepPhases + DeviceIterator) over device-fed synthetic batches —
    # the per-step phase decomposition + training verdict, measured, not
    # asserted
    sys_path_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "examples"
    )
    import sys as _sys

    _sys.path.insert(0, sys_path_dir)
    import _harness

    from tpu_tfrecord.tpu import DeviceIterator

    rec = _harness.StepPhases(window=8)
    toks_np = np.asarray(toks)
    dev_it = DeviceIterator(
        iter([{"tokens": toks_np}] * 16), mesh2, axis="data"
    )
    def _sfn(state, gb):
        p, o = state
        p, o, loss = step(p, o, gb["tokens"])
        return (p, o), loss

    (lm_params, opt), _, _ = _harness.run_train_loop(
        dev_it, produce=lambda gb: gb, step_fn=_sfn,
        state=(lm_params, opt), phases=rec, max_steps=16, log_every=1000,
    )
    out["lm_step_breakdown"] = {
        "shares": {k: round(v, 4) for k, v in rec.shares().items()},
        "verdict": rec.verdict(),
        "steps": rec.steps,
    }

    # --- in-jit model diagnostics: measured pipeline bubble at the bench
    # shape (vs the analytic (S-1)/(M+S-1) the interleaved-V work must
    # beat) + MoE imbalance through the pinned EP dispatch
    _, pdiag = pipeline.pipeline_apply(
        stage_fn, p_sh, xs_sh, mesh, diagnostics=True
    )
    out["pipeline_bubble_fraction"] = round(float(pdiag["bubble_fraction"]), 4)
    out["pipeline_bubble_analytic"] = round((s_axis - 1) / (m + s_axis - 1), 4)

    # --- bubble-vs-V sweep (ISSUE 15): the interleaved schedule's bubble
    # MEASURED by the same per-tick occupancy counter at fixed S and M,
    # V in {1, 2, 4}, against the interleaved analytic (S-1)/(V·M+S-1) —
    # the number ROADMAP #2 asked to shrink, shrinking
    v_s, v_m, v_d = 4, 8, 64
    v_mesh = create_mesh({"pipe": v_s}, jax.devices()[:v_s])
    xs_v = jnp.zeros((v_m, 4, v_d), jnp.float32)
    xs_v_sh = jax.device_put(
        xs_v, pipeline.microbatch_sharding(v_mesh, ndim=xs_v)
    )
    for v in (1, 2, 4):
        shape = (v_s, v, v_d, v_d) if v > 1 else (v_s, v_d, v_d)
        pv_sh = jax.device_put(
            {"w": jnp.asarray(rng.normal(size=shape) * 0.1, jnp.float32)},
            NamedSharding(v_mesh, P("pipe")),
        )
        _, dv = pipeline.pipeline_apply(
            stage_fn, pv_sh, xs_v_sh, v_mesh, n_virtual=v, diagnostics=True
        )
        out[f"pipeline_bubble_v{v}"] = round(float(dv["bubble_fraction"]), 4)
        out[f"pipeline_bubble_v{v}_analytic"] = round(
            (v_s - 1) / (v * v_m + v_s - 1), 4
        )
    out["pipeline_bubble_v_shape"] = f"M={v_m} stages={v_s} mb=[4,{v_d}] f32"

    # --- microbatch-streamed serving (ISSUE 15): requests/s through the
    # persistent per-tick PipelineStream step (per-call feed = ONE
    # [mb, ...] slice; outputs pop with S·V-tick latency), interleaved
    # V=2 — the heavy-traffic serving path's headline number
    sv_s, sv_v, sv_mb = 4, 2, (8, 128)
    sv_mesh = create_mesh({"pipe": sv_s}, jax.devices()[:sv_s])
    sp_sh = jax.device_put(
        {"w": jnp.asarray(
            rng.normal(size=(sv_s, sv_v) + (sv_mb[1], sv_mb[1])) * 0.1,
            jnp.float32,
        )},
        NamedSharding(sv_mesh, P("pipe")),
    )
    stream = pipeline.PipelineStream(
        stage_fn, sp_sh, sv_mesh, n_virtual=sv_v, microbatch_shape=sv_mb
    )
    req = rng.normal(size=sv_mb).astype(np.float32)
    for _ in range(sv_s * sv_v + 4):  # warm: compile + one pipeline fill
        stream.push(req)
    stream.flush()
    stream.reset()
    serve_seconds = float(os.environ.get("TFR_BENCH_SERVE_SECONDS", 1.5))
    t0 = time.perf_counter()
    n_req = 0
    while time.perf_counter() - t0 < serve_seconds:
        stream.push(req)
        n_req += 1
    # outputs are device-resident: block on the drained tail so the
    # wall-clock covers the actual compute, not just dispatch
    jax.block_until_ready(stream.flush())
    # raw per-tick stream rate (the transport under the serving tier);
    # the serving-tier request numbers are _serving_probe's
    out["stream_requests_per_s"] = round(
        n_req / (time.perf_counter() - t0), 1
    )
    out["stream_shape"] = f"mb={list(sv_mb)} S={sv_s} V={sv_v} f32"

    from tpu_tfrecord.models import moe as _moe_mod

    moe_cfg = _moe_mod.MoEConfig(
        d_model=64, d_ff=128, n_experts=8, top_k=2, capacity_factor=1.25
    )
    moe_mesh = create_mesh({"expert": 8})
    moe_params = _moe_mod.init_params(jax.random.key(1), moe_cfg)
    moe_x = jnp.asarray(
        rng.normal(size=(512, 64)).astype(np.float32)
    )
    _, _, mdiag = jax.jit(
        lambda p, x: _moe_mod.moe_apply_ep(
            p, x, moe_cfg, moe_mesh, diagnostics=True
        )
    )(moe_params, moe_x)
    tokens_per_expert = np.asarray(mdiag["expert_tokens"], dtype=float)
    out["moe_imbalance"] = round(
        float(tokens_per_expert.max() / max(tokens_per_expert.mean(), 1e-9)), 3
    )
    out["moe_dropped_fraction"] = round(float(mdiag["dropped_fraction"]), 4)
    out["moe_shape"] = "T=512 d=64 E=8 top2 ep8"

    # --- diagnostics overhead A/B (same <=2% bar as the PR 5 tracing
    # overhead): the MoE LM step with in-jit diagnostics OFF vs ON
    # (including the per-step host fold the instrumented trainer pays).
    # Fixed-step interleaved windows, MIN seconds-per-step each arm — the
    # one-sided-noise estimator every perf leg on this box uses; the B=8
    # shape keeps one step well under a window so the ratio is not
    # quantization noise
    cfg_ab = lm.LMConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, max_len=64,
        moe_experts=4, moe_top_k=2,
    )
    toks_ab = jnp.asarray(lm.make_synthetic_tokens(cfg_ab, 8, seed=0))
    arms = {}
    for diag_on in (False, True):
        params_ab = lm.init_params(jax.random.key(2), cfg_ab)
        opt_ab = tx.init(params_ab)
        fn = jax.jit(
            functools.partial(
                lm.train_step, cfg=cfg_ab, tx=tx, mesh=mesh2,
                data_axis="data", seq_axis="seq", diagnostics=diag_on,
            ),
            donate_argnums=(0, 1),
        )
        res = fn(params_ab, opt_ab, toks_ab)  # compile + warm
        params_ab, opt_ab = res[0], res[1]
        jax.block_until_ready(res[2])
        arms[diag_on] = [fn, params_ab, opt_ab, float("inf")]
    ab_steps = int(os.environ.get("TFR_BENCH_LM_AB_STEPS", 10))
    for _ in range(4):  # interleaved windows, best (min s/step) per arm
        for diag_on, arm in arms.items():
            fn, p_ab, o_ab, best = arm
            t0 = time.perf_counter()
            for _ in range(ab_steps):
                res = fn(p_ab, o_ab, toks_ab)
                p_ab, o_ab, loss = res[0], res[1], res[2]
                jax.block_until_ready(loss)
                if diag_on:
                    _harness.fold_model_diagnostics(res[3])
            arm[1], arm[2] = p_ab, o_ab
            arm[3] = min(best, (time.perf_counter() - t0) / ab_steps)
    off_spp, on_spp = arms[False][3], arms[True][3]
    out["lm_diagnostics_overhead_pct"] = round(
        (on_spp / off_spp - 1.0) * 100.0, 2
    )
    print(json.dumps(out), flush=True)


def _model_parallel_probe() -> dict:
    """Model-parallel leg (ISSUE 10): per-device input-buffer bytes for the
    pipelined step (old replicated shape vs the new O(mb) shard) and a
    train_lm steps/s number, measured in a SUBPROCESS that forces an
    8-device CPU backend — pre-backend-init in the parent (same pattern
    as the service probe's worker subprocesses). A CPU-mesh count, not a
    device rate."""
    import subprocess
    import sys as _sys

    env = dict(os.environ)
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_PLATFORM_NAME", None)
    here = os.path.abspath(__file__)
    try:
        proc = subprocess.run(
            [_sys.executable, here, "--model-parallel-child"],
            env=env,
            cwd=os.path.dirname(here),
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            timeout=600,
        )
    except subprocess.TimeoutExpired:
        # a hung child (stuck compile on a loaded box) must land as a
        # structured error field, not crash the whole artifact
        return {"model_parallel_error": "child exceeded 600s"}
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return {
        "model_parallel_error": (
            f"child rc={proc.returncode}: {proc.stdout[-500:]}"
        )
    }


def _serving_child() -> None:
    """Subprocess body (CPU env forced by the parent): the overload-proof
    serving tier (ISSUE 18) under seeded OPEN-LOOP load — arrivals fire on
    a seeded Poisson clock whether or not the engine keeps up, which is
    what makes the overload leg's shed rate an honest number rather than
    closed-loop backpressure hiding it. Three legs, ONE JSON line:

      1. calibrate: closed-loop saturation -> capacity (requests/s)
      2. steady:    open-loop at 0.5x capacity -> serve_p99_ms (the
                    SLO-relevant latency: queue wait + compute)
      3. overload:  open-loop at 3x capacity -> serve_requests_per_s
                    (throughput AT saturation) + the DISCLOSED shed rate
                    (admission control sheds the excess loudly; a shed
                    rate near 2/3 here is the design working, not a bug)
    """
    import jax

    from tpu_tfrecord.metrics import Metrics
    from tpu_tfrecord.models import lm
    from tpu_tfrecord.serving import (
        ServePolicy, ServeRejected, ServingEngine,
    )
    from tpu_tfrecord.tpu import create_mesh

    cfg = lm.LMConfig(
        vocab_size=96, d_model=32, n_heads=2, n_layers=4, max_len=16,
        n_micro=4, n_virtual=1,
    )
    params = lm.init_params(jax.random.key(0), cfg)
    mesh = create_mesh({"pipe": 2}, jax.devices()[:2])
    rng = np.random.default_rng(0)
    windows = [
        rng.integers(1, cfg.vocab_size, size=cfg.max_len).astype(np.int32)
        for _ in range(64)
    ]
    n_new = 2

    def engine(max_queue):
        return ServingEngine(
            params, cfg, mesh,
            policy=ServePolicy(mb=4, max_queue=max_queue),
            metrics=Metrics(),
        ).start()

    # --- calibrate: saturate the batch, capacity = completed/s. The
    # first request also pays the per-tick compile, so warm separately.
    eng = engine(max_queue=64)
    eng.submit(windows[0], n_new).result(timeout=300)
    t0 = time.perf_counter()
    handles = [eng.submit(windows[i % 64], n_new) for i in range(48)]
    for h in handles:
        h.result(timeout=300)
    capacity = 48 / (time.perf_counter() - t0)
    eng.stop()

    def open_loop(rate, seconds, max_arrivals=2000):
        """Seeded Poisson arrivals at `rate` for `seconds`; returns the
        leg's completed/s, latency quantiles, and shed accounting."""
        e = engine(max_queue=16)
        # a fresh engine is a fresh LMStream: its first request pays the
        # per-tick compile (~0.5s) — warm it off the clock or that stall
        # IS the leg's p99 and the queue sheds behind it
        e.submit(windows[0], n_new).result(timeout=300)
        e._metrics = Metrics()  # drop the warmup's latency sample
        gaps = rng.exponential(1.0 / rate, size=max_arrivals)
        live, shed, i = [], 0, 0
        t0 = time.perf_counter()
        t_next = t0
        while i < max_arrivals:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            if now < t_next:
                time.sleep(min(t_next - now, 0.002))
                continue
            t_next += gaps[i]
            try:
                live.append(e.submit(windows[i % 64], n_new))
            except ServeRejected:
                shed += 1
            i += 1
        for h in live:
            h.result(timeout=300)
        wall = time.perf_counter() - t0
        rep = e.report()
        e.stop()
        offered = len(live) + shed
        return {
            "offered": offered,
            "offered_per_s": round(rate, 1),
            "completed": len(live),
            "requests_per_s": round(len(live) / wall, 1),
            "shed": shed,
            "shed_rate": round(shed / max(1, offered), 3),
            "p50_ms": round(rep["p50_ms"], 2),
            "p99_ms": round(rep["p99_ms"], 2),
            # request-latency decomposition from the serve.queue_wait /
            # serve.service spans (ISSUE 20): where the p99 lives —
            # waiting for a slot, or being computed
            "queue_wait_p99_ms": (
                round(rep["queue_wait_p99_ms"], 2)
                if rep.get("queue_wait_p99_ms") is not None else None
            ),
            "service_p99_ms": (
                round(rep["service_p99_ms"], 2)
                if rep.get("service_p99_ms") is not None else None
            ),
            "verdict": rep["verdict"],
        }

    # overload FIRST: its completed/s is the SUSTAINED capacity with the
    # open-loop driver thread contending for the GIL — the closed-loop
    # calibration number above overstates it. The steady leg then sits
    # UNDER the sparse-packing floor: a tick costs the same wall-clock
    # whether 1 or mb slots are valid, so at low concurrency the engine
    # serves ~1/(mb/n_new) of its saturation rate — a steady rate sized
    # off saturation throughput sheds when it should cruise
    overload = open_loop(3.0 * capacity, 2.0)
    steady = open_loop(0.2 * overload["requests_per_s"], 2.5)
    from tpu_tfrecord.slo import burn_rate

    out = {
        # headline pair (banded in _PREV_NOISE_BANDS): latency where the
        # SLO lives, throughput where the capacity lives
        "serve_p99_ms": steady["p99_ms"],
        "serve_requests_per_s": overload["requests_per_s"],
        # the p99 decomposed: queue wait vs service time at steady state
        "serve_queue_wait_p99_ms": steady["queue_wait_p99_ms"],
        "serve_service_p99_ms": steady["service_p99_ms"],
        # availability (0.999) burn rate at steady state — ~0 when the
        # engine cruises at 0.5x capacity; any sustained value means the
        # steady leg started shedding, a capacity regression the p99
        # alone can hide (the overload leg's ~2/3 shed rate is design,
        # so only the steady leg's burn is a signal)
        "serve_error_budget_burn": round(
            burn_rate(steady["shed"], steady["offered"], 0.999), 2
        ),
        "serving": {
            "capacity_requests_per_s": round(capacity, 1),
            "steady": steady,
            "overload": overload,
            "shape": (
                f"mb=4 n_new={n_new} L={cfg.max_len} "
                f"d={cfg.d_model} S=2 V=1 f32"
            ),
        },
    }
    print(json.dumps(out), flush=True)


def _serving_probe() -> dict:
    """Serving-tier leg (ISSUE 18), measured in a CPU-forced SUBPROCESS
    (same pattern as _model_parallel_probe: pre-backend-init in the
    parent). CPU-mesh counts, not device rates."""
    import subprocess
    import sys as _sys

    env = dict(os.environ)
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    flags.append("--xla_force_host_platform_device_count=8")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_PLATFORM_NAME", None)
    here = os.path.abspath(__file__)
    try:
        proc = subprocess.run(
            [_sys.executable, here, "--serving-child"],
            env=env,
            cwd=os.path.dirname(here),
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            timeout=600,
        )
    except subprocess.TimeoutExpired:
        return {"serving_error": "child exceeded 600s"}
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    return {
        "serving_error": f"child rc={proc.returncode}: {proc.stdout[-500:]}"
    }


def _ckpt_probe() -> dict:
    """Async vs sync checkpointing A/B (ISSUE 16, device-free, ~3s).

    A synthetic train loop (fixed busy-compute per step, fixed save
    cadence) checkpoints a model-shaped pytree through AsyncCheckpointer
    twice under a SEEDED commit throttle (commit_delay_s — the slow-disk
    fault): the sync twin pays the throttle on the step path and must
    verdict ckpt_bound; the async path pays only the snapshot and must
    stay compute_bound, with the restored state byte-identical between
    the two. Then the real (unthrottled) commit p99 on all three artifact
    paths: the sharded model pytree, the train_lm-shaped npz twin
    (params+opt leaves + input/packer payload), and the O(1) input-state
    JSON (AsyncStateSaver)."""
    import shutil
    import sys as _sys
    import tempfile

    _sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "examples"
    ))
    import _harness

    from tpu_tfrecord.checkpoint import AsyncCheckpointer, AsyncStateSaver
    from tpu_tfrecord.io.dataset import IteratorState
    from tpu_tfrecord.metrics import Metrics

    rng = np.random.default_rng(0)
    state = {
        "w": rng.standard_normal((128, 256)).astype(np.float32),
        "b": rng.standard_normal(256).astype(np.float32),
    }
    throttle = float(os.environ.get("TFR_BENCH_CKPT_THROTTLE_S", 0.03))
    steps = int(os.environ.get("TFR_BENCH_CKPT_STEPS", 24))
    cadence = 4
    spin = rng.standard_normal((160, 160)).astype(np.float32)
    compute_s = 0.010

    def busy():
        # fixed-duration host compute (the "device step" stand-in)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < compute_s:
            np.dot(spin, spin)

    def leg(sync: bool, root: str):
        m = Metrics()
        ck = AsyncCheckpointer(
            os.path.join(root, "sync" if sync else "async"),
            process_index=0, process_count=1, sync=sync,
            commit_delay_s=throttle, metrics=m,
        )
        rec = _harness.StepPhases(window=16)
        for step in range(1, steps + 1):
            with rec.phase("compute"):
                busy()
            if step % cadence == 0:
                with rec.phase("ckpt"):
                    ck.save(step, state, {"step": step})
            rec.end_step()
        ck.wait()
        restored = ck.restore({k: np.zeros_like(v) for k, v in state.items()})
        ck.close()
        return rec, m, restored

    root = tempfile.mkdtemp(prefix="tfr_bench_ckpt_")
    try:
        sync_rec, _, sync_restored = leg(True, root)
        async_rec, async_m, async_restored = leg(False, root)
        resume_equal = sync_restored[0] == async_restored[0] and all(
            np.array_equal(sync_restored[1][k], async_restored[1][k])
            for k in state
        )

        def commit_p99_ms(m: Metrics) -> float:
            q = m.quantiles("ckpt.commit").get("ckpt.commit")
            return round(q["p99_s"] * 1000.0, 2) if q else 0.0

        # unthrottled commit p99 per artifact path
        m_pytree = Metrics()
        with AsyncCheckpointer(
            os.path.join(root, "p_pytree"), process_index=0,
            process_count=1, commit_delay_s=0.0, metrics=m_pytree,
        ) as ck:
            for step in range(1, 9):
                ck.save(step * cadence, state, None)
            ck.wait()
        lm_state = (state, {"mu": np.zeros_like(state["w"])})
        m_npz = Metrics()
        with AsyncCheckpointer(
            os.path.join(root, "p_npz"), process_index=0,
            process_count=1, commit_delay_s=0.0, metrics=m_npz,
        ) as ck:
            for step in range(1, 9):
                ck.save(
                    step * cadence, lm_state,
                    {"input": {"epoch": 0, "shard_cursor": step},
                     "packer": {"carry": [step]}},
                )
            ck.wait()
        m_state = Metrics()
        with AsyncStateSaver(
            os.path.join(root, "p_state"), process_index=0,
            commit_delay_s=0.0, metrics=m_state,
        ) as saver:
            for step in range(1, 9):
                saver.save(
                    IteratorState(shard_cursor=step, record_offset=step * 7),
                    step=step * cadence,
                )
            saver.wait()

        wait_stats = async_m.snapshot().get("ckpt.commit_wait", {})
        return {
            "ckpt_sync_share": round(sync_rec.shares().get("ckpt", 0.0), 4),
            "ckpt_async_share": round(async_rec.shares().get("ckpt", 0.0), 4),
            "ckpt_commit_p99_ms_pytree": commit_p99_ms(m_pytree),
            "ckpt_commit_p99_ms_npz": commit_p99_ms(m_npz),
            "ckpt_commit_p99_ms_state": commit_p99_ms(m_state),
            "ckpt": {
                "sync_verdict": sync_rec.verdict(),
                "async_verdict": async_rec.verdict(),
                "resume_equal": resume_equal,
                "commit_throttle_s": throttle,
                "cadence": cadence,
                "steps": steps,
                "async_commit_wait_ms": round(
                    wait_stats.get("seconds", 0.0) * 1000.0, 2
                ),
                "async_commit_waits": int(wait_stats.get("records", 0)),
            },
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


# Self-flagging regression check (ROADMAP #5): the artifact compares its
# own numbers against the previous round's and flags anything outside a
# per-field noise band — r5's host_side 1.32M vs r4's 1.51M went
# un-diagnosed because nothing in the artifact said "this moved".
# Bands reflect each number's observed round-over-round variance on this
# shared box: host-side decode numbers are fairly stable; anything with
# the disk (cold) or the device link (value/sustained) swung wildly.
_PREV_NOISE_BANDS = {
    "host_side_value": 0.15,
    # model-parallel leg: the memory-shape ratio is deterministic (a drop
    # means the pipeline regressed to a replicated layout), the LM rate is
    # a compiled CPU loop on a shared box
    "pipeline_input_shrink": 0.10,
    "lm_steps_per_s": 0.50,
    # fsdp leg (PR 19): both deterministic — per-device at-rest bytes
    # (smaller is better: a rise means weights stopped living sharded)
    # and the best-fit packer density on the fixed ragged corpus (a drop
    # means the binning regressed toward greedy/padding)
    "lm_fsdp_param_bytes_per_device": 0.10,
    "pack_density": 0.05,
    # streamed serving: a compiled CPU per-tick loop on a shared box (the
    # bubble sweep itself is deterministic and not banded — smaller is
    # better, the tests pin it against the analytic)
    "stream_requests_per_s": 0.50,
    # serving tier (ISSUE 18): request throughput at saturation and the
    # steady-state p99 through the continuous-batching engine. NOTE:
    # before ISSUE 18, serve_requests_per_s was the RAW PipelineStream
    # push rate (now stream_requests_per_s) — the first round after the
    # rename diffs across meanings and will flag; ignore that one flag.
    "serve_requests_per_s": 0.50,
    "serve_p99_ms": 0.50,
    # ISSUE 20: the p99 decomposition (same shared-box noise as the p99
    # itself) and the steady-leg error-budget burn — the burn sits at 0
    # when healthy, so ratio noise is meaningless; the wide band only
    # fires when steady-state shedding appears outright
    "serve_queue_wait_p99_ms": 0.50,
    "serve_service_p99_ms": 0.50,
    "serve_error_budget_burn": 2.00,
    "remote_http_cold_value": 0.50,
    "remote_http_cached_value": 0.35,
    "seq_host_value": 0.25,
    "service_value": 0.25,
    # elastic leg: throttled-decode throughput through a resizing fleet —
    # wide band, the injected stalls + scaling transient dominate
    "elastic_value": 0.50,
    "warm_epoch_value": 0.25,
    "cold_value": 0.50,
    "value": 0.35,
    "sustained_value": 0.50,
    # async checkpointing A/B (ISSUE 16). ckpt_sync_share is the CONTRAST
    # guard (bigger is better: a drop means the seeded throttle stopped
    # biting and the A/B lost its meaning); the async share and the
    # commit p99s are smaller-is-better (see _SMALLER_IS_BETTER) — a rise
    # is the regression. The async share sits near 0 so its ratio noise
    # is huge; the wide band only fires when it blows up outright.
    "ckpt_sync_share": 0.50,
    "ckpt_async_share": 2.00,
    "ckpt_commit_p99_ms_pytree": 0.50,
    "ckpt_commit_p99_ms_npz": 0.50,
    "ckpt_commit_p99_ms_state": 0.50,
}

#: Fields where SMALLER is better: _vs_previous inverts the flag logic
#: (delta above the band = regression, below = improvement).
_SMALLER_IS_BETTER = {
    "lm_fsdp_param_bytes_per_device",
    "ckpt_async_share",
    "ckpt_commit_p99_ms_pytree",
    "ckpt_commit_p99_ms_npz",
    "ckpt_commit_p99_ms_state",
    "serve_p99_ms",
    "serve_queue_wait_p99_ms",
    "serve_service_p99_ms",
    "serve_error_budget_burn",
}


def _load_previous_artifact():
    """(filename, artifact dict) of the newest BENCH_r*.json in the repo
    root, or None. Round files are either the raw artifact or the
    harness's {n, cmd, rc, tail[, parsed]} wrapper — the artifact is the
    wrapper's ``parsed`` dict or the last JSON line of ``tail``."""
    import glob
    import re

    here = os.path.dirname(os.path.abspath(__file__))

    def round_no(path: str) -> int:
        # numeric round order: lexicographic sort would put r99 after
        # r100 and silently diff against a stale round
        m = re.search(r"r(\d+)", os.path.basename(path))
        return int(m.group(1)) if m else -1

    candidates = sorted(
        glob.glob(os.path.join(here, "BENCH_r*.json")), key=round_no,
        reverse=True,
    )
    for path in candidates:
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        if "metric" in doc:
            return os.path.basename(path), doc
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed:
            return os.path.basename(path), parsed
        for line in reversed((doc.get("tail") or "").splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                cand = json.loads(line)
            except ValueError:
                continue
            if isinstance(cand, dict) and "metric" in cand:
                return os.path.basename(path), cand
    return None


def _vs_previous(current: dict):
    """The vs-previous-round delta block: per tracked field, previous vs
    current with a noise band and a flag (regression | within_noise |
    improvement). ``regressions`` lists the flagged fields so a reader —
    or the round harness — sees a drop without diffing artifacts by
    hand."""
    prev = _load_previous_artifact()
    if prev is None:
        return None
    name, art = prev
    fields = {}
    regressions = []
    for field, band in _PREV_NOISE_BANDS.items():
        p, c = art.get(field), current.get(field)
        if not isinstance(p, (int, float)) or not isinstance(c, (int, float)) or not p:
            continue
        delta = c / p - 1.0
        if field in _SMALLER_IS_BETTER:
            flag = (
                "regression"
                if delta > band
                else ("improvement" if delta < -band else "within_noise")
            )
        else:
            flag = (
                "regression"
                if delta < -band
                else ("improvement" if delta > band else "within_noise")
            )
        if flag == "regression":
            regressions.append(field)
        fields[field] = {
            "previous": p,
            "current": c,
            "delta_pct": round(delta * 100.0, 1),
            "noise_band_pct": round(band * 100.0),
            "flag": flag,
        }
    return {"previous_round": name, "fields": fields, "regressions": regressions}


def main() -> None:
    import threading

    import jax

    import tpu_tfrecord

    from tpu_tfrecord import compile_cache

    compile_cache.enable()  # JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache

    from tpu_tfrecord.tpu import (
        DeviceIterator,
        HostPrefetcher,
        create_mesh,
        host_batch_from_columnar,
    )
    from tpu_tfrecord.tracing import DutyCycle

    data_dir = os.environ.get("TFR_BENCH_DIR", "/tmp/tpu_tfrecord_bench_v2")
    data_dir = ensure_dataset(data_dir)
    schema = criteo_read_schema()
    hash_buckets, pack = criteo_reader_spec()

    # Device-free phases FIRST: they need no backend, so they complete
    # whatever the device does and ride along in the watchdog's error output.
    host_side_value = _host_side_throughput(
        data_dir, schema, hash_buckets, pack,
        seconds=float(os.environ.get("TFR_BENCH_HOST_SECONDS", 4.0)),
    )
    cold_info = None
    if os.environ.get("TFR_BENCH_COLD", "1") != "0":
        # ON by default so every round's artifact includes a number with
        # real disk IO in it (raw disk probe + one dropped-page-cache
        # pipeline pass, ~2s); set TFR_BENCH_COLD=0 to skip.
        cold_info = _cold_io_throughput(data_dir, schema, hash_buckets, pack)
    remote_info = None
    if os.environ.get("TFR_BENCH_REMOTE", "1") != "0":
        # simulated-link remote readahead evidence (~2s, device-free)
        remote_info = _remote_prefetch_probe()
    remote_http_info = None
    if os.environ.get("TFR_BENCH_HTTP", "1") != "0":
        # REAL-socket remote tier: depth sweep + remote->cache->mmap over
        # the threaded HTTP backend (~6s, device-free) — ISSUE 9
        remote_http_info = _remote_http_probe()
    stall_info = None
    if os.environ.get("TFR_BENCH_STALL", "1") != "0":
        # fault-free deadline+watchdog bookkeeping overhead (~8s, device-free)
        stall_info = _stall_guard_overhead(data_dir, schema, hash_buckets, pack)
    warm_info = None
    if os.environ.get("TFR_BENCH_WARM", "1") != "0":
        # columnar epoch cache: populate once, measure the mmap-served
        # warm-epoch rate (~6s, device-free)
        warm_info = _warm_epoch_throughput(data_dir, schema, hash_buckets, pack)
        if host_side_value:
            warm_info["warm_vs_decode"] = round(
                warm_info["warm_epoch_value"] / host_side_value, 3
            )
    telemetry_info = None
    if os.environ.get("TFR_BENCH_TELEMETRY", "1") != "0":
        # flight-recorder overhead A/B + the telemetry block (quantiles +
        # bound-ness verdict) (~12s, device-free)
        telemetry_info = _tracing_overhead(data_dir, schema, hash_buckets, pack)
    seq_host_info = None
    if os.environ.get("TFR_BENCH_SEQ", "1") != "0":
        # device-free seq leg FIRST: seq_host_value must land in the
        # artifact whatever the device phase does (~3s)
        seq_host_info = _seq_host_throughput(
            seconds=float(os.environ.get("TFR_BENCH_SEQ_HOST_SECONDS", 2.0))
        )
    autotune_info = None
    if os.environ.get("TFR_BENCH_AUTOTUNE", "1") != "0":
        # closed-loop autotune convergence vs the fixed-knob reference
        # (~8s, device-free)
        autotune_info = _autotune_probe(data_dir, schema, hash_buckets, pack)
    service_info = None
    if os.environ.get("TFR_BENCH_SERVICE", "1") != "0":
        # disaggregated data service: K worker subprocesses -> 1 consumer,
        # vs host_side_value (~6s, device-free)
        service_info = _service_probe(data_dir, schema, hash_buckets, pack)
        if host_side_value:
            service_info["service"]["vs_host_side"] = round(
                service_info["service_value"] / host_side_value, 3
            )
    elastic_info = None
    if os.environ.get("TFR_BENCH_ELASTIC", "1") != "0":
        # elastic decode fleet: worker count tracks offered load, drains
        # on load removal (~16s, device-free) — ISSUE 12
        elastic_info = _elastic_probe()
    lease_info = None
    if os.environ.get("TFR_BENCH_LEASE", "1") != "0":
        # partitioned dispatchers: aggregate lease throughput K=1 vs K=2
        # (~6s, device-free) — ISSUE 17
        lease_info = _lease_throughput_probe()
    ckpt_info = None
    if os.environ.get("TFR_BENCH_CKPT", "1") != "0":
        # async vs sync checkpoint A/B under a seeded commit throttle +
        # unthrottled commit p99 per artifact path (~3s, device-free)
        ckpt_info = _ckpt_probe()
    scaling_info = None
    if os.environ.get("TFR_BENCH_SCALING", "1") != "0":
        # workers->ex/s sweep, appended to PARITY.md as the round trend
        # (~6s, device-free)
        scaling_info = _decode_scaling_trend(data_dir, schema, hash_buckets, pack)
    model_parallel_info = None
    if os.environ.get("TFR_BENCH_MODEL", "1") != "0":
        # model-parallel memory shape + LM train rate in a CPU-forced
        # subprocess (~15s incl. compiles, device-free for the parent)
        model_parallel_info = _model_parallel_probe()
    serving_info = None
    if os.environ.get("TFR_BENCH_SERVING", "1") != "0":
        # serving tier under seeded open-loop load: steady p99 + capacity
        # at saturation + disclosed overload shed rate, in a CPU-forced
        # subprocess (~20s incl. compiles, device-free for the parent) —
        # ISSUE 18
        serving_info = _serving_probe()

    # Measurement attempts land here the moment they complete, so a guard
    # firing later (e.g. the train phase hanging) still prints the real,
    # already-measured headline next to the error — and exits non-zero.
    completed_attempts: list = []

    def _fail_degraded(msg: str) -> None:
        """One owner for the guard-fired artifact, and a NON-ZERO exit: a
        device phase that did not run to its end is a failed run, whatever
        host-side evidence rides along. If the measurement attempts already
        completed, print the real headline (best attempt) with the failure
        noted; otherwise the device-free evidence plus the reason.

        Runs on watchdog/deadline daemon threads while the main thread may
        still be appending: snapshot the list once and read only the
        snapshot (r3 advisor — unsynchronized shared state before os._exit)."""
        attempts_snap = list(completed_attempts)
        if attempts_snap:
            best = max(attempts_snap, key=lambda a: a["value"])
            out = {
                "metric": "criteo_tf_example_ingest_to_device",
                "value": best["value"],
                "unit": "examples/sec/host",
                "vs_baseline": round(best["value"] / 1_000_000, 4),
                "windows": best["windows"],
                "sustained_value": best["sustained_value"],
                "link_probe_mbps": best["link_probe_mbps"],
                "ingest_duty_cycle": best["ingest_duty_cycle"],
                "host_side_value": round(host_side_value, 1),
                "attempts": attempts_snap,
                "error": msg,
            }
            for extra in (cold_info, remote_info, remote_http_info,
                          stall_info, warm_info, telemetry_info,
                          seq_host_info, autotune_info, service_info,
                          elastic_info, lease_info, ckpt_info, scaling_info,
                          model_parallel_info, serving_info):
                if extra is not None:
                    out.update(extra)
            _attach_regression_verdict(out)
            print(json.dumps(out), flush=True)
            os._exit(1)
        err = {
            "metric": "criteo_tf_example_ingest_to_device",
            "error": msg,
            # degraded-mode evidence: the device-free pipeline number
            "host_side_value": round(host_side_value, 1),
            "host_side_unit": "examples/sec/host (decode+hash+pack, no device)",
        }
        for extra in (cold_info, remote_info, remote_http_info,
                      stall_info, warm_info, telemetry_info,
                      seq_host_info, autotune_info, service_info,
                      elastic_info, lease_info, ckpt_info, scaling_info,
                      model_parallel_info, serving_info):
            if extra is not None:
                err.update(extra)
        _attach_regression_verdict(err)
        print(json.dumps(err), flush=True)
        os._exit(1)

    # Backend-init watchdog: if jax.devices() never returns, fail loudly
    # with a diagnosable message instead of hanging the harness. Armed only
    # around backend init — dataset generation and the host-side phase above
    # do not count against it.
    backend_up = threading.Event()

    def _watchdog():
        if not backend_up.wait(float(os.environ.get("TFR_BENCH_INIT_TIMEOUT", 300))):
            _fail_degraded(
                "TPU backend initialization timed out "
                "— no device measurement taken"
            )

    threading.Thread(target=_watchdog, daemon=True).start()
    mesh = create_mesh()  # all available devices on the 'data' axis
    backend_up.set()

    # Whole-run deadline: a device call that never returns would end the
    # round with NO artifact at all. Default derives from the configured
    # schedule (attempts,
    # windows, sustain, train) so env overrides keep the guard honest.
    # n_attempts/attempt_rest are parsed HERE, once, and reused by the
    # measurement loop below — two parse sites would let the derived
    # deadline drift out of sync with the actual schedule.
    run_done = threading.Event()
    n_attempts = max(1, int(os.environ.get("TFR_BENCH_ATTEMPTS", 3)))
    attempt_rest = float(os.environ.get("TFR_BENCH_ATTEMPT_REST", 20))
    attempt_cost = MEASURE_SECONDS + SUSTAIN_SECONDS + 30  # probes + slack
    default_deadline = (
        n_attempts * attempt_cost
        + (n_attempts - 1) * attempt_rest
        + 420  # train phases (two model regimes) incl. compiles/recompiles
        + 90   # seq phase incl. one-time ragged dataset generation
    )
    total_timeout = float(
        os.environ.get("TFR_BENCH_TOTAL_TIMEOUT", default_deadline)
    )

    def _deadline():
        if not run_done.wait(total_timeout):
            _fail_degraded(
                f"device phase exceeded {total_timeout:.0f}s "
                "— the device phase did not run to its end"
            )

    threading.Thread(target=_deadline, daemon=True).start()
    ds = _make_dataset(data_dir, schema, hash_buckets, pack, num_epochs=None)

    import statistics

    from tpu_tfrecord.tpu import data_sharding, pack_mixed, packed_width

    link_bytes = 4 * (14 + packed_width(26, CAT_BITS))
    n_windows = max(1, int(os.environ.get("TFR_BENCH_WINDOWS", 4)))
    window_seconds = MEASURE_SECONDS / n_windows
    sharding = data_sharding(mesh, ndim=2)
    # On a single-core host the background-thread machinery (HostPrefetcher
    # + DeviceIterator) only adds GIL hand-offs — there is no second core
    # for it to win; a serial produce->transfer loop measures faster and is
    # what a 1-core host would deploy. Multi-core hosts keep the overlap
    # machinery (decode thread + prefetcher + dispatch-ahead).
    try:
        n_cpus = len(os.sched_getaffinity(0))  # cgroup/affinity-aware
    except AttributeError:  # non-Linux
        n_cpus = os.cpu_count() or 1
    serial = n_cpus == 1

    # Deliberate pack-slowdown injection for validating the attribution
    # protocol (see PARITY.md): a busy-wait of this many ms rides EVERY call
    # through _pack_one — so a genuine pack regression elevates BOTH the
    # in-loop pack stage and the no-transfer pack_floor below, while shaper
    # interference (a concurrent transfer burning the single core) elevates
    # only the in-loop number. That asymmetry is what makes attempts[]
    # self-explaining.
    pack_spin_s = float(os.environ.get("TFR_BENCH_PACK_SPIN_MS", 0)) / 1e3

    def _pack_one(cb):
        hb = host_batch_from_columnar(
            cb, ds.schema, hash_buckets=hash_buckets, pack=pack
        )
        m = pack_mixed(hb["packed"], 14, CAT_BITS)
        if pack_spin_s:
            spin_until = time.perf_counter() + pack_spin_s
            while time.perf_counter() < spin_until:
                pass
        return m

    def _pack_floor_ms(cb, iters: int = 5) -> float:
        """Best-of-N of the full pack stage (host batch assembly + 20-bit
        bit-pack) with NO transfer in flight: the attempt's clean-core
        reference for its in-loop pack number."""
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            _pack_one(cb)
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    # One decoded chunk reused by every attempt's pack floor (decoding it
    # fresh would measure the decode thread, not the pack stage).
    _floor_it = ds.batches()
    try:
        _floor_cb = next(iter(_floor_it))
    finally:
        _floor_it.close()

    def measure_attempt(attempt: int = 0) -> dict:
        """Link probe + measurement windows + sustained phase: one attempt."""
        # Raw-link probe: 8 transfers of one wire-batch-sized array, fresh
        # random content (the shaper treats repeated payloads differently).
        # Recorded in the artifact so the headline number can be read
        # against the host-to-device bandwidth it was measured under.
        # Clean-core pack floor FIRST (before the probe opens the link): the
        # reference its in-loop pack number is judged against.
        pack_floor_ms = _pack_floor_ms(_floor_cb)
        probe_rng = np.random.default_rng(123 + attempt)  # fresh bytes per attempt
        probe_arrs = [
            probe_rng.integers(0, 1 << 20, size=(BATCH_SIZE, 31), dtype=np.int32)
            for _ in range(8)
        ]
        t_probe = time.perf_counter()
        for pa in probe_arrs:
            jax.block_until_ready(jax.device_put(pa, jax.devices()[0]))
        link_probe_mbps = (
            sum(pa.nbytes for pa in probe_arrs) / (time.perf_counter() - t_probe) / 1e6
        )

        it = ds.batches()
        # Per-attempt stage decomposition (verdict r3): decode_wait =
        # blocked on the decode thread; pack = view assembly + 20-bit
        # bit-pack; transfer = device_put dispatch (on a local v5e the
        # dispatch returns before the copy completes — chip_smoke.py's
        # transport probe, PERF.md — so completion is blocked in the consume
        # loop and lands in the duty accounting). Accumulated over windows
        # AND sustain so a future headline swing is attributable to a stage
        # instead of read as a mystery. Only the serial path decomposes —
        # with the overlap machinery the stages run on other threads.
        stage = {"decode_wait_s": 0.0, "pack_s": 0.0, "transfer_s": 0.0, "batches": 0}
        raw_it = iter(it)

        def wire_batches():
            # decode thread -> dense [B, 40] i32 host batches -> transfer
            # form: label+dense stay 32-bit lanes, the 26 hashed cats
            # bit-pack to their 20 significant bits -> [B, 31] i32,
            # 124B/example on the link instead of 160 (the consumer unpacks
            # in its jit for free — tpu/bitpack.py, exactness pinned in
            # tests/test_bitpack.py).
            while True:
                t0 = time.perf_counter()
                try:
                    cb = next(raw_it)
                except StopIteration:
                    return
                t1 = time.perf_counter()
                m = _pack_one(cb)
                stage["decode_wait_s"] += t1 - t0
                stage["pack_s"] += time.perf_counter() - t1
                stage["batches"] += 1
                yield m

        src = wire_batches()
        prefetcher = None
        if serial:
            def get():
                m = next(src)
                t0 = time.perf_counter()
                gb = jax.device_put(m, sharding)
                stage["transfer_s"] += time.perf_counter() - t0
                return gb
        else:
            # DeviceIterator transfers pytrees — wrap the bare wire matrix
            prefetcher = HostPrefetcher({"wire": m} for m in src)
            feed = DeviceIterator(prefetcher, mesh)
            get = lambda: next(feed)  # noqa: E731

        duty = DutyCycle()

        def consume_one():
            with duty.wait():
                gb = get()
            with duty.step():
                jax.block_until_ready(gb)

        # This is a SHARED box: other tenants' load swings any single
        # window by +-25%. Measure N windows back-to-back and report the
        # MEDIAN (the standard interference-robust estimator); every window
        # is disclosed, and a separate steady-state phase right after the
        # windows reports the link-shaped sustained rate.
        windows = []
        sustained_value = None
        import resource

        r0 = resource.getrusage(resource.RUSAGE_SELF)
        t_attempt0 = time.perf_counter()
        try:
            for _ in range(WARMUP_BATCHES):
                consume_one()
            duty = DutyCycle()
            for _ in range(n_windows):
                t_start = time.perf_counter()
                examples = 0
                while True:
                    consume_one()
                    examples += BATCH_SIZE
                    t_end = time.perf_counter()
                    if t_end - t_start >= window_seconds:
                        break
                windows.append(examples / (t_end - t_start))
            ingest_duty = duty.value() or 0.0  # windows only, not sustain
            if SUSTAIN_SECONDS > 0:
                # keep hammering: the link's burst budget is long gone by
                # the end of this phase, so this is the shaped steady state
                t_start = time.perf_counter()
                examples = 0
                while time.perf_counter() - t_start < SUSTAIN_SECONDS:
                    consume_one()
                    examples += BATCH_SIZE
                sustained_value = examples / (time.perf_counter() - t_start)
        finally:
            if prefetcher is not None:
                prefetcher.close()
            it.close()
        r1 = resource.getrusage(resource.RUSAGE_SELF)
        attempt_wall = time.perf_counter() - t_attempt0
        attempt_cpu = (r1.ru_utime - r0.ru_utime) + (r1.ru_stime - r0.ru_stime)
        out = {
            "value": round(statistics.median(windows), 1),
            "windows": [round(w, 1) for w in windows],
            "sustained_value": round(sustained_value, 1) if sustained_value else None,
            "link_probe_mbps": round(link_probe_mbps, 1),
            "ingest_duty_cycle": round(ingest_duty, 4),
            # Attribution context (verdict r4 item 4): pack_floor_ms is the
            # SAME pack code path timed with no transfer in flight, fresh
            # each attempt — in-loop pack >> floor while the floor holds
            # steady means a concurrent transfer was burning the core
            # (shaper busy-wait), NOT a pack regression (which would raise
            # the floor too; validate with TFR_BENCH_PACK_SPIN_MS).
            # cpu_frac near 1.0 says the wall went to CPU work on this
            # 1-core host; well under 1.0 says blocked on the link.
            "pack_floor_ms": round(pack_floor_ms, 2),
            "attempt_cpu_frac": round(attempt_cpu / attempt_wall, 3)
            if attempt_wall > 0
            else None,
            "attempt_nivcsw": r1.ru_nivcsw - r0.ru_nivcsw,
        }
        if stage["batches"]:
            nb = stage["batches"]
            out["stage_ms_per_batch"] = {
                "decode_wait": round(stage["decode_wait_s"] / nb * 1e3, 2),
                "pack": round(stage["pack_s"] / nb * 1e3, 2),
                "transfer": round(stage["transfer_s"] / nb * 1e3, 2),
            }
        return out

    # Interference on this box is strictly ONE-directional: the other
    # tenants on the shared cores can only SLOW the
    # pipeline down, never speed it up. Under one-sided noise the standard
    # estimator of the noise-free rate is the best of a FIXED number of
    # draws (the same argument behind timeit's min-of-repeats rule: the
    # high throughputs are the signal, the low ones are other processes).
    # The attempt count is fixed up front — never conditioned on an
    # attempt's outcome or on the link probe — so there is no re-roll bias:
    # every run takes exactly TFR_BENCH_ATTEMPTS draws and EVERY attempt
    # (value, windows, its own link probe) is disclosed in attempts[].
    # (An earlier revision selected by best link probe; a captured run
    # showed the probe inverting — probe 498MB/s paired with 518k ex/s
    # while probe 204MB/s paired with 992k — because the instantaneous
    # probe does not predict link state over the following 14s.)
    attempts = completed_attempts  # shared with _fail_degraded (see above)
    for i in range(n_attempts):
        if i:
            time.sleep(attempt_rest)  # let the link's burst budget refill
        attempts.append(measure_attempt(i))
    best = max(attempts, key=lambda a: a["value"])
    value = best["value"]
    windows = best["windows"]
    sustained_value = best["sustained_value"]
    link_probe_mbps = best["link_probe_mbps"]
    ingest_duty = best["ingest_duty_cycle"]

    # Secondary disclosed metric: the ragged SequenceExample (long-doc)
    # path — decode->pad->bf16->device (verdict r3 item 8). The host-only
    # half already ran pre-backend (seq_host_info).
    seq_info = None
    if os.environ.get("TFR_BENCH_SEQ", "1") != "0":
        seq_info = _seq_device_throughput(mesh, data_sharding(mesh, ndim=3))

    # Phase 2 — the BASELINE.md duty-cycle metric measured the way it is
    # defined: a real DLRM training step on the device consuming ingested
    # batches, busy = device step time, wait = time blocked on input. The
    # producer thread decodes (GIL released) while the device computes, so
    # overlap is real even on this 1-core host. Two regimes:
    # - duty_cycle: a modest DLRM. Even this step is device-bound on one
    #   chip (XLA's embedding gather/scatter over a 2^20-row table costs
    #   ~100-200ms at B=16384 — the classic TPU embedding bottleneck that
    #   SparseCore hardware exists for), so the pipeline keeps it >=0.999
    #   fed; a host with more cores per chip or a lighter model could flip
    #   this regime production-bound.
    # - duty_cycle_heavy: the top MLP sized so the device step exceeds host
    #   batch time regardless of embedding-op cost (the north-star regime:
    #   BASELINE.md defines >=95% as "input pipeline never the
    #   bottleneck"). This is the red/green machine check of the >=95%
    #   claim on real hardware.
    train_duty = heavy_duty = None
    if os.environ.get("TFR_BENCH_TRAIN", "1") != "0":
        train_duty = _train_duty_cycle(ds, mesh, hash_buckets, pack, top_mlp=(64, 1))
        heavy_top = tuple(
            int(w) for w in os.environ.get("TFR_BENCH_HEAVY_TOP", "8192,8192,1").split(",")
        )
        heavy_duty = _train_duty_cycle(ds, mesh, hash_buckets, pack, top_mlp=heavy_top)

    # Fields from `best` are already rounded/filtered by measure_attempt —
    # formatting lives in ONE place.
    out = {
        "metric": "criteo_tf_example_ingest_to_device",
        "value": value,
        "unit": "examples/sec/host",
        "vs_baseline": round(value / 1_000_000, 4),
        # all measurement windows (median is the reported value)
        "windows": windows,
        # steady-state rate over the longer sustain window (see
        # host_side_value for the device-free reading)
        "sustained_value": sustained_value,
        # bytes/example on the link (cats bit-packed to 20-bit lanes)
        "link_bytes_per_example": link_bytes,
        # raw link bandwidth measured just before the windows (device_put
        # of wire-batch-sized fresh arrays, no pipeline) — the link's
        # ceiling in THIS run
        "link_probe_mbps": link_probe_mbps,
        # transfer-hidden fraction of the ingest-only loop (phase 1,
        # measurement windows only — the sustain phase is excluded)
        "ingest_duty_cycle": ingest_duty,
        # device-free pipeline throughput (decode+hash+pack, no device)
        "host_side_value": round(host_side_value, 1),
    }
    if attempts:
        # full disclosure: every measurement attempt with its link state and
        # attribution context (pack_floor_ms / cpu_frac / nivcsw) — emitted
        # even for a single attempt, which carries the same context
        out["attempts"] = attempts
    if cold_info is not None:
        # dropped-page-cache pass + raw-disk disclosure (TFR_BENCH_COLD=1)
        out.update(cold_info)
    if remote_info is not None:
        # simulated-link remote readahead evidence (TFR_BENCH_REMOTE=1)
        out.update(remote_info)
    if remote_http_info is not None:
        # real-socket remote tier: depth sweep + remote->cache->mmap over
        # the threaded HTTP backend (TFR_BENCH_HTTP=1)
        out.update(remote_http_info)
    if stall_info is not None:
        # fault-free stall-defense bookkeeping overhead (TFR_BENCH_STALL=1)
        out.update(stall_info)
    if warm_info is not None:
        # columnar epoch cache: mmap-served warm-epoch rate vs the decode
        # path (TFR_BENCH_WARM=1)
        out.update(warm_info)
    if telemetry_info is not None:
        # flight-recorder overhead A/B + latency quantiles + bound-ness
        # verdict (TFR_BENCH_TELEMETRY=1)
        out.update(telemetry_info)
    if seq_host_info is not None:
        # device-free seq leg, measured pre-backend (TFR_BENCH_SEQ=1)
        out.update(seq_host_info)
    if autotune_info is not None:
        # autotune convergence trajectory + final knobs vs fixed-knob
        # (TFR_BENCH_AUTOTUNE=1)
        out.update(autotune_info)
    if service_info is not None:
        # disaggregated data service leg: K worker subprocesses -> 1
        # consumer vs host_side_value (TFR_BENCH_SERVICE=1)
        out.update(service_info)
    if elastic_info is not None:
        # elastic fleet: worker count vs offered load + drain-back
        # (TFR_BENCH_ELASTIC=1)
        out.update(elastic_info)
    if lease_info is not None:
        # partitioned-dispatcher lease throughput K=1 vs K=2
        # (TFR_BENCH_LEASE=1)
        out.update(lease_info)
    if ckpt_info is not None:
        # async vs sync checkpoint A/B + per-artifact commit p99
        # (TFR_BENCH_CKPT=1)
        out.update(ckpt_info)
    if scaling_info is not None:
        # workers->ex/s sweep (also appended to PARITY.md as the trend)
        out.update(scaling_info)
    if model_parallel_info is not None:
        # model-parallel memory shape (per-device pipeline input bytes,
        # old replicated vs new O(mb) shard) + LM train rate
        # (TFR_BENCH_MODEL=1)
        out.update(model_parallel_info)
    if serving_info is not None:
        # serving tier: steady p99 + saturation throughput + disclosed
        # overload shed rate (TFR_BENCH_SERVING=1)
        out.update(serving_info)
    if seq_info is not None:
        # ragged SequenceExample decode->pad->device secondary metric
        out.update(seq_info)
    if train_duty is not None:
        # realistic-model regime (device-bound on one chip — see comment
        # at the measurement site)
        out["duty_cycle"] = round(train_duty, 4)
    if heavy_duty is not None:
        # the BASELINE.md >=95% target metric, measured in its own regime
        # (device step >= host batch time by model size)
        out["duty_cycle_heavy"] = round(heavy_duty, 4)
    # self-flagging regression check vs the previous round's artifact,
    # with the first-class top-level verdict + loud stderr line
    _attach_regression_verdict(out)
    run_done.set()
    print(json.dumps(out))


def _train_duty_cycle(ds, mesh, hash_buckets, pack, top_mlp, seconds=6.0):
    """Duty cycle of a DLRM train loop fed by the live pipeline.

    Sparse embedding updates (models.dlrm.sparse_train_step) make the FULL
    2^20-bucket vocabulary trainable — the table gradient never
    materializes, so hashed indices feed the real-size table with no
    on-device folding. The transfer runs on DeviceIterator's worker thread
    (transfer_thread=True), which blocks each copy to completion while the
    device computes. On a local v5e the copy is asynchronous at dispatch
    anyway (chip_smoke.py's transport probe), so plain dispatch-ahead
    would overlap too; ROADMAP D10 decides between them."""
    import functools

    import jax
    import optax

    from tpu_tfrecord.models import init_params, sparse_opt_init, sparse_train_step
    from tpu_tfrecord.tpu import DeviceIterator, HostPrefetcher, host_batch_from_columnar
    from tpu_tfrecord.tracing import DutyCycle

    # TFR_BENCH_VOCAB scales the trainable table down for CPU smoke runs
    # (indices fold on device when it is below the hashed space); on the
    # real chip the default is the FULL 2^20 hashed vocabulary.
    vocab = int(os.environ.get("TFR_BENCH_VOCAB", HASH_BUCKETS))
    cfg = criteo_dlrm_config(vocab, top_mlp=top_mlp)
    params = init_params(jax.random.key(0), cfg)
    tx = optax.sgd(1e-3)
    opt_state = sparse_opt_init(params, cfg, tx)
    step = jax.jit(
        functools.partial(sparse_train_step, cfg=cfg, tx=tx), donate_argnums=(0, 1)
    )

    from tpu_tfrecord.tpu import pack_mixed

    # consume the bit-packed wire form end-to-end
    split = jax.jit(functools.partial(split_wire, vocab=vocab))

    it = ds.batches()  # phase 1 closed its iterator; epochs are infinite

    def host_batches():
        for cb in it:
            hb = host_batch_from_columnar(
                cb, ds.schema, hash_buckets=hash_buckets, pack=pack
            )
            yield {"wire": pack_mixed(hb["packed"], 14, CAT_BITS)}

    # Both constructors spawn worker threads: build them INSIDE the try so a
    # ctor failure still reaches the finally and nothing leaks (r3 advisor).
    prefetcher = dev_it = None
    try:
        prefetcher = HostPrefetcher(host_batches())
        dev_it = DeviceIterator(prefetcher, mesh, transfer_thread=True)
        duty = DutyCycle()
        # warm THREE full iterations: the first call compiles, and the
        # second can recompile (donated outputs come back device-resident
        # with different layouts) — a compile leaking into the measured
        # window would report compile time as device "busy" (observed: a
        # 26s recompile turned the duty cycle into a meaningless 0.999)
        #
        # busy is forced with a scalar fetch of the loss. On a local v5e
        # block_until_ready waits for completion just the same (a chain of
        # twenty 4096^2 bf16 matmuls: 15.0 ms to block_until_ready, 15.3 ms
        # to a scalar fetch — chip_smoke.py's transport probe), so either
        # would do; ROADMAP D10 decides.
        for _ in range(3):
            batch = split(next(dev_it))
            params, opt_state, loss = step(params, opt_state, batch)
            float(loss)
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            with duty.wait():
                gb = next(dev_it)
            with duty.step():
                params, opt_state, loss = step(params, opt_state, split(gb))
                float(loss)  # completion (see note above)
        return duty.value()
    finally:
        if dev_it is not None:
            dev_it.close()
        if prefetcher is not None:
            prefetcher.close()
        it.close()


if __name__ == "__main__":
    if "--model-parallel-child" in sys.argv:
        # subprocess entry for _model_parallel_probe: env already forces
        # the 8-device CPU backend
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")
        _model_parallel_child()
        sys.exit(0)
    if "--serving-child" in sys.argv:
        # subprocess entry for _serving_probe: env already forces the
        # 8-device CPU backend
        import jax as _jax

        _jax.config.update("jax_platforms", "cpu")
        _serving_child()
        sys.exit(0)
    main()
