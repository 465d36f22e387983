"""Real-network remote ingestion that survives a hostile link (ISSUE 9).

Every test here reads over a REAL TCP connection: a threaded stdlib Range
server (tpu_tfrecord.httpfs.serve_directory) fronts a local dataset, and a
seeded FaultPlan fires faults at the server side of the socket — RST
mid-body, truncated bodies, 503/429 with Retry-After, stalls, trickles,
and lying Content-Range headers — while client-side ``connect`` rules
model connection-refused. The contracts pinned:

- recoverable faults heal (RetryPolicy; PrefetchReader block fetches
  resume from the exact byte offset) with rows BYTE-IDENTICAL to a local
  read — zero fallback-to-wrong-data;
- a lying server (wrong Content-Range) is a LOUD BadContentRangeError,
  never silently shifted records;
- the fault ledger is replayable (same plan + same access pattern =>
  identical ledger);
- cold remote shards stream straight into the columnar cache (the link
  is paid once per epoch), and a SIGKILLed consumer mid-populate resumes
  with the cache either valid or bypassed — never wrong;
- PrefetchReader.close() leaves no live fetch thread (ADVICE r5 #2).
"""

import os
import signal
import subprocess
import sys
import threading
import time
import uuid

import numpy as np
import pytest

import tpu_tfrecord.io as tfio
from tpu_tfrecord import fs as tfs
from tpu_tfrecord import httpfs
from tpu_tfrecord.faults import FaultPlan, FaultRule, install_chaos
from tpu_tfrecord.io.dataset import TFRecordDataset
from tpu_tfrecord.metrics import METRICS
from tpu_tfrecord.retry import RetryPolicy
from tpu_tfrecord.schema import (
    LongType,
    StringType,
    StructField,
    StructType,
)

SCHEMA = StructType([
    StructField("id", LongType(), nullable=False),
    StructField("s", StringType()),
])

N_SHARDS = 3
# big enough that a 64 KiB TFR_REMOTE_BLOCK_BYTES engages PrefetchReader
# (size >= 2 * block) in the matrix's prefetch mode: ~140 KiB per shard
ROWS_PER_SHARD = 1200


def _fast_retries(n, **kw):
    return RetryPolicy(max_retries=n, sleep=lambda _s: None, **kw)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(server, dataset url, local dataset dir, sorted shard names)."""
    root = tmp_path_factory.mktemp("httpds")
    out = os.path.join(str(root), "ds")
    for s in range(N_SHARDS):
        tfio.write(
            [[i, f"val{i}" + "x" * (80 + i % 40)]
             for i in range(s * ROWS_PER_SHARD, (s + 1) * ROWS_PER_SHARD)],
            SCHEMA, out, mode="append" if s else "overwrite",
        )
    names = sorted(n for n in os.listdir(out) if n.startswith("part-"))
    with httpfs.serve_directory(str(root)) as srv:
        yield srv, srv.url_for("ds"), out, names
        srv.set_plan(None)


@pytest.fixture
def clean_plan(served):
    srv = served[0]
    srv.set_plan(None)
    yield srv
    srv.set_plan(None)


def read_ids(source, **kw):
    kw.setdefault("batch_size", 16)
    kw.setdefault("drop_remainder", False)
    ds = TFRecordDataset(source, schema=SCHEMA, **kw)
    got = []
    with ds.batches() as it:
        for cb in it:
            got.extend(cb["id"].values.tolist())
    return got


@pytest.fixture(scope="module")
def local_ids(served):
    _, _, out, _ = served
    ids = read_ids(out)
    assert sorted(ids) == list(range(N_SHARDS * ROWS_PER_SHARD))
    return ids


class TestHttpFS:
    def test_dispatch_and_capability(self, served):
        _, url, _, _ = served
        fsys = tfs.filesystem_for(url)
        assert isinstance(fsys, httpfs.HttpFS)
        # every open() is its own connection: concurrent block fetches OK
        assert tfs.independent_read_handles(fsys)

    def test_discovery_matches_local(self, served):
        _, url, out, names = served
        remote = tfio.discover_shards(url)
        local = tfio.discover_shards(out)
        assert [s.path.rsplit("/", 1)[-1] for s in remote] == names
        assert [s.size for s in remote] == [s.size for s in local]

    def test_info_carries_freshness_stamps(self, served):
        srv, url, out, names = served
        fsys = tfs.filesystem_for(url)
        info = fsys.info(f"{url}/{names[0]}")
        assert info["size"] == os.path.getsize(os.path.join(out, names[0]))
        assert "mtime" in info and "ETag" in info

    def test_read_only_is_loud(self, served):
        _, url, _, _ = served
        fsys = tfs.filesystem_for(url)
        with pytest.raises(OSError, match="read-only"):
            fsys.open(url + "/x", "wb")
        with pytest.raises(OSError, match="read-only"):
            fsys.rename(url + "/a", url + "/b")
        with pytest.raises(OSError, match="read-only"):
            fsys.makedirs(url + "/d")

    def test_range_reads_and_eof(self, served):
        srv, url, out, names = served
        path = os.path.join(out, names[0])
        payload = open(path, "rb").read()
        fsys = tfs.filesystem_for(url)
        with fsys.open(f"{url}/{names[0]}", "rb") as fh:
            assert fh.read(64) == payload[:64]
            fh.seek(len(payload) // 2)
            assert fh.read(128) == payload[len(payload) // 2:][:128]
            fh.seek(len(payload) + 10)
            assert fh.read(8) == b""  # past EOF: clean empty, not an error

    def test_clean_epoch_byte_identical(self, served, local_ids, clean_plan):
        _, url, _, _ = served
        assert read_ids(url) == local_ids

    def test_row_reader_over_http(self, served, clean_plan):
        _, url, _, _ = served
        table = tfio.read(url, schema=SCHEMA)
        assert sorted(table.column("id")) == list(
            range(N_SHARDS * ROWS_PER_SHARD)
        )

    def test_redirected_reads_follow_like_metadata(self, served, local_ids,
                                                   clean_plan):
        """A CDN-offload-shaped 302: discovery already follows redirects;
        the DATA read must too, or the epoch dies on a server the
        metadata layer explicitly supports."""
        srv, url, out, names = served
        red = srv.url_for(f"redirect/ds/{names[0]}")
        fsys = tfs.filesystem_for(red)
        assert fsys.size(red) == os.path.getsize(os.path.join(out, names[0]))
        payload = open(os.path.join(out, names[0]), "rb").read()
        with fsys.open(red, "rb") as fh:
            fh.seek(1000)
            assert fh.read(64) == payload[1000:1064]
        # and a whole dataset through the redirecting prefix
        assert read_ids(srv.url_for("redirect/ds")) == local_ids

    def test_small_object_reads_self_heal_below_prefetch_bar(
        self, served, clean_plan,
    ):
        """Objects below the PrefetchReader engagement bar get the SAME
        self-healing contract: a plain handle that reopens and resumes at
        the exact consumed offset (review fix — the retry policy used to
        be silently dropped for small shards)."""
        srv, url, out, names = served
        shard_url = f"{url}/{names[0]}"
        payload = open(os.path.join(out, names[0]), "rb").read()
        plan = FaultPlan([
            FaultRule(op="http", kind="truncated_body", path=names[0],
                      cap_bytes=512, times=1),
        ])
        srv.set_plan(plan)
        METRICS.reset()
        fsys = tfs.filesystem_for(shard_url)
        # default 8 MiB block: far below the bar -> RetryingReadStream
        fh = tfs.open_for_read(fsys, shard_url,
                               retry_policy=_fast_retries(2))
        assert isinstance(fh, tfs.RetryingReadStream)
        with fh:
            assert fh.read() == payload
        assert METRICS.counter("remote.fetch_retries") == 1
        # exact-offset resume: the reopened request was keyed at byte 512
        assert ("http", f"/ds/{names[0]}@512") in plan._calls, \
            sorted(plan._calls)

    def test_http_rules_reject_unexecutable_kinds(self):
        """An op='http' rule with a kind the Range server's dispatch does
        not execute would be LEDGERED as fired while the object serves
        clean — refused at construction instead."""
        for kind in ("short_read", "disconnect", "flaky_listing",
                     "rename_race"):
            with pytest.raises(ValueError, match="http"):
                FaultRule(op="http", kind=kind,
                          **({"cap_bytes": 8} if kind == "short_read" else {}))
        # the generic kinds the server DOES execute stay legal
        FaultRule(op="http", kind="stall", stall_ms=5)
        FaultRule(op="http", kind="transient_error")

    def test_autoindex_redirecting_dir_is_not_a_file(self, tmp_path):
        """A generic autoindex server 301s 'ds' -> 'ds/' and serves an
        HTML listing: isfile must say False (isdir True), or the doctor
        scans the listing page as TFRecord bytes."""
        import http.server as _hs
        import threading as _th

        class _Autoindex(_hs.BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def _respond(self):
                if self.path == "/ds":
                    self.send_response(301)
                    self.send_header("Location", "/ds/")
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return None
                if self.path == "/ds/":
                    body = b'<html><a href="shard.tfrecord">s</a></html>'
                    self.send_response(200)
                    self.send_header("Content-Type", "text/html")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    return body
                self.send_response(404)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return None

            def do_HEAD(self):  # noqa: N802
                self._respond()

            def do_GET(self):  # noqa: N802
                body = self._respond()
                if body:
                    self.wfile.write(body)

        httpd = _hs.ThreadingHTTPServer(("127.0.0.1", 0), _Autoindex)
        t = _th.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        try:
            url = f"http://127.0.0.1:{httpd.server_address[1]}/ds"
            fsys = httpfs.HttpFS()
            assert not fsys.isfile(url)
            assert fsys.isdir(url)
            assert fsys.listdir(url) == ["shard.tfrecord"]
        finally:
            httpd.shutdown()
            httpd.server_close()

    def test_open_fault_during_retry_spends_budget_not_escapes(
        self, served, clean_plan,
    ):
        """A transient fault at REOPEN time (inside the self-healing
        stream's retry) must consume the same budget as a read fault,
        not abort the stream with retries left."""
        srv, url, out, names = served
        payload = open(os.path.join(out, names[0]), "rb").read()
        plan = FaultPlan([
            FaultRule(op="http", kind="truncated_body", path=names[0],
                      cap_bytes=256, times=1),
            # the RETRY's reopen (new connection) is refused once
            FaultRule(op="connect", kind="transient_error", ordinal=1,
                      times=1),
        ])
        srv.set_plan(plan)
        METRICS.reset()
        shard_url = f"{url}/{names[0]}"
        with install_chaos(plan):
            fsys = tfs.filesystem_for(shard_url)
            with tfs.open_for_read(fsys, shard_url,
                                   retry_policy=_fast_retries(3)) as fh:
                got = fh.read()
        assert got == payload
        assert METRICS.counter("remote.fetch_retries") == 2
        kinds = sorted(e["kind"] for e in plan.ledger)
        assert kinds == ["transient_error", "truncated_body"], kinds

    def test_real_connection_refused_is_prompt_oserror(self, tmp_path):
        # a dead port: the OS itself refuses — the realest fault there is
        with httpfs.serve_directory(str(tmp_path)) as srv:
            dead_url = srv.url_for("nothing.bin")
        t0 = time.monotonic()
        with pytest.raises(OSError):
            with httpfs.HttpFS().open(dead_url, "rb") as fh:
                fh.read(1)
        assert time.monotonic() - t0 < 5.0


# -- the fault-kind x read-mode matrix --------------------------------------
#
# Modes share one contract: recoverable faults + retries => rows
# byte-identical to local; the fault provably fired (ledger non-empty).

READ_MODES = {
    "strict": {},
    "salvage": {"on_corrupt": "skip_record"},
    "prefetch": {},  # PrefetchReader engaged via small block env
    "cached": {"cache": "auto"},
}


def _fault_rules(kind, names):
    """Rules for one fault kind against the first two shards."""
    if kind == "refused":
        # client-side: the first two read-time connects are refused
        return [FaultRule(op="connect", kind="transient_error", times=2)]
    if kind == "reset":
        return [FaultRule(op="http", kind="reset", path=names[0],
                          cap_bytes=64, times=1),
                FaultRule(op="http", kind="reset", path=names[1],
                          cap_bytes=256, times=1)]
    if kind == "truncated":
        return [FaultRule(op="http", kind="truncated_body", path=names[0],
                          cap_bytes=100, times=1)]
    if kind == "status_503":
        return [FaultRule(op="http", kind="http_error", path=names[0],
                          status=503, retry_after_s=0.001, times=1),
                FaultRule(op="http", kind="http_error", path=names[1],
                          status=429, retry_after_s=0.001, times=1)]
    if kind == "stall":
        # bounded server-side stall: the client rides it out (the
        # deadline/hedge legs are pinned separately below)
        return [FaultRule(op="http", kind="stall", path=names[0],
                          stall_ms=120, times=1)]
    if kind == "trickle":
        return [FaultRule(op="http", kind="trickle", path=names[0],
                          stall_ms=1, cap_bytes=512, times=1)]
    if kind == "bad_content_range":
        return [FaultRule(op="http", kind="bad_content_range", path=names[0],
                          shift_bytes=32, times=1)]
    raise AssertionError(kind)


FAULT_KINDS = [
    "refused", "reset", "truncated", "status_503", "stall", "trickle",
    "bad_content_range",
]


class TestFaultMatrix:
    @pytest.mark.parametrize("mode", sorted(READ_MODES))
    @pytest.mark.parametrize("kind", FAULT_KINDS)
    def test_fault_heals_byte_identical(
        self, served, local_ids, clean_plan, monkeypatch, tmp_path,
        kind, mode,
    ):
        srv, url, _, names = served
        plan = FaultPlan(_fault_rules(kind, names), seed=11)
        kw = dict(READ_MODES[mode])
        if mode == "prefetch":
            # engage the block pipeline: 64 KiB blocks against ~140 KiB
            # shards, 4 fetches in flight on independent connections
            monkeypatch.setenv("TFR_REMOTE_BLOCK_BYTES", str(64 << 10))
            monkeypatch.setenv("TFR_REMOTE_PREFETCH_DEPTH", "4")
        if mode == "cached":
            kw.update(cache_dir=str(tmp_path / f"cache-{kind}"))
        METRICS.reset()
        srv.set_plan(plan)
        if kind == "refused":
            # construct BEFORE chaos so discovery connects are clean; the
            # refused connects then hit the read path deterministically
            ds = TFRecordDataset(
                url, batch_size=16, schema=SCHEMA, drop_remainder=False,
                retry_policy=_fast_retries(4), **kw,
            )
            got = []
            with install_chaos(plan):
                with ds.batches() as it:
                    for cb in it:
                        got.extend(cb["id"].values.tolist())
        else:
            got = read_ids(url, retry_policy=_fast_retries(4), **kw)
        assert got == local_ids, f"{kind} x {mode}: rows differ from local"
        assert plan.ledger, f"{kind} x {mode}: fault never fired"
        if kind == "bad_content_range":
            # the lie was DETECTED (counter), not absorbed as shifted data
            assert METRICS.counter("remote.bad_range") >= 1

    def test_bad_content_range_without_retries_is_loud(
        self, served, local_ids, clean_plan,
    ):
        srv, url, _, names = served
        srv.set_plan(FaultPlan([
            FaultRule(op="http", kind="bad_content_range", path=names[0],
                      shift_bytes=32, times=None),
        ]))
        METRICS.reset()
        with pytest.raises(OSError):
            read_ids(url)
        assert METRICS.counter("remote.bad_range") >= 1

    def test_permanent_fault_exhausts_retries_loudly(
        self, served, clean_plan,
    ):
        srv, url, _, names = served
        srv.set_plan(FaultPlan([
            FaultRule(op="http", kind="http_error", path=names[0],
                      status=503, times=None),
        ]))
        with pytest.raises(OSError):
            read_ids(url, retry_policy=_fast_retries(2))

    def test_ledger_replay_deterministic(self, served, local_ids, clean_plan):
        """Same plan JSON + same access pattern => byte-identical ledger
        (sequential reads: no prefetch concurrency in this leg)."""
        srv, url, _, names = served
        spec = FaultPlan([
            FaultRule(op="http", kind="truncated_body", path=names[0],
                      cap_bytes=128, times=1),
            FaultRule(op="http", kind="http_error", path=names[1],
                      status=503, times=1),
            FaultRule(op="http", kind="stall", path=names[2],
                      stall_ms=10, times=1),
        ], seed=5).to_json()
        ledgers = []
        for _ in range(2):
            plan = FaultPlan.from_json(spec)
            srv.set_plan(plan)
            assert read_ids(url, retry_policy=_fast_retries(3), num_workers=1) == local_ids
            ledgers.append(plan.ledger_json())
        assert ledgers[0] == ledgers[1]
        assert ledgers[0].count("\n") == 2  # 3 events, one per shard


class TestBlockSelfHeal:
    """PrefetchReader block fetches retry + resume from the exact byte
    offset — the tentpole's self-healing contract, on a big object."""

    @pytest.fixture()
    def big(self, tmp_path):
        payload = bytes(
            np.random.default_rng(7).integers(0, 256, 1 << 20, np.uint8)
        )
        name = f"big-{uuid.uuid4().hex[:6]}.bin"
        (tmp_path / name).write_bytes(payload)
        with httpfs.serve_directory(str(tmp_path)) as srv:
            yield srv, srv.url_for(name), payload

    def _prefetch_open(self, url, policy, monkeypatch, depth=4):
        monkeypatch.setenv("TFR_REMOTE_BLOCK_BYTES", str(128 << 10))
        monkeypatch.setenv("TFR_REMOTE_PREFETCH_DEPTH", str(depth))
        fsys = tfs.filesystem_for(url)
        fh = tfs.open_for_read(fsys, url, retry_policy=policy)
        assert isinstance(fh, tfs.PrefetchReader)
        return fh

    def test_reset_mid_block_resumes_exact_offset(self, big, monkeypatch):
        srv, url, payload = big
        plan = FaultPlan([
            # RST two different blocks mid-body
            FaultRule(op="http", kind="reset", path="@131072",
                      cap_bytes=1000, times=1),
            FaultRule(op="http", kind="reset", path="@524288",
                      cap_bytes=5000, times=1),
        ], seed=3)
        srv.set_plan(plan)
        METRICS.reset()
        with self._prefetch_open(url, _fast_retries(3), monkeypatch) as fh:
            got = fh.read()
        assert got == payload
        assert METRICS.counter("remote.fetch_retries") >= 2
        assert len(plan.ledger) == 2

    def test_truncated_block_resumes(self, big, monkeypatch):
        srv, url, payload = big
        plan = FaultPlan([
            FaultRule(op="http", kind="truncated_body", path="@262144",
                      cap_bytes=4096, times=1),
        ], seed=3)
        srv.set_plan(plan)
        METRICS.reset()
        with self._prefetch_open(url, _fast_retries(2), monkeypatch) as fh:
            got = fh.read()
        assert got == payload
        assert METRICS.counter("remote.fetch_retries") == 1
        # truncation is a clean FIN: exactly cap_bytes were delivered, so
        # the retry re-ranged from the EXACT byte the body broke off at —
        # the server saw a request keyed at block_start + 4096
        assert ("http", "/" + url.rsplit("/", 1)[-1] + "@266240") in plan._calls, \
            sorted(plan._calls)

    def test_retry_after_is_honored_through_sleep_seam(self, big, monkeypatch):
        srv, url, payload = big
        slept = []
        policy = RetryPolicy(max_retries=2, sleep=slept.append)
        plan = FaultPlan([
            FaultRule(op="http", kind="http_error", path="@0",
                      status=429, retry_after_s=0.25, times=1),
        ])
        srv.set_plan(plan)
        with self._prefetch_open(url, policy, monkeypatch) as fh:
            got = fh.read()
        assert got == payload
        assert 0.25 in slept, slept  # the server's hint, not just backoff

    def test_retry_after_is_bounded_by_cap_and_deadline(self, big,
                                                        monkeypatch):
        """A hostile Retry-After (86400s) must not park the reader: the
        hint is clamped to the sanity cap AND the policy's remaining
        wall-clock deadline — pause() promises never to sleep past the
        deadline, and the hint cannot smuggle that promise away."""
        srv, url, payload = big
        slept = []
        clock = {"t": 0.0}
        policy = RetryPolicy(
            max_retries=3, deadline=5.0, jitter=False, base_delay=0.0,
            sleep=slept.append, clock=lambda: clock["t"],
        )
        plan = FaultPlan([
            FaultRule(op="http", kind="http_error", path="@0",
                      status=429, retry_after_s=86400, times=1),
        ])
        srv.set_plan(plan)
        with self._prefetch_open(url, policy, monkeypatch) as fh:
            got = fh.read()
        assert got == payload
        assert slept and max(slept) <= 5.0, slept

    def test_budget_exhausted_raises(self, big, monkeypatch):
        srv, url, _ = big
        srv.set_plan(FaultPlan([
            FaultRule(op="http", kind="reset", path="@0",
                      cap_bytes=100, times=None),
        ]))
        with self._prefetch_open(url, _fast_retries(1), monkeypatch) as fh:
            with pytest.raises(OSError):
                fh.read()

    def test_close_leaves_no_live_fetch_threads(self, big, monkeypatch):
        """ADVICE r5 #2: close() must WAIT for in-flight fetch threads —
        they hold live backend handles that race tempdir cleanup."""
        srv, url, payload = big
        with self._prefetch_open(url, None, monkeypatch) as fh:
            assert fh.read(1024) == payload[:1024]
        # bounded-wait close has returned: no fetch worker may survive it
        alive = [t.name for t in threading.enumerate()
                 if t.name.startswith("tfr-prefetch") and t.is_alive()]
        assert alive == [], alive

    def test_close_waits_for_inflight_fetch(self, big, monkeypatch):
        """A fetch actually in flight at close() time completes (or is
        joined) before close returns — not abandoned holding a handle."""
        srv, url, payload = big
        srv.set_latency(0.05)  # every request answers late: fetches in flight
        try:
            fh = self._prefetch_open(url, None, monkeypatch)
            assert fh.read(1) == payload[:1]
            fh.close()  # blocks (bounded) on the in-flight block fetches
            alive = [t.name for t in threading.enumerate()
                     if t.name.startswith("tfr-prefetch") and t.is_alive()]
            assert alive == [], alive
        finally:
            srv.set_latency(0.0)


class TestStallGuardOverRealSockets:
    """The existing deadline/hedge machinery reading through real
    connections: a server that goes quiet mid-body is detected and
    survived on a live socket, not a wrapped file object."""

    def test_read_deadline_converts_server_stall(
        self, served, local_ids, clean_plan,
    ):
        srv, url, _, names = served
        plan = FaultPlan([
            FaultRule(op="http", kind="stall", path=names[0],
                      stall_ms=60_000, times=1),
        ])
        srv.set_plan(plan)
        METRICS.reset()
        try:
            got = read_ids(
                url, read_deadline_ms=200, retry_policy=_fast_retries(2),
            )
        finally:
            plan.release()
        assert got == local_ids
        assert METRICS.counter("read.deadline_misses") >= 1

    def test_hedge_wins_against_stalled_connection(
        self, served, local_ids, clean_plan,
    ):
        srv, url, _, names = served
        plan = FaultPlan([
            FaultRule(op="http", kind="stall", path=names[0] + "@0",
                      stall_ms=60_000, times=1),
        ])
        srv.set_plan(plan)
        METRICS.reset()
        try:
            got = read_ids(url, hedge_after_ms=150)
        finally:
            plan.release()
        assert got == local_ids
        assert METRICS.counter("read.hedges") >= 1
        assert METRICS.counter("read.hedge_wins") >= 1


class TestRemoteIntoCache:
    """remote -> CachePopulator -> mmap: the link is paid once per epoch."""

    def test_link_paid_once_per_epoch(self, served, local_ids, clean_plan,
                                      tmp_path):
        srv, url, _, _ = served
        cdir = str(tmp_path / "cache")
        METRICS.reset()
        ep1 = read_ids(url, cache="auto", cache_dir=cdir)
        assert ep1 == local_ids
        gets_after_populate = srv.file_get_count
        ep2 = read_ids(url, cache="auto", cache_dir=cdir)
        assert ep2 == local_ids
        assert METRICS.counter("cache.hits") >= N_SHARDS
        # epoch 2 issued ZERO file GETs: served from the local mmap cache
        # (dir-index GETs and HEADs are metadata, not the link being
        # re-paid for shard bytes)
        assert srv.file_get_count == gets_after_populate

    def test_faulted_populate_still_commits_valid_entries(
        self, served, local_ids, clean_plan, tmp_path,
    ):
        """A transient link fault DURING the populating epoch heals via
        retries and the committed entries still serve byte-identical
        rows."""
        srv, url, _, names = served
        cdir = str(tmp_path / "cache")
        plan = FaultPlan([
            FaultRule(op="http", kind="reset", path=names[1],
                      cap_bytes=64, times=1),
        ])
        srv.set_plan(plan)
        METRICS.reset()
        ep1 = read_ids(url, cache="auto", cache_dir=cdir,
                       retry_policy=_fast_retries(3))
        assert ep1 == local_ids and plan.ledger
        srv.set_plan(None)
        ep2 = read_ids(url, cache="auto", cache_dir=cdir)
        assert ep2 == local_ids
        assert METRICS.counter("cache.hits") >= N_SHARDS

    def test_kill9_mid_populate_then_resume_never_wrong(
        self, tmp_path,
    ):
        """Chaos acceptance: SIGKILL the consumer process mid-populate,
        then read again from the same cache dir — rows byte-identical,
        cache either valid or bypassed+repopulated, never wrong."""
        root = tmp_path / "killds"
        out = os.path.join(str(root), "ds")
        n = 3000
        for s in range(3):
            tfio.write(
                [[i, f"v{i}"] for i in range(s * n, (s + 1) * n)],
                SCHEMA, out, mode="append" if s else "overwrite",
            )
        local = read_ids(out, batch_size=256)
        cdir = str(tmp_path / "cache")
        with httpfs.serve_directory(str(root)) as srv:
            url = srv.url_for("ds")
            proc = subprocess.Popen(
                [sys.executable,
                 os.path.join(os.path.dirname(__file__),
                              "http_cache_worker.py"),
                 url, cdir, "--batch-size", "256"],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            line = proc.stdout.readline()  # first batch: populate underway
            assert line.startswith("batch"), (line, proc.stderr.read())
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait()
            # resume in-process against the SAME cache dir: whatever state
            # the kill left (partial staging, committed entries, nothing)
            # must yield ground-truth rows
            METRICS.reset()
            got = read_ids(url, batch_size=256, cache="auto", cache_dir=cdir)
            assert got == local, "post-kill rows differ from ground truth"
            # and a further epoch serves cache hits with identical rows
            METRICS.reset()
            again = read_ids(url, batch_size=256, cache="auto",
                             cache_dir=cdir)
            assert again == local
            assert METRICS.counter("cache.hits") >= 3


class TestChaosAcceptance:
    def test_mixed_hostile_epoch_byte_identical_and_replayable(
        self, served, local_ids, clean_plan,
    ):
        """THE acceptance leg: one epoch under a seeded plan mixing
        resets, stalls, truncations, and 503s completes byte-identical to
        local with zero corrupt rows, and the ledger is replayable."""
        srv, url, _, names = served
        spec = FaultPlan([
            FaultRule(op="http", kind="reset", path=names[0],
                      cap_bytes=200, times=1),
            FaultRule(op="http", kind="stall", path=names[0],
                      stall_ms=50, times=1),
            FaultRule(op="http", kind="truncated_body", path=names[1],
                      cap_bytes=150, times=1),
            FaultRule(op="http", kind="http_error", path=names[2],
                      status=503, retry_after_s=0.001, times=1),
            FaultRule(op="http", kind="http_error", path=names[2],
                      status=429, retry_after_s=0.001, ordinal=1, times=1),
        ], seed=42).to_json()
        ledgers = []
        for _ in range(2):
            plan = FaultPlan.from_json(spec)
            srv.set_plan(plan)
            METRICS.reset()
            # one shard after the other: a ledger replays only in one read order
            got = read_ids(url, retry_policy=_fast_retries(4), num_workers=1)
            assert got == local_ids, "hostile epoch rows differ from local"
            assert METRICS.counter("read.corrupt_records") == 0
            ledgers.append(plan.ledger_json())
        assert ledgers[0] == ledgers[1], "ledger not replayable"
        import json as _json

        fired = sorted(
            _json.loads(line)["kind"] for line in ledgers[0].splitlines()
        )
        assert fired == sorted([
            "reset", "stall", "truncated_body", "http_error", "http_error",
        ]), fired


class TestDoctorOverHttp:
    def test_doctor_scan_accepts_http_sources(self, served, clean_plan):
        _, url, _, names = served
        doc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), "..", "tools",
                          "tfrecord_doctor.py"),
             f"{url}/{names[0]}"],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
        import json as _json

        lines = [_json.loads(l) for l in doc.stdout.splitlines() if l.strip()]
        summary = [l for l in lines if l.get("event") == "summary"][0]
        assert summary["records"] == ROWS_PER_SHARD
        assert summary["corrupt_events"] == 0

    def test_doctor_scan_http_dataset_dir(self, served, clean_plan):
        _, url, _, _ = served
        doc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), "..", "tools",
                          "tfrecord_doctor.py"), url],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert doc.returncode == 0, (doc.returncode, doc.stdout, doc.stderr)
        import json as _json

        lines = [_json.loads(l) for l in doc.stdout.splitlines() if l.strip()]
        summaries = [l for l in lines if l.get("event") == "summary"]
        assert len(summaries) == N_SHARDS
        assert sum(s["records"] for s in summaries) == N_SHARDS * ROWS_PER_SHARD
