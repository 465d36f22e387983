"""Checkpoint persistence for dataset iterator state and train state.

The reference has no resumability beyond the ``_SUCCESS`` marker (SURVEY.md
§5 checkpoint/resume: ABSENT). Here the iterator's O(1) state (epoch, shard
position, record offset — io/dataset.py) persists as a small JSON file per
process, written atomically, so a training job can bundle it with its model
checkpoint (e.g. alongside an orbax step directory) and resume mid-epoch.

ISSUE 16 grows the module into the ASYNC SHARDED checkpoint layer — the
lever that retires the flight recorder's ``ckpt_bound`` verdict
(telemetry.training_verdict). Every writer here splits into two phases:

- **snapshot** (caller's thread, the only part the train loop blocks on):
  one ``jax.device_get`` of the pytree leaves into reusable host buffers
  plus the O(1) input-state/packer payload — ``ckpt.snapshot``;
- **commit** (ONE background thread): stage per-process shard files into a
  generation directory, fsync each, ``os.replace`` into place, and write
  the generation MANIFEST LAST — ``ckpt.commit``. A kill -9 at ANY point
  leaves the newest *complete* generation restorable.

Backpressure is bounded and observable: at most one commit is ever in
flight; the next ``save()`` waits on the previous commit (every blocked
save lands a ``ckpt.commit_wait`` record — never silently dropped) and
``wait()``/``close()`` drain. Commit failures re-raise on the next
``save()``/``wait()`` as ``CheckpointCommitError``.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import sys
import threading
import time
import zipfile
from typing import Callable, Optional

import numpy as np

from tpu_tfrecord.io.dataset import CheckpointableIterator, IteratorState

_FORMAT_VERSION = 1
# Version 2: the state carries ``window_emitted`` (mid-window position of a
# row-shuffled iterator). Semantically load-bearing — an old reader that
# dropped the field would resume at the window start and replay batches —
# so such states are WRITTEN as version 2, which old readers refuse cleanly.
_FORMAT_VERSION_WINDOWED = 2
_READABLE_VERSIONS = (1, 2)


def state_path(directory: str, process_index: Optional[int] = None) -> str:
    """Per-process state file ('_input_state.<pid>.json'): every host owns
    its own position, mirroring the per-host shard assignment."""
    if process_index is None:
        try:
            import jax

            process_index = jax.process_index()
        except Exception:  # graftlint: swallow(no distributed runtime: process 0)
            process_index = 0
    # "_"-prefixed like _SUCCESS: shard discovery treats it as metadata, so a
    # state file inside a dataset directory can never be read as a shard.
    return os.path.join(directory, f"_input_state.{process_index}.json")


def _extract_state(state_or_iterator) -> IteratorState:
    return (
        state_or_iterator.state()
        if isinstance(state_or_iterator, CheckpointableIterator)
        else state_or_iterator
    )


def _make_payload(state: IteratorState, step: Optional[int] = None) -> dict:
    version = (
        _FORMAT_VERSION_WINDOWED
        if getattr(state, "window_emitted", 0)
        else _FORMAT_VERSION
    )
    payload = {"version": version, "state": state.to_json()}
    if step is not None:
        payload["step"] = step
    return payload


def _check_version(payload: dict, where: str) -> None:
    if payload.get("version") not in _READABLE_VERSIONS:
        raise ValueError(
            f"unsupported input-state version {payload.get('version')} {where}"
        )


# ---------------------------------------------------------------------------
# Durability primitives (shared by every checkpoint writer in the tree)
# ---------------------------------------------------------------------------


class TornStateError(ValueError):
    """A state/checkpoint artifact exists but its bytes cannot be parsed —
    the signature of a torn write (a crash that outran fsync, a power
    loss surfacing a zero-length "committed" file) or foreign bytes. The
    loud, NAMED twin of a raw ``json.JSONDecodeError``: the message says
    which file and what to do about it."""


class CheckpointCommitError(RuntimeError):
    """A background checkpoint commit failed. Raised on the NEXT
    ``save()``/``wait()``/``close()`` so an async failure is never
    silent; ``__cause__`` carries the original exception."""


#: Deterministic kill-point seam for the crash-matrix tests
#: (tests/test_ckpt_chaos.py): when TFR_CKPT_CHAOS_STAGE names a stage the
#: writer is about to enter, the writer touches TFR_CKPT_CHAOS_MARK and
#: parks forever — the parent test sees the marker and lands its SIGKILL
#: at EXACTLY that point (snapshot / shard / pre_manifest / manifest /
#: state). Inert (two env reads) outside the chaos tests.
_CHAOS_STAGE_ENV = "TFR_CKPT_CHAOS_STAGE"
_CHAOS_MARK_ENV = "TFR_CKPT_CHAOS_MARK"
#: pass through the armed stage this many times before parking, so the
#: test can land the kill on generation N with N-1 already complete
_CHAOS_SKIP_ENV = "TFR_CKPT_CHAOS_SKIP"
_chaos_hits: dict = {}


def _chaos_point(stage: str) -> None:
    if os.environ.get(_CHAOS_STAGE_ENV) != stage:
        return
    _chaos_hits[stage] = _chaos_hits.get(stage, 0) + 1
    if _chaos_hits[stage] <= int(os.environ.get(_CHAOS_SKIP_ENV, "0")):
        return
    mark = os.environ.get(_CHAOS_MARK_ENV)
    if mark:
        tmp = f"{mark}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            fh.write(stage)
        os.replace(tmp, mark)
    while True:  # park here until the test's SIGKILL lands
        time.sleep(60)


def _fsync_dir(directory: str) -> None:
    """Best-effort fsync of a directory fd, making a just-landed rename
    durable against power loss (the file's bytes were fsynced before the
    rename; the directory entry needs its own flush on POSIX)."""
    try:
        fd = os.open(directory, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
    except OSError:  # graftlint: swallow(dirfd fsync is best-effort: some filesystems refuse O_RDONLY dir fds)
        pass


def durable_write(
    path: str,
    data: Optional[bytes] = None,
    write_fn: Optional[Callable] = None,
    chaos: Optional[str] = None,
) -> None:
    """The ONE stage-and-commit helper every checkpoint writer goes
    through: write ``data`` (or let ``write_fn(fh)`` write) to a
    pid-suffixed tmp twin, flush + fsync the FILE, ``os.replace`` into
    place, then best-effort fsync the directory — so a crash at any
    instant leaves either the old complete artifact or the new complete
    artifact, never a zero-length/torn stump. graftlint's atomic-write
    rule recognizes a call to this helper as the commit of a staged
    write (the manifest-last idiom)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as fh:
            if write_fn is not None:
                write_fn(fh)
            if data is not None:
                fh.write(data)
            fh.flush()
            os.fsync(fh.fileno())
        if chaos is not None:
            _chaos_point(chaos)
        os.replace(tmp, path)
        _fsync_dir(os.path.dirname(os.path.abspath(path)))
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


class FencedWriteError(OSError):
    """A ``durable_append`` found the file replaced underneath it (the
    inode changed): some other writer committed a NEW artifact at the
    same path — for the dispatcher journal, a promoted standby that
    bumped the generation. The append was NOT performed. OSError-shaped
    so existing journal-failure accounting treats it as a write failure,
    while callers that care (zombie-primary fencing) can tell it apart."""


def durable_append(
    path: str, data: bytes, expect_ino: Optional[int] = None
) -> int:
    """Append one record to ``path`` durably: open in append mode, write,
    flush, fsync — so a committed record survives a host crash, and a
    crash mid-append tears at most the UNCOMMITTED tail (readers of
    append-mode journals must replay to the newest consistent prefix;
    the dispatcher journal's line framing makes the torn tail
    detectable). Returns the file's inode.

    ``expect_ino`` is the fencing seam: when given and the opened file's
    inode differs, the file was atomically replaced by another writer
    (``durable_write``/``os.replace`` gives the path a fresh inode) and
    ``FencedWriteError`` is raised BEFORE any byte lands — a fenced
    writer can never interleave stale records into its successor's
    journal. graftlint's atomic-write rule recognizes this append+fsync
    shape (appends never tear previously committed bytes)."""
    with open(path, "ab") as fh:
        st = os.fstat(fh.fileno())
        if expect_ino is not None and st.st_ino != expect_ino:
            raise FencedWriteError(
                f"{path} was replaced underneath this writer "
                f"(inode {st.st_ino} != expected {expect_ino})"
            )
        fh.write(data)
        fh.flush()
        os.fsync(fh.fileno())
        return st.st_ino


def save_state(
    directory: str,
    state_or_iterator,
    process_index: Optional[int] = None,
    step: Optional[int] = None,
) -> str:
    """Atomically AND durably persist iterator state; returns the file
    path. The write goes through ``durable_write`` (fsync before rename),
    so a power-loss-shaped crash can never surface a zero-length
    "committed" state file."""
    state = _extract_state(state_or_iterator)
    os.makedirs(directory, exist_ok=True)
    path = state_path(directory, process_index)
    payload = _make_payload(state, step)
    durable_write(path, json.dumps(payload).encode("utf-8"), chaos="state")
    return path


def load_state(
    directory: str, process_index: Optional[int] = None
) -> Optional[IteratorState]:
    """Load this process's saved state; None if no checkpoint exists.
    An existing-but-unparseable file raises ``TornStateError`` (loud and
    named), never a raw ``json.JSONDecodeError``."""
    path = state_path(directory, process_index)
    if not os.path.exists(path):
        return None
    try:
        with open(path) as fh:
            payload = json.load(fh)
    except (ValueError, UnicodeDecodeError) as e:
        raise TornStateError(
            f"input-state file {path} exists but cannot be parsed "
            f"({type(e).__name__}: {e}) — a torn write from a crash that "
            "outran fsync, or foreign bytes. Delete the file to start "
            "fresh, or restore it alongside its model checkpoint."
        ) from e
    _check_version(payload, f"at {path}")
    return IteratorState.from_json(payload["state"])


# ---------------------------------------------------------------------------
# The background commit lane (shared by AsyncCheckpointer / AsyncStateSaver)
# ---------------------------------------------------------------------------


class _Commit:
    """One in-flight commit: the closure, its completion event, and the
    error slot the worker fills on failure."""

    __slots__ = ("step", "fn", "done", "error")

    def __init__(self, step: int, fn: Callable[[], None]):
        self.step = step
        self.fn = fn
        self.done = threading.Event()
        self.error: Optional[BaseException] = None


class _CommitWorker:
    """ONE daemon commit thread with at-most-one-in-flight backpressure.

    ``reserve()`` (caller's thread) waits out the previous commit — every
    blocked save lands a ``ckpt.commit_wait`` record, so backpressure is
    bounded AND observable — and re-raises any prior failure. ``submit``
    enqueues the next commit; the worker times it into the ``ckpt.commit``
    stage and counts the inflight gauge down. ``run_inline`` is the SYNC
    twin: same throttle, same metrics, caller's thread — what
    ``sync=True`` checkpointers run.

    ``commit_delay_s`` is the seeded slow-disk seam (env
    ``TFR_CKPT_COMMIT_THROTTLE_S`` when unset): the verify chaos
    legs throttle the commit path with it to force the sync twin into a
    ``ckpt_bound`` verdict while the async path stays compute_bound.
    """

    def __init__(self, metrics=None, commit_delay_s: Optional[float] = None):
        if metrics is None:
            from tpu_tfrecord.metrics import METRICS as metrics  # noqa: N813
        self.metrics = metrics
        if commit_delay_s is None:
            env = os.environ.get("TFR_CKPT_COMMIT_THROTTLE_S")
            commit_delay_s = float(env) if env else 0.0
        self.commit_delay_s = float(commit_delay_s)
        self._queue: "queue.Queue[Optional[_Commit]]" = queue.Queue(maxsize=1)
        self._last: Optional[_Commit] = None
        self._thread: Optional[threading.Thread] = None

    # -- caller's thread -----------------------------------------------------

    def reserve(self) -> None:
        """Block until the previous commit (if any) finishes — counted as
        ``ckpt.commit_wait`` — and re-raise its failure loudly."""
        job = self._last
        if job is not None and not job.done.is_set():
            t0 = time.perf_counter()
            job.done.wait()
            waited = time.perf_counter() - t0
            self.metrics.add(
                "ckpt.commit_wait", records=1, seconds=waited, latency=waited
            )
        self.wait()

    def submit(self, step: int, fn: Callable[[], None]) -> None:
        """Hand one commit to the background thread. Callers must
        ``reserve()`` first (the snapshot buffers are reused, so the
        previous commit must have released them)."""
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name="ckpt-commit", daemon=True
            )
            self._thread.start()
        job = _Commit(step, fn)
        self._last = job
        self.metrics.gauge("ckpt.inflight", 1)
        self._queue.put(job)

    def run_inline(self, step: int, fn: Callable[[], None]) -> None:
        """The sync twin: execute the commit on the CALLER's thread under
        the same throttle and the same ``ckpt.commit`` stage."""
        self._execute(_Commit(step, fn))
        self.wait()

    def wait(self) -> None:
        """Drain the in-flight commit; re-raise its failure as
        ``CheckpointCommitError``."""
        job = self._last
        if job is None:
            return
        job.done.wait()
        self._last = None
        if job.error is not None:
            raise CheckpointCommitError(
                f"background checkpoint commit of step {job.step} failed: "
                f"{job.error!r}"
            ) from job.error

    def close(self) -> None:
        """Drain, then stop the worker thread."""
        try:
            self.wait()
        finally:
            if self._thread is not None and self._thread.is_alive():
                self._queue.put(None)
                self._thread.join(timeout=30)
            self._thread = None

    # -- worker thread -------------------------------------------------------

    def _execute(self, job: _Commit) -> None:
        t0 = time.perf_counter()
        try:
            if self.commit_delay_s:
                time.sleep(self.commit_delay_s)
            job.fn()
        except BaseException as e:  # graftlint: swallow(stored on the job; wait()/reserve() re-raise it as CheckpointCommitError)
            job.error = e
        finally:
            dt = time.perf_counter() - t0
            self.metrics.add("ckpt.commit", records=1, seconds=dt, latency=dt)
            self.metrics.gauge("ckpt.inflight", 0)
            job.done.set()

    def _run(self) -> None:
        while True:
            job = self._queue.get()
            if job is None:
                return
            self._execute(job)


# ---------------------------------------------------------------------------
# AsyncCheckpointer: sharded generations, manifest-last, background commit
# ---------------------------------------------------------------------------

_GEN_PREFIX = "gen-"
_MANIFEST_VERSION = 1


class AsyncCheckpointer:
    """Model state + O(1) payload, saved as SHARDED GENERATIONS with a
    manifest-last commit on a background thread (ISSUE 16 / ROADMAP #4).

    ``save(step, state, payload)`` splits into:

    - **snapshot** (caller's thread — all the train loop ever blocks on,
      the ``ckpt.snapshot`` stage): one ``jax.device_get`` of the pytree
      leaves copied into reusable host buffers, plus the JSON payload;
    - **commit** (the single background thread, ``ckpt.commit``): stage
      this process's shard npz into ``gen-<step>/`` (tmp + fsync +
      ``os.replace``), then — process 0, after the optional multihost
      ``barrier`` — write ``MANIFEST.json`` LAST through the same
      fsync-then-rename helper. A kill -9 at ANY point leaves the newest
      generation either fully committed (manifest present, all shards
      landed first) or invisible to ``restore``, which falls back to the
      newest COMPLETE generation.

    Layout (one shard per process, keyed like ``state_path``)::

        directory/
          gen-00000008/
            shard-00000.npz     # leaves + json meta, fsynced, renamed
            MANIFEST.json       # committed last => generation complete
          gen-00000016/ ...

    Backpressure: at most one commit in flight; the next ``save()`` waits
    on the previous commit (``ckpt.commit_wait``, never silently
    dropped); ``wait()``/``close()`` drain. Commits also sweep retired
    generations beyond ``keep`` and DEAD generations (shards without a
    manifest, older than the newest manifest — the orphans an interrupted
    commit leaves), extending the writer's ``_JOB_META``-style staging
    hygiene; each removal counts ``ckpt.generations_swept``.

    ``sync=True`` is the measurement twin: identical bytes and layout,
    commit executed inline on the caller's thread (the tests' reference
    for the async path).

    Scope: single-controller and one-shard-per-process multihost jobs.
    On a multihost mesh pass ``barrier`` (e.g. a
    ``multihost_utils.sync_global_devices`` wrapper) so process 0 writes
    the manifest only after every process committed its shard.
    """

    MANIFEST = "MANIFEST.json"

    def __init__(
        self,
        directory: str,
        *,
        keep: Optional[int] = 2,
        process_index: Optional[int] = None,
        process_count: Optional[int] = None,
        sync: bool = False,
        commit_delay_s: Optional[float] = None,
        barrier: Optional[Callable[[], None]] = None,
        metrics=None,
    ):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        if process_index is None or process_count is None:
            try:
                import jax

                if process_index is None:
                    process_index = jax.process_index()
                if process_count is None:
                    process_count = jax.process_count()
            except Exception:  # graftlint: swallow(no distributed runtime: single process)
                process_index = process_index or 0
                process_count = process_count or 1
        self.process_index = int(process_index)
        self.process_count = int(process_count)
        self.keep = keep
        self.sync = bool(sync)
        self._barrier = barrier
        if metrics is None:
            from tpu_tfrecord.metrics import METRICS as metrics  # noqa: N813
        self.metrics = metrics
        self._worker = _CommitWorker(
            metrics=metrics, commit_delay_s=commit_delay_s
        )
        self._bufs: Optional[list] = None

    # -- layout --------------------------------------------------------------

    def _gen_dir(self, step: int) -> str:
        return os.path.join(self.directory, f"{_GEN_PREFIX}{step:08d}")

    def _shard_name(self, process_index: int) -> str:
        return f"shard-{process_index:05d}.npz"

    # -- save ----------------------------------------------------------------

    def save(self, step: int, state_pytree, payload: Optional[dict] = None) -> None:
        """Snapshot ``state_pytree`` + ``payload`` for ``step`` and hand
        the commit to the background thread (inline when ``sync``)."""
        import jax

        self._worker.reserve()  # buffers are reused: previous commit first
        t0 = time.perf_counter()
        leaves, _ = jax.tree.flatten(state_pytree)
        host = jax.device_get(leaves)  # ONE transfer for the whole tree
        host = [np.asarray(h) for h in host]
        if self._bufs is None or len(self._bufs) != len(host) or any(
            b.shape != h.shape or b.dtype != h.dtype
            for b, h in zip(self._bufs, host)
        ):
            self._bufs = [np.array(h, copy=True) for h in host]
        else:
            for b, h in zip(self._bufs, host):
                np.copyto(b, h)
        meta = json.dumps(
            {"step": int(step), "payload": payload or {}}
        ).encode("utf-8")
        _chaos_point("snapshot")
        dt = time.perf_counter() - t0
        self.metrics.add("ckpt.snapshot", records=1, seconds=dt, latency=dt)
        bufs = self._bufs

        def commit() -> None:
            self._commit(int(step), bufs, meta)

        if self.sync:
            self._worker.run_inline(int(step), commit)
        else:
            self._worker.submit(int(step), commit)

    def _commit(self, step: int, leaves, meta: bytes) -> None:
        gen = self._gen_dir(step)
        os.makedirs(gen, exist_ok=True)
        for name in os.listdir(gen):
            # a previous life of this generation (killed mid-stage, then
            # re-reached after resume) may have left tmp orphans behind
            if ".tmp." in name:
                try:
                    os.remove(os.path.join(gen, name))
                except OSError:
                    pass
        shard = os.path.join(gen, self._shard_name(self.process_index))

        def write(fh) -> None:
            np.savez(
                fh,
                meta=np.frombuffer(meta, np.uint8),
                **{f"leaf_{i}": a for i, a in enumerate(leaves)},
            )

        durable_write(shard, write_fn=write, chaos="shard")
        self.metrics.count("ckpt.bytes_written", os.path.getsize(shard))
        _chaos_point("pre_manifest")
        if self._barrier is not None:
            self._barrier()  # every process's shard must land first
        if self.process_index == 0:
            manifest = {
                "version": _MANIFEST_VERSION,
                "step": step,
                "process_count": self.process_count,
                "shards": [
                    self._shard_name(i) for i in range(self.process_count)
                ],
            }
            durable_write(
                os.path.join(gen, self.MANIFEST),
                json.dumps(manifest).encode("utf-8"),
                chaos="manifest",
            )
            self._sweep(step)

    def _sweep(self, newest_step: int) -> None:
        """Generation hygiene, run after each manifest commit: retire
        complete generations beyond ``keep`` and remove DEAD ones —
        shards without a manifest older than the generation just
        committed, i.e. the orphans of an interrupted commit."""
        complete, dead = [], []
        for name in os.listdir(self.directory):
            if not name.startswith(_GEN_PREFIX):
                continue
            try:
                step = int(name[len(_GEN_PREFIX):])
            except ValueError:
                continue
            if os.path.exists(
                os.path.join(self.directory, name, self.MANIFEST)
            ):
                complete.append(step)
            elif step < newest_step:
                dead.append(step)
        complete.sort()
        retired = complete[: -self.keep] if self.keep else []
        for step in retired + dead:
            shutil.rmtree(self._gen_dir(step), ignore_errors=True)
            self.metrics.count("ckpt.generations_swept")

    # -- restore -------------------------------------------------------------

    def _complete_generations(self):
        """Ascending steps of every COMPLETE generation: manifest parses
        and every shard it names exists. Torn/garbage manifests read as
        incomplete — that is the recovery path, not an error."""
        out = []
        if not os.path.isdir(self.directory):
            return out
        for name in os.listdir(self.directory):
            if not name.startswith(_GEN_PREFIX):
                continue
            try:
                step = int(name[len(_GEN_PREFIX):])
            except ValueError:
                continue
            gen = os.path.join(self.directory, name)
            try:
                with open(os.path.join(gen, self.MANIFEST)) as fh:
                    manifest = json.load(fh)
            except (OSError, ValueError, UnicodeDecodeError):
                continue
            shards = manifest.get("shards") or []
            if shards and all(
                os.path.exists(os.path.join(gen, s)) for s in shards
            ):
                out.append(step)
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self._complete_generations()
        return steps[-1] if steps else None

    def restore(self, template_pytree):
        """(step, pytree, payload) from the newest COMPLETE generation, or
        (None, template, None) when none exists. A generation whose shard
        bytes fail to load (impossible under the fsync-before-manifest
        contract, but disks lie) falls back one generation, loudly."""
        import jax

        for step in reversed(self._complete_generations()):
            shard = os.path.join(
                self._gen_dir(step), self._shard_name(self.process_index)
            )
            try:
                with np.load(shard) as z:
                    meta = json.loads(z["meta"].tobytes().decode("utf-8"))
                    leaves = [z[f"leaf_{i}"] for i in range(len(z.files) - 1)]
            except (OSError, ValueError, KeyError, zipfile.BadZipFile) as e:
                print(
                    f"checkpoint generation {step} at {shard} unreadable "
                    f"({type(e).__name__}: {e}); falling back a generation",
                    file=sys.stderr,
                )
                continue
            _, treedef = jax.tree.flatten(template_pytree)
            state = jax.tree.unflatten(treedef, leaves)
            return meta["step"], state, meta.get("payload") or {}
        return None, template_pytree, None

    # -- lifecycle -----------------------------------------------------------

    def wait(self) -> None:
        """Drain the in-flight commit (re-raising its failure)."""
        self._worker.wait()

    def clear(self) -> None:
        """Remove every generation (the epoch-budget-exhausted path: the
        next run should start a fresh pass, not resume into an empty
        stream). Drains first so a commit can't resurrect one."""
        self._worker.wait()
        for name in os.listdir(self.directory):
            if name.startswith(_GEN_PREFIX):
                shutil.rmtree(
                    os.path.join(self.directory, name), ignore_errors=True
                )

    def close(self) -> None:
        self._worker.close()

    def __enter__(self) -> "AsyncCheckpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class AsyncStateSaver:
    """``save_state``'s async twin for the O(1) input-state JSON.

    State extraction — the only part that must observe the LIVE iterator
    at the save point — runs on the caller's thread (``ckpt.snapshot``);
    the fsync-then-rename write runs on the background commit thread
    (``ckpt.commit``), under the same at-most-one-in-flight /
    ``ckpt.commit_wait`` contract as ``AsyncCheckpointer``. Same file,
    same bytes as ``save_state`` — only the disk latency moves off the
    step path, so ``StepPhases``' ckpt phase measures microseconds."""

    def __init__(
        self,
        directory: str,
        process_index: Optional[int] = None,
        *,
        sync: bool = False,
        commit_delay_s: Optional[float] = None,
        metrics=None,
    ):
        self.directory = directory
        self.process_index = process_index
        self.sync = bool(sync)
        if metrics is None:
            from tpu_tfrecord.metrics import METRICS as metrics  # noqa: N813
        self.metrics = metrics
        self._worker = _CommitWorker(
            metrics=metrics, commit_delay_s=commit_delay_s
        )

    def save(self, state_or_iterator, step: Optional[int] = None) -> str:
        """Snapshot the iterator position now; persist it in the
        background. Returns the (eventual) state-file path."""
        self._worker.reserve()
        t0 = time.perf_counter()
        payload = _make_payload(_extract_state(state_or_iterator), step)
        data = json.dumps(payload).encode("utf-8")
        path = state_path(self.directory, self.process_index)
        dt = time.perf_counter() - t0
        self.metrics.add("ckpt.snapshot", records=1, seconds=dt, latency=dt)

        def commit() -> None:
            os.makedirs(self.directory, exist_ok=True)
            durable_write(path, data, chaos="state")
            self.metrics.count("ckpt.bytes_written", len(data))

        if self.sync:
            self._worker.run_inline(step or 0, commit)
        else:
            self._worker.submit(step or 0, commit)
        return path

    def wait(self) -> None:
        self._worker.wait()

    def close(self) -> None:
        self._worker.close()

    def __enter__(self) -> "AsyncStateSaver":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class TrainCheckpointer:
    """Model state + input position, saved ATOMICALLY together per step.

    The failure mode this removes: params restored from step N while the
    input pipeline resumes from wherever its own file last said — a
    silently skewed data order. Both items go into ONE orbax Composite
    checkpoint (``state`` pytree + ``input_state`` json), so orbax's own
    finalization makes the pairing atomic: a crash mid-save can never
    produce a restorable step with params but no matching input position.
    The iterator-state fingerprint still guards dataset identity on resume.

    Scope: single-controller jobs (the examples' shape). Multi-host
    pipelines, where every process owns a distinct input position, keep
    using per-process ``save_state``/``load_state`` alongside their model
    checkpointer.

    Usage::

        ckpt = TrainCheckpointer("/ckpts", max_to_keep=3)
        ...
        ckpt.save(step, {"params": params, "opt_state": opt_state}, it)
        ...
        step, state, resume = ckpt.restore(
            {"params": params, "opt_state": opt_state})
        with ds.batches(resume) as it: ...
    """

    def __init__(
        self,
        directory: str,
        max_to_keep: Optional[int] = None,
        *,
        async_save: bool = True,
        metrics=None,
    ):
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        if metrics is None:
            from tpu_tfrecord.metrics import METRICS as metrics  # noqa: N813
        self.metrics = metrics
        self.async_save = bool(async_save)
        try:
            options = ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=self.async_save,
            )
        except TypeError:  # older orbax: sync-only manager
            self.async_save = False
            options = ocp.CheckpointManagerOptions(max_to_keep=max_to_keep)
        self._mgr = ocp.CheckpointManager(self.directory, options=options)

    def save(self, step: int, state_pytree, state_or_iterator) -> None:
        """Persist the model pytree and the input position for ``step``.

        With ``async_save`` (the default) orbax finalizes the checkpoint
        on its own background thread under the same contract as
        ``AsyncCheckpointer``: at most one save in flight (blocking here
        counts as ``ckpt.commit_wait``), the caller only pays for the
        device snapshot (``ckpt.snapshot``), and ``close()`` drains."""
        if self.async_save and getattr(self._mgr, "is_saving_in_progress", None):
            if self._mgr.is_saving_in_progress():
                t0 = time.perf_counter()
                self._mgr.wait_until_finished()
                waited = time.perf_counter() - t0
                self.metrics.add(
                    "ckpt.commit_wait", records=1, seconds=waited, latency=waited
                )
        payload = _make_payload(_extract_state(state_or_iterator), step)
        t0 = time.perf_counter()
        self._mgr.save(
            step,
            args=self._ocp.args.Composite(
                state=self._ocp.args.StandardSave(state_pytree),
                input_state=self._ocp.args.JsonSave(payload),
            ),
            force=True,
        )
        dt = time.perf_counter() - t0
        self.metrics.add("ckpt.snapshot", records=1, seconds=dt, latency=dt)

    def wait(self) -> None:
        """Drain any in-flight background save."""
        self._mgr.wait_until_finished()

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def restore(self, template_pytree):
        """(step, pytree, IteratorState) for the latest checkpoint, or
        (None, template, None) when none exists."""
        step = self._mgr.latest_step()
        if step is None:
            return None, template_pytree, None
        restored = self._mgr.restore(
            step,
            args=self._ocp.args.Composite(
                state=self._ocp.args.StandardRestore(template_pytree),
                input_state=self._ocp.args.JsonRestore(),
            ),
        )
        payload = restored["input_state"]
        _check_version(payload, f"in checkpoint step {step}")
        return step, restored["state"], IteratorState.from_json(payload["state"])

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._mgr.close()
