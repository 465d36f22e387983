"""Multi-host coordination over jax.distributed.

The reference's distributed backend is Spark's: driver->executor broadcast of
the job conf + RDD aggregate tree-merge for schema inference (SURVEY.md §2
parallelism table, §5). The TPU-native equivalents:

- process coordination: ``jax.distributed.initialize`` (DCN); collectives on
  data ride ICI only inside jit-compiled computations.
- conf shipping: TFRecordOptions is a plain picklable value (options.py); no
  broadcast machinery is needed because every host derives identical state
  deterministically (same paths -> same sorted shard list -> same
  assignment).
- schema-inference merge: each host computes a partial type map over ITS
  shards (the seqOp of TensorFlowInferSchema.scala:40-43), then the JSON-coded
  partials are allgathered over the mesh and every host applies the same
  deterministic combOp merge — no host-0 special case, no extra broadcast.
"""

from __future__ import annotations

import json
import os
from typing import List, Optional

import jax
import numpy as np

from tpu_tfrecord.infer import TypeMap, merge_type_maps, type_map_to_schema
from tpu_tfrecord.schema import StructType, data_type_from_json


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Initialize multi-host JAX if needed; safe no-op when single-process."""
    if num_processes in (None, 1) and coordinator_address is None:
        return
    # env-only check: this must happen before the backend client exists,
    # and jax.default_backend() would create it
    if os.environ.get("JAX_PLATFORMS", "").strip().lower().startswith("cpu"):
        # CPU fleets need an explicit cross-process collectives impl:
        # without it, a computation spanning processes dies with
        # "Multiprocess computations aren't implemented on the CPU
        # backend" the moment no process holds a whole replica (e.g. the
        # 2-process x 1-device dryrun). Must be set BEFORE the backend
        # client is created; harmless when already initialized.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def _encode_type_map(type_map: TypeMap) -> bytes:
    obj = {
        name: (None if dtype is None else dtype.to_json())
        for name, dtype in type_map.items()
    }
    return json.dumps(obj, sort_keys=True).encode("utf-8")


def _decode_type_map(data: bytes) -> TypeMap:
    obj = json.loads(data.decode("utf-8"))
    return {
        name: (None if t is None else data_type_from_json(t))
        for name, t in obj.items()
    }


def allgather_bytes(payload: bytes) -> List[bytes]:
    """Allgather a variable-length byte string across processes.

    Two phases over jax.experimental.multihost_utils.process_allgather:
    lengths first (so every host can size the padded buffer), then the padded
    payload bytes. Single-process: identity.
    """
    if jax.process_count() == 1:
        return [payload]
    from jax.experimental import multihost_utils

    lengths = multihost_utils.process_allgather(
        np.asarray([len(payload)], dtype=np.int32)
    ).reshape(-1)
    max_len = int(lengths.max())
    padded = np.zeros(max_len, dtype=np.uint8)
    padded[: len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    gathered = multihost_utils.process_allgather(padded)
    gathered = np.asarray(gathered).reshape(jax.process_count(), max_len)
    return [bytes(gathered[i, : int(lengths[i])].tobytes()) for i in range(len(lengths))]


class DistributedInferenceError(RuntimeError):
    """One or more hosts' local inference seqOp failed; raised on EVERY
    host after the allgather completes, naming the failed processes."""


def merge_schema_across_hosts(
    local_type_map: TypeMap, local_error: Optional[str] = None
) -> StructType:
    """Distributed schema inference: allgather per-host partial type maps and
    fold them with the same combOp on every host (deterministic order ->
    identical result everywhere). The TPU-native analog of the reference's
    RDD.aggregate combOp tree-merge (TensorFlowInferSchema.scala:40-43).

    ``local_error``: if this host's local fold failed, pass the error string
    INSTEAD of raising before the collective — a pre-collective raise on one
    host leaves every peer blocked in the allgather forever. The error rides
    the gather in the map's place and every host raises the same
    DistributedInferenceError after the collective completes (the analog of
    Spark failing the job when one aggregate task fails)."""
    payload = (
        b"E" + local_error.encode("utf-8", "replace")
        if local_error is not None
        else b"M" + _encode_type_map(local_type_map)
    )
    gathered = allgather_bytes(payload)
    errors = [
        (i, p[1:].decode("utf-8", "replace"))
        for i, p in enumerate(gathered)
        if p[:1] == b"E"
    ]
    if errors:
        detail = "; ".join(f"process {i}: {msg}" for i, msg in errors)
        raise DistributedInferenceError(
            f"schema inference failed on {len(errors)} process(es): {detail}"
        )
    partials = [_decode_type_map(p[1:]) for p in gathered]
    merged: TypeMap = {}
    for partial in partials:
        merged = merge_type_maps(merged, partial)
    return type_map_to_schema(merged)


def finalize_distributed_write(output_path: str) -> None:
    """Multi-host write commit: every host calls this after its own
    DatasetWriter job committed its shards (each host writes with
    ``task_id=jax.process_index()`` so part files never collide). All hosts
    barrier, then host 0 alone writes the dataset-level ``_SUCCESS`` marker —
    a reader seeing the marker is guaranteed every host's shards are in
    place (the analog of Spark's driver-side job commit)."""
    multi = jax.process_count() > 1
    if multi:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(f"tfr_write_commit:{output_path}")
    if jax.process_index() == 0:
        from tpu_tfrecord.io.paths import write_success_marker

        write_success_marker(output_path)
    if multi:
        # second barrier: when this returns on ANY host, the marker exists
        # (on host 0's filesystem) — the postcondition downstream gating
        # code relies on
        multihost_utils.sync_global_devices(f"tfr_write_done:{output_path}")


def barrier(name: str) -> None:
    """Cross-process barrier (no-op single-process). Used e.g. to publish a
    dataset written by one host before the others read it."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(f"tfr_barrier:{name}")


def adopt_shared_trace_context(role: str = "worker"):
    """Give every process in this multihost run ONE trace id (process 0's),
    adopted onto the process-global span recorder — so per-host Chrome
    traces, pulse lines, and telemetry spool snapshots all correlate under
    a single id and ``telemetry.merge_chrome_traces`` fuses them into one
    labeled timeline. Rides the same allgather as schema inference (each
    host contributes its local context; everyone deterministically adopts
    index 0's ids). Non-zero processes record process 0's root span as
    their parent; every process keeps its own span id/host/pid. Returns
    the adopted TraceContext. Single-process: just adopts the local
    context with ``role``."""
    import dataclasses

    from tpu_tfrecord import telemetry

    local = telemetry.current_context()
    gathered = allgather_bytes(
        json.dumps(local.to_json(), sort_keys=True).encode("utf-8")
    )
    root = telemetry.TraceContext.from_json(
        json.loads(gathered[0].decode("utf-8"))
    )
    ctx = dataclasses.replace(
        local,
        trace_id=root.trace_id,
        parent_span_id=(
            None if jax.process_index() == 0 else root.span_id
        ),
        role=role,
    )
    return telemetry.adopt(ctx)


def shared_service_address(addr: str) -> str:
    """Validate that every host of a multihost run points its consumers at
    the SAME data-service dispatcher before any bytes flow (rides the
    existing allgather). Two hosts talking to two dispatchers would each
    get self-consistent but differently-leased epochs — the classic
    silently-diverged-fleet failure this module's consistency checks
    exist for. Returns ``addr`` so call sites can inline it:
    ``options = {..., "service": shared_service_address(addr)}``."""
    assert_same_across_hosts(
        str(addr).encode("utf-8"), "data-service dispatcher address"
    )
    return str(addr)


def assert_same_across_hosts(value: bytes, what: str = "value") -> None:
    """Cheap cross-host consistency check (e.g. schema JSON, shard-list
    digest) — catches divergent host state before it corrupts a run."""
    gathered = allgather_bytes(value)
    if any(g != value for g in gathered):
        raise RuntimeError(f"{what} differs across hosts")
