"""Profiling hooks: the program's own names on the profiler's trace, and
a host-clock duty-cycle estimate.

The reference has no observability of its own (SURVEY.md §5: tracing ABSENT
— it rides on Spark's UI). Here the input pipeline is the product, so it can
explain itself:

- ``trace(name, **args)``: a ``jax.profiler.TraceAnnotation`` around a
  host-side region, so it lands in a profiler capture on the same clock as
  the device's operations (a shared no-op when jax/profiler is
  unavailable). The flight recorder (tpu_tfrecord.telemetry) rides next to
  these on the host's clock: the operator's view, not read on the chip.
- ``put_or_wait`` / ``get_or_wait``: a bounded queue's hand-off, with the
  time a thread sat blocked on it under one span.
- ``ANNOTATIONS``: every ``tfr:*`` host span and ``tfr.*`` device scope
  (``jax.named_scope`` in ``models/dlrm.py``, ``models/lm.py``,
  ``models/moe.py``) the package emits — the one
  place the names live; ``benchmark/layer_metrics`` and PERF.md quote them.
- ``DutyCycle``: estimates the BASELINE.md north-star secondary metric — the
  fraction of wall time the device spends computing vs waiting on input —
  from step/wait timestamps recorded in the training loop.
"""

from __future__ import annotations

import contextlib
import queue
import time
from typing import Optional

#: name -> what it covers. ``tfr:`` names are host spans (one per batch or
#: rarer, never per record); ``tfr.`` names are scopes inside the jitted
#: programs (DLRM's, the pattern LM's), found in the ``op_name`` of the
#: device's operations.
#: ``<queue>`` is ``batch`` (the dataset's prefetch queue), ``host``
#: (HostPrefetcher's) or ``device`` (DeviceIterator's transfer thread's).
ANNOTATIONS = {
    "tfr:open": "opening a shard",
    "tfr:cache": "serving a chunk from the columnar cache",
    "tfr:decode": "frame scan + CRC + decode + hash of one chunk; rows, bytes",
    "tfr:pack": "host_batch_from_columnar or pack_mixed on one batch; rows, bytes out",
    "tfr:pack_tokens": "TokenPacker (bin modes) placing one reader batch's documents; docs in, "
                       "rows and tokens out",
    "tfr:h2d": "make_global_batch: the dispatch of one batch's copy; rows, bytes",
    "tfr:h2d_land": "the transfer thread's wait for that copy to land",
    "tfr:blocked.batch": "the decode thread's put waited on a full prefetch queue",
    "tfr:starved.batch": "the dataset's consumer found its prefetch queue empty",
    "tfr:blocked.host": "HostPrefetcher's thread waited on its full queue",
    "tfr:starved.host": "HostPrefetcher's consumer found its queue empty",
    "tfr:blocked.device": "the transfer thread waited on its full queue",
    "tfr:starved.device": "DeviceIterator's consumer found its queue empty",
    "tfr.write.encode": "writer: host span (named before the ':' convention) around encode",
    "tfr.write.compress": "writer: host span around compress",
    "tfr.write.io": "writer: host span around the file write",
    "tfr.write.commit": "writer: host span around the commit",
    "tfr.table_cast": "no program opens it since PR 25; kept because the benchmark's recorded "
                      "trace (benchmark/tests/test_trace_scope.py) holds its scopes to this table",
    "tfr.gather": "the embedding rows gathered from the table, rounded to the activation dtype",
    "tfr.bottom_mlp": "bottom MLP (backward ops carry it inside transpose(jvp(..)))",
    "tfr.interaction": "feature interaction",
    "tfr.top_mlp": "top MLP to the logits",
    "tfr.dense_update": "sparse_train_step: optimizer update of the MLPs",
    "tfr.dedup_sort": "sparse_train_step: keys sorted, each run's key sorted to its slot, row gradients reordered",
    "tfr.segment_sum": "sparse_train_step: duplicate keys' gradients summed, each run's sum at its slot",
    "tfr.accum_update": "sparse_train_step: AdaGrad accumulator scatter over all slots; its "
                        "read-back and rsqrt per block of slots, inside the loop over the "
                        "blocks that hold runs",
    "tfr.table_scatter": "sparse_train_step: the row updates scattered into the table, per block "
                         "of slots, inside the loop over the blocks that hold runs",
    "tfr.embed": "pattern LM (models.lm.score): the token rows gathered from the embedding",
    "tfr.gqa": "pattern LM: the softmax layer without a window (norm, projections, Q/K norm, the "
               "attention call: on a TPU attention.flash_attention_widths, the 8 K/V heads read as they lie and "
               "block pairs that share no document skipped, blockwise attention elsewhere; gate, out, branch norm)",
    "tfr.swa_proj": "pattern LM: a sliding-window layer's norm, five projections, Q/K norm, rotary "
                    "turns, gate, out, branch norm",
    "tfr.swa_attn": "pattern LM: a sliding-window layer's attention call alone (the band of block "
                    "pairs inside the window, inside each document)",
    "tfr.kda_proj": "pattern LM: the delta-rule layer's projections, decay, beta, gates, norm, out",
    "tfr.kda_conv": "pattern LM: the short convolution, SiLU and unit norm of q, k, v",
    "tfr.kda_scan": "pattern LM: the chunked gated delta rule (models.linear_attn)",
    "tfr.gdn_proj": "pattern LM: the gated delta-net layer's norm on its way in (where the pattern has "
                    "one), six projections (q, k, v, the output gate z, the decay's and beta's one a "
                    "head), decay, beta in (0, 1) or (0, 2), head norm, gate (2 sigmoid(z) or silu(z)), "
                    "out, branch norm",
    "tfr.gdn_conv": "pattern LM: the gated delta-net layer's short convolution, SiLU and unit norm "
                    "of q and k at their own heads and of v (empty on a TPU: the kernel prepares them)",
    "tfr.gdn_scan": "pattern LM: the gated delta-net layer's recurrence call alone (models.linear_attn "
                    "under one decay a head and token, key heads shared by their value heads, a state "
                    "[d_k, d_v] that need not be square)",
    "tfr.ssm_proj": "pattern LM: the state-space layer's norm, the three products of its one projection in "
                    "(gate, [x | B | C], step), the step's softplus and the decay, the skip, the gate "
                    "before the grouped norm, out",
    "tfr.ssm_conv": "pattern LM: the state-space layer's one short convolution with its bias and SiLU "
                    "over all channels of [x | B | C]",
    "tfr.ssm_scan": "pattern LM: the state-space layer's recurrence call alone (models.linear_attn.ssm_chunked: "
                    "one decay and one step a head and token, B and C read by group from where they lie)",
    "tfr.mla_proj": "pattern LM: the latent-attention layer's norm, query projection, latent "
                    "projection and norm, expansion to keys and values, rotary turns, the output's "
                    "sigmoid gate where the layer has one, out",
    "tfr.mla_attn": "pattern LM: the latent-attention layer's attention call alone (192-wide "
                    "queries and keys against 128-wide values inside each document)",
    "tfr.dsa_proj": "pattern LM: a latent-attention layer's indexer: its queries from the query "
                    "latent, its one key a token through a LayerNorm, rotary turns, the heads' weights",
    "tfr.dsa_index": "pattern LM: the indexer's scores over every causal pair of a document, each "
                     "query's exact k-th largest, and the mask of the keys it keeps",
    "tfr.dense_ffn": "pattern LM: a layer's dense gated feed-forward part (pre-norm, gate, up, down)",
    "tfr.moe_route": "held experts: pre-norm, scores over all experts, top-k, visits sorted by expert",
    "tfr.moe_experts": "held experts: the loop over the tiles of visits to the experts held here",
    "tfr.moe_shared": "held experts: the shared expert, every token",
    "tfr.lm_head": "pattern LM: final norm, the head's log-probabilities (models.head: on a TPU one "
                   "kernel whose float32 logits stay in VMEM a tile at a time, else blocks of them), "
                   "the sampled positions' logits",
}


_PROF = None
_PROF_CHECKED = False


def _profiler():
    global _PROF, _PROF_CHECKED
    if not _PROF_CHECKED:
        _PROF_CHECKED = True
        try:
            import jax.profiler as prof

            _PROF = prof
        except Exception:  # pragma: no cover - jax always present in this repo  # graftlint: swallow(no jax profiler available: tracing disabled)
            _PROF = None
    return _PROF


class _NullTrace:
    """Shared no-op context manager for the profiler-less path."""

    __slots__ = ()

    def __enter__(self) -> "_NullTrace":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set_metadata(self, **args) -> None:
        return None


_NULL_TRACE = _NullTrace()


def trace(name: str, **args):
    """Annotate a host-side region on the profiler timeline.

    Returns the profiler's TraceAnnotation directly (it IS a context
    manager) instead of wrapping it in a generator — ``trace`` sits on
    per-chunk hot paths (decode, cache serve, write stages), where the old
    ``@contextlib.contextmanager`` layer allocated a generator per call
    even with no profiler present. With jax unavailable a shared no-op is
    returned: zero allocation per call. ``args`` become the event's stats
    in a capture; what is only known at the end of the region goes through
    the returned object's ``set_metadata(**args)``."""
    prof = _profiler()
    if prof is None:
        return _NULL_TRACE
    return prof.TraceAnnotation(name, **args)


#: what ``get_or_wait`` returns once ``stop`` is set
STOPPED = object()


def put_or_wait(q: "queue.Queue", item, stop, span: str) -> bool:
    """Put ``item`` on a bounded queue unless ``stop`` is (or gets) set;
    False then. A put that finds room opens no span; one that finds the
    queue full waits under ONE ``span``, however many polls it takes."""
    if stop.is_set():
        return False
    try:
        q.put_nowait(item)
        return True
    except queue.Full:
        pass
    with trace(span):
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
    return False


def get_or_wait(q: "queue.Queue", stop, span: str):
    """Next item of a queue, or ``STOPPED`` once ``stop`` is set. An item
    that is already there opens no span; an empty queue is waited on under
    ONE ``span``."""
    if stop.is_set():
        return STOPPED
    try:
        return q.get_nowait()
    except queue.Empty:
        pass
    with trace(span):
        while not stop.is_set():
            try:
                return q.get(timeout=0.1)
            except queue.Empty:
                continue
    return STOPPED


class DutyCycle:
    """Track device busy vs input-wait time in a training loop.

    Usage::

        duty = DutyCycle()
        for batch in it:
            with duty.wait():     # host blocked on input pipeline
                gb = make_global_batch(...)
            with duty.step():     # device computing (block_until_ready inside)
                loss = step(gb)
        print(duty.value())       # busy / (busy + wait)
    """

    def __init__(self):
        self.busy_seconds = 0.0
        self.wait_seconds = 0.0

    @contextlib.contextmanager
    def step(self):
        t0 = time.perf_counter()
        yield
        self.busy_seconds += time.perf_counter() - t0

    @contextlib.contextmanager
    def wait(self):
        t0 = time.perf_counter()
        yield
        self.wait_seconds += time.perf_counter() - t0

    def value(self) -> Optional[float]:
        total = self.busy_seconds + self.wait_seconds
        return self.busy_seconds / total if total > 0 else None
