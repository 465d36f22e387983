"""The metric/span name registry: ONE owner for every counter, stage,
gauge, and span name literal the package emits.

Six PRs of observability grew ~150 names by convention — a counter here, a
gauge there, each documented (or not) in whatever README section its PR
touched. Names that drift from their docs are worse than undocumented
ones: a dashboard keyed on ``service.shard_done`` silently reads zero
forever when the code says ``service.shards_done``. This module turns the
vocabulary into data so tools/graftlint can enforce it both ways:

- every ``METRICS.count/add/gauge/observe``/``timed``/``span``/``instant``
  /``record_span`` call site with a literal name must use a REGISTERED
  name of the right kind (rule ``vocab-unregistered``);
- every registered name must appear in the README metric docs — the
  generated vocabulary block ``vocabulary_markdown()`` emits and the
  ``vocab-docs`` rule verifies (drift in either direction fails CI).

Adding a metric is a three-line change: emit it, register it here in the
right set with a one-phrase description, and refresh the README block
(``python -m tools.graftlint --vocab-md`` prints it). The linter fails
until all three agree.

Kinds mirror tpu_tfrecord.metrics' three storage classes plus spans:

- **counters** — monotonic ``Metrics.count`` events;
- **stages** — ``Metrics.add``/``timed`` throughput totals (+ latency
  histograms), including the ``Metrics.observe``-only histogram families;
- **gauges** — ``Metrics.gauge`` instantaneous values;
- **spans** — ``telemetry.span``/``instant``/``record_span`` trace names.

Dynamically-formed names are covered by ``DYNAMIC_PREFIXES`` (e.g. the
autotuner's per-knob ``autotune.<knob>`` gauges) and ``DERIVED_SUFFIXES``
(the ``<stage>.errors`` counters ``timed`` mints, the pulse's
``<counter>.delta`` fields). Stdlib only, imports nothing from the
package — every layer (and the linter) can read it without cycles.
"""

from __future__ import annotations

from typing import Dict, Iterable, Optional

__all__ = [
    "COUNTERS",
    "STAGES",
    "GAUGES",
    "SPANS",
    "DYNAMIC_PREFIXES",
    "DERIVED_SUFFIXES",
    "KINDS",
    "is_registered",
    "registered_names",
    "vocabulary_markdown",
    "VOCABULARY_BEGIN",
    "VOCABULARY_END",
]


#: Monotonic event counters (``Metrics.count``): name -> what one tick means.
COUNTERS: Dict[str, str] = {
    # -- read path robustness
    "read.corrupt_records": "corrupt frames skipped by salvage",
    "read.resyncs": "salvage re-locked onto a valid frame boundary",
    "read.retries": "transient read errors retried (incl. remote resume)",
    "read.skipped_shards": "shards dropped by on_corrupt/on_stall=skip_shard",
    "read.stalls": "reads converted to StallError by the deadline",
    "moe.visits_dropped": "visits to held experts that were not computed (stays 0)",
    "noise.masked": "TokenPacker(noise=): document tokens replaced by the mask id",
    "noise.positions": "TokenPacker(noise=): document tokens the noise was drawn for (pads excluded)",
    "read.deadline_misses": "per-read deadlines that fired",
    "read.hedges": "straggler hedge opens issued",
    "read.hedge_wins": "hedge backup finished before the primary",
    "read.watchdog_restarts": "silent decode workers replaced",
    "read.backpressure_waits": "producer blocked on a full prefetch queue",
    # -- remote (HTTP) ingestion
    "remote.bad_range": "lying/unparseable Content-Range rejected",
    "remote.fetch_retries": "remote block fetches resumed on a fresh conn",
    # -- write path
    "write.commit_retries": "shard commit rename retried",
    "write.backpressure_waits": "encoder blocked on the committer",
    # -- columnar epoch cache
    "cache.hits": "shards served from a validated cache entry",
    "cache.misses": "shards decoded from ground truth",
    "cache.bytes_written": "bytes committed into cache entries",
    "cache.evictions": "entries removed by the LRU sweep",
    "cache.corrupt_fallbacks": "corrupt/stale entries fallen back to decode",
    "cache.populate_errors": "cache populate jobs aborted (epoch unaffected)",
    # -- autotune / telemetry plumbing
    "autotune.adjustments": "controller knob moves",
    "pulse.observer_errors": "pulse observers that raised (swallowed)",
    "pulse.tick_errors": "pulse ticks that raised (swallowed)",
    # -- fleet spool
    "fleet.spool_writes": "telemetry snapshots landed in the spool",
    "fleet.spool_errors": "snapshot attempts that failed (never raise)",
    # -- data service
    "service.registrations": "workers registered with the dispatcher",
    "service.fetches": "shard streams served by workers",
    "service.bytes_sent": "chunk bytes sent by workers",
    "service.chunks_sent": "chunks sent by workers",
    "service.chunks_recv": "chunks received by consumers",
    "service.shards_served": "shard streams completed by workers",
    "service.shards_done": "shard completions recorded by the dispatcher",
    "service.reconnects": "consumer stream reconnects",
    "service.redelivered_dropped": "duplicate chunks deduped by consumers",
    "service.lease_reassignments": "expired leases re-routed",
    "service.fallbacks": "consumers degraded to local reads",
    "service.journal_errors": "dispatcher journal writes that failed",
    "service.worker_drained": "workers that completed a graceful drain",
    "service.cache_served": "worker shard streams served from warm cache",
    "service.tenants": "distinct dataset fingerprints served",
    "service.shared_cache_hits": "shard completions that rode another job's cache",
    # -- HA: partitioned dispatchers + warm-standby failover
    "service.failovers": "standby promotions to acting primary",
    "service.fenced_writes": "journal appends rejected by the inode fence (zombie primary)",
    "service.demotions": "primaries that stopped granting leases (journal failures / fenced)",
    "service.not_primary_rejects": "lease-path ops refused by a standby or demoted primary",
    # -- elastic fleet scaler
    "elastic.scale_ups": "decode workers spawned by the scaler",
    "elastic.scale_downs": "drains initiated by the scaler",
    "elastic.drains": "workers that said goodbye after draining",
    "elastic.drained_leases": "unstarted leases handed back by drain victims",
    "elastic.spawn_errors": "worker spawns that failed",
    "elastic.step_errors": "scaler control-loop ticks that raised",
    "elastic.verdict_errors": "fleet verdict reads that failed (not idle)",
    "elastic.census_errors": "scaler ticks skipped on an unreadable partition status",
    # -- training flight recorder
    "train.steps": "completed harness train steps",
    # -- async checkpointing (snapshot/commit split)
    "ckpt.bytes_written": "checkpoint bytes committed to disk",
    "ckpt.generations_swept": "retired/dead checkpoint generations removed",
    # -- streamed serving (pipeline inference mode + the serving tier)
    "serve.requests": "generation requests completed by the serving tier",
    "serve.rejected": "requests shed at admission (queue full / draining)",
    "serve.deadline_expired": "requests dropped by their deadline (admission or in flight)",
    "serve.disconnects": "client connections lost mid-request (slots freed)",
    "serve.ticks": "continuous-batching scheduler ticks (microbatches packed)",
    "serve.errors": "serving engine ticks / completion callbacks that raised",
    "elastic.replicas_lost": "serving replicas that died undrained (SIGKILL/crash)",
    # -- set-up: the compile log (compile_cache.enable)
    "compile.cache_hits": "programs whose executable came out of the persistent compile cache",
    "compile.cache_misses": "programs looked up in the persistent compile cache, not found, and compiled",
}

#: Throughput stages (``Metrics.add``/``timed``) and observe-only histogram
#: families. Every entry grows records/bytes/seconds totals and (when
#: timed/observed) a latency histogram.
STAGES: Dict[str, str] = {
    "read": "raw shard bytes into the decoder",
    "read.open": "shard open (every open seam)",
    "read.io": "slab reads off the store",
    "decode": "TFRecord frame -> columnar batch",
    "h2d": "host batch -> device transfer",
    "batch.wait": "consumer blocked waiting for a batch",
    "batch": "consumer-side batch assembly",
    "write": "rows -> TFRecord shards (whole pipeline)",
    "write.encode": "example encode (native/python)",
    "write.compress": "per-slab codec compression",
    "write.io": "shard appends",
    "write.commit": "shard finalize + rename",
    "cache.open": "cache entry open + first-pass verification",
    "cache.serve": "mmap-served cached chunks",
    "cache.commit": "cache entry footer + rename",
    "train.step": "whole train step (latency histogram + spans)",
    "train.data_wait": "train step blocked in next(it)",
    "train.h2d": "train step host->device transfer",
    "train.compute": "train step device compute",
    "train.ckpt": "train step checkpoint writes",
    "ckpt.snapshot": "checkpoint snapshot (caller-thread device_get + copy)",
    "ckpt.commit": "checkpoint commit (background stage+fsync+rename)",
    "ckpt.commit_wait": "save() blocked on the previous in-flight commit",
    # dimensionless in-jit model diagnostics (histograms of fractions —
    # telemetry.DIMENSIONLESS_HIST_PREFIXES keeps them out of ms renderers)
    "moe.dropped_fraction": "tokens dropped at expert capacity (fraction)",
    "moe.gate_entropy": "router gate entropy per step",
    "moe.expert_imbalance": "max/mean routed tokens across experts",
    "pipeline.bubble_fraction": "pipeline schedule idle-tick fraction",
    "pipeline.bubble_fraction_v": "interleaved (V>1) schedule bubble fraction",
    # streamed serving: real latency histograms (not dimensionless)
    "serve.latency": "one serving request, admission -> last token",
    "serve.queue_wait": "one request's admission queue wait, admission -> first pack",
    "serve.service": "one request's service time, first pack -> last token",
    # set-up: the compile log (compile_cache.enable); seconds count each moment once
    "compile.trace": "a jitted function traced to a jaxpr (a function traced inside another adds no seconds of its own)",
    "compile.lower": "a program lowered to an MLIR module (every Pallas body's lowering to Mosaic is in here)",
    "compile.backend": "a program's backend compile, or the cache read and deserialisation that stood in for it",
    "compile.cache_read": "the persistent cache read inside a compile.backend that hit",
    "kernel.trace.mla_attn": "the attention Pallas kernel built while a program is traced (attention._flash_widths_call: a latent, a windowed or, since PR 43, a full softmax layer's; the name is its first user's)",
    "kernel.trace.kda_scan": "the delta-rule Pallas kernel built while a program is traced (linear_attn._delta_rule_fused, under either form of the decay)",
    "kernel.trace.ssm_scan": "the state-space Pallas kernel built while a program is traced (linear_attn._ssm_fused: once a program, its layers of one shape share the jitted call)",
    "kernel.trace.dsa_index": "the selection Pallas kernel built while a program is traced (sparse_attn._select_fused)",
    "kernel.trace.interaction": "the dot-interaction Pallas kernel built while a program is traced (interaction.dot_interaction_pallas)",
    "kernel.trace.lm_head": "the scoring head's Pallas kernel built while a program is traced (head._head_fused: the product, the running log-sum-exp and the target's pick a VMEM tile at a time)",
    "host.gc": "collections of the cyclic collector since tracing.watch_host(): every one counted, with its seconds (folded in once a second)",
    "host.pause": "wakes of the host watch more than 50 ms late: the process, or the whole guest, stood still (count, seconds, latency histogram)",
}

#: Instantaneous gauges (``Metrics.gauge``): last write wins.
GAUGES: Dict[str, str] = {
    "prefetch.queue_depth": "prefetch queue fill (items)",
    "prefetch.occupancy": "EMA of prefetch queue fill fraction (verdict input)",
    "read.inflight_workers": "decode workers currently busy",
    "write.occupancy": "EMA of writer slab-queue fill (write verdict input)",
    "write.inflight_slabs": "slabs in flight in the write pipeline",
    "elastic.workers": "decode worker processes the scaler believes live",
    "elastic.replicas": "serving replicas the serving scaler believes active",
    "serve.queue_depth": "serving admission queue fill (requests waiting to start)",
    "serve.in_flight": "requests riding the serving pipeline right now",
    "service.partition": "partition index this process serves (or routes to)",
    "train.share.data_wait": "windowed share of step wall in data wait",
    "train.share.h2d": "windowed share of step wall in h2d",
    "train.share.compute": "windowed share of step wall in compute",
    "train.share.ckpt": "windowed share of step wall in checkpointing",
    "ckpt.inflight": "background checkpoint commits in flight (0 or 1)",
    "pack.density": "fraction of emitted packed tokens that are real (bin modes)",
    "noise.masked_share": "TokenPacker(noise=), latest batch: document tokens replaced by the mask id over document tokens (about 1/2 under t ~ U(0, 1])",
    "lm.fsdp_param_bytes": "per-device at-rest param bytes under the fsdp layout",
    "moe.dropped_fraction": "latest per-step dropped-token fraction",
    "moe.visits_max_over_mean": "held experts, latest step: the busiest over the mean (most uneven layer)",
    "conv.kernel_layers": "pattern LM, the score program last traced: delta-rule layers, under either decay, whose kernel prepared q, k and v from the projections itself (taps, SiLU, unit norm: linear_attn.delta_rule_layer; 0 off a TPU)",
    "kda.fused_layers": "pattern LM, the score program last traced: delta-rule layers whose recurrence took the Pallas kernel (0 off a TPU)",
    "gdn.fused_layers": "pattern LM, the score program last traced: gated delta-net layers whose recurrence took the Pallas kernel, one decay a head and token (0 off a TPU)",
    "gdn.key_group": "pattern LM, the score program last traced: value heads of a gated delta-net layer that read one key head, from where it lies",
    "gdn.state_shape": "pattern LM, the score program last traced: d_k x d_v of the state a head of the gated delta-net layers keeps in the Pallas kernel (96 x 192 = 18,432; 0 where no layer took it)",
    "gdn.lane_fill": "pattern LM, the score program last traced: published channels over the lanes the delta-rule kernel's tiles of q, k and v occupy in VMEM (linear_attn.lane_fill: 1.0 at whole 128s, 0.75 for keys of 96 under values of 192; 0 where no layer took the kernel)",
    "ssm.fused_layers": "pattern LM, the score program last traced: state-space layers whose recurrence took the Pallas kernel (0 off a TPU)",
    "ssm.group": "pattern LM, the score program last traced: heads of a state-space layer that read one group's B and C, from where they lie",
    "dsa.kernel_layers": "pattern LM, the score program last traced: latent-attention layers whose selection took the Pallas kernel (0 off a TPU and without an indexer)",
    "mla.split_layers": "pattern LM, the score program last traced: latent-attention layers whose attention kernel was handed q and k in their two parts, plain and rotary, never joined in memory (0 off a TPU)",
    "dsa.selected_share": "pattern LM, latest step recorded: keys the indexers kept over the causal candidates they chose from (lm.record_selected)",
    "mla.plain_pair_share": "pattern LM, latest step recorded: of the block pairs the latent-attention kernel computes, those wholly under the diagonal of one document, where every key is seen (lm.record_pair_kinds)",
    "gqa.kernel_layers": "pattern LM, the score program last traced: full softmax layers whose attention took the Pallas kernel, grouped K/V heads read as the projections wrote them, never copied to the query heads (0 off a TPU)",
    "head.fused": "pattern LM, the score program last traced: 1 where the head's float32 logits stay in VMEM a tile at a time (the Pallas kernel of models.head), 0 where the plain form writes them a block of head_block tokens at a time (off a TPU, and at shapes head.head_tile declines)",
    "moe.tail_unit": "pattern LM, the score program last traced: rows of a tail tile of the expert loop, the unit an expert's visits are rounded up to (0 where the tile is the unit and one loop of whole tiles runs)",
    "moe.tile_fill": "held experts, latest step recorded: real visits over the rows the expert loops compute, each expert's visits rounded up to whole units (emptiest layer; lm.record_moe_counters)",
    "swa.kernel_layers": "pattern LM, the score program last traced: sliding-window layers whose attention took the Pallas kernel under a window (0 off a TPU)",
    "swa.pairs_walked_share": "pattern LM, the score program last traced: the block pairs a sliding-window layer walks (the band) over the pairs at or under the diagonal of a row",
    "bda.kernel_layers": "pattern LM, the score program last traced: block-diffusion layers whose attention took the Pallas kernel under the block mask (0 off a TPU)",
    "bda.block": "pattern LM, the score program last traced: tokens a block of the block-diffusion layers' mask, counted from a document's own first token",
    "bda.pairs_walked_share": "pattern LM, the score program last traced: the block pairs a block-diffusion layer's kernel walks for a row's two streams (each stream's triangle and a noised query block's own block) over the pairs at or under the diagonals of two causal rows",
    "moe.gate_entropy": "latest per-step router gate entropy",
    "moe.expert_imbalance": "latest per-step expert imbalance",
    "pipeline.bubble_fraction": "latest per-step pipeline bubble fraction",
    "pipeline.bubble_fraction_v": "latest interleaved (V>1) bubble fraction",
}

#: Trace span / instant names (``telemetry.span``/``instant``/
#: ``record_span``; the flight-recorder and Perfetto vocabulary). The
#: ``tfr:*`` and ``host:*`` names are the host log's: written to the same
#: ring by ``tracing.trace`` and the host watch whether or not the flight
#: recorder is on, and owned by ``tracing.ANNOTATIONS`` (tests/test_host_log.py
#: holds the two tables together).
SPANS: Dict[str, str] = {
    "tfr:open": "one shard open (shard-attributed)",
    "read": "one guarded read region",
    "tfr:decode": "one chunk decode: frame scan + CRC + decode + hash (shard-attributed; rows, bytes)",
    "tfr:cache": "one cached chunk serve (shard-attributed; rows)",
    "tfr:pack": "one batch through host_batch_from_columnar or pack_mixed (rows, bytes out)",
    "tfr:pack_tokens": "one reader batch's documents placed by TokenPacker (docs in, rows and tokens out)",
    "tfr:noise": "one closing batch noised by TokenPacker(noise=): a level a block, tokens masked with that probability (rows, positions, masked)",
    "tfr:h2d": "the dispatch of one batch's host-to-device copy (rows, bytes)",
    "tfr:h2d_land": "the transfer thread's wait for that copy to land",
    "tfr:blocked.batch": "the decode thread's put waited on a full prefetch queue",
    "tfr:starved.batch": "the dataset's consumer found its prefetch queue empty",
    "tfr:blocked.host": "HostPrefetcher's thread waited on its full queue",
    "tfr:starved.host": "HostPrefetcher's consumer found its queue empty",
    "tfr:blocked.device": "the transfer thread waited on its full queue",
    "tfr:starved.device": "DeviceIterator's consumer found its queue empty",
    "host:pause": "the host watch woke more than 50 ms late (late_s, cause, and what the operating system says of the interval)",
    "host:gc": "one collection of 1 ms or more (generation, collected)",
    "batch": "one consumer batch get",
    "write.encode": "one slab encode",
    "write.compress": "one slab compression",
    "write.io": "one slab append",
    "write.commit": "one shard commit",
    "cache.open": "one cache entry open",
    "cache.commit": "one cache entry commit",
    "service.serve": "one worker shard stream",
    "train.step": "one train step (phase-decomposed)",
    "train.verdict": "windowed training verdict instant",
    "read.stall": "a read deadline fired",
    "read.retry": "a read retry was granted",
    "read.hedge": "a straggler hedge was issued",
    "read.hedge_win": "a hedge backup won",
    "watchdog_restart": "a silent worker was replaced",
    "autotune.adjust": "an autotune knob move",
    "elastic.decision": "a fleet scaler decision",
    "elastic.drain": "a drain was initiated",
    "elastic.drain_complete": "a worker finished draining",
    "service.fallback": "a consumer degraded to local reads",
    "service.lease_reassigned": "an expired lease was re-routed",
    "service.failover": "a standby took over a partition (or adopted its address)",
    "service.demoted": "a primary stopped granting leases",
    # request-scoped tracing (client-minted TraceContext over the wire)
    "serve.request": "one serving request, admission -> completion (root span)",
    "serve.queue_wait": "one request waiting for its first pack (child of serve.request)",
    "serve.tick": "one scheduler tick's slice of one request (child of serve.request)",
    "serve.shed": "a request was shed at admission (instant)",
    "serve.deadline_expired": "a request's deadline fired (instant)",
    "service.lease": "one consumer shard lease, route -> eof (root span)",
    "service.route": "dispatcher routed a shard to a worker (instant, lease-linked)",
    # set-up, recorded after the fact by the compile log (attr fun= the program)
    "compile.trace": "one jitted function's trace",
    "compile.lower": "one program's lowering",
    "compile.backend": "one program's backend compile or cache read",
    "compile.cache_read": "one persistent cache read",
    "kernel.trace.mla_attn": "one build of the attention kernel (a latent, a windowed or a full softmax layer's)",
    "kernel.trace.kda_scan": "one build of the delta-rule kernel",
    "kernel.trace.ssm_scan": "one build of the state-space kernel",
    "kernel.trace.dsa_index": "one build of the selection kernel",
    "kernel.trace.interaction": "one build of the dot-interaction kernel",
    "kernel.trace.lm_head": "one build of the scoring head's kernel",
}

#: Prefixes under which names are formed at runtime and cannot be
#: enumerated statically: kind -> (prefix, what varies).
DYNAMIC_PREFIXES: Dict[str, Dict[str, str]] = {
    "gauge": {
        "autotune.": "one gauge per tuned knob (workers, prefetch, ...)",
        "train.share.": "one gauge per train phase",
        "train.mesh.": "one gauge per mesh axis (extent)",
        "slo.": "SLO engine state per objective kind (budget remaining, window burns)",
    },
    "stage": {
        "train.": "one stage per train phase",
    },
}

#: Suffixes derived mechanically from any registered name: ``timed`` mints
#: ``<stage>.errors`` counters, the pulse emits ``<counter>.delta`` fields.
DERIVED_SUFFIXES = (".errors", ".delta")

KINDS: Dict[str, Dict[str, str]] = {
    "counter": COUNTERS,
    "stage": STAGES,
    "gauge": GAUGES,
    "span": SPANS,
}


def is_registered(name: str, kind: Optional[str] = None) -> bool:
    """Is ``name`` a registered vocabulary entry of ``kind`` (any kind when
    None)? Derived ``.errors``/``.delta`` spellings of a registered name
    and names under a registered dynamic prefix count as registered."""
    kinds = [kind] if kind is not None else list(KINDS)
    for k in kinds:
        if name in KINDS[k]:
            return True
        for prefix in DYNAMIC_PREFIXES.get(k, ()):
            if name.startswith(prefix):
                return True
    for suffix in DERIVED_SUFFIXES:
        if name.endswith(suffix) and is_registered(name[: -len(suffix)], None):
            return True
    return False


def registered_names(kind: Optional[str] = None) -> Iterable[str]:
    """Every explicitly registered name (dynamic prefixes excluded), for
    the docs-drift check."""
    if kind is not None:
        return sorted(KINDS[kind])
    out = set()
    for table in KINDS.values():
        out.update(table)
    return sorted(out)


# -- README generation -------------------------------------------------------

VOCABULARY_BEGIN = "<!-- graftlint:vocabulary:begin (generated; run python -m tools.graftlint --vocab-md) -->"
VOCABULARY_END = "<!-- graftlint:vocabulary:end -->"

_KIND_TITLES = (
    ("counter", "Counters (`Metrics.count`)"),
    ("stage", "Stages & histograms (`Metrics.add`/`timed`/`observe`)"),
    ("gauge", "Gauges (`Metrics.gauge`)"),
    ("span", "Spans & instants (`telemetry.span`/`instant`)"),
)


def vocabulary_markdown() -> str:
    """The generated README vocabulary block (between the BEGIN/END
    markers). tools/graftlint's ``vocab-docs`` rule fails when the README
    block differs from this output — regenerating is
    ``python -m tools.graftlint --vocab-md``."""
    lines = [VOCABULARY_BEGIN, ""]
    for kind, title in _KIND_TITLES:
        lines.append(f"**{title}**")
        lines.append("")
        lines.append("| name | meaning |")
        lines.append("| --- | --- |")
        for name in sorted(KINDS[kind]):
            lines.append(f"| `{name}` | {KINDS[kind][name]} |")
        dyn = DYNAMIC_PREFIXES.get(kind, {})
        for prefix in sorted(dyn):
            lines.append(f"| `{prefix}*` | {dyn[prefix]} |")
        lines.append("")
    lines.append(
        "Derived spellings: any registered name + `.errors` (counter "
        "`timed` mints on a failed block) or `.delta` (per-interval pulse "
        "field) is also registered."
    )
    lines.append("")
    lines.append(VOCABULARY_END)
    return "\n".join(lines)
