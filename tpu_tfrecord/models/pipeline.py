"""Pipeline parallelism (PP) over a mesh axis: GPipe-style microbatching
with GSPMD-style INTERLEAVED VIRTUAL STAGES, scale-shaped, plus a
microbatch-streamed serving mode.

The reference framework has no model-side parallelism (SURVEY.md §2) — this
is the PP member of the consumer-model family, completing the dp/tp/sp/ep/pp
set the mesh design supports (dlrm: dp×tp×sp, attention: sp, moe: ep).

TPU-idiomatic construction (the collective-permute pipeline from the
public scaling playbook, jax-ml.github.io/scaling-book — NOT a torch-style
send/recv scheduler), rebuilt so every per-device quantity scales with the
SHARD, not the global tensor (GSPMD's contract, PAPERS.md):

- `shard_map` over the ``pipe`` axis; each device holds ONE stage's
  parameters (the stacked [S, ...] stage pytree is sharded on its leading
  dim, so stage weights never replicate — that is what makes it PP).
- **interleaved virtual stages** (``n_virtual=V`` > 1, GSPMD / Megatron
  interleaving, arxiv 2105.04663): stage weights stack ``[S, V, ...]`` and
  device d owns V ROUND-ROBIN chunks of the layer sequence — virtual
  stages d, d+S, d+2S, … Each compute tick applies ONE chunk (1/V of the
  device's layers), and the schedule visits chunks in the interleaved
  order, so a microbatch re-enters stage 0 after each lap of the ring.
  Warmup shrinks by ~V: the bubble falls from (S-1)/(M+S-1) to
  (S-1)/(V·M+S-1) — measured, not assumed, by the per-tick occupancy
  counter below. The interleaving costs nothing structural: virtual stage
  k runs on device k mod S, so consecutive virtual stages are ALWAYS one
  forward ring hop apart (including the S-1 → 0 wrap onto the next
  virtual slot) and the same three ppermute rings carry the schedule.
- the microbatch tensor is SHARDED on the pipe axis too: device d holds
  only its block of ceil(M/S) microbatches, never the full [M, mb, ...]
  stream (the old construction replicated it to every stage, so per-device
  input memory grew with M and defeated the point of pipelining).
- the stream enters at stage 0 only, via a FEED RING: one microbatch slice
  per device rotates one hop toward stage 0 each tick (`lax.ppermute`),
  timed so microbatch m arrives at stage 0 exactly at its injection tick
  inj(m) = (m // S)·V·S + (m mod S) (for V=1, inj(m)=m — the classic
  schedule). In-flight input per device is ONE [mb, ...] slice — O(mb),
  constant in M and V.
- activations hop device s -> s+1 with `lax.ppermute` each tick; M
  microbatches flow through S·V virtual stages in V·M + S - 1 compute
  ticks inside one `lax.fori_loop` (static trip count -> one compiled
  program, reverse-mode differentiable via scan).
- outputs are born on the LAST stage's LAST virtual chunk and ride an OUT
  RING (one more O(mb) ppermute per tick) back to the device that owns
  that microbatch's output shard — a targeted permute, not the old `psum`
  broadcast that replicated the full [M, mb, ...] result to every device.
  A trailing S - 1 permute-only drain delivers the final in-flight
  outputs without extra stage compute.

Per-device totals: input ceil(M/S)·mb (the shard), loop state 3 slices +
the output shard, collectives 3 ppermutes of ONE slice per tick. The
compiled HLO therefore contains collective-permutes of microbatch-slice
size only — no all-gather, no all-reduce — pinned by the
tools/graftlint/hlo_contracts manifest (plain, dp-composed, interleaved,
and streaming rows).

`pipeline_apply` is the sharded entry point; `pipeline_reference` is the
sequential oracle used by the tests. `microbatch_sharding` gives callers
the input layout so the stream can be device_put straight into its shard
(feeding the pipeline never materializes [M, mb, ...] anywhere).
`PipelineStream` is the SERVING mode: a persistent jitted per-tick step
whose feed is exactly one [mb, ...] slice — microbatches stream through
the same rings one request at a time, outputs pop with pipeline latency,
and no M-deep stream exists anywhere (the per-call argument is the pin).
"""

from __future__ import annotations

import collections
import functools
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


StageFn = Callable[[Any, jax.Array], jax.Array]


def _stage_count(stage_params: Any, n_virtual: int) -> int:
    leaves = jax.tree.leaves(stage_params)
    if not leaves:
        raise ValueError("stage_params has no leaves")
    if n_virtual > 1 and any(
        l.ndim < 2 or l.shape[1] != n_virtual for l in leaves
    ):
        bad = [l.shape for l in leaves if l.ndim < 2 or l.shape[1] != n_virtual]
        raise ValueError(
            f"n_virtual={n_virtual} needs stage_params leaves stacked "
            f"[S, V, ...]; offending leaf shapes: {bad}"
        )
    return leaves[0].shape[0]


def pipeline_reference(
    stage_fn: StageFn, stage_params: Any, xs: jax.Array, n_virtual: int = 1
) -> jax.Array:
    """Sequential oracle: fold every microbatch through all S·V virtual
    stages in interleaved order (virtual stage k = v·S + s runs chunk v of
    device s). stage_params: pytree stacked on a leading S dim ([S, V, ...]
    when ``n_virtual`` > 1); xs: [M, mb, ...]."""
    n_stages = _stage_count(stage_params, n_virtual)

    def one(x):
        for v in range(n_virtual):
            for s in range(n_stages):
                if n_virtual == 1:
                    params_c = jax.tree.map(lambda a: a[s], stage_params)
                else:
                    params_c = jax.tree.map(
                        lambda a: a[s, v], stage_params  # noqa: B023
                    )
                x = stage_fn(params_c, x)
        return x

    return jax.vmap(one)(xs)


def microbatch_sharding(
    mesh: Mesh, pipe_axis: str = "pipe", ndim: Any = 3,
    batch_spec: P = P(),
) -> NamedSharding:
    """Input layout for ``pipeline_apply``: microbatch dim 0 sharded on the
    pipe axis (device d holds its ceil(M/S) block), trailing dims per
    ``batch_spec``. device_put the stream with this so no device ever
    materializes the full [M, mb, ...] tensor. Needs M % S == 0 (pad the
    stream first when it does not divide — `pipeline_apply` only pads
    internally for inputs that arrive unsharded).

    ``ndim`` is the stream's rank — pass either the int or the stream
    array itself (anything with an ``.ndim``), so call sites stop
    hand-threading ``ndim=xs.ndim``."""
    nd = int(getattr(ndim, "ndim", ndim))
    tail = tuple(batch_spec) + (None,) * (nd - 1 - len(tuple(batch_spec)))
    return NamedSharding(mesh, P(pipe_axis, *tail))


def _chunk_params(params, v_idx, n_virtual: int):
    """This tick's chunk of the local [V, ...] stage stack: static for the
    classic V=1 schedule (the exact pre-interleaving program), a
    differentiable dynamic_index for V>1."""
    if n_virtual == 1:
        return params
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, v_idx, keepdims=False),
        params,
    )


def _schedule_decode(u, s, n_stages: int, n_virtual: int):
    """THE per-tick schedule decode — (v_idx, chunk0, last_chunk) for
    per-device step u = t - s: which virtual chunk this device applies,
    whether it is virtual stage 0 (eats the feed) and whether it is the
    LAST virtual stage (births an output). Shared by the batch loop and
    the serving tick, so the streamed-vs-batch bitwise contract cannot
    drift from a one-sided edit. V=1 keeps the static predicates of the
    pre-interleaving program."""
    if n_virtual == 1:
        return None, s == 0, s == n_stages - 1
    v_idx = jax.lax.rem(
        jnp.maximum(u, 0) // n_stages, n_virtual
    ).astype(jnp.int32)
    return (
        v_idx,
        (s == 0) & (v_idx == 0),
        (s == n_stages - 1) & (v_idx == n_virtual - 1),
    )


def _pipeline_local(
    params_stk, xs_local, *, stage_fn: StageFn, n_micro: int, n_stages: int,
    n_virtual: int, block: int, axis: str, diagnostics: bool = False,
):
    """Per-device body (inside shard_map): params_stk is THIS stage's slice
    (leading dim 1; [1, V, ...] when interleaved); xs_local is THIS
    device's [R, mb, ...] block of the microbatch stream (R = ceil(M/S);
    device d owns microbatches [d*R, (d+1)*R)).

    Per-device schedule: local step u = t - s walks (round r, chunk v,
    offset i) in the interleaved order u = r·V·S + v·S + i — microbatch
    m = r·S + i, virtual chunk v. Every chunk's input is the activation
    produced ONE tick earlier ONE ring hop back (virtual stage k = v·S + s
    runs on device k mod S, so both the intra-lap hop s -> s+1 and the
    lap wrap S-1 -> 0 are a single forward permute) — the V=1 dataflow,
    unchanged; only the weights indexed per tick and the injection /
    birth timing generalize.

    Three O(mb) rings, all ppermute:
      feed ring (hop -1): device d injects its slice for microbatch m at
        tick inj(m) - d (inj(m) = (m // S)·V·S + m mod S), so it reaches
        stage 0 exactly when chunk 0 of m is due. Invariant: at tick t,
        device j's feed slot holds the microbatch whose inj is t + j.
      activation ring (hop +1): a chunk's output becomes the next virtual
        stage's input.
      out ring (hop +1): the last stage injects each microbatch finishing
        its LAST chunk (v = V-1); the owner (m // R) captures it into its
        output shard. Invariant: at tick t device j holds the output
        injected at tick t - ((j+1) mod S).

    ``diagnostics`` (static flag) additionally threads a per-tick
    occupancy counter through the loop carry: device s's compute at tick
    t is USEFUL iff its local step u = t - s decodes to a real microbatch
    (u >= 0 and m(u) < n_micro — the same predicate the capture mask
    enforces; warmup/drain ticks compute garbage and count as bubble).
    The counter measures the occupancy of THIS compiled schedule's loop,
    tick by tick — so the interleaved schedule reports its own number
    instead of someone re-deriving a closed form. For V=1 it equals
    (S-1)/(M+S-1) exactly and for the interleaved schedule
    (S-1)/(V·M+S-1) (both pinned by tests); it is identical on every
    device, so no collective is needed and the gather-free HLO pin
    survives with the flag on. Typed as varying over the pipe axis (it
    reads the stage index), it leaves the body as a [1] array per stage —
    (outbuf, useful[None]) — and `pipeline_apply` builds the diag dict.
    """
    params = jax.tree.map(lambda a: a[0], params_stk)
    s = jax.lax.axis_index(axis)
    r_blk = block
    vs = n_stages * n_virtual
    mb_shape = xs_local.shape[1:]
    fwd = [(j, (j + 1) % n_stages) for j in range(n_stages)]
    back = [(j, (j - 1) % n_stages) for j in range(n_stages)]
    # every loop carry starts out typed as varying over the same mesh axes
    # as the local stream (pipe, plus whatever batch_spec shards): the
    # tick body makes them so, and fori_loop wants carry-in == carry-out
    vma = tuple(jax.typeof(xs_local).vma)

    def varying(a):
        return jax.lax.pcast(a, vma, to="varying") if vma else a

    zero = varying(jnp.zeros(mb_shape, xs_local.dtype))
    feed0, act0, ring0 = zero, zero, zero
    outbuf0 = varying(jnp.zeros((r_blk,) + mb_shape, xs_local.dtype))

    def m_of(u):
        # microbatch index of per-device step u = r·V·S + v·S + i:
        # m = r·S + i. jnp // floors, so negative u lands at m < 0, which
        # every consumer masks out (occupancy and capture both require a
        # real microbatch index).
        if n_virtual == 1:
            return u
        return (u // vs) * n_stages + jax.lax.rem(
            jnp.maximum(u, 0), n_stages
        )

    def capture(t, ring, outbuf):
        # device j holds the output injected at tick t - ((j+1) mod S);
        # that output was born when device S-1 finished step
        # u_o = (injection tick) - (S-1), which is a BIRTH step only when
        # its chunk is the last (u_o mod V·S >= (V-1)·S); capture it iff j
        # owns that microbatch's output shard
        ti = t - jax.lax.rem(s + 1, n_stages)
        u_o = ti - (n_stages - 1)
        if n_virtual == 1:
            m_cap = u_o
            born = m_cap >= 0
        else:
            born = (u_o >= 0) & (
                jax.lax.rem(u_o, vs) >= vs - n_stages
            )
            m_cap = m_of(u_o)
        cap = born & (m_cap >= 0) & (m_cap < n_micro) & (m_cap // r_blk == s)
        slot = jnp.clip(m_cap - s * r_blk, 0, r_blk - 1)
        got = jax.lax.dynamic_index_in_dim(outbuf, slot, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            outbuf, jnp.where(cap, ring, got), slot, axis=0
        )

    def tick(t, state):
        feed, act, ring, outbuf, useful = state
        u = t - s
        v_idx, chunk0, last_chunk = _schedule_decode(
            u, s, n_stages, n_virtual
        )
        # feed ring: rotate toward stage 0, then inject this device's
        # next owned slice the moment its travel time is due. The slot at
        # (t, j) carries the microbatch with inj(m) = t + j; a = t + s
        # decodes to a real injection slot iff a mod V·S < S
        a = t + s
        if n_virtual == 1:
            m_inj = a
            slot_ok = True
        else:
            in_round = jax.lax.rem(a, vs)
            slot_ok = in_round < n_stages
            m_inj = (a // vs) * n_stages + in_round
        inject = slot_ok & (m_inj < n_micro) & (m_inj // r_blk == s)
        local_r = jnp.clip(m_inj - s * r_blk, 0, r_blk - 1)
        mine = jax.lax.dynamic_index_in_dim(xs_local, local_r, keepdims=False)
        feed = jnp.where(inject, mine, jax.lax.ppermute(feed, axis, back))
        # stage compute: chunk (v=0, s=0) eats the feed, every other
        # virtual stage the arriving activation (clipped reads past M
        # compute garbage that the capture mask never collects)
        out = stage_fn(
            _chunk_params(params, v_idx, n_virtual),
            jnp.where(chunk0, feed, act),
        )
        # out ring: rotate, the last virtual stage injects its finished
        # microbatch
        ring = jnp.where(
            last_chunk, out, jax.lax.ppermute(ring, axis, fwd)
        )
        outbuf = capture(t, ring, outbuf)
        act = jax.lax.ppermute(out, axis, fwd)  # hop to the next stage
        if diagnostics:
            # this tick computed chunk step u; useful iff its microbatch
            # is real
            useful = useful + jnp.where(
                (u >= 0) & (m_of(u) < n_micro), 1.0, 0.0
            ).astype(jnp.float32)
        return feed, act, ring, outbuf, useful

    def drain(t, state):
        # permute-only tail: the last S - 1 in-flight outputs finish their
        # ring journey; no stage compute, no feed
        ring, outbuf = state
        ring = jax.lax.ppermute(ring, axis, fwd)
        outbuf = capture(t, ring, outbuf)
        return ring, outbuf

    # the main loop must run THROUGH the last real microbatch's birth tick
    t_end = _total_ticks(n_micro, n_stages, n_virtual)
    # the occupancy counter reads the stage index, so it is varying over
    # the pipe axis like the rings (and only over it)
    useful0 = jax.lax.pcast(jnp.float32(0.0), (axis,), to="varying")
    _, _, ring, outbuf, useful = jax.lax.fori_loop(
        0, t_end, tick,
        (feed0, act0, ring0, outbuf0, useful0),
    )
    if n_stages > 1:
        _, outbuf = jax.lax.fori_loop(
            t_end, t_end + n_stages - 1, drain,
            (ring, outbuf),
        )
    if not diagnostics:
        return outbuf
    # one counter per stage, sharded on the pipe axis; pipeline_apply reads
    # stage 0's (they are equal) and derives the rest outside the body
    return outbuf, jax.lax.stop_gradient(useful)[None]


def _total_ticks(n_micro: int, n_stages: int, n_virtual: int) -> int:
    """Main-loop length (exclusive end tick): the last real microbatch's
    final chunk is born on device S-1 at step u_last = r·V·S + (V-1)·S + i,
    i.e. at tick u_last + S - 1."""
    r_last, i_last = (n_micro - 1) // n_stages, (n_micro - 1) % n_stages
    u_last = r_last * n_stages * n_virtual + (n_virtual - 1) * n_stages + i_last
    return u_last + n_stages


def pipeline_apply(
    stage_fn: StageFn,
    stage_params: Any,
    xs: jax.Array,
    mesh: Mesh,
    pipe_axis: str = "pipe",
    batch_spec: P = P(),
    n_virtual: int = 1,
    param_spec: Any = None,
    diagnostics: bool = False,
):
    """Run M microbatches through S pipeline stages sharded on
    ``mesh[pipe_axis]`` — optionally S·V interleaved virtual stages.

    stage_params: pytree whose leaves are stacked [S, ...] (S = axis
    size), or [S, V, ...] with ``n_virtual=V`` > 1 — device d then owns
    the V round-robin virtual stages d, d+S, …, each a chunk the schedule
    applies on its own tick; every stage must map shape [mb, ...] ->
    [mb, ...] (same shape, so the activation hop is shape-stable).
    xs: [M, mb, ...]. Returns [M, mb, ...], bitwise the sequential
    composition (pinned by tests) for any V.

    Scale shape: xs is consumed SHARDED on the pipe axis (block layout —
    device d holds microbatches [d*R, (d+1)*R), R = ceil(M/S); see
    `microbatch_sharding`), so per-device input is the shard, the
    in-flight feed is one [mb, ...] slice, and every collective moves one
    slice — in M and in V.

    ``batch_spec`` optionally shards the PER-MICROBATCH dims over further
    mesh axes (e.g. ``P('data')`` to keep the mb dim data-parallel inside
    the pipeline — the dp×pp composition); stage_fn then sees its
    (pipe, data)-local block and may itself use collectives over those
    axes, which are manual inside the same shard_map (models.moe's
    ``moe_ep_body`` composes EP under a pipe×V×expert mesh this way).

    ``param_spec`` optionally gives the stage_params pytree per-leaf
    PartitionSpecs (each must lead with ``pipe_axis``) so stage weights
    can shard FURTHER axes — e.g. the expert dim of an MoE stage on the
    expert axis. Default: every leaf P(pipe_axis).

    ``diagnostics`` (static flag) returns (out, diag) where diag carries
    the bubble as THIS compiled schedule's loop pays it:
    ``bubble_fraction`` (idle compute ticks / total, counted per tick
    from the schedule's own occupancy predicate, so a rebuilt schedule
    reports its own number — (S-1)/(M+S-1) for the classic V=1 schedule,
    (S-1)/(V·M+S-1) interleaved, both pinned by tests),
    ``useful_ticks``, ``total_ticks``, and (V>1) ``virtual_stages`` — f32
    scalars, identical on every device (no collective added: the HLO
    stays gather-free).
    """
    if n_virtual < 1:
        raise ValueError(f"n_virtual must be >= 1, got {n_virtual}")
    n_stages = mesh.shape[pipe_axis]
    leaves = jax.tree.leaves(stage_params)
    if not leaves or any(l.shape[0] != n_stages for l in leaves):
        bad = [l.shape for l in leaves if l.shape[0] != n_stages]
        raise ValueError(
            f"stage_params leaves must stack {n_stages} stages on the "
            f"leading dim (mesh['{pipe_axis}']); offending leaf shapes: "
            f"{bad or 'no leaves'}"
        )
    _stage_count(stage_params, n_virtual)  # validates the [S, V, ...] stack
    n_micro = xs.shape[0]
    block = -(-n_micro // n_stages)  # ceil: each device's owned slice count
    padded = block * n_stages
    if padded != n_micro:
        # pad the stream so the block layout divides; padded microbatches
        # compute garbage the capture mask never collects
        xs = jnp.concatenate(
            [xs, jnp.zeros((padded - n_micro,) + xs.shape[1:], xs.dtype)]
        )
    tail = tuple(batch_spec) + (None,) * (xs.ndim - 1 - len(tuple(batch_spec)))
    spec = P(pipe_axis, *tail)
    if param_spec is None:
        param_spec = P(pipe_axis)
    else:
        # a spec not leading with the pipe axis would hand every device
        # the FULL stage stack and _pipeline_local's [0]-slice would
        # silently run stage 0's weights everywhere — reject loudly
        # is_leaf must also catch None: tree.leaves would silently DROP
        # None entries, and shard_map reads None as replicated — the
        # exact silent-wrong-weights case this guard exists to reject
        for p_leaf in jax.tree.leaves(
            param_spec, is_leaf=lambda x: x is None or isinstance(x, P)
        ):
            entries = tuple(p_leaf) if p_leaf is not None else ()
            if not entries or entries[0] != pipe_axis:
                raise ValueError(
                    f"param_spec leaves must lead with the pipe axis "
                    f"{pipe_axis!r} (stage weights shard on it); got "
                    f"{p_leaf}"
                )
    fn = jax.shard_map(
        functools.partial(
            _pipeline_local, stage_fn=stage_fn, n_micro=n_micro,
            n_stages=n_stages, n_virtual=n_virtual, block=block,
            axis=pipe_axis, diagnostics=diagnostics,
        ),
        mesh=mesh,
        in_specs=(param_spec, spec),
        out_specs=(spec, P(pipe_axis)) if diagnostics else spec,
    )
    res = fn(stage_params, xs)
    out = res[0] if diagnostics else res
    if padded != n_micro:
        out = out[:n_micro]
    if not diagnostics:
        return out
    useful = res[1][0]  # [S] counters sharded on pipe, all equal
    total = jnp.float32(_total_ticks(n_micro, n_stages, n_virtual))
    diag = {
        "bubble_fraction": 1.0 - useful / total,
        "useful_ticks": useful,
        "total_ticks": total,
    }
    if n_virtual > 1:
        diag["virtual_stages"] = jnp.float32(n_virtual)
    return out, diag


# ---------------------------------------------------------------------------
# Microbatch-streamed serving mode
# ---------------------------------------------------------------------------


def _stream_tick_local(
    params_stk, t, act_l, x, *, stage_fn: StageFn, n_stages: int,
    n_virtual: int, axis: str,
):
    """One schedule tick of the SERVING pipeline (inside shard_map).

    The same interleaved schedule as `_pipeline_local`, with the host as
    the microbatch owner: the per-call feed is ONE replicated [mb, ...]
    slice delivered at stage 0 with zero travel time (the degenerate feed
    ring — the host injects at the consumption tick, so no transport hops
    are needed), and outputs are read straight off the last stage's lane
    of the stacked return instead of riding the out ring home (the host
    IS home). The activation ring is bit-identical to the batch
    schedule's, which is why streamed outputs equal batch-mode
    `pipeline_apply` BITWISE (pinned by tests)."""
    params = jax.tree.map(lambda a: a[0], params_stk)
    s = jax.lax.axis_index(axis)
    act = act_l[0]
    u = t - s
    v_idx, chunk0, _last = _schedule_decode(u, s, n_stages, n_virtual)
    out = stage_fn(
        _chunk_params(params, v_idx, n_virtual),
        jnp.where(chunk0, x, act),
    )
    fwd = [(j, (j + 1) % n_stages) for j in range(n_stages)]
    act_next = jax.lax.ppermute(out, axis, fwd)
    return act_next[None], out[None]


class PipelineStream:
    """Microbatch-streamed inference over the pipelined stages: the
    heavy-traffic serving mode (ROADMAP #2).

    One persistent jitted per-tick step; each `push` feeds exactly ONE
    [mb, ...] slice (the compiled step's only data argument — no
    [M, mb, ...] stream is ever materialized, host- or device-side;
    pinned via the compiled argument bytes) and advances the schedule to
    that microbatch's injection slot. Outputs pop in FIFO order with the
    pipeline's latency (S·V ticks): in steady state within a round, one
    push is one tick and one completed microbatch pops per push. `flush`
    drains the tail microbatches after the last push.

    Stage weights and schedule are shared with `pipeline_apply`
    (``[S, ...]``, or ``[S, V, ...]`` interleaved) and streamed outputs
    are BITWISE equal to the batch mode on the same slices — the serving
    path cannot drift from the trained graph.
    """

    def __init__(
        self,
        stage_fn: StageFn,
        stage_params: Any,
        mesh: Mesh,
        pipe_axis: str = "pipe",
        n_virtual: int = 1,
        microbatch_shape: Optional[Tuple[int, ...]] = None,
        dtype: Any = jnp.float32,
    ):
        if n_virtual < 1:
            raise ValueError(f"n_virtual must be >= 1, got {n_virtual}")
        self._mesh = mesh
        self._axis = pipe_axis
        self._n_stages = mesh.shape[pipe_axis]
        self._n_virtual = n_virtual
        if _stage_count(stage_params, n_virtual) != self._n_stages:
            raise ValueError(
                f"stage_params must stack {self._n_stages} stages "
                f"(mesh['{pipe_axis}'])"
            )
        self._params = stage_params
        self._vs = self._n_stages * n_virtual
        self._step = jax.jit(
            jax.shard_map(
                functools.partial(
                    _stream_tick_local, stage_fn=stage_fn,
                    n_stages=self._n_stages, n_virtual=n_virtual,
                    axis=pipe_axis,
                ),
                mesh=mesh,
                in_specs=(P(pipe_axis), P(), P(pipe_axis), P()),
                out_specs=(P(pipe_axis), P(pipe_axis)),
            )
        )
        self._dtype = dtype
        self._mb_shape: Optional[Tuple[int, ...]] = (
            tuple(microbatch_shape) if microbatch_shape is not None else None
        )
        self.served = 0  # microbatches whose outputs have been returned
        self.reset()

    # -- state ---------------------------------------------------------------

    def reset(self) -> None:
        """Forget all in-flight microbatches and restart at tick 0 (the
        compiled step survives — warmup pays compilation once)."""
        self._t = 0
        self._m = 0
        self._pending: collections.deque = collections.deque()
        self._act = None
        self._zeros = None
        if self._mb_shape is not None:
            self._ensure_state(self._mb_shape, self._dtype)

    def _ensure_state(self, mb_shape, dtype) -> None:
        if self._act is not None:
            if tuple(mb_shape) != self._mb_shape or np.dtype(
                dtype
            ) != np.dtype(self._dtype):
                raise ValueError(
                    f"microbatch {tuple(mb_shape)}/{np.dtype(dtype)} != "
                    f"the stream's {self._mb_shape}/"
                    f"{np.dtype(self._dtype)} (one compiled step, one "
                    f"shape, one dtype)"
                )
            return
        self._mb_shape = tuple(mb_shape)
        self._dtype = dtype
        self._act = jax.device_put(
            jnp.zeros((self._n_stages,) + self._mb_shape, dtype),
            NamedSharding(self._mesh, P(self._axis)),
        )
        self._zeros = jnp.zeros(self._mb_shape, dtype)

    def step_spec(self):
        """(jitted step fn, example args) for the HLO contract manifest —
        the compiled program every `push` runs. Requires the microbatch
        shape (pass ``microbatch_shape`` at construction or push once)."""
        if self._act is None:
            raise ValueError(
                "stream state not initialized: pass microbatch_shape to "
                "the constructor (or push once) before step_spec()"
            )
        return self._step, (
            self._params, jnp.int32(self._t), self._act, self._zeros
        )

    # -- schedule ------------------------------------------------------------

    def _inj(self, m: int) -> int:
        return (m // self._n_stages) * self._vs + m % self._n_stages

    def _tick(self, x, ready: List[Tuple[jax.Array, Any]]) -> None:
        # the host owns the tick counter (self._t); the device step takes
        # it as a plain traced scalar each call
        head = self._pending[0][1] if self._pending else None
        self._act, out = self._step(
            self._params, jnp.int32(self._t), self._act, x
        )
        if head is not None and self._t == head:
            # this tick finished the oldest in-flight microbatch's last
            # chunk on the last stage: its output is that device's lane.
            # Returned DEVICE-resident so downstream jits (e.g. the LM
            # head) consume it without a host round trip — callers that
            # want host bytes np.asarray it themselves
            _, _, tag = self._pending.popleft()
            ready.append((out[self._n_stages - 1], tag))
            self.served += 1
        self._t += 1

    def push(self, x) -> List[jax.Array]:
        """Inject one [mb, ...] microbatch and advance the schedule to its
        injection slot; returns the device-resident outputs (FIFO order)
        that completed along the way — usually one per push once the
        pipeline is full, none during warmup."""
        return [out for out, _ in self.push_tagged(x)]

    def push_tagged(self, x, tag: Any = None) -> List[Tuple[jax.Array, Any]]:
        """`push` that rides an opaque host-side tag on the microbatch's
        FIFO entry and returns ``(output, tag)`` pairs. The tag never
        enters the compiled step (the per-call argument-bytes pin is
        unchanged) — it exists so a multiplexer (the serving tier) can map
        popped outputs back to the requests packed into each slot."""
        x = jnp.asarray(x)
        self._ensure_state(x.shape, x.dtype)
        # next injection slot the clock has not passed yet: a flush (or
        # any idle drain) advances the tick counter, so the schedule
        # re-bases onto the first usable slot — skipped slots just
        # compute garbage on their own diagonals, which nothing collects
        m = self._m
        while self._inj(m) < self._t:
            m += 1
        inj = self._inj(m)
        # birth tick of m's last chunk on the last stage: inj + S·V - 1
        self._pending.append((m, inj + self._vs - 1, tag))
        self._m = m + 1
        ready: List[Tuple[jax.Array, Any]] = []
        while self._t < inj:
            self._tick(self._zeros, ready)   # gap ticks between rounds
        self._tick(x, ready)                 # the injection tick itself
        return ready

    def flush(self) -> List[jax.Array]:
        """Drain: run permute/compute ticks (zero feed) until every pushed
        microbatch's output has popped; returns them in FIFO order."""
        return [out for out, _ in self.flush_tagged()]

    def flush_tagged(self) -> List[Tuple[jax.Array, Any]]:
        """`flush` returning ``(output, tag)`` pairs (see `push_tagged`)."""
        ready: List[Tuple[jax.Array, Any]] = []
        while self._pending:
            self._tick(self._zeros, ready)
        return ready

    @property
    def in_flight(self) -> int:
        return len(self._pending)
