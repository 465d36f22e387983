"""Device time by the program's own scopes (``jax.named_scope("tfr.<x>")`` in
``tpu_tfrecord/models/dlrm.py``; the names are ``tracing.ANNOTATIONS``'):

    scopes=[...]              ms a step under any of these scopes
    quantity="unscoped_pct"   share of device-op time under no ``tfr.`` scope

and one information line, ``[idle_chain]``: for the five longest idle gaps of
the first chip (``trace_reduce``'s gaps), the loop's span and every ``tfr:*``
host span open at the gap's middle, from the device back to the decode
thread — which stage of the feed the loop was waiting on.

What one chip trace looked like by hand (TPU v5 lite, jax 0.9.0; PR 24):

- An ``XLA Ops`` event carries three stats of its own (``device_offset_ps``,
  ``device_duration_ps``, ``Time Scale Multiplier``). The HLO ``op_name`` is
  the stat ``tf_op`` (``jit(<unknown>)/transpose(jvp(tfr.top_mlp))/dot_general:``)
  of the event's METADATA record, beside ``hlo_category``, ``program_id``,
  ``source``, ``flops``, ``bytes_accessed``. ``jax.profiler.ProfileData``
  hands out an event's own stats only, so this file reads the protobuf wire
  format of ``*.xplane.pb`` itself (XSpace > XPlane > XLine > XEvent and the
  plane's ``event_metadata`` / ``stat_metadata`` maps); a string stat may be a
  reference into ``stat_metadata``.
- A fusion reports its ROOT's ``op_name``: the accumulator gather, whose
  index arithmetic comes from three scopes, reads ``tfr.accum_update/gather``.
- The TPU compiler rewrites ``x.at[f, v].add(...)`` into fusions with NO
  ``op_name`` at all (``%fusion.12``, the table scatter, 32% of the train
  step; ``%fusion.11``, the accumulator's), and the scatter instruction
  inside has none either; only arithmetic fused in with it has. The trace
  holds each program's optimized HLO (plane ``/host:metadata``, stat ``Hlo
  Proto``), so such an event takes the ``tfr.`` scope that most instructions
  in its fused computations carry (``tfr.table_scatter/mul``); an event with
  neither (``copy-done``, the loss, ``split_wire``) is unscoped.
- Innermost wins: of ``a/tfr.x/b/tfr.y/c`` the scope is ``tfr.y``.

A program without scopes (the parent of PR 24, or an executable cached
before the scopes existed) gives nothing to read: every quantity is None.
"""

from __future__ import annotations

import re
from collections import Counter, defaultdict

from benchmark.harness import trace_reduce

SCOPE = re.compile(r"tfr\.\w+")
#: host spans in the order of the hand-offs, from the device back
CHAIN = ("starved.device", "blocked.device", "h2d_land", "h2d", "starved.host",
         "blocked.host", "pack", "starved.batch", "blocked.batch", "decode", "open", "cache")


# -- protobuf wire format ----------------------------------------------------


def _varint(buf, i: int):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for num, v in fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def parse_plane(buf) -> dict:
    """{"name", "lines": {line: [(metadata id, start_ns, duration_ns)]},
    "events": {metadata id: {"name", "display_name", stat name: value}}}.
    A device's plane keeps every event; the host's plane (a hundred
    thousand runtime events) only the ``tfr:*`` and the loop's spans."""
    name, line_bufs, raw_events, stat_names = "", [], {}, {}
    for num, v in fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            line_bufs.append(v)
        elif num == 4:  # event_metadata entry
            key, value = _map_entry(v)
            raw_events[key] = value
        elif num == 5:  # stat_metadata entry: id -> name
            key, value = _map_entry(v)
            stat_names[key] = next((_text(x) for n2, x in fields(value) if n2 == 2), "")
    events = {}
    for mid, raw in raw_events.items():
        record = {"name": "", "display_name": ""}
        for n2, v2 in fields(raw):
            if n2 == 2:
                record["name"] = _text(v2)
            elif n2 == 4:
                record["display_name"] = _text(v2)
            elif n2 == 5:  # XStat: metadata_id, then one value
                stat = dict(fields(v2))
                key = stat_names.get(stat.get(1), "")
                if 5 in stat:
                    record[key] = _text(stat[5])
                elif 7 in stat:
                    record[key] = stat_names.get(stat[7], "")
                elif 6 in stat:
                    record[key] = stat[6]
                elif 3 in stat or 4 in stat:
                    record[key] = stat.get(3, stat.get(4))
        events[mid] = record
    if name.startswith(trace_reduce.DEVICE_PLANE):
        kept = events
    else:
        kept = {mid for mid, r in events.items()
                if r["name"].startswith("tfr:") or r["name"] in trace_reduce.LOOP_SPANS}
    lines = defaultdict(list)
    for line in line_bufs if kept else []:
        line_name, at_ns, found = "", 0, []
        for n2, v2 in fields(line):
            if n2 == 2:
                line_name = _text(v2)
            elif n2 == 3:
                at_ns = v2
            elif n2 == 4 and v2[0] == 0x08:  # XEvent, metadata_id first
                mid, at = _varint(v2, 1)
                if mid in kept:
                    event = dict(fields(v2[at:]))  # offset_ps 2, duration_ps 3
                    found.append((mid, event.get(2, 0), event.get(3, 0)))
        lines[line_name].extend((m, at_ns + o / 1e3, d / 1e3) for m, o, d in found)
    return {"name": name, "lines": dict(lines), "events": events}


def parse_xspace(data: bytes) -> dict:
    """{plane name: parse_plane(...)} of one ``*.xplane.pb``."""
    planes = (parse_plane(v) for num, v in fields(memoryview(data)) if num == 1)
    return {p["name"]: p for p in planes}


def parse_hlo(buf) -> dict:
    """An ``HloProto`` -> {"instructions": {name: (op_name, [called
    computation ids])}, "computations": {id: [instruction names]}}."""
    instructions, computations = {}, {}
    module = next((v for num, v in fields(buf) if num == 1), b"")
    for num, comp in fields(module):
        if num != 3:
            continue
        comp_id, members = None, []
        for n2, v2 in fields(comp):
            if n2 == 5:
                comp_id = v2
            elif n2 == 2:
                name, op_name, calls = "", "", []
                for n3, v3 in fields(v2):
                    if n3 == 1:
                        name = _text(v3)
                    elif n3 == 7:
                        op_name = next((_text(x) for n4, x in fields(v3) if n4 == 2), "")
                    elif n3 == 38:  # packed or one at a time
                        calls += [v3] if isinstance(v3, int) else _packed(v3)
                instructions[name] = (op_name, calls)
                members.append(name)
        computations[comp_id] = members
    return {"instructions": instructions, "computations": computations}


def _packed(buf) -> list:
    out, i = [], 0
    while i < len(buf):
        value, i = _varint(buf, i)
        out.append(value)
    return out


# -- scopes -------------------------------------------------------------------


def scope_of(op_name: str):
    """The innermost ``tfr.`` scope of an HLO ``op_name`` path, or None."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


def body_scope(hlo: dict, instruction: str):
    """The scope most instructions of ``instruction``'s called computations
    carry, transitively; None if none carries any."""
    counts, seen = Counter(), set()
    todo = list(hlo["instructions"].get(instruction, ("", []))[1])
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for member in hlo["computations"].get(comp, []):
            op_name, calls = hlo["instructions"][member]
            scope = scope_of(op_name)
            if scope:
                counts[scope] += 1
            todo.extend(calls)
    return min(counts, key=lambda s: (-counts[s], s)) if counts else None


def hlo_modules(planes: dict) -> dict:
    """{program id: parse_hlo(...)} of every program whose HLO the trace holds."""
    out = {}
    for record in planes.get("/host:metadata", {"events": {}})["events"].values():
        program = re.search(r"\((\d+)\)$", record["name"])
        if program and "Hlo Proto" in record:
            out[int(program.group(1))] = parse_hlo(record["Hlo Proto"])
    return out


def scoped_seconds(planes: dict, modules: dict = None):
    """({scope or None: seconds}, {name, seconds} of the unscoped operations)
    over the first chip's ``XLA Ops``; ``modules`` defaults to the HLO the
    trace holds."""
    chips = sorted(p for p in planes if p.startswith(trace_reduce.DEVICE_PLANE))
    if not chips:
        return {}, {}
    chip = planes[chips[0]]
    modules = hlo_modules(planes) if modules is None else modules
    scope_by_event = {}
    for mid, record in chip["events"].items():
        scope = scope_of(record.get("tf_op"))
        if scope is None and "tf_op" not in record:
            hlo = modules.get(record.get("program_id"))
            if hlo is not None:
                scope = body_scope(hlo, record["display_name"])
        scope_by_event[mid] = scope
    by_scope, unscoped = defaultdict(float), defaultdict(float)
    for mid, _, duration_ns in chip["lines"].get(trace_reduce.OPS_LINE, []):
        scope = scope_by_event.get(mid)
        by_scope[scope] += duration_ns / 1e9
        if scope is None:
            unscoped[chip["events"].get(mid, {}).get("display_name", "?")] += duration_ns / 1e9
    return dict(by_scope), dict(unscoped)


def idle_chain(planes: dict, gaps: int = 5) -> list:
    """[[gap seconds, the loop's span, [tfr: spans open at its middle]], ...]."""
    chips = sorted(p for p in planes if p.startswith(trace_reduce.DEVICE_PLANE))
    if not chips:
        return []
    ops = planes[chips[0]]["lines"].get(trace_reduce.OPS_LINE, [])
    spans = []
    for name, plane in planes.items():
        if not name.startswith("/host:"):
            continue
        wanted = {mid: r["name"] for mid, r in plane["events"].items()
                  if r["name"].startswith("tfr:") or r["name"] in trace_reduce.LOOP_SPANS}
        for events in plane["lines"].values():
            spans += [(s, s + d, wanted[mid]) for mid, s, d in events if mid in wanted]
    rank = {name: i for i, name in enumerate(CHAIN)}
    out = []
    longest = sorted(trace_reduce.gaps_between((s, s + d) for _, s, d in ops),
                     key=lambda ab: ab[0] - ab[1])[:gaps]
    for a, b in longest:
        mid = (a + b) / 2
        open_ = [name for s, e, name in spans if s <= mid < e]
        loop = [n for n in open_ if n in trace_reduce.LOOP_SPANS]
        feed = sorted({n[len("tfr:"):] for n in open_ if n.startswith("tfr:")},
                      key=lambda n: (rank.get(n, len(rank)), n))
        out.append([(b - a) / 1e9, loop[0] if loop else "no_span", feed])
    return out


def _parsed(ctx):
    """Parse the run's trace once; keep what the quantities need on ``ctx``."""
    if "trace_scope" not in ctx:
        path = trace_reduce.find_trace(ctx["env"].trace_dir)
        ctx["trace_scope"] = None
        if path is not None:
            with open(path, "rb") as f:
                planes = parse_xspace(f.read())
            by_scope, unscoped = scoped_seconds(planes)
            ctx["trace_scope"] = by_scope
            top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:5]
            ctx["env"].info("scopes", seconds={str(k): v for k, v in by_scope.items()},
                            unscoped_top=top)
            ctx["env"].info("idle_chain", gaps=idle_chain(planes))
    return ctx["trace_scope"]


def read(ctx, scopes=(), quantity: str = "ms_per_step"):
    by_scope, steps = _parsed(ctx), ctx["trace"]["steps"]
    if not by_scope or not steps or not any(by_scope):  # no chip, or no scope anywhere
        return None
    if quantity == "ms_per_step":
        return sum(by_scope.get(s, 0.0) for s in scopes) / steps * 1e3
    if quantity == "unscoped_pct":
        return 100.0 * by_scope.get(None, 0.0) / sum(by_scope.values())
    raise ValueError(f"unknown quantity {quantity!r}")
