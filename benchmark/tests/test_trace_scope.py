"""readers/trace_scope.py and readers/trace_host_span_ms.py on a trace encoded
by hand, byte for byte, and on a cut of a real chip trace of each mix.

``recorded_scopes.json`` is the first three steps of ``criteo_mlperf.train``
and the first four of ``criteo_mlperf.score`` from this PR's first traced
runs on a TPU v5 lite (PR 24), as ``trace_scope.parse_xspace`` read them
with the metadata's stats kept (``python -m benchmark.tests.test_trace_scope
<mix>=<file.xplane.pb> ...`` makes the cut): the device's ``XLA Ops``, each
operation's ``display_name`` / ``tf_op`` / ``program_id``, the instructions
of the programs' HLO that a fusion without ``tf_op`` is read through, and
the host's ``tfr:*`` and loop spans over the same stretch.
"""

import json
import os
import struct
import sys

import pytest

from benchmark.readers import trace_host_span_ms, trace_host_span_or_zero, trace_scope as ts

HERE = os.path.dirname(os.path.abspath(__file__))


# -- a protobuf encoder, as small as the reader's decoder ----------------------


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def msg(*pairs) -> bytes:
    """(field, int) -> varint; (field, float) -> fixed64; (field, bytes or
    str) -> length-delimited."""
    out = b""
    for num, value in pairs:
        if isinstance(value, int):
            out += varint(num << 3) + varint(value)
        elif isinstance(value, float):
            out += varint(num << 3 | 1) + struct.pack("<d", value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(num << 3 | 2) + varint(len(value)) + value
    return out


def entry(key: int, value: bytes) -> bytes:
    return msg((1, key), (2, value))


def event(mid: int, offset_ps: int, duration_ps: int) -> bytes:
    return msg((1, mid), (2, offset_ps), (3, duration_ps),
               (4, msg((1, 9), (2, 1.0))))  # a stat of its own (a double), skipped


PROGRAM = 2 ** 63 + 77  # a program id that needs all 64 bits


def hand_made_xspace() -> bytes:
    def instruction(name, op_name=None, calls=()):
        pairs = [(1, name), (2, "fusion")]
        if op_name:
            pairs.append((7, msg((1, "t"), (2, op_name))))
        pairs += [(38, c) for c in calls]
        return msg(*pairs)

    hlo = msg((1, msg(
        (1, "jit_step"),
        (3, msg((1, "main"), (5, 5),
                (2, instruction("fusion.12", calls=[9])),
                (2, instruction("copy-done.1")))),
        (3, msg((1, "fused.9"), (5, 9),
                (2, instruction("mul.1", "jit(f)/tfr.table_scatter/mul")),
                (2, instruction("scatter.8")),
                (2, instruction("fusion.20", "jit(f)/tfr.segment_sum/mul", calls=[11])))),
        (3, msg((1, "fused.11"), (5, 11),
                (2, instruction("add.1", "jit(f)/tfr.table_scatter/add")),
                (2, instruction("neg.1", "jit(f)/tfr.table_scatter/neg")))),
    )))
    ms = 10 ** 9  # ps
    device = msg(
        (2, "/device:TPU:0"),
        (3, msg((2, "XLA Ops"), (3, 1000),
                (4, event(1, 0, 4 * ms)), (4, event(2, 4 * ms, 2 * ms)),
                (4, event(3, 6 * ms, 10 * ms)), (4, event(4, 16 * ms, 1 * ms)),
                # a gap of 8 ms, then the next step
                (4, event(1, 25 * ms, 4 * ms)), (4, event(3, 29 * ms, 10 * ms)))),
        (3, msg((2, "XLA Modules"), (3, 1000), (4, event(5, 0, 17 * ms)))),
        (4, entry(1, msg((1, 1), (2, "%fusion.1 = bf16[8]"), (4, "fusion.1"),
                         (5, msg((1, 1), (5, "jit(f)/tfr.gather/gather:")))))),
        (4, entry(2, msg((1, 2), (2, "%fusion.2 = bf16[8]"), (4, "fusion.2"),
                         (5, msg((1, 1), (7, 3)))))),  # tf_op by reference
        (4, entry(3, msg((1, 3), (2, "%fusion.12 = f32[8]"), (4, "fusion.12"),
                         (5, msg((1, 2), (3, PROGRAM)))))),
        (4, entry(4, msg((1, 4), (2, "%copy-done.1 = f32[8]"), (4, "copy-done.1"),
                         (5, msg((1, 2), (3, PROGRAM)))))),
        (4, entry(5, msg((1, 5), (2, "jit_step"), (4, "jit_step")))),
        (5, entry(1, msg((1, 1), (2, "tf_op")))),
        (5, entry(2, msg((1, 2), (2, "program_id")))),
        (5, entry(3, msg((1, 3), (2, "jit(f)/tfr.top_mlp/transpose(jvp(tfr.interaction))/dot:")))),
    )
    metadata = msg(
        (2, "/host:metadata"),
        (4, entry(PROGRAM, msg((1, PROGRAM), (2, f"jit_step({PROGRAM})"),
                               (5, msg((1, 1), (6, hlo)))))),
        (5, entry(1, msg((1, 1), (2, "Hlo Proto")))),
    )
    names = ["wait_batch", "observe", "tfr:starved.device", "tfr:starved.host", "tfr:decode",
             "tfr:blocked.batch", "PjRt runtime noise"]
    mid = {n: i + 1 for i, n in enumerate(names)}
    host = msg(
        (2, "/host:CPU"),
        # the loop's thread: waiting for a batch across the gap [17, 25] ms
        (3, msg((2, "python3"), (3, 1000),
                (4, event(mid["observe"], 0, 16 * ms)),
                (4, event(mid["wait_batch"], 16 * ms, 9 * ms)),
                (4, event(mid["tfr:starved.device"], 16 * ms + 1000, 9 * ms - 2000)),
                (4, event(mid["PjRt runtime noise"], 0, 30 * ms)))),
        # the transfer thread (its line's clock starts elsewhere) and the decode thread
        (3, msg((2, "python3"), (3, 500),
                (4, event(mid["tfr:starved.host"], 15 * ms + 500000, 9 * ms)))),
        (3, msg((2, "python3"), (3, 1000),
                (4, event(mid["tfr:blocked.batch"], 0, 12 * ms)),
                (4, event(mid["tfr:decode"], 12 * ms, 12 * ms)))),
        *[(4, entry(i, msg((1, i), (2, n)))) for n, i in mid.items()],
    )
    return msg((1, device), (1, metadata), (1, host))


def test_the_wire_by_hand():
    planes = ts.parse_xspace(hand_made_xspace())
    assert set(planes) == {"/device:TPU:0", "/host:metadata", "/host:CPU"}
    chip = planes["/device:TPU:0"]
    assert [(m, s, d) for m, s, d in chip["lines"]["XLA Ops"][:2]] == [
        (1, 1000.0, 4e6), (2, 1000.0 + 4e6, 2e6)]  # ns, from the line's own start
    assert chip["events"][1]["tf_op"] == "jit(f)/tfr.gather/gather:"
    assert chip["events"][2]["tf_op"].startswith("jit(f)/tfr.top_mlp/")  # the reference followed
    assert chip["events"][3]["program_id"] == PROGRAM and "tf_op" not in chip["events"][3]
    # the host's plane keeps the program's and the loop's spans and nothing else
    kept = {planes["/host:CPU"]["events"][m]["name"]
            for evs in planes["/host:CPU"]["lines"].values() for m, _, _ in evs}
    assert kept == {"wait_batch", "observe", "tfr:starved.device", "tfr:starved.host",
                    "tfr:decode", "tfr:blocked.batch"}
    hlo = ts.hlo_modules(planes).get(PROGRAM)
    assert hlo["instructions"]["fusion.12"] == ("", [9])
    assert hlo["computations"][9] == ["mul.1", "scatter.8", "fusion.20"]
    assert ts.hlo_modules(planes).get(12345) is None


def test_scope_sums_and_unscoped_make_the_total():
    planes = ts.parse_xspace(hand_made_xspace())
    by_scope, unscoped = ts.scoped_seconds(planes)
    assert by_scope == pytest.approx({
        "tfr.gather": 0.008,          # fusion.1 twice, by its own tf_op
        "tfr.interaction": 0.002,     # fusion.2: the innermost of two scopes
        "tfr.table_scatter": 0.020,   # fusion.12 twice: 3 of its body's 4 scoped instructions
        None: 0.001,                  # copy-done.1: no tf_op, no body
    })
    assert unscoped == pytest.approx({"copy-done.1": 0.001})
    ops = planes["/device:TPU:0"]["lines"]["XLA Ops"]
    assert sum(by_scope.values()) == pytest.approx(sum(d for _, _, d in ops) / 1e9)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(<unknown>)/transpose(jvp(tfr.top_mlp))/dot_general:", "tfr.top_mlp"),
    ("jit(f)/tfr.gather/jit(take_along_axis)/gather", "tfr.gather"),
    ("jit(f)/tfr.a/x/tfr.b_c/y", "tfr.b_c"),
    ("jit(<unknown>)/reduce_sum:", None),
    ("", None),
    (None, None),
])
def test_the_innermost_scope_wins(op_name, scope):
    assert ts.scope_of(op_name) == scope


def test_the_idle_chain_names_what_was_open_at_a_planted_gap():
    chain = ts.idle_chain(ts.parse_xspace(hand_made_xspace()))
    # one gap, [17, 25] ms: the loop waited for a batch, the transfer thread for
    # the pack thread, and the decode thread was decoding: it led
    assert chain == [[pytest.approx(0.008), "wait_batch",
                      ["starved.device", "starved.host", "decode"]]]


class FakeEnv:
    def __init__(self, trace_dir):
        self.trace_dir, self.lines = trace_dir, []

    def info(self, what, **fields):
        self.lines.append((what, fields))


def ctx_for(tmp_path, data: bytes, steps: int):
    where = tmp_path / "plugins" / "profile" / "2026_09_27"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(data)
    return {"env": FakeEnv(str(tmp_path)), "trace": {"steps": steps}}


def test_read_parses_once_and_gives_each_quantity(tmp_path):
    ctx = ctx_for(tmp_path, hand_made_xspace(), steps=2)
    assert ts.read(ctx, scopes=["tfr.table_scatter"]) == pytest.approx(10.0)
    assert ts.read(ctx, scopes=["tfr.gather", "tfr.interaction"]) == pytest.approx(5.0)
    assert ts.read(ctx, scopes=["tfr.table_cast"]) == 0.0  # looked for, and nothing spent there
    assert ts.read(ctx, quantity="unscoped_pct") == pytest.approx(100 * 0.001 / 0.031)
    assert [what for what, _ in ctx["env"].lines] == ["scopes", "idle_chain"]  # once
    with pytest.raises(ValueError):
        ts.read(ctx, quantity="no_such")


def test_a_program_without_scopes_gives_nothing_to_read(tmp_path):
    plain = hand_made_xspace().replace(b"tfr.", b"xyz.")  # same lengths, no scope anywhere
    ctx = ctx_for(tmp_path, plain, steps=2)
    assert ts.read(ctx, scopes=["tfr.gather"]) is None
    assert ts.read(ctx, quantity="unscoped_pct") is None
    empty = {"env": FakeEnv(str(tmp_path / "nowhere")), "trace": {"steps": 2}}
    assert ts.read(empty, scopes=["tfr.gather"]) is None  # no trace at all


def test_host_span_ms_by_hand():
    ctx = {"trace": {"host": {"tfr:h2d": [4, 0.002], "tfr:h2d_land": [4, 0.006], "x": [9, 1.0]}}}
    assert trace_host_span_ms.read(ctx, spans=["tfr:h2d", "tfr:h2d_land"], per="tfr:h2d") == 2.0
    assert trace_host_span_ms.read(ctx, spans=["tfr:h2d", "tfr:gone"], per="tfr:h2d") == 0.5
    assert trace_host_span_ms.read(ctx, spans=["tfr:h2d"], per="tfr:gone") is None


def test_a_span_that_never_opened_reads_zero_beside_its_witness():
    trace = {"window_s": 4.0, "host": {"tfr:pack": [8, 0.01], "tfr:blocked.host": [7, 3.0]}}
    read = trace_host_span_or_zero.read
    assert read({"trace": trace}, span="tfr:blocked.host", witness="tfr:pack") == 75.0
    assert read({"trace": trace}, span="tfr:blocked.batch", witness="tfr:pack") == 0.0
    # the parent of PR 24 has neither: nothing to read, not a zero
    assert read({"trace": trace}, span="tfr:blocked.device", witness="tfr:h2d_land") is None



# -- the recorded cut ----------------------------------------------------------


def cut(path: str, steps: int) -> dict:
    """The first ``steps`` runs of the program with most device time, and all
    that ran before them, out of one ``*.xplane.pb``."""
    with open(path, "rb") as f:
        planes = ts.parse_xspace(f.read())
    chip = planes["/device:TPU:0"]
    by_module = {}
    for mid, start, dur in chip["lines"]["XLA Modules"]:
        by_module.setdefault(mid, []).append((start, start + dur))
    runs = sorted(max(by_module.values(), key=lambda r: sum(e - s for s, e in r)))
    t0 = min(s for _, s, _ in chip["lines"]["XLA Ops"])
    t1 = runs[steps - 1][1]
    ops = [[m, s - t0, d] for m, s, d in chip["lines"]["XLA Ops"] if s < t1]
    events = {str(m): {"name": chip["events"][m]["name"][:60],
                       **{k: chip["events"][m][k] for k in ("display_name", "tf_op", "program_id")
                          if k in chip["events"][m]}}
              for m in {m for m, _, _ in ops}}
    modules, hlo = ts.hlo_modules(planes), {}
    for record in events.values():
        whole = modules.get(record.get("program_id")) if "tf_op" not in record else None
        if whole is None:
            continue
        kept = hlo.setdefault(str(record["program_id"]), {"instructions": {}, "computations": {}})
        todo, name = [], record["display_name"]
        if name in whole["instructions"]:
            kept["instructions"][name] = whole["instructions"][name]
            todo = list(whole["instructions"][name][1])
        while todo:
            comp = todo.pop()
            if str(comp) in kept["computations"]:
                continue
            kept["computations"][str(comp)] = whole["computations"][comp]
            for member in whole["computations"][comp]:
                kept["instructions"][member] = whole["instructions"][member]
                todo.extend(whole["instructions"][member][1])
    host = planes["/host:CPU"]
    spans = [[host["events"][m]["name"], s - t0, d]
             for evs in host["lines"].values() for m, s, d in evs if s + d > t0 and s < t1]
    return {"steps": steps, "ops": ops, "events": events, "hlo": hlo, "host_spans": spans}


def planes_of(cut_: dict):
    """The cut back in the shape ``parse_xspace`` gives, and its HLO."""
    names = sorted({n for n, _, _ in cut_["host_spans"]})
    planes = {
        "/device:TPU:0": {"lines": {"XLA Ops": [tuple(e) for e in cut_["ops"]]},
                          "events": {int(m): r for m, r in cut_["events"].items()}},
        "/host:CPU": {"lines": {"python3": [(names.index(n), s, d)
                                            for n, s, d in cut_["host_spans"]]},
                      "events": {i: {"name": n} for i, n in enumerate(names)}},
    }
    modules = {int(p): {"instructions": {k: tuple(v) for k, v in h["instructions"].items()},
                        "computations": {int(c): m for c, m in h["computations"].items()}}
               for p, h in cut_["hlo"].items()}
    return planes, modules


def recorded(mix: str) -> dict:
    with open(os.path.join(HERE, "recorded_scopes.json")) as f:
        return json.load(f)[mix]


@pytest.mark.parametrize("mix", ["train", "score"])
def test_on_the_recorded_cut(mix):
    from tpu_tfrecord.tracing import ANNOTATIONS

    cut_ = recorded(mix)
    planes, modules = planes_of(cut_)
    by_scope, unscoped = ts.scoped_seconds(planes, modules)
    events = planes["/device:TPU:0"]["events"]
    by_name = {}
    for mid, _, d in cut_["ops"]:
        name = events[mid]["display_name"]
        by_name[name] = by_name.get(name, 0.0) + d / 1e9
    total, steps = sum(by_name.values()), cut_["steps"]
    # scope sums + unscoped = the operations' total, and every scope is a listed one
    assert sum(by_scope.values()) == pytest.approx(total, rel=1e-12)
    assert sum(unscoped.values()) == pytest.approx(by_scope[None], rel=1e-12)
    assert set(by_scope) - {None} <= set(ANNOTATIONS)
    assert by_scope[None] / total < 0.01
    record = {r["display_name"]: r for r in events.values()}
    if mix == "train":
        # the two scatters carry no op_name and are read through their fused bodies
        assert "tf_op" not in record["fusion.12"] and "tf_op" not in record["fusion.11"]
        scatter = by_scope["tfr.table_scatter"]
        assert by_name["fusion.12"] <= scatter <= 1.01 * by_name["fusion.12"]
        assert 24.5e-3 < scatter / steps < 25.6e-3
        accum = by_scope["tfr.accum_update"]
        assert by_name["fusion.11"] + by_name["fusion.7"] <= accum < 13.5e-3 * steps
        assert 29e-3 * steps < by_scope["tfr.dedup_sort"] + by_scope["tfr.segment_sum"] < 32e-3 * steps
        assert record["fusion.21"]["tf_op"].startswith(
            "jit(<unknown>)/transpose(jvp(tfr.interaction))/")  # a backward op, forward's scope
        assert "reshape.93" in unscoped  # no op_name and no body: honestly unscoped
        assert set(by_scope) - {None} == {
            "tfr.gather", "tfr.bottom_mlp", "tfr.interaction", "tfr.top_mlp", "tfr.dense_update",
            "tfr.dedup_sort", "tfr.segment_sum", "tfr.accum_update", "tfr.table_scatter"}
    else:
        cast = by_scope["tfr.table_cast"]
        assert cast == pytest.approx(by_name["convert_element_type.26"], rel=1e-9)
        assert 15.8e-3 < cast / steps < 16.1e-3
        assert 5.3e-3 < by_scope["tfr.gather"] / steps < 5.7e-3
    chain = ts.idle_chain(planes)
    assert len(chain) == 5 and chain[0][1] == "dispatch_split"  # before the first step
    assert [seconds for seconds, _, _ in chain] == sorted((g[0] for g in chain), reverse=True)
    # between steps the loop observes while the transfer thread waits for room: the device leads
    assert all(loop == "observe" and "blocked.device" in feed for _, loop, feed in chain[1:])


if __name__ == "__main__":  # <mix>=<file.xplane.pb>:<steps> ...
    out = {}
    for arg in sys.argv[1:]:
        mix, rest = arg.split("=")
        path, steps = rest.rsplit(":", 1)
        out[mix] = cut(path, int(steps))
    with open(os.path.join(HERE, "recorded_scopes.json"), "w") as f:
        json.dump(out, f, separators=(",", ":"))
