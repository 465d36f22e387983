"""``readers/host_log.py`` on a hand-made log: records before, inside and
after a two-part window, a span that straddles a part's edge; a program that
never started its watch, one that has no log, a ring that dropped what a part
held; the two clocks matched by the spans both hold."""

import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import trace_reduce
from benchmark.readers import host_log
from tpu_tfrecord import tracing

TRACED, UNTRACED = (100.0, 104.0), (106.0, 116.0)
WINDOWS = [TRACED, UNTRACED]


def record(name, begin, end, thread=1, **args):
    return tracing.HostEvent(name, begin, end, thread, args or None)


RECORDS = [
    record("host:gc", 20.0, 20.5, generation=2, collected=9),       # set-up
    record("host:gc", 50.0, 50.25, generation=2, collected=0),
    record("host:pause", 60.0, 61.0, late_s=1.0, cause="unknown"),
    record("host:gc", 99.9, 100.1, generation=1, collected=0),       # ends inside the window: not set-up
    record("tfr:h2d", 101.0, 101.001), record("tfr:h2d_land", 101.001, 101.005),
    record("tfr:blocked.device", 101.0, 103.0, thread=2),            # traced part
    record("host:pause", 102.0, 102.2, late_s=0.2, cause="steal", steal_s=0.3),
    record("tfr:blocked.device", 103.5, 106.5, thread=2),            # straddles both edges of the gap
    record("host:gc", 105.0, 105.5, generation=2, collected=0),      # between the parts: in neither
    record("tfr:h2d", 107.0, 107.001), record("tfr:h2d_land", 107.001, 107.002),
    record("tfr:h2d", 108.0, 108.0005), record("tfr:h2d_land", 108.0005, 108.001),
    record("tfr:blocked.device", 108.0, 112.0, thread=2),
    record("tfr:blocked.host", 107.0, 110.0, thread=3), record("tfr:blocked.host", 107.0, 109.0, thread=4),
    record("tfr:decode", 107.0, 107.002), record("tfr:decode", 108.0, 108.004),
    record("tfr:decode", 109.0, 109.009),
    record("host:pause", 110.0, 110.3, late_s=0.3, cause="runqueue"),
    record("host:gc", 111.0, 111.004, generation=2, collected=3),
    record("tfr:blocked.device", 115.0, 118.0, thread=2),            # runs past the window's end
    record("host:gc", 125.0, 127.0, generation=2, collected=0),      # the reference, after the window
]


class Info:
    """The part of ``run.Env`` the reader touches."""

    traced, trace_dir = None, "/nonexistent"

    def __init__(self):
        self.lines = []

    def info(self, what, **fields):
        self.lines.append((what, fields))


@pytest.fixture
def log(monkeypatch):
    state = {"records": list(RECORDS), "dropped": 0, "reads": 0}

    def events(since=None, until=None):
        state["reads"] += 1
        return sorted(state["records"], key=lambda r: r.begin)

    monkeypatch.setattr(tracing, "host_events", events)
    monkeypatch.setattr(tracing, "host_log_dropped", lambda: state["dropped"])
    monkeypatch.setattr(tracing, "watching", lambda: True)
    return state


def ctx(windows=WINDOWS, env=False):
    made = {"measured": {"windows": list(windows)}}
    if env:
        made["env"] = Info()
    return made


@pytest.mark.parametrize("what, params, want", [
    ("sum_ms", dict(names=["host:pause"]), 200.0 + 300.0),
    ("max_ms", dict(names=["host:pause"]), 300.0),
    ("sum_ms", dict(names=["host:gc"]), 4.0),                        # 111.0 alone began in a part
    ("sum_ms", dict(names=["tfr:starved.batch"]), 0.0),              # a quiet run reads 0, not None
    ("max_ms", dict(names=["tfr:starved.batch"]), 0.0),
    ("sum_s", dict(names=["host:gc"], part="setup"), 0.75),          # what ENDED before the window
    ("sum_ms", dict(names=["host:pause"], part="setup"), 1000.0),
    ("median_ms", dict(names=["tfr:decode"], part="untraced"), 4.0),
    ("median_ms", dict(names=["tfr:pack"], part="untraced"), None),  # a median of nothing
    ("per_ms", dict(names=["tfr:h2d", "tfr:h2d_land"], per="tfr:h2d", part="untraced"),
     (1.0 + 1.0 + 0.5 + 0.5) / 2),
    ("per_ms", dict(names=["tfr:h2d", "tfr:h2d_land"], per="tfr:h2d", part="window"),
     (5.0 + 2.0 + 1.0) / 3),
    ("per_ms", dict(names=["tfr:h2d"], per="tfr:pack_tokens"), None),
    # cut to the part: 106-106.5, 108-112, 115-116 of ten seconds
    ("busy_pct", dict(names=["tfr:blocked.device"], part="untraced"), 100.0 * 5.5 / 10.0),
    ("busy_pct", dict(names=["tfr:blocked.device"], part="window"), 100.0 * (2.0 + 0.5 + 5.5) / 14.0),
    ("busy_pct", dict(names=["tfr:blocked.host"], part="untraced"), 100.0 * 5.0 / 10.0),  # two threads
    ("busy_pct", dict(names=["tfr:blocked.batch"], part="untraced"), 0.0),
])
def test_each_quantity_over_its_part(log, what, params, want):
    got = host_log.read(ctx(), what, **params)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_untraced_part_of_a_one_part_window_is_nothing(log):
    one = ctx(windows=[TRACED])
    assert host_log.read(one, "busy_pct", names=["tfr:blocked.device"], part="untraced") is None
    assert host_log.read(one, "sum_ms", names=["host:pause"]) == pytest.approx(200.0)


def test_a_third_part_is_untraced_too(log):
    three = ctx(windows=[TRACED, (106.0, 110.0), (110.0, 116.0)])
    assert host_log.read(three, "sum_ms", names=["host:pause"], part="untraced") == pytest.approx(300.0)


def test_a_program_without_a_log_reports_nothing(log, monkeypatch):
    monkeypatch.delattr(tracing, "host_events")
    monkeypatch.delattr(tracing, "watching")  # the parent has neither
    for part in ("window", "untraced", "setup"):
        assert host_log.read(ctx(), "sum_ms", names=["host:pause"], part=part) is None


def test_a_program_that_never_started_its_watch_reports_nothing(log, monkeypatch):
    monkeypatch.setattr(tracing, "watching", lambda: False)
    assert host_log.read(ctx(), "sum_ms", names=["host:pause"]) is None
    assert host_log.read(ctx(), "busy_pct", names=["tfr:blocked.host"], part="untraced") is None


def test_a_part_whose_records_the_ring_dropped_reports_nothing(log):
    # the ring let go of everything that ended before 105.5: set-up's and the traced part's
    log["records"] = [r for r in RECORDS if r.end >= 105.5]
    log["dropped"] = len(RECORDS) - len(log["records"])
    made = ctx()
    assert host_log.read(made, "sum_s", names=["host:gc"], part="setup") is None
    assert host_log.read(made, "sum_ms", names=["host:pause"], part="window") is None
    assert host_log.read(made, "sum_ms", names=["host:pause"], part="untraced") == pytest.approx(300.0)
    log["dropped"] = 0  # the same records and no drop: nothing is in doubt
    assert host_log.read(ctx(), "sum_ms", names=["host:pause"], part="window") == pytest.approx(300.0)


def test_the_log_is_read_once_a_run_and_says_so_in_one_line(log):
    made = ctx(env=True)
    for _ in range(3):
        host_log.read(made, "sum_ms", names=["host:pause"])
    assert log["reads"] == 1
    ((what, fields),) = made["env"].lines
    assert what == "host_log" and fields["records"] == len(RECORDS) and fields["dropped"] == 0
    assert [p["cause"] for p in fields["pauses"]] == ["steal", "runqueue"]
    assert fields["pauses"][0]["at_s"] == pytest.approx(2.0) and fields["pauses"][0]["steal_s"] == 0.3
    assert fields["collections"] == 1 and fields["collections_longest"][0]["collected"] == 3
    assert fields["setup_pauses"] == 1 and fields["setup_pause_s"] == pytest.approx(1.0)
    assert fields["by_name"]["tfr:h2d"] == 3
    json.dumps(fields)  # the line is printed as JSON


def test_an_unknown_quantity_or_part_is_an_error(log):
    with pytest.raises(ValueError):
        host_log.read(ctx(), "bytes", names=["tfr:h2d"])
    with pytest.raises(ValueError):
        host_log.read(ctx(), "sum_ms", names=["tfr:h2d"], part="reference")


# -- the two clocks ---------------------------------------------------------------------


def test_the_clocks_are_matched_by_the_spans_both_hold():
    log_s = [10.0, 10.5, 11.7, 12.0, 13.1, 13.2, 14.9]           # the log holds more than the trace
    trace_s = [t + 5000.0 + jitter for t, jitter in zip(log_s[2:6], (1e-6, -2e-6, 0.0, 3e-6))]
    offset, matched, disagree = host_log.clock_offset(trace_s, log_s)
    assert offset == pytest.approx(5000.0, abs=1e-5) and matched == 4 and disagree < 1e-5
    assert host_log.clock_offset([], log_s) is None
    assert host_log.clock_offset(trace_s + [1.0, 2.0, 3.0, 4.0], log_s) is None  # more than the log holds


def test_idle_host_names_what_the_log_had_open_at_a_gaps_middle():
    offset = 7000.0  # trace clock = log clock + 7000 s
    h2d = [r for r in RECORDS if r.name == "tfr:h2d" and r.begin < 104.0] + [
        record("tfr:h2d", 102.0, 102.001), record("tfr:h2d", 103.0, 103.002)]
    records = sorted(RECORDS + h2d[1:], key=lambda r: r.begin)

    def ns(t):
        return (t + offset) * 1e9

    planes = {
        "/device:TPU:0": {"events": {}, "lines": {trace_reduce.OPS_LINE: [
            (1, ns(100.0), 1.9e9), (1, ns(102.3), 0.2e9), (1, ns(102.6), 1.0e9)]}},
        "/host:CPU": {"events": {7: {"name": "tfr:h2d"}, 8: {"name": "observe"}},
                      "lines": {"transfer": [(7, ns(r.begin), 1e6) for r in h2d],
                                "loop": [(8, ns(100.0), 4e9)]}},
    }
    found = host_log.idle_host(planes, records, TRACED)
    assert found["matched"] == 3 and found["offset_s"] == pytest.approx(offset, abs=1e-4)
    (longest, open_), (second, _) = found["gaps"]
    assert longest == pytest.approx(400.0) and second == pytest.approx(100.0)
    # the gap 101.9-102.3: its middle, 102.1, lies in the pause and in the blocked put
    assert [row[0] for row in open_] == ["tfr:blocked.device", "host:pause"]
    assert open_[1][1] == pytest.approx(100.0, abs=0.5) and open_[1][2] == pytest.approx(200.0)
    assert host_log.idle_host({"/host:CPU": planes["/host:CPU"]}, records, TRACED) is None


# -- the files -----------------------------------------------------------------------------

ALL_CELLS = ("host_pause_ms", "host_pause_max_ms", "gc_pause_ms", "setup_gc_s", "h2d_ms.untraced",
             "h2d_blocked_pct.untraced", "pack_blocked_pct.untraced", "decode_blocked_pct.untraced")
CRITEO = {"decode_ms.untraced": ("criteo_mlperf.train", "criteo_mlperf.score")}


@pytest.mark.parametrize("name", ALL_CELLS + tuple(CRITEO))
def test_a_new_metrics_file_fires_in_the_cells_that_report_it_and_no_other(name):
    """``run.per_layer`` goes by the file's ``mixes``, the driver by
    ``BENCHMARK.json``'s ``workloads``, and the two agree cell by cell."""
    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    spec = bench_run.load_json("layer_metrics", name + ".json")
    assert spec["reader"]["kind"] == "host_log"
    for cell in bench["workloads"]:
        fires = spec["mixes"] is None or cell["traffic"] in spec["mixes"]
        assert fires == (name in bench_run.reports(bench, "per_layer", cell["name"])), cell["name"]
        assert fires == (name in ALL_CELLS or cell["name"] in CRITEO[name])


@pytest.mark.parametrize("name", ALL_CELLS + tuple(CRITEO))
def test_a_new_metrics_file_reads_a_number_from_the_hand_made_log(log, name):
    params = dict(bench_run.load_json("layer_metrics", name + ".json")["reader"])
    assert params.pop("kind") == "host_log"
    assert host_log.read(ctx(), **params) is not None
    assert host_log.read(ctx(windows=[TRACED]), **params) is None or params.get("part") != "untraced"
