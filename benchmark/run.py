#!/usr/bin/env python3
"""One run of one benchmark cell, one process.

    python3 benchmark/run.py --workload W --seed N --seconds S --trace 0|1

knows no model and no data set: it looks ``W`` up in BENCHMARK.json, loads
the configuration's ``file`` and ``traffic/<traffic>.json``, and finds the
rest by the names those two carry:

    configuration "data"   data/<name>.py    write(data_dir, seed, cfg, mix) -> expected,
                                             describe(expected, cfg, mix) -> [data] fields
    configuration "model"  models/<name>.py  handed to the loop as env.model
    mix "loop"             loops/<name>.py   run(env) -> what was measured and compared
    "rehearsal" in either  the keys that file overrides under --rehearse

With ``--trace 1`` it evaluates every ``layer_metrics/*.json`` whose mixes
include the cell's through ``readers/<kind>.py``. The last line of stdout is
the result object, the numbers compared beside their limits last in it and
on stderr; counts and compile seconds go on earlier lines. See
benchmark/README.md.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


class Refused(Exception):
    """The run cannot be a measurement; exit non-zero with no result line."""


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def at_rehearsal_size(spec: dict) -> dict:
    """A configuration or a mix with its own ``"rehearsal"`` laid over it:
    the sizes at which a CPU can walk the whole path. A group (the limits)
    is overridden key by key."""
    spec = dict(spec)
    for key, small in spec.pop("rehearsal", {}).items():
        group = isinstance(small, dict) and isinstance(spec.get(key), dict)
        spec[key] = {**spec[key], **small} if group else small
    return spec


def cpu_seconds() -> float:
    s, c = resource.getrusage(resource.RUSAGE_SELF), resource.getrusage(resource.RUSAGE_CHILDREN)
    return s.ru_utime + s.ru_stime + c.ru_utime + c.ru_stime


class Env:
    """What a loop gets: the cell's files, the seed's data, the device, the
    spans, and ``measure`` — the one place a window is opened."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.setup_s = None
        self.traced = None
        self.compared = {}  # {number: [value, limit]}, filled by window.judge
        self.memory_peak_bytes = 0

    def info(self, what: str, **fields) -> None:
        print(f"[{what}] " + json.dumps(fields, sort_keys=True, default=str), flush=True)

    def measure(self, loop) -> dict:
        import jax

        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **kw: compiles.append(name)
            if name.endswith("backend_compile_duration") else None
        )
        self.setup_s = time.perf_counter() - T_START
        cpu0 = cpu_seconds()
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            traced_for = min(self.seconds, self.mix["trace_seconds"])
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            try:
                parts = [loop.run_for(traced_for)]
            finally:
                jax.profiler.stop_trace()
            self.traced = parts[0]
            if self.seconds - traced_for >= 1.0:
                parts.append(loop.run_for(self.seconds - traced_for))
        else:
            parts = [loop.run_for(self.seconds)]
        cpu1 = cpu_seconds()
        stats = self.device.memory_stats() or {}
        self.memory_peak_bytes = int(stats.get("peak_bytes_in_use", 0))
        import numpy as np

        return {
            "steps": sum(p["steps"] for p in parts),
            "window_s": sum(p["t1"] - p["t0"] for p in parts),
            "windows": [(p["t0"], p["t1"]) for p in parts],
            "gaps_s": np.concatenate([p["gaps_s"] for p in parts]),
            "spent_s": np.concatenate([p["spent_s"] for p in parts]),
            "waited_s": sum(p["waited_s"] for p in parts),
            "cpu_s": cpu1 - cpu0,
            "compiles_in_window": len(compiles),
        }


def longest_gaps(measured: dict, count: int = 5) -> list:
    """[[gap number, ms, of which the loop waited for the batch, dispatched,
    observed], ...]: the stalls a user would feel, longest first."""
    import numpy as np

    gaps, spent = measured["gaps_s"], measured["spent_s"] * 1e3
    return [[int(i), float(gaps[i]) * 1e3, *map(float, spent[i])]
            for i in np.argsort(-gaps)[:count]]


def end_to_end(env, measured: dict) -> dict:
    out = {
        "examples_per_s": (measured["rows"] / measured["window_s"], "examples/s"),
        "setup_s": (env.setup_s, "s"),
    }
    gaps = measured["gaps_s"]
    if len(gaps) >= 200:  # a 95th percentile with ten samples beyond it
        import numpy as np

        out["step_gap_p95_ms"] = (float(np.percentile(gaps, 95.0)) * 1e3, "ms")
    return out


def per_layer(env, measured: dict) -> tuple:
    from benchmark.harness import trace_reduce

    reduced = trace_reduce.reduce_dir(env.trace_dir, env.traced["t1"] - env.traced["t0"])
    peaks = load_json("harness", "peaks.json")[env.device.device_kind] if not env.rehearse \
        else None
    ctx = {"env": env, "measured": measured, "trace": reduced, "peaks": peaks}
    out = {}
    for fname in sorted(os.listdir(os.path.join(HERE, "layer_metrics"))):
        if not fname.endswith(".json"):
            continue
        spec = load_json("layer_metrics", fname)
        if spec.get("mixes") is not None and env.workload["traffic"] not in spec["mixes"]:
            continue
        params = dict(spec["reader"])
        reader = importlib.import_module("benchmark.readers." + params.pop("kind"))
        value = reader.read(ctx, **params)
        if value is not None:
            out[fname[:-len(".json")]] = (float(value), spec["unit"])
    return out, reduced


def reports(bench: dict, group: str, workload: str) -> set:
    """Names of ``group``'s metrics that ``workload`` is listed to report."""
    return {m["name"] for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]}


def require_chip(found: dict, chips: int, peaks: dict) -> None:
    """A measurement needs ``chips`` TPU devices of a kind whose peaks are
    known; anything else is refused, never measured under a device's name."""
    if found["platform"] != "tpu":
        raise Refused(f"the benchmark needs a TPU; JAX found {found}")
    if found["kind"] not in peaks:
        raise Refused(f"device kind {found['kind']!r} is not in harness/peaks.json")
    if found["count"] < chips:
        raise Refused(f"the cell asks for {chips} chip(s); JAX found {found}")


def main(argv=None, bench_path: str = os.path.join(ROOT, "BENCHMARK.json")) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds; prints no metric")
    args = ap.parse_args(argv)

    with open(bench_path) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise Refused(f"no workload {args.workload!r} in BENCHMARK.json")
    workload = cells[args.workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[workload["config"]]
    with open(os.path.join(ROOT, config_entry["file"])) as f:
        cfg = json.load(f)
    mix = load_json("traffic", workload["traffic"] + ".json")
    if args.rehearse:
        cfg, mix = at_rehearsal_size(cfg), at_rehearsal_size(mix)

    marks = {"parsed": time.perf_counter() - T_START}
    import jax

    from tpu_tfrecord import _native, compile_cache
    from tpu_tfrecord.tpu import create_mesh

    marks["imported"] = time.perf_counter() - T_START
    device = jax.devices()[0]
    marks["device_found"] = time.perf_counter() - T_START
    found = {"platform": device.platform, "kind": device.device_kind,
             "count": len(jax.devices())}
    if not args.rehearse:
        require_chip(found, workload["chips"], load_json("harness", "peaks.json"))
        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _native.available():
        raise Refused(f"native extension unavailable: {_native.load_error()}")

    from benchmark.harness.spans import Spans

    work = os.path.join(ROOT, ".bench_work", args.workload)
    data = importlib.import_module("benchmark.data." + cfg["data"])
    t0 = time.perf_counter()
    expected = data.write(os.path.join(work, "data"), args.seed, cfg, mix)
    env = Env(
        workload=workload, cfg=cfg, mix=mix, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearse=args.rehearse, device=device,
        mesh=create_mesh(devices=jax.devices()[: workload["chips"]]),
        spans=Spans(bool(args.trace)), expected=expected,
        data_dir=os.path.join(work, "data"), trace_dir=os.path.join(work, "trace"),
        model=importlib.import_module("benchmark.models." + cfg["model"]),
    )
    env.info("device", **found)
    env.info("start", native_checked=t0 - T_START, **marks)
    env.info("data", seconds=time.perf_counter() - t0, rows=len(expected),
             **data.describe(expected, cfg, mix))

    loop = importlib.import_module("benchmark.loops." + mix["loop"])
    measured = loop.run(env)
    correct = bool(measured["correct"]) and measured["compiles_in_window"] == 0 \
        and measured["failed"] == 0 and measured["steps"] > 0
    env.info("window", steps=measured["steps"], window_s=measured["window_s"],
             rows=measured["rows"], gap_samples=int(len(measured["gaps_s"])),
             compiles_in_window=measured["compiles_in_window"], cpu_s=measured["cpu_s"],
             waited_for_batches_s=measured["waited_s"], longest_gaps=longest_gaps(measured),
             **{k: measured[k] for k in ("loss_first", "loss_last") if k in measured})

    device_out = dict(found, count=workload["chips"], memory_peak_bytes=env.memory_peak_bytes)
    result = {"correct": correct, "attempted": measured["attempted"],
              "failed": measured["failed"], "metrics": {}, "device": device_out}
    if args.trace:
        values, reduced = per_layer(env, measured)
        wanted = reports(bench, "per_layer", args.workload)
        device_out["busy_s"] = reduced["busy_s"]
        device_out["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["top_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:5]}
        env.info("trace", events=reduced["events"], steps_traced=reduced["steps"])
    else:
        values = end_to_end(env, measured)
        wanted = reports(bench, "end_to_end", args.workload)
    if args.rehearse:
        result["rehearsal"] = True
    else:
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (v, u) in values.items() if k in wanted}
    result["compared"] = env.compared
    print("compared [value, limit]: " + json.dumps(env.compared), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        sys.exit(3)
