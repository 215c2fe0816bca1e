#!/usr/bin/env python3
"""One run of one benchmark cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, no children. The cell is looked up by name in
``BENCHMARK.json``; its configuration, traffic mix, op and per-layer metrics
are files found by the names written there (benchmark/README.md). The last
line of stdout is the one JSON object the driver reads; everything else is on
earlier lines, on stderr or under ``benchmark/out/``.

It measures the chip or nothing: unless JAX's devices are TPUs, and as many as
the cell asks for, it exits 2 without a number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import arith
import trace_reduce

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CALL_ANNOTATION = "bench_call"
WINDOW_ANNOTATION = "bench_window"
GIB = float(2 ** 30)
#: ``call_p90_s``: the highest percentile with ten samples beyond it needs
#: a hundred calls in the window.
P90_MIN_CALLS = 100


def say(tag: str, **kv) -> None:
    print(f"[{tag}] " + " ".join(
        f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
        for k, v in kv.items()), flush=True)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (a dotted metric family
    such as ``stage_s.x`` is the one file ``stage_s.py``)."""
    stem = name.split(".")[0]
    path = os.path.join(HERE, kind, stem + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> dict:
    """The cell with its configuration, traffic mix and metric lists."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    return {
        "name": workload, "chips": cell["chips"],
        "config": load_json(os.path.join(root, entry["file"])),
        "traffic": load_json(os.path.join(
            root, bench["paths"][0], "traffic", cell["traffic"] + ".json")),
        "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
    }


def require_devices(chips: int):
    """The cell's TPU chips, or exit 2: a run never falls back."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"benchmark: JAX's first device is {devices[0].platform!r}, "
              "not a TPU; nothing was run", file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"benchmark: the cell needs {chips} chip(s), JAX sees "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    return devices[:chips]


class Events:
    """JAX's own compile and cache events, counted (jax.monitoring)."""

    def __init__(self):
        import jax

        self.counts: dict = {}
        jax.monitoring.register_event_listener(self._count)
        jax.monitoring.register_event_duration_secs_listener(self._count)

    def _count(self, event: str, *_a, **_kw) -> None:
        self.counts[event] = self.counts.get(event, 0) + 1

    def compiles(self) -> int:
        return self.counts.get(COMPILE_EVENT, 0)


def fallbacks() -> dict:
    """``dlaf_fallback_total`` by labels, as far as non-zero."""
    from dlaf_tpu import obs

    return {json.dumps(m.get("labels", {}), sort_keys=True): m["value"]
            for m in obs.registry().snapshot()
            if m.get("name") == "dlaf_fallback_total" and m["value"]}


def one_call(op, state, traced: bool):
    """Fresh input (untimed, fenced), then the timed call to its fence."""
    import oplib

    inp = oplib.fence(op.fresh(state))
    t0 = time.perf_counter()
    out = oplib.fence(op.call(state, inp, traced=traced))
    return out, time.perf_counter() - t0


def run_window(op, state, seconds: float, traced: bool, min_calls: int = 1):
    """Closed loop of one caller for ``seconds`` (and ``min_calls``):
    ``(walls, failed, last result)``."""
    import jax

    walls, failed, out = [], 0, None
    t_end = time.perf_counter() + seconds
    while time.perf_counter() < t_end or len(walls) + failed < min_calls:
        annotation = jax.profiler.TraceAnnotation(CALL_ANNOTATION) \
            if traced else contextlib.nullcontext()
        try:
            with annotation:
                out, wall = one_call(op, state, traced)
            walls.append(wall)
        except Exception as exc:   # a failed call is counted, the loop goes on
            failed += 1
            print(f"benchmark: call failed: {exc!r}", file=sys.stderr)
            if failed >= 3 and not walls:
                break
    return walls, failed, out


def traced_window(op, state, spec: dict, seconds: float, out_dir: str):
    """A short window under the profiler: ``(walls, failed, last result,
    reduced trace or None)``."""
    import jax

    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
            walls, failed, out = run_window(
                op, state, min(spec["min_seconds"], seconds), True,
                min_calls=spec["min_calls"])
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.newest_xplane(trace_dir)
    if path is None:
        return walls, failed, out, None
    devices, host_spans, listing = trace_reduce.read_xplane(path)
    window = next(((s, e) for s, e, n in host_spans
                   if n == WINDOW_ANNOTATION), None)
    host_spans = [h for h in host_spans if h[2] != WINDOW_ANNOTATION]
    with open(os.path.join(out_dir, "trace_listing.json"), "w") as f:
        json.dump({"xplane": os.path.relpath(path, out_dir),
                   "lines": listing}, f, indent=1)
    if window is None:
        return walls, failed, out, None
    reduced = trace_reduce.reduce_trace(devices, host_spans, window)
    with open(os.path.join(out_dir, "trace_reduced.json"), "w") as f:
        json.dump(reduced, f, indent=1)
    return walls, failed, out, reduced


def require_peaks(device) -> None:
    """A TPU whose kind has no row in peaks.json is an error, not a default."""
    if device.platform == "tpu" and device.device_kind not in load_json(
            os.path.join(HERE, "peaks.json")):
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{device.device_kind!r} in benchmark/peaks.json")


def device_record(devices, trace) -> dict:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if trace is not None:
        rec["busy_s"] = trace.get("busy_s_mean", 0.0)
        rec["window_s"] = trace["window_s"]
    return rec


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    traced = bool(args.trace)

    cell = load_cell(root, args.workload)
    config, traffic = cell["config"], cell["traffic"]
    op = load_module("ops", config["op"])
    readers = {m["name"]: load_module("layer_metrics", m["name"])
               for m in cell["per_layer"]} if traced else {}

    devices = require_devices(cell["chips"])
    require_peaks(devices[0])
    platform = devices[0].platform
    out_dir = os.path.join(root, "benchmark", "out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    # the fallback and route counters only count while the metrics sink is on
    metrics_path = os.path.join(out_dir, "metrics.jsonl")
    if os.path.exists(metrics_path):
        os.remove(metrics_path)
    os.environ["DLAF_METRICS_PATH"] = metrics_path

    import jax

    import dlaf_tpu
    from dlaf_tpu import obs

    events = Events()
    dlaf_tpu.initialize()
    if not obs.metrics_active():
        raise SystemExit("benchmark: the metrics sink is off, so a fallback "
                         "would not be counted")
    say("device", platform=platform, kind=repr(devices[0].device_kind),
        count=len(devices), jax=jax.__version__,
        cache_dir=jax.config.jax_compilation_cache_dir)

    # ---- set-up: inputs from the seed, first call, warm-up ----------------
    rng_seed = args.seed % (2 ** 32)
    state = op.build(config, rng_seed, devices)
    say("setup", inputs_s=time.perf_counter() - T_START)
    out, first_call_s = one_call(op, state, False)
    say("setup", first_call_s=first_call_s, compiles=events.compiles())
    for _ in range(traffic["warmup_calls"]):
        out, warm_s = one_call(op, state, traced)
        say("setup", warmup_call_s=warm_s)
    warm_host = op.host(out)
    del out
    state.get("stage_walls", []).clear()    # the window's calls only
    cache_events = dict(events.counts)
    compiles_before = events.compiles()
    setup_s = time.perf_counter() - T_START

    # ---- the window -------------------------------------------------------
    trace = None
    t_win = time.perf_counter()
    if traced:
        walls, failed, out, trace = traced_window(
            op, state, traffic["traced_window"], args.seconds, out_dir)
    else:
        walls, failed, out = run_window(op, state, args.seconds, False)
    window_s = time.perf_counter() - t_win
    compiled_in_window = events.compiles() - compiles_before

    # ---- correctness, outside the window ----------------------------------
    n = config["n"]
    tol = arith.tolerance(config["guarantee"], n, platform)
    worst = 0.0
    hosts = [("warmup", warm_host)]
    if out is not None:
        hosts.append(("last", op.host(out)))
    ok = bool(walls) and failed == 0
    for which, host_out in hosts:
        for what, value in op.check(state, host_out).items():
            good = bool(value <= tol)          # NaN fails
            say("check", call=which, what=repr(what), value=float(value),
                tol=tol, ok=good)
            ok = ok and good
            if worst == worst and not value <= worst:   # NaN sticks
                worst = value
    fb = fallbacks()
    say("check", dlaf_fallback_total=sum(fb.values()),
        compiled_in_window=compiled_in_window)
    if fb:
        print(f"benchmark: degraded path taken: {fb}", file=sys.stderr)
    ok = ok and not fb and compiled_in_window == 0
    obs.flush()

    # ---- metrics ----------------------------------------------------------
    dev = device_record(devices, trace)
    values = {}
    if walls:
        call_s = statistics.median(walls)
        values["call_s"] = call_s
        if len(walls) >= P90_MIN_CALLS:
            values["call_p90_s"] = arith.percentile(walls, 90)
        say("window", calls=len(walls), window_s=window_s, call_s=call_s,
            call_mean_s=sum(walls) / len(walls), call_max_s=max(walls),
            stalls=sum(w > 2 * call_s for w in walls),
            gflops=op.flops(config) / call_s / 1e9)
        with open(os.path.join(out_dir, "walls.json"), "w") as f:
            json.dump(walls, f)
    values["residual_digits"] = arith.residual_digits(worst)
    values["peak_hbm_gib"] = dev["memory_peak_bytes"] / GIB
    values["setup_s"] = setup_s
    if traced:
        run = {"trace": trace, "traced_calls": len(walls), "walls": walls,
               "stage_walls": state.get("stage_walls", []),
               "first_call_s": first_call_s, "cache_events": cache_events,
               "counters": obs.registry().snapshot(), "config": config,
               "device": dev}
        wanted = cell["per_layer"]
        values = {m["name"]: readers[m["name"]].read(run, m["name"])
                  for m in wanted}
    else:
        wanted = cell["end_to_end"]
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values.get(m["name"]) is not None}
    line = {"correct": ok, "attempted": len(walls) + failed,
            "failed": failed, "metrics": metrics, "device": dev}
    if trace is not None and trace.get("devices"):
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
