"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell gives its
configuration (``bench/configs/<config>.json``) and traffic
(``bench/traffic/<traffic>.json``); the traffic names its driver
(``bench/drivers/<driver>.py``); each per-layer metric has its reader
(``bench/metrics/<metric>.py``, or for a metric split by the end-to-end
metric it moves, such as ``device_idle.serve``, the reader of its stem,
``bench/metrics/device_idle.py``); the cell's comparison limits are in
``bench/checks/<workload>.json``.

A run: set-up (inputs from the seed, warm-up of every shape the cell uses;
``setup_s`` runs from process start to the end of it), the measured window
of ``--seconds`` (with ``--trace 1`` a shorter traced slice instead, see the
traffic's ``trace_seconds``), then the comparison of what the window
produced against the plain reference.  The last line of standard output is
one JSON object; the numbers compared, each beside its limit, are the last
lines of standard error and the last key of that object.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")
CACHE_LOADS = "/jax/compilation_cache/compile_requests_use_cache"


class SpecError(Exception):
    """A name in BENCHMARK.json that has no file behind it."""


def load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def reader_path(metrics: Path, name: str) -> Path:
    """``<name>.py``, else the reader of the name's stem before its first
    dot: ``device_idle.solve`` and ``device_idle.serve`` share one."""
    own = metrics / f"{name}.py"
    return own if own.is_file() else metrics / f"{name.split('.')[0]}.py"


def resolve(workload: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, found by name; raises ``SpecError``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    d = root / "bench"
    traffic = load_json(d / "traffic" / f"{cell['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload] if m["moves"]
                                  in reported else [])]
    spec = {"cell": cell,
            "config": load_json(d / "configs" / f"{cell['config']}.json"),
            "traffic": traffic,
            "limits": load_json(d / "checks" / f"{workload}.json")["limits"],
            "driver": d / "drivers" / f"{traffic['driver']}.py",
            "end_to_end": e2e,
            "per_layer": layer,
            "readers": {m["name"]: reader_path(d / "metrics", m["name"])
                        for m in layer}}
    for path in [spec["driver"], *spec["readers"].values()]:
        if not path.is_file():
            raise SpecError(f"missing {path.relative_to(root)}")
    return spec


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info(jax, chips: int) -> dict | None:
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        say(f"bench: the cell needs {chips} TPU chip(s); found {len(devs)} x "
            f"{devs[0].platform} ({devs[0].device_kind})")
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(jax) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class CompileCounter:
    """Compilations, traces and compile-cache loads while it is on."""

    def __init__(self, monitoring):
        self.counts = {"compiles": 0, "traces": 0, "cache_loads": 0}
        self.on = False
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if self.on and event in COMPILE_EVENTS:
            key = "compiles" if "backend" in event else "traces"
            self.counts[key] += 1

    def _event(self, event, **_kw):
        if self.on and event == CACHE_LOADS:
            self.counts["cache_loads"] += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = resolve(args.workload)
    except (SpecError, OSError, KeyError, ValueError) as e:
        say(f"bench: {e}")
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        say(f"bench: no src/repro under {ROOT}: run from a checkout of the "
            "repository")
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    # a fixed path inside the checkout: the path is part of the cache key
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = device_info(jax, int(spec["cell"]["chips"]))
    if device is None:
        return 3
    t_device = time.perf_counter() - T_START

    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, float(spec["traffic"].get("trace_seconds", 2.0)))
    counter = CompileCounter(jax.monitoring)
    driver = load_module(spec["driver"], f"bench_driver_{spec['traffic']['driver']}")
    cell = driver.Cell(spec["config"], spec["traffic"], args.seed, seconds)
    setup_s = time.perf_counter() - T_START
    say(f"bench: set-up {setup_s:.3f} s: {t_device:.3f} s to the chips, "
        f"{setup_s - t_device:.3f} s in the cell's set-up")

    trace, trace_dir = None, None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    from repro.core.tracking import TRACE_COUNTS
    engine_traces = sum(TRACE_COUNTS.values())
    counter.on = True
    with jax.profiler.TraceAnnotation("window"):
        window = cell.run(seconds)
    counter.on = False
    counter.counts["engine_traces"] = sum(TRACE_COUNTS.values()) - engine_traces
    if args.trace:
        jax.profiler.stop_trace()
        from bench import trace_reduce
        trace = trace_reduce.read(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    in_window = counter.counts
    say(f"bench: in the window: {in_window['compiles']} compilations, "
        f"{in_window['cache_loads']} compile-cache loads and "
        f"{in_window['engine_traces']} engine traces (TRACE_COUNTS; all three "
        f"must be 0), {in_window['traces']} jaxpr traces; "
        f"{window['attempted']} attempted, "
        f"{window['failed']} failed, window {window['window_s']:.3f} s")
    device["memory_peak_bytes"] = memory_peak(jax)

    cell.collect()
    gc.collect()
    from bench import compare
    t_check = time.perf_counter()
    numbers = cell.check()
    say(f"bench: the comparison took {time.perf_counter() - t_check:.3f} s")
    correct, rows = compare.judge(numbers, spec["limits"])
    correct &= window["attempted"] > 0

    result = {"correct": bool(correct), "attempted": int(window["attempted"]),
              "failed": int(window["failed"])}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}
    metrics = {}
    if args.trace:
        run = types.SimpleNamespace(trace=trace, counters=window["counters"],
                                    device_kind=device["kind"])
        for name, path in spec["readers"].items():
            value = load_module(path, f"bench_metric_{len(metrics)}").read(run)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": units[name]}
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = {"device_ops": trace.top_ops(),
                               "idle_gaps": trace.idle_gaps()}
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["compiles_in_window"] = in_window
    result["counters"] = window["counters"]
    result["checks"] = rows
    for name, row in rows.items():
        say(f"check {name}: {row['value']!r} (limit {row['limit']!r})")
    say(f"check correct: {correct}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
