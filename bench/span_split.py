"""Run one cell's traced slice and split its time by what the program
records about itself: the reading behind PERF.md's "Where the time goes".

    python3 bench/span_split.py --workload <name> --seed <n> [--seconds <s>]

Set-up as ``bench/run.py`` makes it (the cell's driver, its inputs from the
seed, the warm-up), then one slice of ``--seconds`` (default: the traffic's
``trace_seconds``) under the profiler, inside the harness's ``window``
span.  Prints one JSON line: the slice's end-to-end numbers and counters,
device busy and idle, each program span's count and mean duration, the
idle gaps labelled with the program span inside each, and, where the cell
has them, the service's stage numbers and the share of busy time under the
``sic_power`` scope (``bench/program_trace.py``).  A program that records
no spans, stages or scopes gives empty or null fields.  Needs the cell's
TPU chips, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import run as harness
    spec = harness.resolve(args.workload)

    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = harness.device_info(jax, int(spec["cell"]["chips"]))
    if device is None:
        return 3
    traffic = spec["traffic"]
    seconds = args.seconds or float(traffic.get("trace_seconds", 2.0))
    driver = harness.load_module(spec["driver"], "span_split_driver")
    cell = driver.Cell(spec["config"], traffic, args.seed, seconds)

    trace_dir = tempfile.mkdtemp(prefix="span_split_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        window = cell.run(seconds)
    jax.profiler.stop_trace()
    from bench import program_trace, trace_reduce
    try:
        trace = trace_reduce.read(trace_dir)
        spans = program_trace.program_spans(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    out = {"workload": args.workload, "seed": args.seed, "device": device,
           "metrics": window["metrics"], "counters": window["counters"],
           "busy_s": trace.busy_s, "window_s": trace.window_s,
           "idle_pct": 100.0 * (1.0 - trace.busy_s / trace.window_s),
           "spans": program_trace.span_means_ms(spans, trace.window),
           "idle_gaps": program_trace.label_gaps(trace, spans),
           "device_ops": trace.top_ops()}
    if traffic["driver"] == "open_loop_service":
        out["serve_stages"] = program_trace.serve_stages(
            [res for _, res in cell.answered])
    if hasattr(driver, "compiled_text"):
        scopes = program_trace.scope_map(
            driver.compiled_text(spec["config"], traffic))
        out["sic_power_device_share"] = program_trace.scope_share(
            trace, scopes, "sic_power")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
