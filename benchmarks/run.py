"""Benchmark harness — one module per paper table/figure + kernel
micro-benchmarks.  Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only fig4,fig9,kernels
    PYTHONPATH=src python -m benchmarks.run --only equilibrium   # fast mode:
        # just the batched Stackelberg engine throughput (~seconds), writes
        # BENCH_equilibrium.json for trajectory tracking
    PYTHONPATH=src python -m benchmarks.run --only training      # fast mode:
        # trajectory + config-grid sweep tiers, writes BENCH_training.json
    PYTHONPATH=src python -m benchmarks.run --only fig5          # one figure
        # (fig5 / fig6 / fig78 each run + gate individually the same way)
    PYTHONPATH=src python -m benchmarks.run --devices 4          # re-exec
        # with 4 forced host devices (see benchmarks/common.py) before any
        # suite loads jax — every suite then runs sharded

Unknown ``--only`` names are an error (they used to silently run nothing).
A suite that raises is reported as an ``ERROR`` row, the remaining suites
still run, and the process then exits non-zero.
The summary (stdout + ``runs/bench/summary.csv``) ends with ``#``-comment
rows recording the device count and per-suite wall-clock seconds.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from . import common  # noqa: F401  applies --devices/REPRO_FORCE_DEVICES
                      # (re-exec) before any suite initializes jax

SUITES = ("fig4", "fig5", "fig6", "fig78", "fig9", "ablation", "kernels",
          "equilibrium", "training", "robustness", "mechanism")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=",".join(SUITES),
                    help="comma-separated subset of: " + ",".join(SUITES))
    ap.add_argument("--devices", type=int, default=None,
                    help="forced host device count (consumed pre-jax by "
                         "benchmarks.common; listed here for --help)")
    args = ap.parse_args()
    wanted = set(filter(None, args.only.split(",")))
    unknown = wanted - set(SUITES)
    if unknown:
        ap.error(f"unknown suite(s) {','.join(sorted(unknown))}; "
                 f"valid: {','.join(SUITES)}")
    if not wanted:
        ap.error(f"--only selected no suites; valid: {','.join(SUITES)}")

    print("name,us_per_call,derived")
    rows = []
    suite_walls = []
    failed = []
    for suite in SUITES:
        if suite not in wanted:
            continue
        t_suite = time.perf_counter()
        try:
            if suite == "fig4":
                from . import fig4_dinkelbach as mod
            elif suite == "fig5":
                from . import fig5_poisoners as mod
            elif suite == "fig6":
                from . import fig6_dt_deviation as mod
            elif suite == "fig78":
                from . import fig78_schemes as mod
            elif suite == "fig9":
                from . import fig9_total_cost as mod
            elif suite == "ablation":
                from . import ablation_weights as mod
            elif suite == "equilibrium":
                from . import equilibrium_throughput as mod
            elif suite == "training":
                from . import training_throughput as mod
            elif suite == "robustness":
                from . import robustness_grid as mod
            elif suite == "mechanism":
                from . import mechanism_design as mod
            else:
                from . import kernels_microbench as mod
            for name, us, derived in mod.run():
                line = f"{name},{us:.1f},{derived}"
                print(line, flush=True)
                rows.append(line)
        except Exception:  # noqa: BLE001 — report, run the rest, exit 1
            print(f"{suite},NaN,ERROR", flush=True)
            traceback.print_exc()
            failed.append(suite)
        suite_walls.append((suite, time.perf_counter() - t_suite))

    import jax
    footer = [f"# devices,{len(jax.devices())}"]
    footer += [f"# suite_wall_s,{suite},{wall:.1f}"
               for suite, wall in suite_walls]
    for line in footer:
        print(line, flush=True)
    os.makedirs("runs/bench", exist_ok=True)
    with open("runs/bench/summary.csv", "w") as f:
        f.write("name,us_per_call,derived\n")
        f.write("\n".join(rows) + "\n")
        f.write("\n".join(footer) + "\n")
    if failed:
        sys.exit(f"benchmark suite(s) failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
