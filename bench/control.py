"""Readings that set a cell's comparison limits, on the chip.

    python3 bench/control.py --workload <name> --seeds 1 2 3 ... [--control 3] [--seconds 1]

For each seed, in one process: the cell's set-up and a short window of its
own traffic at its own size, then the numbers compared for the program (the
lower readings) and, on the first ``--control`` seeds, for the control: the
plain reference computed in bfloat16, the precision below the float32 that
the configurations state, put in the program's place (the upper readings).
One JSON line per seed and a last line with the worst program reading and
the least control reading of each number.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    import ml_dtypes
    import jax
    from bench import run
    spec = run.resolve(args.workload)
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    if run.device_info(jax, int(spec["cell"]["chips"])) is None:
        return 3
    driver = run.load_module(spec["driver"], "bench_driver")
    lower, upper = {}, {}
    for i, seed in enumerate(args.seeds):
        cell = driver.Cell(spec["config"], spec["traffic"], seed, args.seconds)
        cell.run(args.seconds)
        cell.collect()
        gc.collect()
        row = {"seed": seed, "program": cell.check()}
        if i < args.control:
            row["control"] = cell.check(dtype=ml_dtypes.bfloat16)
            for k, v in row["control"].items():
                upper[k] = min(upper.get(k, float("inf")), v)
        for k, v in row["program"].items():
            lower[k] = max(lower.get(k, 0.0), v)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
