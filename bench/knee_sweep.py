"""The serve cell's traffic at a list of rates and flush ticks, on the chip:
where the service stops keeping up, and how much of the tail the tick sets.

    python3 bench/knee_sweep.py --workload paper_serve_n5 --rates 400 500 600 \
        [--ticks 50] [--seconds 40] [--seed 1]

In one process, for each rate and each tick: the cell's set-up and one
window of ``--seconds``, every other parameter as the traffic file has it.
One JSON line each: the offered and the served rate, the p95 of all
requests from their due time, the generator's lateness, the statuses, and
the p95 over the window's first and last quarters, which part as a backlog
grows.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def quarter_p95_ms(due: np.ndarray, lat: np.ndarray, seconds: float) -> tuple:
    first, last = due < seconds / 4, due >= 3 * seconds / 4
    return tuple(1e3 * float(np.percentile(lat[m], 95, method="higher"))
                 for m in (first, last))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--ticks", type=float, nargs="+")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import jax
    from bench import run
    spec = run.resolve(args.workload)
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    if run.device_info(jax, int(spec["cell"]["chips"])) is None:
        return 3
    driver = run.load_module(spec["driver"], "bench_driver")
    for tick in args.ticks or [spec["traffic"]["tick_ms"]]:
        for rate in args.rates:
            traffic = dict(spec["traffic"], rate_per_s=rate, tick_ms=tick)
            cell = driver.Cell(spec["config"], traffic, args.seed, args.seconds)
            out = cell.run(args.seconds)
            first, last = quarter_p95_ms(cell.due, cell.lat, args.seconds)
            print(json.dumps({
                "rate_per_s": rate, "tick_ms": tick,
                **out["metrics"], "attempted": out["attempted"],
                "failed": out["failed"],
                "gen_late_p95_ms": out["counters"]["gen_late_p95_ms"],
                "p95_first_quarter_ms": first, "p95_last_quarter_ms": last,
                "dispatches": out["counters"]["dispatches"],
                "statuses": out["counters"]["statuses"]}), flush=True)
            cell.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
