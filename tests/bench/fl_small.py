"""The FL training cell at a size a CPU test run holds: M = 6 clients,
N = 3 selected, 8 to 16 samples of 32 features, hidden width 16, C = 2 points x
S = 2 seeds x R = 3 rounds, a validation set of 256 samples, 2 poisoners
(``seeds`` and ``points`` may be changed).  Classes lie 10 apart, so that a
network this small learns in three rounds."""
from bench import run

CELL = "paper_fl_sweep_4chip"
CONFIG = {"clients_total": 6, "clients_per_round": 3,
          "data_samples": [8.0, 16.0]}


def small(seeds: int = 2, points: int = 2, spec: dict | None = None):
    """(config, traffic) of the small cell, from ``spec``, the cell's own
    resolved spec by default."""
    spec = spec or run.resolve(CELL)
    config = dict(spec["config"], **CONFIG)
    traffic = dict(spec["traffic"])
    traffic.update(points=traffic["points"][:points], seeds=seeds, rounds=3,
                   pool=2, check_slots=4, model={"hidden": 16},
                   data=dict(traffic["data"], dim=32, val_size=256,
                             class_sep=10.0, poison_ratio=0.34))
    return config, traffic


def small_resolve(real_resolve):
    """``run.resolve`` with the FL cell at the small size."""
    def resolve(workload, root=run.ROOT):
        spec = real_resolve(workload, root)
        if workload == CELL:
            spec["config"], spec["traffic"] = small(spec=spec)
        return spec
    return resolve
