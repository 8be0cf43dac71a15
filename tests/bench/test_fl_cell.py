"""The FL training cell: found by name with end-to-end and per-layer
metrics of its own, its work count against hand counts, and the plain
training reference against ``sweep_training`` through the cell's own
driver at a small size."""
import numpy as np

from bench import compare, run, work
from fl_small import CELL, small

OTHERS = {
    "paper_mc_n5": ({"setup_s", "solves_per_s"},
                    {"device_idle.solve", "leader_iters_mean",
                     "leader_lane_waste", "feasible_share",
                     "sic_power_device_share"}),
    "paper_serve_n5": ({"setup_s", "req_p95_ms", "served_req_per_s"},
                       {"device_idle.serve", "serve_batch_fill",
                        "serve_gen_late_p95_ms"}),
}


def test_cell_resolves_with_metrics_of_its_own():
    spec = run.resolve(CELL)
    assert spec["cell"]["chips"] == 4
    assert {m["name"] for m in spec["end_to_end"]} == {"setup_s",
                                                       "rounds_per_s"}
    assert set(spec["readers"]) == {"device_idle.fl", "fl_mfu",
                                    "fl_alloc_device_share",
                                    "fl_update_waste"}
    assert spec["readers"]["device_idle.fl"].name == "device_idle.py"
    for name, (e2e, layer) in OTHERS.items():
        other = run.resolve(name)
        assert {m["name"] for m in other["end_to_end"]} == e2e, name
        assert set(other["readers"]) == layer, name


def test_fl_training_flops_hand_count():
    """An MLP 4-3-3-2 (27 weights); two clients of 10 and 6 samples with
    v = 0.5 and 0.25: 5 + 4.5 = 9.5 unmapped and 5 + 1.5 = 6.5 mapped
    samples; 2 local and 3 twin steps; 5 passes over 8 validation
    samples."""
    assert work.mlp_weights([(4, 3), (3, 3), (3, 2)]) == 27
    got = work.fl_training(27, local_samples=9.5, mapped_samples=6.5,
                           local_steps=2, server_steps=3, val_samples=40)
    assert got["flops"] == 6 * 27 * (2 * 9.5 + 3 * 6.5) + 2 * 27 * 40


def test_driver_counts_the_selected_clients_samples():
    """The same hand count through the driver: one grid point, one round,
    clients 0 and 1 of three selected, N + 3 = 5 validation passes."""
    driver = run.load_module(run.ROOT / "bench/drivers/fl_sweep.py",
                             "fl_driver_flops")
    cell = driver.Cell.__new__(driver.Cell)
    cell.sizes = [(np.array([[10.0, 6.0, 8.0]]), np.array([[0.5, 0.25, 0.0]]))]
    cell.weights = 27
    cell.proto = {"local_steps": 2, "server_steps": 3, "use_roni": True}
    cell.traffic = {"data": {"val_size": 8}}
    selected = np.array([0, 1]).reshape(1, 1, 1, 2)
    assert cell._flops(0, selected) == work.fl_training(27, 9.5, 6.5, 2, 3,
                                                        40)["flops"]


def test_reference_agrees_with_program_small():
    """The window's grid at the small size against the plain reference:
    every decision the same, the numbers within the cell's limits, and at
    least one trajectory compared to its end."""
    spec = run.resolve(CELL)
    config, traffic = small()
    driver = run.load_module(spec["driver"], "fl_driver_small")
    cell = driver.Cell(config, traffic, 2 ** 33 + 5, 0.3)
    window = cell.run(0.3)
    assert window["attempted"] == window["counters"]["calls"] * 2 * 2 * 3
    assert window["failed"] == 0
    cell.collect()
    numbers = cell.check()
    ok, rows = compare.judge(numbers, spec["limits"])
    assert ok, rows
    assert numbers["decision_flips"] == 0
    assert np.isfinite(numbers["param_change_gap"])


def test_edge_verdicts_follow_the_programs_counts():
    """Clients 2 and 3 at the RONI edge, client 1 rejected away from it:
    the program's count of rejected updates leaves the verdicts that agree
    with it; a count that cannot be met leaves the reference's own; the
    twin at its edge is taken either way."""
    from bench.fl_reference import _candidates
    meets = np.ones(4, bool)
    ok = np.array([True, False, True, False, True])
    edge = np.array([False, False, True, True, True])
    follow = {"n_stragglers": [0], "n_excluded_roni": [2]}
    got = _candidates(meets, np.zeros(4, bool), ok, edge, follow, 0)
    assert sorted(c[1].tolist() for c in got) == [
        [True, False, False, True, False], [True, False, False, True, True],
        [True, False, True, False, False], [True, False, True, False, True]]
    follow["n_excluded_roni"] = [0]
    got = _candidates(meets, np.zeros(4, bool), ok, edge, follow, 0)
    assert [c[1][:4].tolist() for c in got] == [ok[:4].tolist()] * 2
    assert _candidates(meets, np.zeros(4, bool), ok, edge, None, 0) == []


def test_verdicts_taken_against_the_reference_are_counted(monkeypatch):
    """With every RONI verdict at the edge, a program's count of rejected
    updates that the reference's own verdicts do not give is followed, and
    each verdict taken the other way is counted in ``edge_overrides``; the
    program's own count is followed with none."""
    from bench import fl_compare, fl_reference
    spec = run.resolve(CELL)
    config, traffic = small()
    driver = run.load_module(spec["driver"], "fl_driver_overrides")
    cell = driver.Cell(config, traffic, 2 ** 33 + 9, 0.2)
    cell.run(0.2)
    cell.collect()
    monkeypatch.setitem(fl_reference.EDGE, "roni", 1.0)
    monkeypatch.setitem(fl_reference.EDGE, "val_acc_apart", 0.0)
    c, got, inp = cell.checked[0]
    args = (inp, cell.points[c], cell.proto, cell.phys, config["channel"],
            cell.solver, cell.rounds)
    ref = fl_reference.trajectory(*args, follow=got)
    assert ref["overridden"] == []
    n = cell.proto["n_selected"]
    told = dict(got, n_excluded_roni=np.array(got["n_excluded_roni"]))
    told["n_excluded_roni"][0] += 1 if told["n_excluded_roni"][0] < n else -1
    ref = fl_reference.trajectory(*args, follow=told)
    assert ref["overridden"] and all(k == "roni" for k, _ in ref["overridden"])
    numbers = fl_compare.point_numbers(told, ref, inp)
    assert numbers["edge_overrides"] == len(ref["overridden"]) >= 1
