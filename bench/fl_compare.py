"""The comparison that decides ``correct`` in an FL training cell: one grid
point's trajectory against the plain reference (``bench/fl_reference.py``).

Rounds before the first edge decision that the reference could not settle
by the program's history are compared:

* the discrete decisions exactly: the selected clients in SIC order, the
  count of RONI-rejected updates and of stragglers in each round, and, for a
  trajectory compared to its end, every client's tally of positive and
  negative RONI verdicts (the program reports counts, not verdicts);
* the validation accuracy of each round, as the largest absolute gap;
* the round's energy and latency as relative errors divided by the draw's
  condition number (``compare.condition``), as the allocation cells hold
  them;
* for a trajectory compared to its end, the parameters' change over the R
  rounds, w_R - w_0, by the worst leaf: the gap between the program's norm
  and the reference's, against the reference's norm of that leaf or of the
  median leaf, whichever is larger.  Plain SGD leaves no leaf still to
  rounding, so every leaf counts.

``uncompared_share`` is the share of the checked rounds that lie at or past
an unsettled edge; most of them have to be compared.  ``edge_overrides``
counts the edge decisions that the reference took the program's side of
against its own verdict: a sound float32 program differs from the reference
there only where the two models' drift carries a margin across its
threshold, a program that decides by another rule wherever its rule and the
reference's part within the edge.  Two more numbers are reported and held
to no limit: ``edges_followed``, every edge decision that the program's
history settled, and ``logit_rel``, how close the final models came: the
largest gap of a validation sample's logits over their norm, for the
trajectories compared to their end.
"""
from __future__ import annotations

import numpy as np

from bench import compare

def _change_gap(got: dict, ref: dict, init: dict) -> float:
    norm = lambda p, k: float(np.linalg.norm(np.asarray(p[k], np.float64)
                                             - np.asarray(init[k], np.float64)))
    ref_n = {k: norm(ref, k) for k in init}
    floor = float(np.median(list(ref_n.values())))
    return max(abs(norm(got, k) - ref_n[k]) / max(ref_n[k], floor, 1e-30)
               for k in init)


def _logits(p: dict, x) -> np.ndarray:
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    h = np.maximum(np.asarray(x, np.float64) @ p["w1"] + p["b1"], 0.0)
    h = np.maximum(h @ p["w2"] + p["b2"], 0.0)
    return h @ p["w3"] + p["b3"]


def point_numbers(got: dict, ref: dict, inp: dict) -> dict:
    """Numbers of one grid point; ``got`` holds the program's [R] / [R, N]
    history, final ``params``, ``pi_count`` and ``ni_count``; ``inp`` the
    point's inputs (``bench/fl_inputs.py``)."""
    init = inp["params"]
    rounds = len(got["val_acc"])
    e = int(ref["first_edge"])
    cut = lambda a: np.asarray(a)[:e]
    flips = (int(np.sum(np.any(cut(got["selected"]) != cut(ref["selected"]),
                               axis=-1)))
             + int(np.sum(np.abs(cut(got["n_excluded_roni"])
                                 - cut(ref["n_excluded_roni"]))))
             + int(np.sum(np.abs(cut(got["n_stragglers"])
                                 - cut(ref["n_stragglers"])))))
    out = {"rounds_compared": e, "rounds_checked": rounds,
           "edges_followed": ref["followed"],
           "edge_overrides": len(ref["overridden"])}
    if e == rounds:
        flips += int(np.sum(np.abs(np.asarray(got["pi_count"])
                                   - ref["pi_count"])))
        flips += int(np.sum(np.abs(np.asarray(got["ni_count"])
                                   - ref["ni_count"])))
        out["param_change_gap"] = _change_gap(got["params"], ref["params"],
                                              init)
        z = _logits(ref["params"], inp["x_val"])
        out["logit_rel"] = float(np.max(
            np.max(np.abs(_logits(got["params"], inp["x_val"]) - z), axis=1)
            / np.linalg.norm(z, axis=1)))
    cond = cut(ref["cond"])
    worst = lambda f: float(np.max(compare._rel(cut(got[f]), cut(ref[f]))
                                   / cond, initial=0.0))
    out.update(decision_flips=float(flips),
               val_acc_gap=float(np.max(np.abs(
                   cut(got["val_acc"]).astype(np.float64)
                   - cut(ref["val_acc"])), initial=0.0)),
               energy_cond=worst("energy"), latency_cond=worst("latency"))
    return out


def merge(numbers: list) -> dict:
    """The cell's numbers over its checked grid points: flips summed, the
    rest their worst.  Where no point was compared to its end, the
    parameters were not compared: ``param_change_gap`` is then infinite."""
    full = [n for n in numbers if "param_change_gap" in n]
    checked = sum(n["rounds_checked"] for n in numbers)
    worst = lambda k: max(n[k] for n in full) if full else float("inf")
    return {
        "decision_flips": sum(n["decision_flips"] for n in numbers),
        "val_acc_gap": max(n["val_acc_gap"] for n in numbers),
        "energy_cond": max(n["energy_cond"] for n in numbers),
        "latency_cond": max(n["latency_cond"] for n in numbers),
        "param_change_gap": worst("param_change_gap"),
        "uncompared_share": 1.0 - sum(n["rounds_compared"] for n in numbers)
        / max(checked, 1),
        "edge_overrides": sum(n["edge_overrides"] for n in numbers),
        "edges_followed": sum(n["edges_followed"] for n in numbers),
        "logit_rel": worst("logit_rel")}
