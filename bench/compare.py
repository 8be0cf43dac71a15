"""The comparison that decides ``correct``: allocations against the plain
reference (``bench/reference.py``), number by number.

Three properties of the equilibrium shape what is compared.

* A rate B·log2(1 + x) carries the rounding of 1 + x: at a small SINR x a
  relative error u in 1 + x is an error u / ln(1 + x) in the rate and so in
  the airtime t_com = d / R.  Cell-edge clients in a deep fade reach x of
  1e-4 and below.  rho_n = max(1, 1 / ln(1 + x_n)).
* The leader runs each client's CPU exactly to the deadline, so f_n =
  c(1-v)D_n / (t_max - t_com_n) turns a relative error d in the airtime
  into kappa_n·d in f_n and in the energy, kappa_n = t_com_n /
  (t_max - t_com_n), counted for the clients ahead of the deadline.
* So the latency, the DT shares (which follow it), the CPU frequencies and
  the energy are held per draw to their relative error divided by the
  draw's condition number, cond = max_n rho_n·max(1, kappa_n): the error in
  units of the rounding that the draw amplifies.  Powers are held flat.
* Feasibility is ``max(t_cmp + t_com) <= t_max + 1e-6``, and a deadline-
  exact draw sits within a few float32 ulps of that threshold, where either
  flag is a correct float32 answer.  Flags are compared on the draws whose
  reference lies more than ``EDGE_ULPS`` float32 ulps of t_max away from it.
"""
from __future__ import annotations

import numpy as np

EDGE_ULPS = 8

# name -> what it is; the order is the order they are printed in
NUMBERS = {
    "p_rel": "max relative error of the transmit powers",
    "alpha_cond": "max over draws of the DT shares' relative error / cond",
    "t_total_cond": "max over draws of the round latency's relative error / cond",
    "f_cond": "max over draws of the CPU frequencies' relative error / cond",
    "energy_cond": "max over draws of the energy's relative error / cond",
    "flag_flips": "draws away from the deadline whose feasible flag differs",
}


def _rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    with np.errstate(invalid="ignore", divide="ignore"):
        err = np.abs(a - b) / np.maximum(np.abs(b), 1e-30)
    return np.where(np.isfinite(err), err, np.inf)


def _worst(x, axis=None):
    x = np.asarray(x, np.float64)
    return float(np.max(x, axis=axis)) if axis is None else np.max(x, axis=axis)


def condition(ref: dict, t_max) -> np.ndarray:
    """Per draw, max_n rho_n·max(1, kappa_n) (see the module docstring)."""
    t_com = np.asarray(ref["t_com"], np.float64)
    t_max = np.asarray(t_max, np.float64).reshape(-1, 1)
    kappa = np.where(t_com < t_max, t_com / np.maximum(t_max - t_com, 1e-3), 0.0)
    rho = 1.0 / np.log1p(np.asarray(ref["sinr"], np.float64))
    return np.max(np.maximum(1.0, rho) * np.maximum(1.0, kappa), axis=1)


def allocation_numbers(got: dict, ref: dict, t_max) -> dict:
    """The numbers compared, for [K, N] / [K] arrays of one batch of draws.

    ``got`` holds the program's p, f, alpha, t_total, energy, feasible;
    ``ref`` the reference's, with sinr, t_com and deadline_slack."""
    cond = condition(ref, t_max)
    t_max = np.asarray(t_max, np.float64) * np.ones(cond.shape)
    edge = EDGE_ULPS * np.spacing(t_max.astype(np.float32)).astype(np.float64)
    slack = np.asarray(ref["deadline_slack"], np.float64) - 1e-6
    away = np.abs(slack) > edge
    flips = away & (np.asarray(got["feasible"], bool)
                    != np.asarray(ref["feasible"], bool))
    return {
        "p_rel": _worst(_rel(got["p"], ref["p"])),
        "alpha_cond": _worst(_worst(_rel(got["alpha"], ref["alpha"]), axis=1) / cond),
        "t_total_cond": _worst(_rel(got["t_total"], ref["t_total"]) / cond),
        "f_cond": _worst(_worst(_rel(got["f"], ref["f"]), axis=1) / cond),
        "energy_cond": _worst(_rel(got["energy"], ref["energy"]) / cond),
        "flag_flips": float(np.sum(flips)),
    }


def merge(numbers: list) -> dict:
    """Worst of each number over several batches."""
    return {k: max(n[k] for n in numbers) for k in numbers[0]}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, rows): each number beside its limit; a missing, non-finite
    or over-limit number is not correct."""
    rows = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok &= bool(good)
        rows[name] = {"value": value, "limit": limit}
    return ok, rows
