"""Plain reference of the DT-assisted FL round (arXiv 2501.01584, Fig. 1,
Sec. II-V), for one grid point: one configuration point on one seed's data.

Written from the paper's protocol with nothing taken from the program under
test: Python loops over rounds and clients, full-batch SGD (Eq. 2) in
``jax.numpy`` under ``jax.default_matmul_precision("highest")``, on the host's
CPU where JAX has one, and each round's allocation from
``bench/reference.equilibrium`` (NumPy float64, Alg. 2).  A round:

  1. selection: Z_n = xi1·AC_n + xi2·MS_n/sum(MS) + xi3·PI_n (Eq. 16) with
     AC_n = 1 - exp(-(D_n + eps)/2000) (Eq. 12), PI_n = I_PI/(I_PI + I_NI)
     (Eq. 15); the N highest, ties to the lower client index;
  2. a fresh Rayleigh fade per client, |h|^2 = G0·r^-alpha·|g|^2, and the
     selected clients in SIC order (descending |h|^2);
  3. the Stackelberg allocation of the round (v = v_max, f, p, t_cmp, t_com);
  4. the DT split: each valid sample maps to the twin with probability v_n;
     mapped features carry the deviation x·(1 + eps·u), u ~ U(-1, 1);
  5. local SGD of each client on its unmapped samples (a poisoner on flipped
     labels) and SGD of the twin on every mapped sample, from the global model;
  6. the deadline: a client with t_cmp + t_com > 1.001·t_max straggles;
  7. RONI: an update whose validation accuracy falls more than the threshold
     below the global model's is rejected; the twin's update too;
  8. aggregation, Eq. (3): w = (1/D)·sum_n[(1-v_n)D_n·w_n + (v_n·D_n + eps)·w_S]
     over the accepted terms, D the accepted mass; with nothing accepted the
     global model stays;
  9. the bookkeeping: I_PI / I_NI of each selected client by its RONI verdict,
     staleness reset to 1 for the selected, +1 for the rest (Eq. 13).

The round's random draws come from its key as the program's protocol splits
it: ``key, k_channel, k_map, k_twin, k_alloc = split(key, 5)``; the fades are
Exp(1) = -ln(1 - U) of ``uniform(k_channel, (M,))``, the DT split compares
``uniform(k_map, (N, cap))`` with v_n, the deviation uses ``uniform(k_twin,
(N, cap, dim), -1, 1)``.  Uniform draws are integer arithmetic and a scaling,
the same bits on every backend; the logarithm is taken here in float64.

A decision whose margin lies within ``EDGE`` of its threshold is one that a
correct float32 program may take either way: a near tie in Z across the N-th
place between clients whose inputs differ, a near tie in |h|^2, a latency at
the deadline's tolerance, an accuracy drop within 8 of 512 validation samples
of the RONI threshold (the program's models and these drift apart over the
rounds by a few samples in what they predict).  Given the program's history
(``follow``), the reference takes the program's side of such decisions:
the selected clients and their order as the program reports them; of the
verdicts at the deadline and RONI edges (the twin's among them, which the
program does not report), those that the program's counts of stragglers
and rejected updates allow, and among several, the one whose aggregate
predicts the validation set nearest the program's reported accuracy, where
it stands ``EDGE["val_acc_apart"]`` clear of the next.  The first
edge that the history does not settle so ends the comparison of the grid
point: the trajectory is compared up to the round before it.  Each edge
decision taken so against the reference's own verdict is recorded
(``overridden``); a sound program gives none or almost none, a program that
decides by another rule one wherever its rule and this one part inside the
edge.

``dtype`` is the working precision of the training; the allocation runs in
float64 unless ``dtype`` is lower, which makes the whole reference the
control.
"""
from __future__ import annotations

import contextlib
import itertools
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from bench import compare, reference

AC_SCALE = 2000.0          # w3 = 1/2000 of the Weibull AC model, Eq. (12)
DEADLINE_TOL = 1.001       # the straggler test's tolerance on t_max
# Z and |h|^2 pass through the TPU's default float32 exp and log, which are
# approximations (relative errors of order 1e-5 to 1e-4): a sound run on a
# TPU v5e ordered two clients whose |h|^2 lay 3.9e-5 apart the other way
EDGE = {
    "selection": 1e-4,     # gap of Z between the N-th and the next client
    "sic_order": 1e-3,     # relative gap between neighbouring |h|^2
    "deadline": 1e-4,      # |t_cmp + t_com - 1.001·t_max| / t_max
    "roni": 8 / 512,       # |accuracy drop - threshold|
    "val_acc_apart": 2 / 512,  # accuracy between two candidates
}


def _mlp(p, x):
    h = jnp.maximum(x @ p["w1"] + p["b1"], 0)
    h = jnp.maximum(h @ p["w2"] + p["b2"], 0)
    return h @ p["w3"] + p["b3"]


def _loss(p, x, y, w):
    """Weighted mean cross-entropy over the samples of weight 1."""
    z = _mlp(p, x)
    z = z - jnp.max(z, axis=1, keepdims=True)
    logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=1, keepdims=True))
    nll = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1)


@partial(jax.jit, static_argnames=("steps",))
def _sgd(p, x, y, w, lr, steps):
    grad = jax.grad(_loss)

    def step(_, q):
        g = grad(q, x, y, w)
        return {k: q[k] - lr * g[k] for k in q}
    return jax.lax.fori_loop(0, steps, step, p)


def _rows(weight, bucket=128):
    """(rows, weights): the slots of weight 1, in order, padded with
    zero-weight copies of the first to a multiple of ``bucket`` rows; the
    same loss as over every slot, on a few shapes."""
    keep = np.flatnonzero(weight)
    size = bucket * max(1, -(-len(keep) // bucket))
    idx = np.zeros(size, np.int64)
    idx[:len(keep)] = keep
    return idx, np.arange(size) < len(keep)


@jax.jit
def _hits(p, x, y):
    return jnp.sum(jnp.argmax(_mlp(p, x), axis=1) == y)


def _selection_edge(z, rank, n, inputs):
    """The clients near the cut, where the gap of Z across it is under
    ``EDGE["selection"]`` and their (D, MS, I_PI, I_NI) differ: a float32 Z
    may order them either way.  Clients with the same inputs have the same
    Z in any precision, and the lower index goes first.  None where the cut
    is clear."""
    lo, hi = z[rank[n]], z[rank[n - 1]]
    if hi - lo >= EDGE["selection"]:
        return None
    near = (z >= lo - EDGE["selection"]) & (z <= hi + EDGE["selection"])
    rows = np.stack(inputs, axis=1)[near]
    return near if np.any(rows != rows[0]) else None


def _assignments(good, edge, bad_count):
    """Verdict arrays that agree with ``good`` away from the edge and hold
    ``bad_count`` bad ones in all; ``good`` alone where none can."""
    need = bad_count - int(np.sum(~good & ~edge))
    at = np.flatnonzero(edge)
    if not 0 <= need <= len(at):
        return [good]
    out = []
    for bad in itertools.combinations(at, need):
        v = good.copy()
        v[at] = True
        v[list(bad)] = False
        out.append(v)
    return out


def _candidates(meets, late_edge, ok, roni_edge, follow, r):
    """The (meets, ok) verdicts that the program's counts of stragglers and
    of rejected client updates allow, every verdict at an edge taken either
    way; the twin's own verdict is not counted by the program.  Without a
    history, none: the edge stays open."""
    if follow is None:
        return []
    n = len(meets)
    late = _assignments(meets, late_edge, int(follow["n_stragglers"][r]))
    twin = [ok[n:]] if not roni_edge[n] else [np.array([True]),
                                               np.array([False])]
    roni = _assignments(ok[:n], roni_edge[:n],
                        int(follow["n_excluded_roni"][r]))
    return [(a, np.concatenate([b, t])) for a in late for b in roni
            for t in twin]


def _aggregate(params, clients, twin, verdicts, v, d_n, eps, cast):
    """Eq. (3) over the accepted terms: client n when it met the deadline
    and passed RONI, the twin when it passed; the global model stays when
    nothing was accepted."""
    meets, ok = verdicts
    n = len(clients)
    include, twin_ok = ok[:n] & meets, bool(ok[n])
    if not (include.any() or twin_ok):
        return params
    w_local = np.where(include, (1.0 - v) * d_n, 0.0)
    w_twin = np.sum(v * d_n + eps) if twin_ok else 0.0
    # D of Eq. (3) less each excluded term's own weight
    mass = (np.sum(d_n) - np.sum(np.where(include, 0.0, (1.0 - v) * d_n))
            - (0.0 if twin_ok else np.sum(v * d_n + eps)))
    return {k: (sum(cast(w_local[i]) * clients[i][k] for i in range(n))
                + cast(w_twin) * twin[k]) / cast(max(mass, 1e-9))
            for k in params}


def _device():
    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:                    # a JAX held to the accelerator
        return contextlib.nullcontext()


def trajectory(inp: dict, point: dict, protocol: dict, phys: dict,
               channel: dict, solver: dict, rounds: int,
               dtype=np.float32, follow: dict | None = None) -> dict:
    """One grid point's R rounds.

    inp      : one seed's inputs (``bench/fl_inputs.py``), NumPy arrays.
    point    : ``lr``, ``epsilon``, ``t_max``.
    protocol : ``n_selected``, ``local_steps``, ``server_steps``,
               ``roni_threshold``, ``weights``, ``samples_per_unit``.
    Returns per-round ``selected`` [R, N], ``n_excluded_roni``,
    ``n_stragglers``, ``val_acc``, ``energy``, ``latency``, ``cond`` [R],
    ``first_edge`` (the first round with an edge decision left unsettled,
    else R; the rounds end after it), ``edges`` (that round's unsettled
    kinds), ``followed`` (edge decisions settled by the program's history),
    ``overridden`` (each decision among them taken against the reference's
    own, as [kind, margin]: the gap of Z, the least relative gap of |h|^2,
    the relative distance of t_cmp + t_com from 1.001·t_max, or the drop's
    distance from the RONI threshold in validation samples),
    the final ``params``, ``pi_count``, ``ni_count``.  Without ``follow``
    no edge is settled and the rounds run to R: the reference as the
    program, in the control.
    """
    with _device(), jax.default_matmul_precision("highest"):
        return _trajectory(inp, point, protocol, phys, channel, solver,
                           rounds, np.dtype(dtype), follow)


def _trajectory(inp, point, protocol, phys, channel, solver, rounds, dt,
                follow):
    game_dt = np.float64 if dt == np.float32 else dt
    n = int(protocol["n_selected"])
    eps, lr, t_max = (float(point[k]) for k in ("epsilon", "lr", "t_max"))
    thr = float(protocol["roni_threshold"])
    xi = np.asarray(protocol["weights"], np.float64)
    d_all = np.asarray(inp["sizes"], np.float64)
    m, cap, dim = inp["x"].shape
    v_val = len(inp["y_val"])
    ph = dict(phys, t_max=t_max)
    x_val, y_val = jnp.asarray(inp["x_val"], dt), jnp.asarray(inp["y_val"])
    cast = lambda a: jnp.asarray(a, dt)
    params = {k: cast(v) for k, v in inp["params"].items()}
    ms = np.asarray(inp["ms"], np.float64)
    pi = np.asarray(inp["pi_count"], np.float64)
    ni = np.asarray(inp["ni_count"], np.float64)
    gain = (channel["ref_gain"] * np.asarray(inp["distances"], np.float64)
            ** -channel["pathloss_exp"])
    key = jnp.asarray(inp["key"], jnp.uint32)
    out = {f: [] for f in ("selected", "n_excluded_roni", "n_stragglers",
                           "val_acc", "energy", "latency", "cond")}
    first_edge, edges, followed, overridden = rounds, [], 0, []
    for r in range(rounds):
        at_edge = []
        told = None if follow is None else np.asarray(follow["selected"][r])
        # the round's five streams (the program's key protocol)
        key, k_ch, k_map, k_dt, _ = jax.random.split(key, 5)
        # 1. selection, Eq. (16)
        z = (xi[0] * (1.0 - np.exp(-(d_all + eps) / AC_SCALE))
             + xi[1] * ms / np.sum(ms) + xi[2] * pi / (pi + ni))
        rank = np.argsort(-z, kind="stable")
        sel = rank[:n]
        near = (_selection_edge(z, rank, n, (d_all, ms, pi, ni))
                if n < m else None)
        if near is not None:
            keep = np.setdiff1d(sel, np.flatnonzero(near))
            if (told is not None and np.all(np.isin(keep, told))
                    and np.all(near[told] | np.isin(told, keep))):
                overridden += [["selection",
                                float(z[rank[n - 1]] - z[rank[n]])]] * int(
                    np.sum(~np.isin(told, sel)))
                sel, followed = told, followed + 1
            else:
                at_edge.append("selection")
        # 2. Rayleigh fade, Exp(1) = -ln(1 - U), and the SIC order
        u = np.asarray(jax.random.uniform(k_ch, (m,)), np.float64)
        fade = gain * -np.log1p(-u)
        order = sel[np.argsort(-fade[sel], kind="stable")]
        if np.any(-np.diff(fade[order]) < EDGE["sic_order"] * fade[order][1:]):
            if (told is not None and set(told) == set(order) and np.all(
                    -np.diff(fade[told])
                    >= -EDGE["sic_order"] * fade[told][1:])):
                if np.any(told != order):
                    gaps = -np.diff(fade[order]) / fade[order][1:]
                    overridden.append(["sic_order", float(np.min(gaps))])
                order, followed = told, followed + 1
            else:
                at_edge.append("sic_order")
        h2 = fade[order]
        # 3. the allocation of the round (Alg. 2)
        d_n = d_all[order] * float(protocol["samples_per_unit"])
        v = np.asarray(inp["v_max"])[order]
        al = reference.equilibrium(
            h2[None], d_n[None], v[None].astype(np.float64), ph, epsilon=0.0,
            max_iter=solver["max_iter"], tol=solver["tol"],
            dinkelbach_delta=solver["dinkelbach_delta"],
            dinkelbach_iter=solver["dinkelbach_iter"], dtype=game_dt)
        # 4. DT split: sample mapped with probability v_n
        valid = np.asarray(inp["mask"])[order]
        mapped = (np.asarray(jax.random.uniform(k_map, (n, cap)))
                  < v[:, None]) & valid
        x_n = np.asarray(inp["x"])[order]
        y_n = np.asarray(inp["y_train"])[order]
        dev = np.asarray(jax.random.uniform(k_dt, (n, cap, dim), minval=-1.0,
                                            maxval=1.0))
        # 5. local SGD per client, twin SGD on the mapped samples, each on
        # its samples of weight 1
        clients = []
        for i in range(n):
            rows, w = _rows(valid[i] & ~mapped[i])
            clients.append(_sgd(params, cast(x_n[i][rows]),
                                jnp.asarray(y_n[i][rows]), cast(w), cast(lr),
                                int(protocol["local_steps"])))
        rows, w = _rows(mapped.reshape(-1))
        x_dt = (cast(x_n.reshape(-1, dim)[rows])
                * (1 + cast(eps) * cast(dev.reshape(-1, dim)[rows])))
        twin = _sgd(params, x_dt, jnp.asarray(y_n.reshape(-1)[rows]), cast(w),
                    cast(lr), int(protocol["server_steps"]))
        # 6. the deadline
        t_done = np.asarray(al["t_cmp"][0] + al["t_com"][0], np.float64)
        meets = t_done <= DEADLINE_TOL * t_max
        late_edge = (np.abs(t_done - DEADLINE_TOL * t_max)
                     < EDGE["deadline"] * t_max)
        # 7. RONI, in validation samples: drop <= thr·V (the twin's last)
        base = int(_hits(params, x_val, y_val))
        drops = np.asarray([base - int(_hits(p, x_val, y_val))
                            for p in clients + [twin]], np.float64)
        ok = drops <= thr * v_val
        roni_edge = np.abs(drops / v_val - thr) < EDGE["roni"]
        # 8. aggregation, Eq. (3), of the verdicts, those at an edge settled
        # by the program's history where it names them
        agg = lambda c: _aggregate(params, clients, twin, c, v, d_n, eps,
                                   cast)
        if late_edge.any() or roni_edge.any():
            verdicts = _candidates(meets, late_edge, ok, roni_edge, follow, r)
            if len(verdicts) > 1:
                hits = [int(_hits(agg(c), x_val, y_val)) for c in verdicts]
                miss = np.abs(np.asarray(hits) / v_val
                              - float(follow["val_acc"][r]))
                best = np.argsort(miss, kind="stable")
                verdicts = ([verdicts[best[0]]] if miss[best[1]] - miss[best[0]]
                            >= EDGE["val_acc_apart"] else [])
            if verdicts:
                late_gap = np.abs(t_done / t_max - DEADLINE_TOL)
                overridden += [["deadline", float(late_gap[i])] for i in
                               np.flatnonzero(verdicts[0][0] != meets)]
                overridden += [["roni", float(abs(drops[i] - thr * v_val))]
                               for i in np.flatnonzero(verdicts[0][1] != ok)]
                meets, ok = verdicts[0]
                followed += follow is not None
            else:
                at_edge.append("verdicts")
        positive = ok[:n]
        params = agg((meets, ok))
        # 9. bookkeeping, Eqs. (13), (15)
        pi[order] += positive
        ni[order] += ~positive
        ms = np.where(np.isin(np.arange(m), order), 1.0, ms + 1.0)
        cond = float(compare.condition(al, t_max)[0])
        for f, val in (("selected", order),
                       ("n_excluded_roni", int(np.sum(~positive))),
                       ("n_stragglers", int(np.sum(~meets))),
                       ("val_acc", int(_hits(params, x_val, y_val)) / v_val),
                       ("energy", float(al["energy"][0])),
                       ("latency", float(al["t_total"][0])),
                       ("cond", cond)):
            out[f].append(val)
        if at_edge and follow is not None:
            first_edge, edges = r, at_edge
            break
    res = {f: np.asarray(v) for f, v in out.items()}
    res.update(first_edge=first_edge, edges=edges, followed=followed,
               overridden=overridden,
               params={k: np.asarray(v, np.float64) for k, v in params.items()},
               pi_count=pi, ni_count=ni)
    return res
