"""One round of DT-assisted federated learning over NOMA (paper Fig. 1).

Round pipeline (§II–§V):
  1. reputation-based selection of N of M clients            (§III)
  2. fresh block-fading channel realization, SIC ordering    (§II-C)
  3. Stackelberg allocation (v*, f*, p*, α*) or baseline     (§IV–V)
  4. DT data split: Bernoulli(v_n) per sample → server-mapped (with ε
     feature deviation) vs local                             (§II)
  5. local SGD on clients (poisoners train on flipped labels) (Eq. 2)
     + server/DT SGD on the union of mapped data; a client model too
     large for a copy per client is trained one client at a time, each
     judged (7) and summed into Eq. (3) (8) as it is done
  6. deadline check: clients with t_cmp + t_com > T_max straggle and
     drop out (the mechanism DT/NOMA alleviate)
  7. RONI validation → PI/NI bookkeeping, exclusion          (§III-3)
  8. DT-aware aggregation, Eq. (3)
  9. staleness update, Eq. (13)

Schemes: "proposed" (DT+NOMA), "wo_dt" (v≡0), "oma", "ideal" (no resource
constraints), matching §VI-C benchmarks.

Execution tiers — the whole R-round trajectory is ONE compiled program:

  * ``_round_body``        — the trace-safe round: static arguments are the
    discrete algorithm choices (scheme, use_roni, shapes/steps, logits_fn,
    dinkelbach inner); every numeric knob (lr, ε, RONI threshold, selection
    weights, the ``GamePhysics`` floats) is a traced operand, so distinct
    ``FLConfig``/``GameConfig`` values reuse one executable.  The
    "RONI rejected everything → keep the previous global model" decision is
    a ``jnp.where`` over the parameter pytree, not a host branch.
  * ``run_training_scan``  — R rounds as a single jitted ``lax.scan``
    dispatch.  Metrics come back as a dict of stacked arrays with a leading
    ``(R,)`` axis (``(R, N)`` for ``selected``) — the stacked-metrics
    history format; ``stackelberg.TRACE_COUNTS['run_round']`` proves the
    round body traces exactly once per (scheme, use_roni, shape).
  * ``batched_training``   — ``vmap`` of the scan over a leading seed axis
    (optionally with per-seed data, e.g. a poisoned-fraction axis): an
    S-seed × R-round sweep is one dispatch, seed axis device-sharded.
  * ``sweep_training``     — a leading CONFIG axis on top of the seed axis:
    C (``FLConfig``, ``GameConfig``) points × S seeds × R rounds as ONE
    dispatch of one executable.  The C points' numeric knobs are stacked
    into ``[C]``-leaved pytrees (``stack_physics`` / ``stack_fl_ops``), the
    C×S grid is flattened and device-sharded, and a whole Fig. 5/6/7/8-style
    figure grid traces the round body exactly once per (scheme, use_roni,
    shape) — scheme/use_roni/shapes are the only compile keys.
  * ``run_training``       — compat shim over ``run_training_scan``: same
    list-of-dicts history (python scalars) as the legacy host loop.
  * ``run_round`` / ``run_training_eager`` — the legacy host-side path
    (one dispatch per stage, per-round host syncs), kept as the numerical
    reference and the benchmark baseline of
    ``benchmarks/training_throughput.py``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..data.federated import FedData
from ..sharding import game_mesh
from . import reputation as rep
from .aggregation import dt_aggregate, eq3_weights, fedavg, fedavg_weights
from .digital_twin import dt_feature_noise, split_mapping_mask
from .faults import (FaultConfig, FaultOps, attack_active, faded_channel,
                     fault_ops, sample_round_faults, slowdown_multiplier,
                     stack_fault_ops)
from .roni import roni_filter, roni_verdict
from .stackelberg import (TRACE_COUNTS, Allocation, GameConfig,
                          _oma_body, _physics_cached, _random_body,
                          _shard_axis, _solve, batched_equilibrium,
                          batched_oma_allocation, batched_oma_tdma_allocation,
                          batched_random_allocation, batched_wo_dt_allocation,
                          equilibrium, oma_allocation, oma_tdma_allocation,
                          random_allocation, stack_physics, sweep_equilibrium,
                          sweep_oma_allocation, sweep_oma_tdma_allocation,
                          sweep_random_allocation, sweep_wo_dt_allocation,
                          wo_dt_allocation)
from .channel import sample_round_channels


@dataclass(frozen=True)
class FLConfig:
    n_selected: int = 5
    local_steps: int = 20
    server_steps: int = 20
    lr: float = 0.05
    epsilon: float = 0.0            # DT mapping deviation
    roni_threshold: float = 0.02
    weights: Tuple[float, float, float] = rep.PROPOSED_WEIGHTS
    scheme: str = "proposed"   # proposed | wo_dt | oma | oma_tdma | ideal | random
    use_roni: bool = True
    samples_per_unit: float = 1.0   # D_n (samples) → data units for latency


@dataclass
class FLState:
    params: dict
    rep: rep.ReputationState
    v_max: jax.Array        # [M]
    distances: jax.Array    # [M]
    key: jax.Array
    round: jax.Array | int = 0


# pytree registration: FLState is the lax.scan carry of the compiled
# trajectory (every field is a data leaf; ``round`` rides as an int32 array).
_FLSTATE_FIELDS = tuple(f.name for f in dataclasses.fields(FLState))
jax.tree_util.register_dataclass(FLState, data_fields=_FLSTATE_FIELDS,
                                 meta_fields=())


# ---------------------------------------------------------------------------
# local / server SGD
# ---------------------------------------------------------------------------
def masked_loss(logits_fn, p, x, y, w):
    logits = logits_fn(p, x)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


@partial(jax.jit, static_argnames=("logits_fn", "steps"))
def sgd_train(logits_fn, params, x, y, w, steps: int, lr: float):
    """Full-batch SGD (Eq. 2) for ``steps`` steps with per-sample weights.

    jit-cached on (logits_fn, steps) — an eager ``lax.scan`` here would
    retrace (and recompile the conv backward) every FL round."""
    def step(p, _):
        g = jax.grad(partial(masked_loss, logits_fn))(p, x, y, w)
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), None

    params, _ = jax.lax.scan(step, params, None, length=steps)
    return params


@partial(jax.jit, static_argnames=("logits_fn", "steps"))
def local_train_all(logits_fn, params, x, y, w, steps, lr):
    """vmap local SGD over the selected clients. x: [N, cap, dim]."""
    return jax.vmap(lambda xi, yi, wi: sgd_train(logits_fn, params, xi, yi,
                                                 wi, steps, lr))(x, y, w)


@partial(jax.jit, static_argnames=("logits_fn",))
def _val_acc(logits_fn, x_val, y_val, params):
    logits = logits_fn(params, x_val)
    return jnp.mean((jnp.argmax(logits, -1) == y_val).astype(jnp.float32))


# ---------------------------------------------------------------------------
# allocation dispatch (host-side tiers)
# ---------------------------------------------------------------------------
def allocate(scheme: str, game_cfg: GameConfig, key, h2_sorted, d_units,
             v_max_sel) -> Allocation:
    """Per-round resource allocation.  Every scheme routes through a fully
    jitted body whose physics floats are traced operands — one compile per
    (scheme, shape), shared across GameConfig parameterizations, no host
    syncs inside the solve."""
    if scheme in ("proposed", "ideal"):
        return equilibrium(game_cfg, h2_sorted, d_units, v_max_sel)
    if scheme == "wo_dt":
        return wo_dt_allocation(game_cfg, h2_sorted, d_units)
    if scheme == "oma":
        return oma_allocation(game_cfg, h2_sorted, d_units, v_max_sel)
    if scheme == "oma_tdma":
        return oma_tdma_allocation(game_cfg, h2_sorted, d_units, v_max_sel)
    if scheme == "random":
        return random_allocation(game_cfg, key, h2_sorted, d_units, v_max_sel)
    raise ValueError(scheme)


def allocate_batched(scheme: str, game_cfg: GameConfig, h2_batch, d_batch,
                     v_max_batch, epsilon: float = 0.0,
                     key=None) -> Allocation:
    """Monte-Carlo allocation: solve K network realizations in one XLA
    call (used by the Fig. 6–9 benchmark sweeps and throughput bench).
    EVERY scheme batches — proposed/ideal/wo_dt through the Stackelberg
    engine, OMA-FDMA/OMA-TDMA/random through their vmapped baseline
    bodies — and the K axis is device-sharded (single-device no-op).
    Large-N cells opt into the blocked SIC power engine through
    ``game_cfg.sic_mode`` (a static key — see ``repro.core.sic``), which
    reaches every Stackelberg-backed scheme here.
    ``epsilon`` (DT mapping deviation) reaches the engine for the DT
    schemes; "wo_dt" has no twin and ignores it (matching
    ``wo_dt_allocation``).  ``key`` seeds the "random" scheme's per-draw
    randomness (defaults to PRNGKey(0))."""
    if scheme in ("proposed", "ideal"):
        return batched_equilibrium(game_cfg, h2_batch, d_batch, v_max_batch,
                                   epsilon=epsilon)
    if scheme == "wo_dt":
        return batched_wo_dt_allocation(game_cfg, h2_batch, d_batch)
    if scheme == "oma":
        return batched_oma_allocation(game_cfg, h2_batch, d_batch,
                                      v_max_batch, epsilon=epsilon)
    if scheme == "oma_tdma":
        return batched_oma_tdma_allocation(game_cfg, h2_batch, d_batch,
                                           v_max_batch, epsilon=epsilon)
    if scheme == "random":
        key = jax.random.PRNGKey(0) if key is None else key
        return batched_random_allocation(game_cfg, key, h2_batch, d_batch,
                                         v_max_batch, epsilon=epsilon)
    raise ValueError(f"no batched path for scheme {scheme!r}")


def sweep_allocation(scheme: str, configs, h2_batch, d_batch, v_max_batch,
                     epsilon=0.0, key=None) -> Allocation:
    """Benchmark-grid allocation: C config points × K realizations of one
    scheme in ONE XLA dispatch of one compiled executable (the fig9 sweep
    workload).  ``configs`` is a sequence of GameConfig whose physics are
    stacked into a traced [C] axis; ``epsilon`` may be scalar or [C].
    Returns an ``Allocation`` with a [C, K] prefix on every field."""
    if scheme in ("proposed", "ideal"):
        return sweep_equilibrium(configs, h2_batch, d_batch, v_max_batch,
                                 epsilon=epsilon)
    if scheme == "wo_dt":
        return sweep_wo_dt_allocation(configs, h2_batch, d_batch)
    if scheme == "oma":
        return sweep_oma_allocation(configs, h2_batch, d_batch, v_max_batch,
                                    epsilon=epsilon)
    if scheme == "oma_tdma":
        return sweep_oma_tdma_allocation(configs, h2_batch, d_batch,
                                         v_max_batch, epsilon=epsilon)
    if scheme == "random":
        key = jax.random.PRNGKey(0) if key is None else key
        return sweep_random_allocation(configs, key, h2_batch, d_batch,
                                       v_max_batch, epsilon=epsilon)
    raise ValueError(f"no sweep path for scheme {scheme!r}")


def _allocate_traced(scheme: str, phys, inner: str, key, h2_sorted, d_units,
                     v_max_sel, sic_mode: str = "sequential",
                     mask=None) -> Allocation:
    """Scheme dispatch inside the traced round body: direct calls into the
    shared solver bodies with the traced ``GamePhysics`` — no nested jit
    wrappers, no host syncs, one executable across GameConfig values.
    ``scheme``/``inner``/``sic_mode`` are static (compile keys); everything
    else is an operand.

    ``mask`` ([N] bool operand, default None) is the graceful-degradation
    path of the fault engine: lanes of mid-round dropouts carry h2 = 0 (the
    SIC tail) and are masked through the same traced ``mask`` plumbing the
    padded serving buckets use (``stackelberg._solve``/``_oma_body``/
    ``_random_body``), so the equilibrium re-solves over the n_eff
    survivors instead of allocating power to a dead client."""
    dtype = jnp.result_type(h2_sorted)
    tol = jnp.asarray(1e-6, dtype)
    eps0 = jnp.asarray(0.0, dtype)
    if scheme in ("proposed", "ideal"):
        return _solve(phys, h2_sorted, d_units, v_max_sel, eps0, 20, tol,
                      inner, sic_mode, mask=mask)
    if scheme == "wo_dt":
        return _solve(phys, h2_sorted, d_units, jnp.zeros_like(h2_sorted),
                      eps0, 20, tol, inner, sic_mode, mask=mask)
    if scheme == "oma":
        return _oma_body(phys, h2_sorted, d_units, v_max_sel, eps0, inner,
                         tdma=False, mask=mask)
    if scheme == "oma_tdma":
        return _oma_body(phys, h2_sorted, d_units, v_max_sel, eps0, inner,
                         tdma=True, mask=mask)
    if scheme == "random":
        return _random_body(phys, key, h2_sorted, d_units, v_max_sel, eps0,
                            mask=mask)
    raise ValueError(scheme)


def _scans_clients(model) -> bool:
    """Whether the round trains ``model`` one client at a time: a client
    model object that says so (``scan_clients``, e.g.
    ``models.nemotron_h.LMClient``); a classifier's logits function is
    trained for every selected client at once."""
    return bool(getattr(model, "scan_clients", False))


def _train_clients_vmapped(logits_fn, params, data, xs, ys_train, local_w,
                           map_mask, meets, d_units, v, ops, k_dt,
                           scheme: str, use_roni: bool, local_steps: int,
                           server_steps: int):
    """Stages 5, 7 and 8 for a classifier: full-batch local SGD of every
    selected client at once (``local_train_all``), the twin's SGD on the
    mapped samples, RONI, Eq. (3).  Returns (new params, positive [N],
    no extra metrics)."""
    n_selected = xs.shape[0]
    # 5a. local SGD (poisoners flip labels locally)
    with jax.named_scope("fl.local_sgd"):
        client_params = local_train_all(logits_fn, params, xs, ys_train,
                                        local_w, local_steps, ops["lr"])
    # 5b. server/DT SGD on mapped data (ε feature deviation).  The twin
    # mirrors the client's data AS-IS — a poisoner's mapped samples carry
    # the flipped labels too (the twin holds no clean copy to check them by)
    n, cap, dim = xs.shape
    with jax.named_scope("fl.twin_sgd"):
        x_dt = dt_feature_noise(k_dt, xs, ops["epsilon"]).reshape(n * cap,
                                                                  dim)
        server_params = sgd_train(logits_fn, params, x_dt,
                                  ys_train.reshape(-1),
                                  map_mask.reshape(-1).astype(jnp.float32),
                                  server_steps, ops["lr"])

    # 7. RONI
    with jax.named_scope("fl.roni"):
        if use_roni:
            # per-update RONI against the pre-round global model (Biscotti
            # [31]); the DT/server update is validated the same way — the
            # twin mirrors poisoned mapped data too
            positive, acc_base, _ = roni_filter(client_params, params,
                                                d_units, v, ops["epsilon"],
                                                logits_fn, data.x_val,
                                                data.y_val,
                                                ops["roni_threshold"])
            server_ok = roni_verdict(
                acc_base[0], _val_acc(logits_fn, data.x_val, data.y_val,
                                      server_params), ops["roni_threshold"])
        else:
            positive = jnp.ones((n_selected,), bool)
            server_ok = jnp.asarray(True)
    include = positive & meets

    # 8. aggregation (Eq. 3); ideal uses plain FedAvg on full local data.
    # If RONI rejected EVERYTHING this round, keep the previous global model
    # (an empty aggregate would zero the parameters) — a jnp.where over the
    # parameter pytree, so the decision stays on-device inside the scan.
    with jax.named_scope("fl.aggregate"):
        if scheme == "ideal":
            agg = fedavg(client_params, d_units, include_mask=include)
            any_included = jnp.any(include)
        else:
            agg = dt_aggregate(client_params, server_params, d_units, v,
                               ops["epsilon"], include_mask=include,
                               server_include=server_ok)
            any_included = jnp.any(include) | server_ok
        new_params = jax.tree_util.tree_map(
            lambda new, old: jnp.where(any_included, new, old),
            agg, params)
    return new_params, positive, {}


def minibatch_sgd(model, params, x, y, w, keys, lr, deviate=None):
    """SGD on ``model.loss`` with one minibatch of ``model.batch`` sequences
    a step, one step per key of ``keys``.  Each draw picks, among the rows
    of weight > 0, the one with the largest U[0, 1) of the step's key
    (``uniform(key, (batch, rows))``): uniform over those rows, with
    replacement; where none has weight the step draws weight 0 and does
    nothing.  ``deviate`` = (key, ε) puts the twin's deviation on the drawn
    rows' inputs, from ``fold_in(key, row)``, so that a row reads the same
    each time it is drawn.  Returns (params, the loss's aux stacked over the
    steps)."""
    rows = w.shape[0]

    def step(p, key):
        u = jax.random.uniform(key, (model.batch, rows))
        pick = jnp.argmax(jnp.where(w > 0, u, -1.0), axis=1)
        xb = x[pick]
        if deviate is not None:
            k_dev, eps = deviate
            xb = jax.vmap(lambda j, xr: model.deviate(
                jax.random.fold_in(k_dev, j), xr, eps))(pick, xb)
        (_, aux), g = jax.value_and_grad(model.loss, has_aux=True)(
            p, xb, y[pick], w[pick])
        return jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g), aux

    return jax.lax.scan(step, params, keys)


def _train_clients_scanned(model, params, data, xs, ys_train, local_w,
                           map_mask, meets, d_units, v, ops, k_dt,
                           scheme: str, use_roni: bool, local_steps: int,
                           server_steps: int):
    """Stages 5, 7 and 8 for a model trained one client at a time: the
    twin's minibatch SGD on every mapped sequence, then a scan over the N
    selected clients, each trained (minibatch SGD on its unmapped
    sequences), judged by RONI against the pre-round global model and added
    to a running Eq. (3) sum, which is divided once at the end.  At most
    one client's parameters and the running sum live at a time.

    Keys: ``k_dev, k_local, k_twin = split(k_dt, 3)``; client i's step j
    draws from ``split(k_local, (N, local_steps))[i, j]``, the twin's step
    j from ``split(k_twin, server_steps)[j]``, and the twin's deviation of
    flat row r (client-major, N·cap rows) from ``fold_in(k_dev, r)``.
    Returns (new params, positive [N], the loss's aux of every step,
    stacked twin first over the N + 1 trainings: [N + 1, steps, ...])."""
    n, cap = local_w.shape
    k_dev, k_local, k_twin = jax.random.split(k_dt, 3)
    thr = ops["roni_threshold"]
    acc = lambda p: model.accuracy(p, data.x_val, data.y_val)
    with jax.named_scope("fl.roni"):
        acc_base = acc(params)
    flat = lambda a: a.reshape((n * cap,) + a.shape[2:])
    with jax.named_scope("fl.twin_sgd"):
        twin, twin_aux = minibatch_sgd(
            model, params, flat(xs), flat(ys_train),
            flat(map_mask).astype(jnp.float32),
            jax.random.split(k_twin, server_steps), ops["lr"],
            deviate=(k_dev, ops["epsilon"]))
    with jax.named_scope("fl.roni"):
        server_ok = roni_verdict(acc_base, acc(twin), thr) if use_roni \
            else jnp.asarray(True)
    # each client's weight and the twin's (zero in plain FedAvg, a rejected
    # twin's too); a client's is dropped in the scan if RONI or the
    # deadline excludes it, as ``include_mask`` drops it in Eq. (3)
    if scheme == "ideal":
        a, _ = fedavg_weights(d_units)
        w_twin = jnp.zeros_like(jnp.sum(d_units))
    else:
        a, w_twin, _ = eq3_weights(d_units, v, ops["epsilon"],
                                   server_include=server_ok)
    with jax.named_scope("fl.aggregate"):
        total = jax.tree_util.tree_map(lambda q: w_twin * q, twin)

    def client(total, inp):
        x_i, y_i, w_i, keys_i, a_i, meets_i = inp
        with jax.named_scope("fl.local_sgd"):
            p_i, aux = minibatch_sgd(model, params, x_i, y_i, w_i, keys_i,
                                     ops["lr"])
        with jax.named_scope("fl.roni"):
            ok = roni_verdict(acc_base, acc(p_i), thr) if use_roni else \
                jnp.asarray(True)
        with jax.named_scope("fl.aggregate"):
            c = jnp.where(ok & meets_i, a_i, 0.0)
            total = jax.tree_util.tree_map(lambda t, q: t + c * q, total,
                                           p_i)
        return total, (ok, aux)

    local_keys = jax.random.split(k_local, n * local_steps).reshape(
        (n, local_steps) + k_local.shape)
    total, (positive, aux) = jax.lax.scan(
        client, total, (xs, ys_train, local_w, local_keys, a, meets))
    include = positive & meets
    with jax.named_scope("fl.aggregate"):
        if scheme == "ideal":
            _, mass = fedavg_weights(d_units, include)
            any_included = jnp.any(include)
        else:
            _, _, mass = eq3_weights(d_units, v, ops["epsilon"], include,
                                     server_ok)
            any_included = jnp.any(include) | server_ok
        new_params = jax.tree_util.tree_map(
            lambda t, old: jnp.where(any_included,
                                     t / jnp.maximum(mass, 1e-9), old),
            total, params)
    extra = jax.tree_util.tree_map(
        lambda tw, cl: jnp.concatenate([tw[None], cl]), twin_aux, aux)
    return new_params, positive, extra


# ---------------------------------------------------------------------------
# round (trace-safe body + legacy eager wrapper)
# ---------------------------------------------------------------------------
def _round_body(state: FLState, data: FedData, phys, ops: Dict, scheme: str,
                use_roni: bool, n_selected: int, local_steps: int,
                server_steps: int, inner: str, logits_fn: Callable,
                sic_mode: str = "sequential",
                fops: FaultOps | None = None) -> Tuple[FLState, Dict]:
    """One FL round as a pure traced function.

    ``phys`` is the ``GamePhysics`` pytree; ``ops`` the dict of traced FL
    scalars (lr / epsilon / roni_threshold / samples_per_unit / weights).
    Returns (new_state, metrics) with metrics a dict of ARRAYS — under
    ``lax.scan`` they stack into the (R, ...) history.

    ``logits_fn`` is the client model: a classifier's logits function,
    whose selected clients train at once (``_train_clients_vmapped``), or
    a client model object that trains one client at a time
    (``scan_clients``, e.g. ``models.nemotron_h.LMClient``: its loss,
    accuracy and twin deviation; ``_train_clients_scanned``), whose loss
    aux joins the metrics.  The stages run under the named scopes
    ``fl.local_sgd``, ``fl.twin_sgd``, ``fl.roni`` and ``fl.aggregate``.

    ``fops`` (a ``FaultOps`` pytree, or None) switches on the fault
    engine (``repro.core.faults``): adaptive/duty-cycled poisoning gated
    on the attacker's own pre-round reputation, Bernoulli channel outages
    that re-solve the equilibrium over the surviving lanes (the traced
    ``mask`` path), and compute-slowdown stragglers.  ``fops=None``
    compiles the EXACT pre-fault round program — the None-vs-pytree
    treedef is the only structural compile flag, every fault knob is an
    operand.  When faults are on, one extra PRNG split feeds the fault
    draws (the fault trajectory is a different — equally deterministic —
    stream from the fault-free one)."""
    m = data.x.shape[0]
    key, k_ch, k_map, k_dt, k_alloc = jax.random.split(state.key, 5)
    if fops is not None:
        key, k_fault = jax.random.split(key)

    # 1. selection (z is every client's current reputation — the adaptive
    # attacker reads its OWN score off the same Eq.-16 vector)
    sel, z_all = rep.select_clients(state.rep, data.sizes, n_selected,
                                    ops["epsilon"], ops["weights"])
    sel_mask = jnp.zeros((m,), bool).at[sel].set(True)

    # 2. channel + SIC order (descending gain among the selected); fault
    # processes apply BEFORE the sort, so outage lanes (h2 = 0) sink to
    # the SIC tail — the masked-solve invariant of stackelberg._solve
    h2 = sample_round_channels(k_ch, state.distances)[sel]
    if fops is not None:
        outage, slow = sample_round_faults(k_fault, fops, n_selected)
        h2 = faded_channel(fops, h2, outage, slow)
    order = jnp.argsort(-h2)
    sel_sorted = sel[order]
    h2_sorted = h2[order]
    alive = None if fops is None else ~outage[order]
    slow_sorted = None if fops is None else slow[order]

    # 3. allocation — dropped lanes masked, so the game re-solves with
    # n_eff survivors (graceful mid-round degradation, not a crash)
    d_units = data.sizes[sel_sorted] * ops["samples_per_unit"]
    v_max_sel = state.v_max[sel_sorted]
    alloc = _allocate_traced(scheme, phys, inner, k_alloc, h2_sorted,
                             d_units, v_max_sel, sic_mode, mask=alive)
    v = alloc.v if scheme != "ideal" else jnp.zeros_like(alloc.v)

    # 4. DT split of the selected clients' data.  (A dropped lane's v is
    # zeroed by the masked solve, so none of its samples map this round —
    # the dropout erases the client from the round end-to-end.)
    xs = data.x[sel_sorted]
    if fops is None:
        ys_train = data.y_train[sel_sorted]
    else:
        # adaptive attacker: poison only while the behavioral gates pass
        # (own reputation ≥ rep_gate · median(Z) AND the duty cycle is in
        # an on-phase); otherwise train honestly on the true labels
        attacking = attack_active(fops, data.poisoned[sel_sorted],
                                  z_all[sel_sorted], jnp.median(z_all),
                                  state.round)
        gate = attacking.reshape((-1,) + (1,) * (data.y.ndim - 1))
        ys_train = jnp.where(gate, data.y_train[sel_sorted],
                             data.y[sel_sorted])
    msk = data.mask[sel_sorted]
    map_mask = split_mapping_mask(k_map, msk, v)      # True = mapped to DT
    if scheme == "ideal":
        map_mask = jnp.zeros_like(map_mask)
    local_w = (msk & ~map_mask).astype(jnp.float32)

    # 6. straggler deadline check (tolerance: the leader schedules
    # deadline-EXACT finishes, so `<=` would coin-flip on float error).
    # A slowed client's CPU underdelivers the allocated f_n: its ACHIEVED
    # compute time is t_cmp·slowdown, so deadline-exact schedules miss.
    if scheme == "ideal":
        meets = jnp.ones((n_selected,), bool)
    else:
        t_cmp_real = alloc.t_cmp if fops is None else (
            alloc.t_cmp * slowdown_multiplier(fops, slow_sorted))
        meets = (t_cmp_real + alloc.t_com) <= phys.t_max * 1.001
    if fops is not None:
        meets = meets & alive            # a dropped update never arrives

    # 5, 7, 8: local and twin SGD, RONI, Eq. (3); a model too large to
    # train a copy per client at once is trained one client at a time
    scanned = _scans_clients(logits_fn)
    train = _train_clients_scanned if scanned else _train_clients_vmapped
    new_params, positive, extra = train(
        logits_fn, state.params, data, xs, ys_train, local_w, map_mask,
        meets, d_units, v, ops, k_dt, scheme, use_roni, local_steps,
        server_steps)
    val_acc = (logits_fn.accuracy(new_params, data.x_val, data.y_val)
               if scanned else
               _val_acc(logits_fn, data.x_val, data.y_val, new_params))

    # 9. reputation bookkeeping (a dropped client's verdict is not
    # recorded — the server never received an update to judge)
    new_rep = rep.update_interactions(state.rep, sel_sorted, positive,
                                      count_mask=alive)
    new_rep = rep.update_staleness(new_rep, sel_mask)

    metrics = {
        "round": state.round,
        "selected": sel_sorted,
        "val_acc": val_acc,
        "latency": alloc.t_total,
        "energy": alloc.energy,
        "total_cost": alloc.t_total + alloc.energy,
        "n_excluded_roni": jnp.sum(~positive).astype(jnp.int32),
        "n_stragglers": jnp.sum(~meets).astype(jnp.int32),
        "n_poisoned_selected":
            jnp.sum(data.poisoned[sel_sorted]).astype(jnp.int32),
        "mean_v": jnp.mean(v),
        **extra,
    }
    if fops is not None:
        metrics["n_dropped"] = jnp.sum(~alive).astype(jnp.int32)
        metrics["n_slowed"] = jnp.sum(slow_sorted & alive).astype(jnp.int32)
        metrics["n_attacking"] = jnp.sum(attacking).astype(jnp.int32)
    new_state = FLState(params=new_params, rep=new_rep, v_max=state.v_max,
                        distances=state.distances, key=key,
                        round=state.round + 1)
    return new_state, metrics


def _fl_ops(fl: FLConfig, dtype) -> Dict:
    """The traced-operand remainder of ``FLConfig`` (every numeric knob as
    a device scalar), mirroring ``GameConfig.physics()``: sweeping lr / ε /
    thresholds / selection weights reuses one executable."""
    return {
        "lr": jnp.asarray(fl.lr, dtype),
        "epsilon": jnp.asarray(fl.epsilon, dtype),
        "roni_threshold": jnp.asarray(fl.roni_threshold, dtype),
        "samples_per_unit": jnp.asarray(fl.samples_per_unit, dtype),
        "weights": jnp.asarray(fl.weights, dtype),
    }


# public alias: the FL knob dict IS a differentiable pytree — every entry
# is a traced array operand of the round body, so callers (the mechanism
# layer's ``to_fl_ops``) may pass (possibly grad-carrying) replacements
# through the ``ops_override`` argument of the training entry points.
fl_ops = _fl_ops


def _merge_ops(ops: Dict, ops_override) -> Dict:
    """Overlay caller-supplied knob arrays on the config-derived dict.
    Keys must already exist (typos must not silently vanish); values are
    cast to the engine dtype so an f64 mechanism run still hits the f32
    executable."""
    if ops_override is None:
        return ops
    unknown = set(ops_override) - set(ops)
    if unknown:
        raise ValueError(f"ops_override keys {sorted(unknown)} are not FL "
                         f"knobs; expected a subset of {sorted(ops)}")
    merged = dict(ops)
    for k, v in ops_override.items():
        merged[k] = jnp.asarray(v, ops[k].dtype)
    return merged


def _canon_state(state: FLState) -> FLState:
    """Fixed-dtype scan carry: a weak-typed python-int ``round`` would
    retrace the scan (or fail the carry fixpoint)."""
    return dataclasses.replace(state,
                               round=jnp.asarray(state.round, jnp.int32))


def _fault_operand(faults, dtype) -> FaultOps | None:
    """Normalize the user-facing ``faults`` argument: None passes through
    (the structural off flag), a ``FaultConfig`` lowers to traced operands,
    a pre-built ``FaultOps`` (e.g. a stacked [C] pytree) is used as-is."""
    if faults is None or isinstance(faults, FaultOps):
        return faults
    return fault_ops(faults, dtype)


def _prep(state: FLState, fl: FLConfig, game: GameConfig, faults=None):
    dtype = jnp.result_type(jnp.asarray(state.distances))
    return (_canon_state(state), _physics_cached(game, dtype),
            _fl_ops(fl, dtype), _fault_operand(faults, dtype))


def _static_kwargs(fl: FLConfig, game: GameConfig, logits_fn: Callable):
    return dict(scheme=fl.scheme, use_roni=fl.use_roni,
                n_selected=fl.n_selected, local_steps=fl.local_steps,
                server_steps=fl.server_steps, inner=game.dinkelbach_inner,
                logits_fn=logits_fn, sic_mode=game.sic_mode)


def run_round(state: FLState, data: FedData, fl: FLConfig, game: GameConfig,
              logits_fn: Callable, faults=None) -> Tuple[FLState, Dict]:
    """Legacy per-round entry point: executes the shared round body through
    the eager stage-by-stage path and syncs metrics to python scalars (the
    per-round host round-trips the scanned path exists to remove)."""
    state, phys, ops, fops = _prep(state, fl, game, faults)
    new_state, metrics = _round_body(state, data, phys, ops, fops=fops,
                                     **_static_kwargs(fl, game, logits_fn))
    host = {k: jax.device_get(v) for k, v in metrics.items()}
    for k, v in host.items():
        if k == "selected":
            continue
        host[k] = v.item()
    return new_state, host


def run_training_eager(state: FLState, data: FedData, fl: FLConfig,
                       game: GameConfig, logits_fn: Callable, rounds: int,
                       faults=None):
    """Legacy host-side round loop: R separate dispatch chains with
    per-round metric syncs.  Kept as the numerical reference for the
    scanned trajectory (tests) and as the baseline tier of
    ``benchmarks/training_throughput.py``."""
    history = []
    for _ in range(rounds):
        state, metrics = run_round(state, data, fl, game, logits_fn, faults)
        history.append(metrics)
    return state, history


# ---------------------------------------------------------------------------
# scan-compiled trajectory + seed-vmapped sweeps
# ---------------------------------------------------------------------------
_TRAINING_STATIC = ("scheme", "use_roni", "n_selected", "local_steps",
                    "server_steps", "inner", "logits_fn", "rounds",
                    "sic_mode")


@partial(jax.jit, static_argnames=_TRAINING_STATIC)
def _training_scan_jit(phys, state, data, ops, fops, *, rounds, **static):
    TRACE_COUNTS["run_training_scan"] += 1

    def body(carry, _):
        TRACE_COUNTS["run_round"] += 1
        return _round_body(carry, data, phys, ops, fops=fops, **static)

    return jax.lax.scan(body, state, None, length=rounds)


@partial(jax.jit,
         static_argnames=_TRAINING_STATIC + ("data_batched", "shards"))
def _batched_training_jit(phys, states, data, ops, fops, *, rounds,
                          data_batched, shards=1, **static):
    TRACE_COUNTS["batched_training"] += 1

    def run(ph, sts, dt, op, fo):
        def scan_one(st, d1):
            def body(carry, _):
                TRACE_COUNTS["run_round"] += 1
                return _round_body(carry, d1, ph, op, fops=fo, **static)

            return jax.lax.scan(body, st, None, length=rounds)

        if data_batched:
            return jax.vmap(scan_one)(sts, dt)
        return jax.vmap(lambda st: scan_one(st, dt))(sts)

    if shards > 1:
        # each device scans its local seed block independently (no
        # collectives — the trajectories never talk to each other)
        dspec = P(game_mesh.DRAW_AXIS) if data_batched else P()
        run = jax.shard_map(run, mesh=game_mesh.mesh_1d(shards),
                            in_specs=(P(), P(game_mesh.DRAW_AXIS), dspec,
                                      P(), P()),
                            out_specs=P(game_mesh.DRAW_AXIS),
                            check_vma=False)
    return run(phys, states, data, ops, fops)


def run_training_scan(state: FLState, data: FedData, fl: FLConfig,
                      game: GameConfig, logits_fn: Callable, rounds: int,
                      faults=None, ops_override=None):
    """The whole R-round trajectory as ONE ``lax.scan`` dispatch of one
    compiled program.

    Returns ``(final_state, metrics)`` where ``metrics`` is a dict of
    stacked arrays — scalars become ``(R,)``, ``selected`` becomes
    ``(R, N)`` — i.e. the per-round dicts of the legacy path transposed
    into arrays (``run_training`` converts back for compatibility).
    Compile key: (scheme, use_roni, shapes/steps, rounds, logits_fn,
    dinkelbach inner); all physics and FL scalars are traced operands, so
    e.g. an lr or t_max sweep reuses the executable.

    ``faults`` (a ``FaultConfig``, or None) switches on the fault engine —
    see ``repro.core.faults``.  Its presence is the only new structural
    compile flag; every fault knob is a traced operand, so a scenario
    sweep shares the executable.

    ``ops_override`` (dict, a subset of the ``fl_ops`` keys) replaces
    individual traced knobs with caller-supplied arrays — the mechanism
    layer's evaluate-learned-knobs path (``mechanism.to_fl_ops``); same
    executable, the override is just different operand values.
    """
    state, phys, ops, fops = _prep(state, fl, game, faults)
    ops = _merge_ops(ops, ops_override)
    return _training_scan_jit(phys, state, data, ops, fops, rounds=rounds,
                              **_static_kwargs(fl, game, logits_fn))


def run_training(state: FLState, data: FedData, fl: FLConfig,
                 game: GameConfig, logits_fn: Callable, rounds: int,
                 faults=None):
    """Compat shim over ``run_training_scan``: same signature and return
    shape as the legacy host loop — a list of per-round metric dicts with
    python scalars (``selected`` stays an ``[N]`` int array per round)."""
    state, stacked = run_training_scan(state, data, fl, game, logits_fn,
                                       rounds, faults)
    host = {k: jax.device_get(v) for k, v in stacked.items()}
    history = [{k: (v[r] if v.ndim > 1 else v[r].item())
                for k, v in host.items()} for r in range(rounds)]
    return state, history


def stack_states(states) -> FLState:
    """Stack S per-seed ``FLState``s into one with a leading seed axis on
    every leaf — the ``batched_training`` input layout."""
    states = [_canon_state(s) for s in states]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def stack_fl_ops(fls: Sequence[FLConfig], dtype=jnp.float32) -> Dict:
    """Stack C ``FLConfig`` points into one traced-ops dict with a leading
    [C] axis on every numeric knob ([C, 3] for the selection weights) — the
    config axis of ``sweep_training``, mirroring ``stack_physics``.

    All points must agree on the discrete algorithm choices (scheme,
    use_roni, n_selected, local/server steps): those are static compile
    keys, so a grid that varies them is several sweeps, not one."""
    fls = list(fls)
    statics = {(f.scheme, f.use_roni, f.n_selected, f.local_steps,
                f.server_steps) for f in fls}
    if len(statics) != 1:
        raise ValueError(
            "sweep config points mix static algorithm keys "
            f"{sorted(statics)}; scheme/use_roni/n_selected/steps are "
            "compile keys — sweep each combination separately")
    per_point = [_fl_ops(f, dtype) for f in fls]
    return {k: jnp.stack([ops[k] for ops in per_point])
            for k in per_point[0]}


def _shard_tree(tree, size: int):
    """``_shard_axis`` over every leaf of a pytree (leading batch/grid
    axis) — the legacy GSPMD placement recipe, kept for external callers;
    the training tiers now pad + ``shard_map`` via ``game_mesh``."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(
        treedef, _shard_axis(tuple(leaves), axis=0, size=size))


def _unpad_result(final, metrics, *dims):
    """Slice a training result's leading axes back to the caller's
    logical sizes (no-op when the batch axes weren't padded)."""
    probe = jax.tree_util.tree_leaves(final)[0]
    if tuple(probe.shape[:len(dims)]) == dims:
        return final, metrics
    sl = tuple(slice(0, d) for d in dims)
    cut = lambda x: x[sl]
    return (jax.tree_util.tree_map(cut, final),
            jax.tree_util.tree_map(cut, metrics))


def batched_training(states: FLState, data: FedData, fl: FLConfig,
                     game: GameConfig, logits_fn: Callable, rounds: int,
                     faults=None):
    """S independent R-round trajectories in ONE XLA dispatch: ``vmap`` of
    the scanned round loop over a leading seed axis, device-sharded across
    the seed axis (single-device no-op).

    states : ``FLState`` with a leading S axis on every leaf (see
             ``stack_states``) — typically S seeds of the same experiment.
    data   : shared ``FedData``, or one with a leading S axis
             (``data.x.ndim == 4``) for per-seed datasets — e.g. an
             attacker-fraction axis where seed s was poisoned at ratio r_s.
    faults : optional ``FaultConfig`` (one scenario, broadcast across the
             seed axis) switching on the fault engine for every seed.

    Returns ``(final_states, metrics)`` with an extra leading S axis on
    every leaf/metric relative to ``run_training_scan``.  Seed s of the
    result equals ``run_training_scan`` on seed s alone (pure batching).
    """
    states, phys, ops, fops = _prep(states, fl, game, faults)
    data_batched = data.x.ndim == 4
    s = jax.tree_util.tree_leaves(states)[0].shape[0]
    shards = game_mesh.batch_shards(s)
    if shards > 1:
        sp = game_mesh.padded_size(s, shards)
        states = game_mesh.put_tree(game_mesh.pad_tree(states, 0, sp),
                                    0, shards)
        if data_batched:
            data = game_mesh.put_tree(game_mesh.pad_tree(data, 0, sp),
                                      0, shards)
    final, metrics = _batched_training_jit(
        phys, states, data, ops, fops, rounds=rounds,
        data_batched=data_batched, shards=shards,
        **_static_kwargs(fl, game, logits_fn))
    return _unpad_result(final, metrics, s)


@partial(jax.jit,
         static_argnames=_TRAINING_STATIC + ("data_mode", "grid_shards"))
def _sweep_training_jit(phys, states, data, ops, fops, *, rounds,
                        data_mode, grid_shards=(1, 1), **static):
    """Nested vmap of the scanned trajectory over the TRUE 2D C×S grid —
    config axis outer (physics/FL ops/fault ops mapped per point), seed
    axis inner — so one executable covers the whole config grid and the
    grid tiles directly onto the 2D (cfg, draw) device mesh.  ``fops=None``
    (an empty pytree under vmap) compiles the fault-free grid program.

    ``data_mode`` keys how the dataset rides the grid: ``"shared"`` (one
    dataset for every cell), ``"seed"`` (leading [S] axis, shared across
    configs) or ``"config"`` (leading [C] axis, shared across seeds)."""
    TRACE_COUNTS["sweep_training"] += 1
    scanned = _scans_clients(static["logits_fn"])

    def grid(ph_c, sts, dt, op_c, fo_c):
        def per_config(ph, st_s, d_c, op, fo):
            def scan_cell(st, d1):
                def body(carry, _):
                    TRACE_COUNTS["run_round"] += 1
                    return _round_body(carry, d1, ph, op, fops=fo, **static)

                return jax.lax.scan(body, st, None, length=rounds)

            if scanned:
                # one grid cell at a time, as one client at a time
                if data_mode == "seed":
                    return jax.lax.map(lambda a: scan_cell(*a), (st_s, d_c))
                return jax.lax.map(lambda st: scan_cell(st, d_c), st_s)
            if data_mode == "seed":
                return jax.vmap(scan_cell)(st_s, d_c)      # d_c is [S, ...]
            return jax.vmap(lambda st: scan_cell(st, d_c))(st_s)

        if scanned:
            # the states are shared by the C points (not broadcast to them)
            if data_mode == "config":
                return jax.lax.map(lambda a: per_config(a[0], sts, *a[1:]),
                                   (ph_c, dt, op_c, fo_c))
            return jax.lax.map(lambda a: per_config(a[0], sts, dt, *a[1:]),
                               (ph_c, op_c, fo_c))
        data_in = 0 if data_mode == "config" else None
        return jax.vmap(per_config, in_axes=(0, 0, data_in, 0, 0))(
            ph_c, sts, dt, op_c, fo_c)

    dc, dk = grid_shards
    if dc * dk > 1:
        # 2D (cfg, draw) mesh: each device owns a [C/dc, S/dk] grid tile;
        # seed-shared data splits along draw only, config-shared along cfg
        dspec = {"shared": P(), "seed": P(game_mesh.DRAW_AXIS),
                 "config": P(game_mesh.CFG_AXIS)}[data_mode]
        cfg_p = P(game_mesh.CFG_AXIS)
        grid_p = P(game_mesh.CFG_AXIS, game_mesh.DRAW_AXIS)
        grid = jax.shard_map(grid, mesh=game_mesh.mesh_2d(dc, dk),
                             in_specs=(cfg_p, grid_p, dspec, cfg_p, cfg_p),
                             out_specs=grid_p, check_vma=False)
    return grid(phys, states, data, ops, fops)


def _sweep_fault_ops(faults, c: int, dtype) -> FaultOps | None:
    """Normalize ``sweep_training``'s ``faults`` argument to [C]-leaved
    ``FaultOps`` (or None): a single ``FaultConfig`` broadcasts across the
    config axis, a sequence must have C entries (one scenario per config
    point), a pre-stacked ``FaultOps`` is validated and used as-is."""
    if faults is None:
        return None
    if isinstance(faults, FaultOps):
        got = faults.rep_gate.shape
        if got != (c,):
            raise ValueError(f"stacked FaultOps leaves must be [{c}]-shaped "
                             f"(one per config point); got {got}")
        return faults
    if isinstance(faults, FaultConfig):
        faults = [faults] * c
    faults = list(faults)
    if len(faults) == 1:
        faults = faults * c
    if len(faults) != c:
        raise ValueError(f"fault axis mismatch: {len(faults)} FaultConfig "
                         f"points vs {c} config points")
    return stack_fault_ops(faults, dtype)


def sweep_training(states: FLState, data: FedData, fls, games,
                   logits_fn: Callable, rounds: int, faults=None,
                   data_axis: str = "seed", ops_override=None):
    """A whole config-grid of training runs — C (``FLConfig``,
    ``GameConfig``) points × S seeds × R rounds — as ONE XLA dispatch of
    one executable (the Fig. 5/6/7/8 workload).

    fls    : C ``FLConfig`` points (or a single one, broadcast to match
             ``games``).  Every numeric knob (lr, ε, RONI threshold,
             selection weights, samples_per_unit) rides the config axis as
             a traced operand; the discrete keys (scheme, use_roni,
             n_selected, steps) must agree across points — they are the
             only compile keys.
    games  : C ``GameConfig`` points (or a single one); their eleven
             physics floats are stacked into a [C]-leaved ``GamePhysics``.
    logits_fn : the client model (see ``_round_body``).  A model trained
             one client at a time also runs its grid one cell at a time
             (``lax.map``) on one device, the C points sharing the states.
    states : ``FLState`` with a leading S seed axis (``stack_states``),
             shared across the config axis.
    data   : shared ``FedData`` (``x.ndim == 3``), or one with a leading
             batch axis (``x.ndim == 4``) whose meaning ``data_axis``
             selects — ``"seed"`` (default): S per-seed datasets shared
             across configs (fig5's attacker-fraction axis); ``"config"``:
             C per-config datasets shared across seeds (the attack-grid
             axis, where each scenario plants different poisoned/sybil
             clients).
    faults : optional fault-engine axis — a single ``FaultConfig``
             (broadcast), a C-sequence of them (one scenario per config
             point), or a pre-stacked [C]-leaved ``FaultOps``.  Its
             presence is the only structural compile flag; every knob is
             traced, so the whole attack grid shares one executable.

    The C×S grid is a true 2D layout tiled over the (cfg, draw) device
    mesh of ``sharding/game_mesh.py`` — the same machinery as the C×K
    grid of the equilibrium sweeps; non-divisible grids pad with
    edge-replicated cells that are sliced off the result (single-device
    no-op).  Returns
    ``(final_states, metrics)`` with a leading ``(C, S)`` prefix on every
    leaf — cell (c, s) equals ``run_training_scan`` with configs c on seed
    s alone (pure batching).
    """
    if data_axis not in ("seed", "config"):
        raise ValueError(f"data_axis must be 'seed' or 'config', "
                         f"got {data_axis!r}")
    fls = [fls] if isinstance(fls, FLConfig) else list(fls)
    games = [games] if isinstance(games, GameConfig) else list(games)
    # the config-axis length is set by whichever axis is non-singleton —
    # fls/games first, then the fault axis (an attack grid may sweep
    # scenarios over ONE (FLConfig, GameConfig) point); singletons
    # broadcast, non-singleton axes must agree
    if isinstance(faults, FaultOps):
        n_faults = faults.rep_gate.shape[0]
    elif faults is None or isinstance(faults, FaultConfig):
        n_faults = 1
    else:
        faults = list(faults)
        n_faults = len(faults)
    c = max(len(fls), len(games))
    if len(fls) == 1:
        fls = fls * c
    if len(games) == 1:
        games = games * c
    if len(fls) != len(games):
        raise ValueError(f"config axis mismatch: {len(fls)} FLConfig vs "
                         f"{len(games)} GameConfig points")
    if c == 1 and n_faults > 1:
        fls = fls * n_faults
        games = games * n_faults
        c = n_faults
    states = _canon_state(states)
    dtype = jnp.result_type(jnp.asarray(states.distances))
    phys = stack_physics(games, dtype)            # [C] leaves
    ops = stack_fl_ops(fls, dtype)                # [C] / [C, 3] leaves
    # knob override (see run_training_scan): leaves must carry the [C] axis
    ops = _merge_ops(ops, ops_override)
    fops = _sweep_fault_ops(faults, c, dtype)     # [C] leaves (or None)
    s = jax.tree_util.tree_leaves(states)[0].shape[0]

    # the states grid is TRUE 2D — [C, S, ...] leaves, configs outer,
    # seeds inner — so it tiles directly onto the (cfg, draw) device mesh.
    # A model trained one client at a time runs its grid one cell at a time
    # on one device, the C points sharing the [S] states: no copy per point
    scanned = _scans_clients(logits_fn)
    if not scanned:
        bcast_cfg = lambda x: jnp.broadcast_to(x[None], (c,) + x.shape)
        states = jax.tree_util.tree_map(bcast_cfg, states)
    if data.x.ndim == 4:
        data_mode = data_axis
        if data_axis == "config" and data.x.shape[0] != c:
            raise ValueError(
                f"data_axis='config' needs a leading [{c}] axis on the "
                f"data (one dataset per config point); got "
                f"{data.x.shape[0]}")
    else:
        data_mode = "shared"

    # multi-device: pad the grid to the (dc, dk) mesh factorization with
    # edge-replicated cells (sliced back off below) and place the shards
    grid = (1, 1) if scanned else game_mesh.grid_layout(c, s)
    dc, dk = grid
    if dc * dk > 1:
        cp = game_mesh.padded_size(c, dc)
        sp = game_mesh.padded_size(s, dk)
        pad_cfg = lambda t: game_mesh.pad_tree(t, 0, cp)
        phys = game_mesh.put_grid_tree(pad_cfg(phys), grid, cfg_only=True)
        ops = game_mesh.put_grid_tree(pad_cfg(ops), grid, cfg_only=True)
        if fops is not None:
            fops = game_mesh.put_grid_tree(pad_cfg(fops), grid,
                                           cfg_only=True)
        states = game_mesh.put_grid_tree(
            game_mesh.pad_tree(pad_cfg(states), 1, sp), grid)
        if data_mode == "seed":
            data = game_mesh.pad_tree(data, 0, sp)
        elif data_mode == "config":
            data = pad_cfg(data)

    final, metrics = _sweep_training_jit(
        phys, states, data, ops, fops, rounds=rounds,
        data_mode=data_mode, grid_shards=grid,
        **_static_kwargs(fls[0], games[0], logits_fn))
    return _unpad_result(final, metrics, c, s)
