"""Streaming allocation service: ragged-N continuous batching over the
masked Stackelberg engine (ISSUE-6 tentpole), wrapped in an SLA-aware
resilience layer (ISSUE-9 tentpole).

The offline engine answers fixed-N, fixed-K questions; production is an
*online* stream of heterogeneous cells — every request carries its own
client count N, channel draws, and physics knobs, and clients join/drop
between rounds so N never stays put.  Recompiling per N would burn ~1 s
of XLA compile per distinct shape; this module instead routes requests
through a SMALL FIXED SET of bucket executables:

  * **N-buckets** — a request with n clients is padded up to the smallest
    bucket width nb ≥ n (default widths 8/16/32/64/128) with ZERO channel
    gains and an [nb] boolean mask.  Zero-gain padding is invisible to
    the SIC chain by construction (p·|h|² = 0 in every suffix sum — see
    ``repro.core.sic``), keeps the descending SIC order, and the mask
    erases the padded lanes from d_hat, the latency maxima, the energy
    sums and the feasibility test (``stackelberg._solve(mask=...)``), so
    a padded solve is BIT-IDENTICAL to the exact-N solve.
  * **request-batching** — up to ``max_batch`` same-bucket requests ride
    one dispatch as a leading vmap axis; partial batches are topped up
    with all-masked dummy rows so the executable's batch shape is fixed
    (zero retraces over a warm stream, counted by
    ``TRACE_COUNTS["serve_allocation"]``).  Per-request physics
    (t_max / bandwidth / model_bits / …) stack into [B]-leaved
    ``GamePhysics`` operands — heterogeneous cells share the executable.
  * **double-buffered dispatch** — flushes enqueue asynchronously (JAX
    async dispatch keeps the device busy) and block only when more than
    ``max_inflight`` batches are outstanding, overlapping host-side
    pack/unpack with device compute.  A batch goes to the device as two
    host buffers (``pack_rows``: every float operand in one, the client
    counts and seeds in the other) in one ``device_put``; the executable
    slices them apart and builds the mask and PRNG keys itself.  The
    readback of the fields a result needs starts as soon as the dispatch
    call returns, so it lands while the host waits for the next reap.

One executable exists per (scheme, bucket width, batch width,
dinkelbach_inner, sic_mode); ``warmup()`` pre-compiles the set so a
latency-SLA deployment pays no cold-start on the stream.

Results come back in the REQUEST'S OWN client order (the service sorts
into SIC order on the way in and unsorts on the way out).

The SLA / resilience contract (ISSUE 9)
=======================================

Every submitted rid yields EXACTLY ONE ``AllocResult`` from ``drain()``
— the exactly-once invariant — with a status from the five-word
vocabulary:

  * ``"ok"``          — solved, feasible, delivered inside any deadline.
  * ``"infeasible"``  — solved, but the equilibrium violates the
    deadline/resource box even after the retry ladder (arrays are the
    solver's best answer; ``degradation`` records the ladder).
  * ``"rejected"``    — the service could not produce a valid allocation:
    oversized N, non-finite channel gains, admission control (predicted
    queue wait already busts ``deadline_s``), circuit breaker open, or a
    dispatch that failed after backoff retries.  Arrays are NaN,
    ``error`` says why.
  * ``"shed"``        — dropped by priority-ordered load shedding when
    the bounded queue (``max_queue``) overflowed: the LOWEST-priority,
    youngest pending request is shed first, never silently.
  * ``"timeout"``     — solved, but delivered after the request's
    ``deadline_s`` (or expired in the queue before dispatch).

**Per-request SLA.**  ``AllocRequest.deadline_s`` (submit→result wall
budget) and ``AllocRequest.priority`` (higher = more important) drive
three scheduler mechanisms: (1) admission control — an EWMA of each
(bucket, scheme) batch's time from dispatch to ready-at-reap predicts the
queue wait; a request whose deadline the prediction already busts is
rejected FAST, before it wastes a batch lane; (2) bounded queues — when ``max_queue`` is set the
service stops blocking the producer (PR-8 behavior) and instead defers
dispatch while the in-flight window is full, opportunistically retiring
ready batches (``jax.Array.is_ready`` polling), and sheds the
lowest-priority pending request once the bound is hit; (3) batches are
packed highest-priority-first, so under overload high-priority p99
degrades gracefully while low-priority sheds.

**Degraded-retry.**  An infeasible equilibrium walks a bounded retry
ladder (default ``("relax_tmax", "fallback_oma")``): first re-solve with
``t_max × relax_factor`` (a traced operand — same executable, zero
retrace), then fall back to the cheaper ``oma`` scheme.  Each result
carries its ``degradation`` trail (e.g. ``("relax_tmax:1.5",
"fallback:oma")``); ``latency_s`` stays honest (original submit time).
Transient dispatch FAILURES (the dispatch seam raising) retry with
exponential backoff up to ``dispatch_retries`` times before the batch's
requests become structured ``"rejected"`` rows.

**Containment.**  A cooperative watchdog records in-flight batches whose
dispatch→complete wall exceeds ``watchdog_s`` (counted, fed to the
breaker — a stalled executable is unhealthy); per-(bucket, scheme)
circuit breakers trip OPEN after ``breaker_threshold`` consecutive bad
batches (non-finite outputs, a watchdog trip, a dispatch failure — plus
all-infeasible batches when ``breaker_on_infeasible`` is opted in:
infeasibility is a data property and a valid answer, not executable
ill-health, so it doesn't open the breaker by default), fast-fail
submissions while open, move to HALF_OPEN
after ``breaker_cooldown_s`` and close again on the next healthy batch.
``health()`` snapshots queue depths, breaker states, every resilience
counter, per-priority p50/p99 latency and p50/p99 of each request stage
(``STAGES``: queued, packing, the dispatch call, in flight, the reap's
wait for the device, readback).  ``submit``, the packing, the dispatch
call and the reap are host spans (``repro.core.tracking.span``), joined
through ``rid`` → ``AllocResult.stages["batch"]``.

The BASELINE path — no deadline, no ``max_queue``, feasible,
uncontended — is bit-identical to the PR-8 scheduler: same batch
composition (priority sort is stable and all-equal), same executables,
same operands; the resilience layer only adds host-side bookkeeping.

``benchmarks/serve_latency.py`` measures the steady state plus overload
and chaos sections (→ ``BENCH_serve.json``, claims-gated by
``scripts/check_bench.py``); ``repro.launch.serve_chaos`` is the
service-level fault-injection harness.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.stackelberg import (_PHYSICS_FIELDS, GameConfig, GamePhysics,
                                _oma_body, _random_body, _solve)
from ..core.tracking import TRACE_COUNTS, span
from ..sharding import game_mesh

DEFAULT_BUCKETS = (8, 16, 32, 64, 128)
SERVE_SCHEMES = ("proposed", "ideal", "wo_dt", "oma", "oma_tdma", "random")
STATUS_VOCAB = ("ok", "infeasible", "rejected", "shed", "timeout")
# a batched row's wall time, submit to result, split where the work happens:
# queued, packing the batch, the dispatch call, dispatched until its reap
# starts, the reap's wait for the device, the readback and unpacking
STAGES = ("queue_s", "pack_s", "launch_s", "inflight_s", "ready_wait_s",
          "readback_s")


# ---------------------------------------------------------------------------
# the bucket executable
# ---------------------------------------------------------------------------
# the fields of an Allocation that a result reads back
READ_FIELDS = ("p", "q", "f", "alpha", "rates", "t_total", "energy",
               "feasible", "iterations")
_N_PHYS = len(_PHYSICS_FIELDS)


@lru_cache(maxsize=4096)
def _physics_row(cfg: GameConfig) -> np.ndarray:
    """A config's GamePhysics floats in ``_PHYSICS_FIELDS`` order, float32
    as ``stack_physics`` rounds them (read-only: it is shared)."""
    row = np.asarray([getattr(cfg, name) for name in _PHYSICS_FIELDS],
                     np.float32)
    row.flags.writeable = False
    return row


def pack_rows(rows: Sequence, nb: int, b: int):
    """Host buffers of one bucket dispatch of ``b`` rows × ``nb`` lanes:
    float32 [b, 3·nb + 1 + 11] (h2, D, v_max, epsilon, the GamePhysics
    floats) and uint32 [b, 2] (real-client count, request seed).  Each
    row is a ``_Pending`` in SIC order; rows past ``len(rows)`` are
    dummies (no clients) that reuse the first row's physics."""
    fbuf = np.zeros((b, 3 * nb + 1 + _N_PHYS), np.float32)
    ibuf = np.zeros((b, 2), np.uint32)
    for i, r in enumerate(rows):
        fbuf[i, :r.n] = r.h2
        fbuf[i, nb:nb + r.n] = r.d
        fbuf[i, 2 * nb:2 * nb + r.n] = r.v_max
        fbuf[i, 3 * nb] = r.req.epsilon
        fbuf[i, 3 * nb + 1:] = _physics_row(r.eff_cfg)
        ibuf[i] = r.n, r.req.seed
    fbuf[len(rows):, 3 * nb + 1:] = fbuf[0, 3 * nb + 1:]
    return fbuf, ibuf


def _start_readback(out) -> bool:
    """Start the device→host copy of every field in ``READ_FIELDS``; False
    where the output's arrays cannot copy ahead (the reap reads them
    all the same)."""
    try:
        for f in READ_FIELDS:
            getattr(out, f).copy_to_host_async()
    except AttributeError:
        return False
    return True


def unpack_rows(fbuf, ibuf):
    """The executable's view of ``pack_rows``' buffers: (GamePhysics with
    [B] leaves, [B, 2] PRNG keys, h2, D, v_max [B, nb], epsilon [B], mask
    [B, nb] True on real client lanes)."""
    nb = (fbuf.shape[-1] - 1 - _N_PHYS) // 3
    phys = GamePhysics(**{name: fbuf[:, 3 * nb + 1 + j]
                          for j, name in enumerate(_PHYSICS_FIELDS)})
    keys = jax.vmap(jax.random.PRNGKey)(ibuf[:, 1])
    mask = jnp.arange(nb, dtype=ibuf.dtype)[None, :] < ibuf[:, :1]
    return (phys, keys, fbuf[:, :nb], fbuf[:, nb:2 * nb],
            fbuf[:, 2 * nb:3 * nb], fbuf[:, 3 * nb], mask)


def solve_row(scheme, max_iter, inner, sic_mode, ph, key, h2, D, v_max,
              eps, mask, tol):
    """One request's allocation in its bucket: the scheme's body on one
    row of ``unpack_rows``' operands (h2 descending with a zero tail, D and
    v_max zero on padded lanes)."""
    dtype = jnp.result_type(h2)
    if scheme in ("proposed", "ideal"):
        return _solve(ph, h2, D, v_max, eps, max_iter, tol, inner, sic_mode,
                      mask=mask)
    if scheme == "wo_dt":
        return _solve(ph, h2, D, jnp.zeros_like(h2), jnp.zeros((), dtype),
                      max_iter, tol, inner, sic_mode, mask=mask)
    if scheme == "oma":
        return _oma_body(ph, h2, D, v_max, eps, inner, tdma=False, mask=mask)
    if scheme == "oma_tdma":
        return _oma_body(ph, h2, D, v_max, eps, inner, tdma=True, mask=mask)
    if scheme == "random":
        return _random_body(ph, key, h2, D, v_max, eps, mask=mask)
    raise ValueError(f"unknown scheme {scheme!r}")


@partial(jax.jit,
         static_argnames=("scheme", "max_iter", "inner", "sic_mode",
                          "shards"))
def _serve_batch_jit(fbuf, ibuf, tol, scheme, max_iter, inner, sic_mode,
                     shards=1):
    """One padded bucket dispatch: B requests × nb client lanes.

    fbuf : [B, 3·nb + 12] float32 operands of ``pack_rows``
    ibuf : [B, 2] uint32 real-client count and request seed
    tol  : Alg.-2 stopping tolerance (scalar operand)

    Returns an ``Allocation`` with [B, nb] / [B] fields, batch axis first.

    Static keys: scheme / max_iter / inner / sic_mode (+ the B, nb
    shapes).  Everything else — including every physics float — is a
    traced operand, so one executable serves arbitrarily heterogeneous
    cells.  Nothing is donated: no output has a packed buffer's shape.

    ``shards`` > 1 splits the batch axis over the 1D draw mesh via
    ``shard_map`` (each device solves its local rows' independent
    while_loops); the service sizes B to a device multiple, so the
    split is exact and the executable shape never changes.
    """
    TRACE_COUNTS["serve_allocation"] += 1

    def batch(fb, ib, tl):
        one = partial(solve_row, scheme, max_iter, inner, sic_mode)
        return jax.vmap(one, in_axes=(0,) * 7 + (None,))(
            *unpack_rows(fb, ib), tl)

    if shards > 1:
        d = P(game_mesh.DRAW_AXIS)
        batch = jax.shard_map(batch, mesh=game_mesh.mesh_1d(shards),
                              in_specs=(d, d, P()), out_specs=d,
                              check_vma=False)
    return batch(fbuf, ibuf, tol)


# ---------------------------------------------------------------------------
# requests / results
# ---------------------------------------------------------------------------
@dataclass
class AllocRequest:
    """One cell's allocation question.  ``h2`` may arrive in ANY client
    order — the service sorts into SIC order and unsorts the answer.
    ``d`` / ``v_max`` are scalars or per-client [n] arrays aligned with
    ``h2``'s order.

    SLA knobs (ISSUE 9): ``deadline_s`` is the submit→result wall budget
    — admission control reject-fasts when the predicted queue wait
    already busts it, and a result delivered late is tagged
    ``status="timeout"``; ``priority`` orders load shedding (lowest shed
    first) and batch packing (highest packed first); ``allow_degraded``
    opts this request out of the infeasible retry ladder."""
    h2: object
    d: object = 200.0
    v_max: object = 0.5
    cfg: GameConfig = field(default_factory=GameConfig)
    scheme: str = "proposed"
    epsilon: float = 0.0
    seed: int = 0              # per-request randomness ("random" scheme)
    deadline_s: float | None = None
    priority: int = 0
    allow_degraded: bool = True


@dataclass
class AllocResult:
    """Per-request allocation, in the request's own client order.

    ``status`` is the graceful-degradation contract (STATUS_VOCAB — see
    the module docstring for the full five-word semantics):
      * ``"ok"``         — solved, ``feasible=True``, inside deadline.
      * ``"infeasible"`` — solved, but the equilibrium violates the
        deadline/resource box (``feasible=False``) even after the retry
        ladder; the allocation arrays are still the solver's best answer
        — the caller decides whether to use, relax, or drop the cell.
      * ``"rejected"``   — no valid allocation: oversized N, non-finite
        input, admission control, open circuit breaker, failed dispatch,
        or non-finite solver output.  Arrays are NaN, ``error`` says why.
      * ``"shed"``       — dropped by priority-ordered load shedding
        under queue overflow.  A bad or shed request yields a structured
        row instead of killing the in-flight stream — never silent loss.
      * ``"timeout"``    — completed (or expired in queue) after
        ``deadline_s``; completed rows still carry the solved arrays.

    ``degradation`` is the retry-ladder trail, e.g.
    ``("relax_tmax:1.5", "fallback:oma")`` — empty on the baseline path.
    ``scheme`` is the scheme that produced the final arrays (``"oma"``
    after a fallback).  ``latency_s`` is always submit→emit wall time,
    including for rejected/shed rows (honest latency, ISSUE-9
    satellite).

    ``stages`` splits ``latency_s`` of a row that went through a batch:
    one entry per name in ``STAGES``, summing to ``latency_s``, plus
    ``batch``, the dispatch's sequence number (shared by the rows of one
    batch, and the ``batch=`` id of its ``serve.*`` spans).  A retried
    row's ``queue_s`` runs from its first submit to the packing of the
    batch that answered it; a batch whose dispatch call was retried
    counts the failed attempts and their backoff in ``pack_s``.  None
    for a rejected, shed or expired-in-queue row."""
    rid: int
    n: int
    bucket: int
    scheme: str
    p: np.ndarray
    q: np.ndarray
    f: np.ndarray
    alpha: np.ndarray
    rates: np.ndarray
    t_total: float
    energy: float
    feasible: bool
    iterations: int
    latency_s: float           # submit → result available on host
    status: str = "ok"
    error: str = ""
    priority: int = 0
    deadline_s: float | None = None
    degradation: tuple = ()
    stages: dict | None = None


@dataclass
class _Pending:
    rid: int
    req: AllocRequest
    n: int
    order: np.ndarray          # SIC sort permutation of the request's h2
    h2: np.ndarray             # [n] sorted descending
    d: np.ndarray              # [n] aligned with h2
    v_max: np.ndarray          # [n]
    t_submit: float
    eff_cfg: GameConfig = None     # effective config (ladder may relax t_max)
    eff_scheme: str = ""           # effective scheme (ladder may fall back)
    stage: int = 0                 # retry-ladder stages consumed
    degradation: tuple = ()


@dataclass
class _InFlight:
    key: tuple
    pending: list               # the real _Pending rows (dummies excluded)
    out: object                 # device Allocation, [B, nb] fields
    t_dispatch: float           # the dispatch call returned
    batch: int                  # dispatch sequence number
    t_pack: float               # packing started
    t_launch: float             # the dispatch call started


class _Breaker:
    """Per-(bucket, scheme, inner, sic_mode) circuit breaker state."""
    __slots__ = ("state", "fails", "opened_at")

    def __init__(self):
        self.state = "closed"       # closed | open | half_open
        self.fails = 0              # consecutive bad batches
        self.opened_at = 0.0        # monotonic time of the last open


class AllocationService:
    """Continuous-batching scheduler over the masked bucket executables,
    with the ISSUE-9 resilience layer (admission control, bounded-queue
    shedding, degraded-retry, circuit breakers, watchdog).

    submit() enqueues (auto-flushing full batches), flush() force-packs
    partial batches with dummy rows, drain() completes everything —
    including retry-ladder re-dispatches — and returns the accumulated
    ``AllocResult``s sorted by rid.  ``warmup()`` pre-compiles the
    bucket set.  ``health()`` snapshots the resilience state.  See the
    module docstring for the design and the SLA contract.

    ``max_queue=None`` (default) keeps the PR-8 blocking scheduler
    bit-identically; setting it switches to the bounded-queue
    non-blocking mode with priority shedding.  ``self._dispatch`` is the
    dispatch seam — the chaos harness (``repro.launch.serve_chaos``)
    wraps it to inject stalls, transient failures and poisoned outputs.
    """

    def __init__(self, buckets: Sequence[int] = DEFAULT_BUCKETS,
                 max_batch: int = 8, max_inflight: int = 2,
                 max_iter: int = 20, tol: float = 1e-6,
                 max_queue: int | None = None,
                 ewma_alpha: float = 0.25,
                 degraded_retry: bool = True,
                 retry_ladder: Sequence[str] = ("relax_tmax",
                                                "fallback_oma"),
                 relax_factor: float = 1.5,
                 dispatch_retries: int = 2,
                 backoff_base_s: float = 0.05,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 1.0,
                 breaker_on_infeasible: bool = False,
                 watchdog_s: float | None = 30.0,
                 latency_window: int = 512):
        if not buckets or any(b <= 0 for b in buckets):
            raise ValueError(f"bad bucket widths {buckets}")
        bad = [s for s in retry_ladder
               if s not in ("relax_tmax", "fallback_oma")]
        if bad:
            raise ValueError(f"unknown retry-ladder stages {bad}")
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.max_batch = int(max_batch)
        # multi-device: shard the batch axis of every bucket dispatch —
        # the fixed dispatch width rounds up to a device multiple once at
        # init (extra rows are all-masked dummies, same as partial-batch
        # fill), so the executable shape stays retrace-free
        self.shards = game_mesh.batch_shards(self.max_batch)
        self.batch_width = game_mesh.padded_size(self.max_batch, self.shards)
        self.max_inflight = int(max_inflight)
        self.max_iter = int(max_iter)
        self.tol = float(tol)
        # where a dispatch's packed buffers and tol live: split on the batch
        # axis, and replicated, over the mesh the executable runs on
        rows_at = tol_at = None
        if self.shards > 1:
            mesh = game_mesh.mesh_1d(self.shards)
            rows_at = NamedSharding(mesh, P(game_mesh.DRAW_AXIS))
            tol_at = NamedSharding(mesh, P())
        self._rows_at = rows_at
        self._tol = jax.device_put(np.float32(self.tol), tol_at)
        self.max_queue = None if max_queue is None else int(max_queue)
        self.ewma_alpha = float(ewma_alpha)
        self.degraded_retry = bool(degraded_retry)
        self.retry_ladder = tuple(retry_ladder)
        self.relax_factor = float(relax_factor)
        self.dispatch_retries = int(dispatch_retries)
        self.backoff_base_s = float(backoff_base_s)
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        # infeasibility is a DATA property (a valid answer in the status
        # vocabulary), not executable ill-health: all-infeasible batches
        # feed the breaker only on request — e.g. a deployment whose
        # stream is known-feasible and wants miscompiles caught.  On a
        # mixed stream (the bench trace runs ~38% infeasible cells) the
        # default would fast-fail healthy requests.
        self.breaker_on_infeasible = bool(breaker_on_infeasible)
        self.watchdog_s = None if watchdog_s is None else float(watchdog_s)
        self.latency_window = int(latency_window)
        self._next_rid = 0
        self._pending: dict = collections.defaultdict(list)
        self._inflight: collections.deque = collections.deque()
        self._done: list = []
        self._dispatch = _serve_batch_jit      # chaos-injection seam
        self._ewma: dict = {}                  # key -> dispatch seconds
        self._breakers: dict = {}              # key -> _Breaker
        self.breaker_log: list = []            # (key_str, old, new) capped
        self._lat: dict = collections.defaultdict(
            lambda: collections.deque(maxlen=self.latency_window))
        self._stages = {s: collections.deque(maxlen=self.latency_window)
                        for s in STAGES}
        self._next_batch = 0
        self.stats = collections.Counter()

    # -- intake -------------------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest bucket width ≥ n; raises ValueError when n exceeds
        the largest bucket (``submit`` catches this same error and turns
        it into a structured rejection — single source of truth for the
        oversize message)."""
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"request with {n} clients exceeds the largest "
                         f"bucket {self.buckets[-1]}; widen `buckets`")

    def _key_str(self, key: tuple) -> str:
        nb, scheme, inner, sic_mode = key
        return f"n{nb}/{scheme}/{inner}/{sic_mode}"

    def _reject(self, req: AllocRequest, n: int, why: str, t0: float,
                status: str = "rejected") -> int:
        """Graceful degradation: a request the service cannot dispatch
        becomes a structured per-request error row (NaN allocation) with
        HONEST submit→reject latency instead of an exception that kills
        the in-flight stream.  Malformed LOCAL input (empty request,
        unknown scheme) still raises from ``submit`` — those are caller
        bugs, not stream conditions."""
        rid = self._next_rid
        self._next_rid += 1
        nanv = np.full((max(n, 0),), np.nan, np.float32)
        self._done.append(AllocResult(
            rid=rid, n=n, bucket=0, scheme=req.scheme,
            p=nanv, q=nanv.copy(), f=nanv.copy(), alpha=nanv.copy(),
            rates=nanv.copy(), t_total=float("nan"), energy=float("nan"),
            feasible=False, iterations=0,
            latency_s=time.perf_counter() - t0,
            status=status, error=why, priority=req.priority,
            deadline_s=req.deadline_s))
        self.stats[status] += 1
        return rid

    def _emit_structured(self, r: _Pending, status: str, error: str,
                         bucket: int = 0) -> None:
        """Exactly-once bookkeeping for a queued row that never reached a
        healthy completion (shed / expired / dispatch failure)."""
        nanv = np.full((max(r.n, 0),), np.nan, np.float32)
        self._done.append(AllocResult(
            rid=r.rid, n=r.n, bucket=bucket, scheme=r.eff_scheme,
            p=nanv, q=nanv.copy(), f=nanv.copy(), alpha=nanv.copy(),
            rates=nanv.copy(), t_total=float("nan"), energy=float("nan"),
            feasible=False, iterations=0,
            latency_s=time.perf_counter() - r.t_submit,
            status=status, error=error, priority=r.req.priority,
            deadline_s=r.req.deadline_s, degradation=r.degradation))
        self.stats[status] += 1

    def _predict_wait(self, key: tuple) -> float | None:
        """Coarse queue-wait model for admission control: the EWMA of a
        batch's seconds from the dispatch call's return to the end of its
        ``block_until_ready`` at reap, × (in-flight batches + this key's
        queued full batches + the batch this request would join).  That
        time holds the device run and the wait until the batch is reaped
        (the next ``drain``, a full in-flight window, a host stall), so
        it errs high by that wait (``AllocResult.stages["inflight_s"]``
        shows its size).  None (admit) until the first measured
        completion seeds the EWMA."""
        ew = self._ewma.get(key)
        if ew is None:
            return None
        ahead = (len(self._inflight)
                 + len(self._pending.get(key, ())) // self.max_batch + 1)
        return ew * ahead

    def submit(self, req: AllocRequest) -> int:
        """Enqueue one request; returns its rid.  Flushes the bucket as
        soon as it holds ``max_batch`` requests (PR-8 behavior); with
        ``max_queue`` set, dispatch instead defers while the in-flight
        window is full and the bounded queue sheds lowest-priority-first.

        Fast-fail paths (all structured rows, never raises mid-stream):
        N exceeding the largest bucket, non-finite channel gains, an open
        circuit breaker, and admission control on ``deadline_s``."""
        # every path below gives the request the rid that is next now
        with span("serve.submit", rid=self._next_rid):
            return self._submit(req)

    def _submit(self, req: AllocRequest) -> int:
        t0 = time.perf_counter()
        if req.scheme not in SERVE_SCHEMES:
            raise ValueError(f"unknown scheme {req.scheme!r}; "
                             f"expected one of {SERVE_SCHEMES}")
        h2 = np.asarray(req.h2, np.float32).reshape(-1)
        n = h2.shape[0]
        if n == 0:
            raise ValueError("empty request (0 clients)")
        if not np.all(np.isfinite(h2)):
            return self._reject(req, n, "non-finite channel gains in h2",
                                t0)
        try:
            nb = self.bucket_for(n)     # single source of the oversize msg
        except ValueError as e:
            return self._reject(req, n, str(e), t0)
        key = (nb, req.scheme, req.cfg.dinkelbach_inner, req.cfg.sic_mode)
        br = self._breakers.get(key)
        if br is not None and br.state == "open":
            if time.monotonic() - br.opened_at >= self.breaker_cooldown_s:
                self._breaker_transition(key, br, "half_open")
            else:
                self.stats["breaker_rejected"] += 1
                return self._reject(
                    req, n, f"circuit breaker open for "
                            f"{self._key_str(key)} "
                            f"({br.fails} consecutive bad batches)", t0)
        if req.deadline_s is not None:
            wait = self._predict_wait(key)
            if wait is not None and wait > req.deadline_s:
                self.stats["admission_rejected"] += 1
                return self._reject(
                    req, n, f"admission control: predicted queue wait "
                            f"{wait:.4f}s exceeds deadline "
                            f"{req.deadline_s:.4f}s", t0)
        order = np.argsort(-h2, kind="stable")      # SIC decode order
        d = np.broadcast_to(np.asarray(req.d, np.float32), (n,))[order]
        vm = np.broadcast_to(np.asarray(req.v_max, np.float32), (n,))[order]
        rid = self._next_rid
        self._next_rid += 1
        self._pending[key].append(_Pending(
            rid=rid, req=req, n=n, order=order, h2=h2[order], d=d, v_max=vm,
            t_submit=t0, eff_cfg=req.cfg, eff_scheme=req.scheme))
        self.stats["submitted"] += 1
        if self.max_queue is None:
            if len(self._pending[key]) >= self.max_batch:
                self._flush_key(key)               # PR-8 blocking path
        else:
            self._shed_over_bound()
            self._pump()
        return rid

    # -- bounded queue / shedding ------------------------------------------
    def _shed_over_bound(self) -> None:
        """Priority-ordered load shedding: while the pending total
        exceeds ``max_queue``, the LOWEST-priority, YOUNGEST (largest
        rid) queued request becomes a structured ``status="shed"`` row —
        older same-priority requests are closer to dispatch and survive."""
        while (sum(len(v) for v in self._pending.values())
               > self.max_queue):
            victim_key, victim_i = None, None
            victim_rank = None
            for key, rows in self._pending.items():
                for i, r in enumerate(rows):
                    if r.rid < 0:
                        continue                   # warmup probes exempt
                    rank = (r.req.priority, -r.rid)
                    if victim_rank is None or rank < victim_rank:
                        victim_rank, victim_key, victim_i = rank, key, i
            if victim_key is None:
                return
            r = self._pending[victim_key].pop(victim_i)
            if not self._pending[victim_key]:
                del self._pending[victim_key]
            self._emit_structured(
                r, "shed", f"bounded queue full (max_queue="
                           f"{self.max_queue}): shed priority "
                           f"{r.req.priority}", bucket=victim_key[0])

    def _reap_ready(self) -> None:
        """Opportunistically retire in-flight batches whose results are
        already on host (non-blocking ``is_ready`` poll) — the bounded-
        queue mode's replacement for the PR-8 blocking completion."""
        while self._inflight:
            head = self._inflight[0]
            try:
                if not head.out.energy.is_ready():
                    break
            except AttributeError:     # no is_ready on this array type
                break
            self._complete(self._inflight.popleft())

    def _pump(self) -> None:
        """Bounded-queue dispatch policy: reap ready batches, then
        dispatch full highest-priority chunks while the in-flight window
        has room.  Never blocks the producer — overflow is handled by
        ``_shed_over_bound``, partial batches wait for ``flush``."""
        self._reap_ready()
        progressed = True
        while progressed and len(self._inflight) <= self.max_inflight:
            progressed = False
            keys = sorted(
                self._pending,
                key=lambda k: -max((r.req.priority
                                    for r in self._pending[k]), default=0))
            for key in keys:
                if len(self._pending.get(key, ())) < self.max_batch:
                    continue
                chunk = self._take_chunk(key)
                if chunk:
                    self._dispatch_chunk(key, chunk)
                    progressed = True
                if len(self._inflight) > self.max_inflight:
                    return

    # -- dispatch -----------------------------------------------------------
    def _take_chunk(self, key: tuple) -> list:
        """Pop up to ``max_batch`` rows from this key's queue, highest
        priority first (stable — FIFO within a priority level, so the
        all-default stream packs exactly like PR 8).  Rows whose deadline
        already expired while queued emit ``status="timeout"`` without
        wasting a batch lane."""
        rows = self._pending.pop(key, [])
        now = time.perf_counter()
        live = []
        for r in rows:
            if (r.rid >= 0 and r.req.deadline_s is not None
                    and now - r.t_submit > r.req.deadline_s):
                self.stats["expired_in_queue"] += 1
                self._emit_structured(
                    r, "timeout", f"deadline {r.req.deadline_s:.4f}s "
                                  f"expired while queued", bucket=key[0])
            else:
                live.append(r)
        if not live:
            return []
        live.sort(key=lambda r: (-r.req.priority, r.rid))
        chunk, rest = live[:self.max_batch], live[self.max_batch:]
        if rest:
            self._pending[key] = rest + self._pending.pop(key, [])
        return chunk

    def _dispatch_chunk(self, key: tuple, rows: list) -> None:
        """Pack one padded batch and dispatch it, retrying transient
        dispatch failures with exponential backoff; a dispatch that
        still fails turns every request in the chunk into a structured
        ``"rejected"`` row and feeds the circuit breaker."""
        t_pack = time.perf_counter()
        batch = self._next_batch
        self._next_batch += 1
        nb, scheme, inner, sic_mode = key
        b = self.batch_width                    # fixed batch width per
        n_real = len(rows)                      # executable (zero retraces)
        with span("serve.pack", batch=batch):
            bufs = pack_rows(rows, nb, b)
            operands = jax.device_put(bufs, self._rows_at)
        last_err = None
        for attempt in range(self.dispatch_retries + 1):
            if attempt:
                self.stats["dispatch_retries"] += 1
                time.sleep(self.backoff_base_s * (2 ** (attempt - 1)))
            try:
                t_launch = time.perf_counter()
                with span("serve.launch", batch=batch):
                    out = self._dispatch(
                        *operands, self._tol, scheme=scheme,
                        max_iter=self.max_iter, inner=inner,
                        sic_mode=sic_mode, shards=self.shards)
                    # on what the seam returned, after any wrapper ran
                    prefetched = _start_readback(out)
                break
            except Exception as e:              # noqa: BLE001 — seam errors
                last_err = e
        else:
            self.stats["dispatch_failures"] += 1
            self._breaker_record(key, bad=True)
            for r in rows:
                if r.rid >= 0:
                    self._emit_structured(
                        r, "rejected",
                        f"dispatch failed after "
                        f"{self.dispatch_retries + 1} attempts: "
                        f"{last_err}", bucket=nb)
            return
        self._inflight.append(_InFlight(
            key=key, pending=rows, out=out, t_dispatch=time.perf_counter(),
            batch=batch, t_pack=t_pack, t_launch=t_launch))
        self.stats["dispatches"] += 1
        self.stats["operand_buffers"] += len(bufs)
        self.stats["readback_prefetched"] += prefetched
        self.stats["padded_slots"] += b - n_real

    def _flush_key(self, key: tuple) -> None:
        while True:
            chunk = self._take_chunk(key)
            if not chunk:
                return
            self._dispatch_chunk(key, chunk)
            while len(self._inflight) > self.max_inflight:
                self._complete(self._inflight.popleft())

    def flush(self) -> None:
        """Dispatch every partial batch (dummy-padded to the fixed width)."""
        for key in sorted(list(self._pending.keys())):
            self._flush_key(key)

    # -- circuit breaker ----------------------------------------------------
    def _breaker_transition(self, key: tuple, br: _Breaker,
                            state: str) -> None:
        self.breaker_log.append((self._key_str(key), br.state, state))
        del self.breaker_log[:-256]            # bounded transition history
        self.stats[f"breaker_{state}"] += 1
        br.state = state
        if state == "open":
            br.opened_at = time.monotonic()
        elif state == "closed":
            br.fails = 0

    def _breaker_record(self, key: tuple, bad: bool) -> None:
        """Feed one batch-health observation: ``breaker_threshold``
        consecutive bad batches (or one bad half-open probe) open the
        breaker; a healthy half-open probe closes it."""
        br = self._breakers.setdefault(key, _Breaker())
        if bad:
            br.fails += 1
            if br.state == "half_open" or (
                    br.state == "closed"
                    and br.fails >= self.breaker_threshold):
                self._breaker_transition(key, br, "open")
        else:
            if br.state == "half_open":
                self._breaker_transition(key, br, "closed")
            elif br.state == "closed":
                br.fails = 0

    # -- degraded retry -----------------------------------------------------
    def _ladder_next(self, r: _Pending):
        """Next applicable retry-ladder stage for an infeasible row, or
        None when exhausted.  ``relax_tmax`` applies to every
        deterministic scheme; ``fallback_oma`` only to the Stackelberg
        family (falling back from oma to oma is a no-op, and the random
        baseline earns no retries)."""
        i = r.stage
        while i < len(self.retry_ladder):
            s = self.retry_ladder[i]
            if s == "relax_tmax" and r.eff_scheme != "random":
                return i, s
            if s == "fallback_oma" and r.eff_scheme in ("proposed", "ideal",
                                                        "wo_dt"):
                return i, s
            i += 1
        return None

    def _requeue_retry(self, r: _Pending, nxt) -> None:
        i, stage = nxt
        if stage == "relax_tmax":
            cfg2 = dataclasses.replace(
                r.eff_cfg, t_max=r.eff_cfg.t_max * self.relax_factor)
            scheme2 = r.eff_scheme
            tag = f"relax_tmax:{self.relax_factor:g}"
        else:
            cfg2, scheme2, tag = r.eff_cfg, "oma", "fallback:oma"
        r2 = dataclasses.replace(r, eff_cfg=cfg2, eff_scheme=scheme2,
                                 stage=i + 1,
                                 degradation=r.degradation + (tag,))
        nb = self.bucket_for(r.n)
        self._pending[(nb, scheme2, cfg2.dinkelbach_inner,
                       cfg2.sic_mode)].append(r2)
        self.stats["retries"] += 1

    # -- completion ---------------------------------------------------------
    def _complete(self, inf: _InFlight) -> None:
        """Reap one in-flight batch: wait for it, read it back, emit or
        requeue its rows."""
        t_reap = time.perf_counter()
        with span("serve.reap", batch=inf.batch):
            self._retire(inf, t_reap)

    def _retire(self, inf: _InFlight, t_reap: float) -> None:
        key = inf.key
        nb = key[0]
        try:
            out = jax.block_until_ready(inf.out)
        except Exception as e:         # device-side failure surfaces here
            self.stats["dispatch_failures"] += 1
            self._breaker_record(key, bad=True)
            for r in inf.pending:
                if r.rid >= 0:
                    self._emit_structured(
                        r, "rejected", f"batch execution failed: {e}",
                        bucket=nb)
            return
        t_ready = time.perf_counter()
        dt = t_ready - inf.t_dispatch
        real = [i for i, r in enumerate(inf.pending) if r.rid >= 0]
        if real:
            # EWMA of dispatch-return -> ready-at-reap seconds feeds
            # admission control: the device run plus the wait until this
            # reap (the next drain, or a host stall), not the device run
            # alone.  Warmup probes (compile-dominated, no real rows)
            # don't seed it
            prev = self._ewma.get(key)
            self._ewma[key] = dt if prev is None else (
                self.ewma_alpha * dt + (1.0 - self.ewma_alpha) * prev)
        watchdog_trip = (self.watchdog_s is not None
                         and dt > self.watchdog_s)
        if watchdog_trip:
            self.stats["watchdog_trips"] += 1
        host = jax.device_get({f: getattr(out, f) for f in READ_FIELDS})
        if real:
            idx = np.asarray(real)
            finite = all(np.all(np.isfinite(host[f][idx]))
                         for f in ("p", "t_total", "energy"))
            all_infeasible = not bool(np.any(host["feasible"][idx]))
            self._breaker_record(
                key, bad=((not finite) or watchdog_trip
                          or (self.breaker_on_infeasible
                              and all_infeasible)))
        now = time.perf_counter()
        batch_stages = {"pack_s": inf.t_launch - inf.t_pack,
                        "launch_s": inf.t_dispatch - inf.t_launch,
                        "inflight_s": t_reap - inf.t_dispatch,
                        "ready_wait_s": t_ready - t_reap,
                        "readback_s": now - t_ready}
        for i, r in enumerate(inf.pending):
            if r.rid < 0:              # warmup probe row — not a user request
                continue
            row_finite = (np.all(np.isfinite(host["p"][i, :r.n]))
                          and np.isfinite(host["t_total"][i])
                          and np.isfinite(host["energy"][i]))
            feasible = bool(host["feasible"][i])
            if not row_finite:
                self._emit_structured(
                    r, "rejected", "non-finite allocation from solver",
                    bucket=nb)
                continue
            if (not feasible and self.degraded_retry
                    and r.req.allow_degraded):
                nxt = self._ladder_next(r)
                if nxt is not None:    # re-dispatch, don't emit yet
                    self._requeue_retry(r, nxt)
                    continue
            inv = np.empty_like(r.order)
            inv[r.order] = np.arange(r.n)        # SIC order → request order
            unsort = lambda a: np.ascontiguousarray(a[i, :r.n][inv])
            latency = now - r.t_submit
            stages = {"queue_s": inf.t_pack - r.t_submit, **batch_stages,
                      "batch": inf.batch}
            late = (r.req.deadline_s is not None
                    and latency > r.req.deadline_s)
            if not feasible:
                status, error = "infeasible", \
                    "equilibrium violates the deadline/resource box"
            elif late:
                status = "timeout"
                error = (f"completed {latency:.4f}s after submit > "
                         f"deadline {r.req.deadline_s:.4f}s")
            else:
                status, error = "ok", ""
            self._done.append(AllocResult(
                rid=r.rid, n=r.n, bucket=nb, scheme=r.eff_scheme,
                p=unsort(host["p"]), q=unsort(host["q"]),
                f=unsort(host["f"]), alpha=unsort(host["alpha"]),
                rates=unsort(host["rates"]),
                t_total=float(host["t_total"][i]),
                energy=float(host["energy"][i]),
                feasible=feasible,
                iterations=int(host["iterations"][i]),
                latency_s=latency,
                status=status, error=error, priority=r.req.priority,
                deadline_s=r.req.deadline_s, degradation=r.degradation,
                stages=stages))
            self.stats["completed"] += 1
            self._lat[r.req.priority].append(latency)
            for name in STAGES:
                self._stages[name].append(stages[name])
            if not feasible:
                self.stats["infeasible"] += 1
            elif late:
                self.stats["timeout"] += 1
            elif r.degradation:
                self.stats["degraded_ok"] += 1

    def drain(self) -> list:
        """Flush all partial batches, retire all in-flight dispatches
        (looping until retry-ladder re-dispatches settle too), and
        return every accumulated result SORTED BY RID — one row per
        submitted rid, exactly once."""
        while self._pending or self._inflight:
            self.flush()
            while self._inflight:
                self._complete(self._inflight.popleft())
        done, self._done = self._done, []
        done.sort(key=lambda r: r.rid)
        return done

    # -- observability ------------------------------------------------------
    def health(self) -> dict:
        """Resilience snapshot: queue depths, breaker states, EWMA
        dispatch latencies, every counter, per-priority p50/p99 latency
        and p50/p99 of each of ``STAGES`` over the last
        ``latency_window`` completions."""
        def pct(window):
            arr = np.asarray(window, np.float64) * 1e3
            return {"n": int(arr.size),
                    "p50_ms": float(np.percentile(arr, 50)),
                    "p99_ms": float(np.percentile(arr, 99))}

        lat = {str(pri): pct(self._lat[pri])
               for pri in sorted(self._lat) if self._lat[pri]}
        stages = {name: pct(window)
                  for name, window in self._stages.items() if window}
        d = max(self.stats["dispatches"], 1)
        return {
            "queued": {self._key_str(k): len(v)
                       for k, v in self._pending.items() if v},
            "queued_total": sum(len(v) for v in self._pending.values()),
            "inflight": len(self._inflight),
            "breakers": {self._key_str(k): {"state": b.state,
                                            "fails": b.fails}
                         for k, b in self._breakers.items()},
            "breaker_transitions": list(self.breaker_log),
            "ewma_dispatch_s": {self._key_str(k): round(v, 6)
                                for k, v in self._ewma.items()},
            "counters": {k: int(v) for k, v in sorted(self.stats.items())},
            # host buffers sent, and readbacks started at dispatch, per
            # dispatch (2.0 and 1.0 on the packed path)
            "per_dispatch": {k: self.stats[k] / d for k in
                             ("operand_buffers", "readback_prefetched")},
            "latency_by_priority_ms": lat,
            "stages": stages,
        }

    # -- pre-compilation ----------------------------------------------------
    def warmup(self, schemes: Sequence[str] = ("proposed",),
               cfg: GameConfig | None = None) -> float:
        """Compile every (bucket, scheme) executable with an all-dummy
        batch; returns the wall seconds spent (the cold-start tax a warm
        deployment never pays on the stream).  Probe rows (rid=-1) never
        surface in ``drain()``, ``stats["completed"]`` or the EWMA."""
        cfg = cfg or GameConfig()
        t0 = time.perf_counter()
        for scheme in schemes:
            for nb in self.buckets:
                key = (nb, scheme, cfg.dinkelbach_inner, cfg.sic_mode)
                row = _Pending(rid=-1, req=AllocRequest(h2=np.ones(1),
                                                        cfg=cfg,
                                                        scheme=scheme),
                               n=1, order=np.zeros(1, np.int64),
                               h2=np.ones(1, np.float32),
                               d=np.zeros(1, np.float32),
                               v_max=np.zeros(1, np.float32),
                               t_submit=time.perf_counter(),
                               eff_cfg=cfg, eff_scheme=scheme)
                self._pending[key] = [row]
                self._flush_key(key)
        while self._inflight:
            self._complete(self._inflight.popleft())
        return time.perf_counter() - t0
