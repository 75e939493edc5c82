"""Vectorized JAX scenario engine: batch-simulate markets × strategies ×
seeds in one jit.

The legacy ``SpotMarket``/``VolatileCluster`` stack advances one scenario at
a time in a Python loop; every fig3/fig4-style sweep multiplies wall-clock
linearly and runs single-seed. This module extracts the per-tick step logic
(price draw → bid→active-mask → time/cost/idle accounting → masked model
update) into pure functions over an explicit ``SimState`` pytree, drives
them with ``lax.scan`` over market ticks, and ``vmap``s twice — over a
stacked ``ScenarioBatch`` and over seeds — so an S-scenario × R-seed grid
runs in a single compiled call.

The *model under simulation* is pluggable (``ModelProgram``): the default is
the Theorem-1 quadratic oracle, but any pure step over an arbitrary
``(params, opt_state)``-style pytree plugs into the same scan —
``repro.train.trainer.train_batched`` runs real reduced models (the elastic
masked train step) this way, so a strategy × market grid trains end-to-end
inside one compiled call with no host sync between ticks.

Time model (§III-C), identical to the legacy loop: each *tick* queries the
price prevailing at the current wall clock; if ≥1 worker is active an SGD
iteration runs and the clock advances by the sampled runtime R(y), else the
clock advances by ``idle_step`` (idle time, no iteration). Replayed traces
(``PriceSpec.from_trace``) are *time-indexed*: the carry's wall clock ``t``
— not the tick counter — selects the trace entry, so replay stays exact
under stochastic (``exp``) iteration durations where ticks and elapsed time
diverge (the fig4 regime; ``from_trace_ticks`` keeps the legacy per-tick
consumption for tick-exact parity pins). A scenario stops accumulating once
it has completed its ``J`` iterations. Active workers pay the *price*, not
the bid (§IV). Iterations with zero active workers are a *true no-op*: the
whole model pytree is gated on ``running`` with ``jnp.where``, so
idle/finished ticks cannot leak scaled gradients into the iterate.

Checkpointing is scan-native: ``SimConfig.snapshot_every = k`` restructures
the scan into k-tick chunks whose per-chunk output is the *entire* carry
(`SimState`, model included), stacked into ``EngineResult.snapshots``;
``simulate_program(init_state=..., tick0=...)`` resumes from any snapshot
bit-exactly (per-tick RNG keys fold the absolute tick index), so a
preempted batched run restarts mid-trace with no drift.

Adaptive (time-dependent) strategies enter the scan as precomputed *plan
tables*: ``bid_table[b, j]`` holds the bids for iteration ``j`` under
elapsed-time bucket ``b`` (``bucket_starts``); at the first tick of
iteration ``replan_at`` the engine latches the bucket for the current clock
— recovering the legacy ``DynamicBids`` replan-on-actual-time semantics up
to the bucket resolution, with zero Python callbacks mid-scan.

The shared pure helpers (`spot_active_mask`, `iteration_cost`,
`preemptible_active`) are the single source of truth for the market/cost
semantics: the legacy ``SpotMarket.step`` and ``VolatileCluster`` delegate
their inner steps to them, so the Python-loop path (still used by
``ElasticTrainer.run``) and the batched path cannot drift apart.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.special import ndtr, ndtri
from jax.sharding import NamedSharding, PartitionSpec

# The pad value for absent workers in stacked bid schedules lives with the
# strategies (which build the schedules); re-exported here for engine users.
from repro.core.strategies import NEVER_BID
# The shared §IV/§V market/cost semantics live in the dependency-free
# sim.market_core (so the legacy numpy loop uses them without importing
# JAX); re-exported here for engine users.
from repro.sim.market_core import (BID_EPS, iteration_cost,  # noqa: F401
                                   preemptible_active, spot_active_mask)

# Modes / price kinds (ints so they vmap as data).
SPOT, PREEMPTIBLE = 0, 1
PRICE_UNIFORM, PRICE_TRUNC_GAUSS, PRICE_TRACE, PRICE_EMPIRICAL = 0, 1, 2, 3
PRICE_TRACE_TICK = 4


# --------------------------------------------------------------------------
# Scenario specification
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PriceSpec:
    """Batchable price-distribution parameters (one scenario).

    kind=PRICE_UNIFORM:      U[lo, hi].
    kind=PRICE_TRUNC_GAUSS:  N(mu, sigma²) truncated to [lo, hi] (exact
                             inverse-CDF via ndtri — no bisection).
    kind=PRICE_TRACE:        *time-indexed* trace replay: the price at wall
                             clock ``t`` is the trace entry whose timestamp
                             is the last one ≤ ``t mod period`` — exactly
                             ``TracePrices.price(t)`` for uniform ``step``
                             timestamps, and correct under stochastic
                             iteration durations (the fig4 regime). Per-seed
                             variation comes from a deterministic index
                             offset (seed 0 replays verbatim).
    kind=PRICE_TRACE_TICK:   legacy *tick-indexed* replay: one entry per
                             engine tick regardless of the clock — matches
                             ``TickPrices`` (call-counting) for tick-exact
                             parity tests.
    kind=PRICE_EMPIRICAL:    i.i.d. draws from the empirical quantile of
                             ``trace`` (must be sorted) — matches
                             ``IIDPrices(EmpiricalPrice(samples))``.
    """

    kind: int
    lo: float
    hi: float
    mu: float = 0.0
    sigma: float = 1.0
    trace: Optional[np.ndarray] = None
    times: Optional[np.ndarray] = None     # (L,) ascending, times[0] == 0
    period: Optional[float] = None         # wrap length, > times[-1]

    @classmethod
    def uniform(cls, lo: float, hi: float) -> "PriceSpec":
        return cls(kind=PRICE_UNIFORM, lo=lo, hi=hi)

    @classmethod
    def trunc_gaussian(cls, mu: float, sigma: float, lo: float,
                       hi: float) -> "PriceSpec":
        return cls(kind=PRICE_TRUNC_GAUSS, lo=lo, hi=hi, mu=mu, sigma=sigma)

    @classmethod
    def from_trace(cls, trace: np.ndarray, times: Optional[np.ndarray] = None,
                   step: float = 1.0,
                   period: Optional[float] = None) -> "PriceSpec":
        """Time-indexed trace replay (the faithful ``TracePrices`` analogue).

        ``times`` are explicit per-entry timestamps (ascending from 0); when
        omitted they default to ``step * arange(len(trace))`` — the uniform
        resolution of ``TracePrices(trace, step=step)``. ``period`` is the
        wrap length (default: one step past the last timestamp, i.e.
        ``len(trace) * step`` for uniform traces, matching the legacy
        ``int(t/step) % len`` modulo). Defaulting and validation are shared
        with every other trace consumer via ``sim.traces.PriceTrace``."""
        from repro.sim.traces import PriceTrace
        if isinstance(trace, PriceTrace):
            pt = trace
        else:
            trace = np.asarray(trace, np.float32)
            if times is None:
                # default timestamps in f32 arithmetic, as always — the
                # fig4 trace-parity pins are ULP-sensitive
                times = np.float32(step) * np.arange(len(trace),
                                                     dtype=np.float32)
                if period is None:
                    period = float(step) * len(trace)
            pt = PriceTrace.from_arrays(trace, times=np.asarray(times, float),
                                        step=step, period=period)
        trace = np.asarray(pt.values, np.float32)
        return cls(kind=PRICE_TRACE, lo=float(trace.min()),
                   hi=float(trace.max()), trace=trace,
                   times=np.asarray(pt.times, np.float32),
                   period=float(pt.period))

    @classmethod
    def from_trace_ticks(cls, trace: np.ndarray) -> "PriceSpec":
        """Legacy tick-indexed replay: one entry per engine tick (wrapping),
        regardless of the wall clock — the ``TickPrices`` consumption order,
        kept for tick-exact parity pins."""
        trace = np.asarray(trace, np.float32)
        return cls(kind=PRICE_TRACE_TICK, lo=float(trace.min()),
                   hi=float(trace.max()), trace=trace)

    @classmethod
    def empirical(cls, samples: np.ndarray) -> "PriceSpec":
        samples = np.sort(np.asarray(samples, np.float32))
        return cls(kind=PRICE_EMPIRICAL, lo=float(samples[0]),
                   hi=float(samples[-1]), trace=samples)

    @classmethod
    def from_dist(cls, dist) -> "PriceSpec":
        """Map a core.cost_model.PriceDist onto a batchable spec."""
        from repro.core.cost_model import (EmpiricalPrice, TruncGaussianPrice,
                                           UniformPrice)
        if isinstance(dist, UniformPrice):
            return cls.uniform(dist.lo, dist.hi)
        if isinstance(dist, TruncGaussianPrice):
            return cls.trunc_gaussian(dist.mu, dist.sigma, dist.lo, dist.hi)
        if isinstance(dist, EmpiricalPrice):
            return cls.empirical(dist.samples)
        raise TypeError(f"no batchable spec for {type(dist).__name__}")


@dataclasses.dataclass
class Scenario:
    """One simulation scenario = market × strategy-plan × runtime model.

    Exactly one of ``bid_schedule`` (mode=SPOT: per-iteration per-worker
    bids, shape (J, n)), ``bid_table`` (mode=SPOT, adaptive: per-time-bucket
    bid schedules, shape (B, J, n) — see ``bucket_starts``/``replan_at``) or
    ``worker_schedule`` (mode=PREEMPTIBLE: provisioned worker counts, shape
    (J,)) must be given.

    ``bucket_starts`` (B,) are ascending bucket start times with
    ``bucket_starts[0] == 0``; at the first tick of iteration ``replan_at``
    the engine latches the bucket containing the current wall clock and uses
    that table slice for the rest of the run (the precomputed analogue of
    the legacy ``DynamicBids`` replan-on-actual-elapsed-time).
    """

    price: PriceSpec
    alpha: float                            # SGD step size
    bid_schedule: Optional[np.ndarray] = None
    worker_schedule: Optional[np.ndarray] = None
    bid_table: Optional[np.ndarray] = None
    bucket_starts: Optional[np.ndarray] = None
    replan_at: Optional[int] = None
    J_target: Optional[int] = None  # stop after this many iterations even
    #                                 though the plan arrays are wider — lets
    #                                 replanners keep table shapes constant
    #                                 (no recompile) while shrinking the
    #                                 remaining-work target
    n_fleet: Optional[int] = None  # preemptible: mask width override (the
    #                                job's worker count when the schedule
    #                                provisions fewer than n_workers)
    preempt_q: float = 0.0
    on_demand_price: float = 1.0
    rt_kind: str = "exp"                    # "exp" | "det"
    rt_lam: float = 1.0
    rt_delta: float = 0.05
    rt_const: float = 1.0
    idle_step: float = 0.1
    name: str = ""

    def __post_init__(self):
        given = sum(x is not None for x in
                    (self.bid_schedule, self.bid_table,
                     self.worker_schedule))
        if given != 1:
            raise ValueError("give exactly one of bid_schedule / bid_table "
                             "/ worker_schedule")
        if self.bid_schedule is not None:
            self.bid_schedule = np.atleast_2d(
                np.asarray(self.bid_schedule, np.float32))
            # a plain schedule is a 1-bucket table
            self.bid_table = self.bid_schedule[None]
        if self.bid_table is not None:
            self.bid_table = np.asarray(self.bid_table, np.float32)
            if self.bid_table.ndim != 3:
                raise ValueError(f"bid_table must be (B, J, n), got shape "
                                 f"{self.bid_table.shape}")
            if self.bucket_starts is None:
                self.bucket_starts = np.zeros(self.bid_table.shape[0],
                                              np.float32)
            self.bucket_starts = np.asarray(self.bucket_starts, np.float32)
            if len(self.bucket_starts) != self.bid_table.shape[0]:
                raise ValueError(
                    f"{len(self.bucket_starts)} bucket_starts for "
                    f"{self.bid_table.shape[0]} table buckets")
            if (self.bucket_starts[0] != 0.0
                    or np.any(np.diff(self.bucket_starts) < 0)):
                raise ValueError("bucket_starts must ascend from 0, got "
                                 f"{self.bucket_starts}")
            if self.bid_table.shape[0] > 1 and self.replan_at is None:
                raise ValueError(
                    "a multi-bucket bid_table needs replan_at (the "
                    "iteration at which the engine latches the bucket) — "
                    "without it only bucket 0 would ever be used")
        if self.J_target is not None:
            if not 1 <= int(self.J_target) <= self.plan_width:
                raise ValueError(
                    f"J_target={self.J_target} must lie in [1, "
                    f"{self.plan_width}] (the plan width)")

    @property
    def mode(self) -> int:
        return SPOT if self.bid_table is not None else PREEMPTIBLE

    @property
    def n_buckets(self) -> int:
        return 1 if self.bid_table is None else int(self.bid_table.shape[0])

    @property
    def plan_width(self) -> int:
        """Rows in the plan arrays (≥ J when J_target overrides)."""
        if self.bid_table is not None:
            return int(self.bid_table.shape[1])
        return int(np.shape(self.worker_schedule)[0])

    @property
    def J(self) -> int:
        if self.J_target is not None:
            return int(self.J_target)
        return self.plan_width

    @property
    def n_workers(self) -> int:
        if self.bid_table is not None:
            return int(self.bid_table.shape[2])
        return max(int(np.max(self.worker_schedule)), self.n_fleet or 0)

    @classmethod
    def from_runtime(cls, rt, **kw) -> "Scenario":
        """Fill the runtime fields from a core.cost_model.RuntimeModel."""
        return cls(rt_kind=rt.kind, rt_lam=rt.lam, rt_delta=rt.delta,
                   rt_const=rt.r_const, **kw)


class ScenarioBatch(NamedTuple):
    """Stacked scenarios (leading axis S) — a vmap-able pytree."""

    bid_table: jnp.ndarray         # (S, B_max, J_max, N) f32, NEVER_BID-pad
    bucket_starts: jnp.ndarray     # (S, B_max) f32, +inf-padded
    replan_at: jnp.ndarray         # (S,) i32 (J_max+1 => never latch)
    worker_schedule: jnp.ndarray   # (S, J_max) i32
    mode: jnp.ndarray              # (S,) i32
    price_kind: jnp.ndarray        # (S,) i32
    price_lo: jnp.ndarray          # (S,) f32
    price_hi: jnp.ndarray
    price_mu: jnp.ndarray
    price_sigma: jnp.ndarray
    trace: jnp.ndarray             # (S, L_tr) f32 (zeros when unused)
    trace_len: jnp.ndarray         # (S,) i32
    trace_times: jnp.ndarray       # (S, L_tr) f32 timestamps, +inf-padded
    trace_period: jnp.ndarray      # (S,) f32 wrap length (1 when unused)
    preempt_q: jnp.ndarray         # (S,) f32
    on_demand_price: jnp.ndarray
    rt_kind: jnp.ndarray           # (S,) i32: 0 exp, 1 det
    rt_lam: jnp.ndarray
    rt_delta: jnp.ndarray
    rt_const: jnp.ndarray
    alpha: jnp.ndarray
    J: jnp.ndarray                 # (S,) i32 target iterations
    idle_step: jnp.ndarray

    @property
    def n_scenarios(self) -> int:
        return self.mode.shape[0]

    @property
    def n_buckets(self) -> int:
        return self.bid_table.shape[1]

    @property
    def j_max(self) -> int:
        return self.bid_table.shape[2]

    @property
    def n_max(self) -> int:
        return self.bid_table.shape[3]


def stack_scenarios(scenarios: Sequence[Scenario]) -> ScenarioBatch:
    """Pad and stack heterogeneous scenarios into one ScenarioBatch.

    Bid tables are padded to (B_max, J_max, N_max): extra workers get
    NEVER_BID, iterations past a scenario's own J repeat its last row and
    buckets past its own B repeat its last bucket (neither is ever selected
    — the engine stops at J, and padded bucket starts are +inf — the repeat
    just keeps gathers in-bounds).
    """
    S = len(scenarios)
    b_max = max(s.n_buckets for s in scenarios)
    j_max = max(s.plan_width for s in scenarios)
    n_max = max(s.n_workers for s in scenarios)
    l_tr = max([len(s.price.trace) for s in scenarios
                if s.price.trace is not None] or [1])

    bid = np.full((S, b_max, j_max, n_max), NEVER_BID, np.float32)
    starts = np.full((S, b_max), np.inf, np.float32)
    starts[:, 0] = 0.0
    replan = np.full(S, j_max + 1, np.int32)
    wrk = np.zeros((S, j_max), np.int32)
    trc = np.zeros((S, l_tr), np.float32)
    tln = np.ones(S, np.int32)
    # timestamps: +inf past a scenario's own trace so a right-bisect of any
    # finite clock value lands inside the real entries; row 0 stays 0 so the
    # lookup index is never negative
    tms = np.full((S, l_tr), np.inf, np.float32)
    tms[:, 0] = 0.0
    period = np.ones(S, np.float32)
    cols: Dict[str, np.ndarray] = {
        k: np.zeros(S, np.float32) for k in
        ["price_lo", "price_hi", "price_mu", "price_sigma", "preempt_q",
         "on_demand_price", "rt_lam", "rt_delta", "rt_const", "alpha",
         "idle_step"]}
    mode = np.zeros(S, np.int32)
    pk = np.zeros(S, np.int32)
    rtk = np.zeros(S, np.int32)
    J = np.zeros(S, np.int32)

    for i, s in enumerate(scenarios):
        J[i] = s.J
        mode[i] = s.mode
        pk[i] = s.price.kind
        rtk[i] = 0 if s.rt_kind == "exp" else 1
        if s.bid_table is not None:
            b = s.bid_table                       # (B, J, n)
            bid[i, :b.shape[0], :b.shape[1], :b.shape[2]] = b
            bid[i, :b.shape[0], b.shape[1]:, :b.shape[2]] = b[:, -1:]
            bid[i, b.shape[0]:] = bid[i, b.shape[0] - 1]
            starts[i, :len(s.bucket_starts)] = s.bucket_starts
            if s.replan_at is not None:
                replan[i] = s.replan_at
        else:
            w = np.asarray(s.worker_schedule, np.int32)
            wrk[i, :len(w)] = w
            wrk[i, len(w):] = w[-1]
        if s.price.trace is not None:
            tr = np.asarray(s.price.trace, np.float32)
            reps = int(np.ceil(l_tr / len(tr)))
            trc[i] = np.tile(tr, reps)[:l_tr]
            tln[i] = len(tr)
        if s.price.kind == PRICE_TRACE:
            if s.price.times is None or s.price.period is None:
                # without timestamps the lookup would silently pin to
                # entry 0 — a hand-built spec must go through from_trace
                raise ValueError(
                    f"scenario {i} ({s.name!r}): a PRICE_TRACE spec needs "
                    "timestamps and a period — build it with "
                    "PriceSpec.from_trace (or use from_trace_ticks for "
                    "tick-indexed replay)")
            tms[i, :len(s.price.times)] = s.price.times
            period[i] = s.price.period
        for k, v in [("price_lo", s.price.lo), ("price_hi", s.price.hi),
                     ("price_mu", s.price.mu),
                     ("price_sigma", s.price.sigma),
                     ("preempt_q", s.preempt_q),
                     ("on_demand_price", s.on_demand_price),
                     ("rt_lam", s.rt_lam), ("rt_delta", s.rt_delta),
                     ("rt_const", s.rt_const), ("alpha", s.alpha),
                     ("idle_step", s.idle_step)]:
            cols[k][i] = v
    return ScenarioBatch(
        bid_table=jnp.asarray(bid), bucket_starts=jnp.asarray(starts),
        replan_at=jnp.asarray(replan), worker_schedule=jnp.asarray(wrk),
        mode=jnp.asarray(mode), price_kind=jnp.asarray(pk),
        trace=jnp.asarray(trc), trace_len=jnp.asarray(tln),
        trace_times=jnp.asarray(tms), trace_period=jnp.asarray(period),
        rt_kind=jnp.asarray(rtk), J=jnp.asarray(J),
        **{k: jnp.asarray(v) for k, v in cols.items()})


# --------------------------------------------------------------------------
# The Theorem-1 quadratic oracle in JAX
# --------------------------------------------------------------------------


#: the oracle's products run at full float32 precision: an accelerator's
#: default single bf16 pass would move the paper's error trajectories off
#: the float64 legacy loop they are checked against
_F32 = lax.Precision.HIGHEST


class JaxQuadratic(NamedTuple):
    """Device-side view of data.synthetic.QuadraticProblem. The quadratic is
    exact, so error = G(w) − G* = ½ (w−w*)ᵀ H (w−w*) — no residual pass."""

    A: jnp.ndarray          # (n_samples, d, d)
    b: jnp.ndarray          # (n_samples, d)
    H: jnp.ndarray          # (d, d) average Hessian
    w_star: jnp.ndarray     # (d,)

    @property
    def n_samples(self) -> int:
        return self.A.shape[0]

    def error(self, w: jnp.ndarray) -> jnp.ndarray:
        d = w - self.w_star
        return 0.5 * jnp.dot(d, jnp.dot(self.H, d, precision=_F32),
                             precision=_F32)

    def full_grad(self, w: jnp.ndarray) -> jnp.ndarray:
        return jnp.dot(self.H, w - self.w_star, precision=_F32)

    def minibatch_grads(self, key, w: jnp.ndarray, n_workers: int,
                        batch: int) -> jnp.ndarray:
        """Per-worker minibatch gradients, shape (n_workers, d)."""
        idx = jax.random.randint(key, (n_workers, batch), 0, self.n_samples)
        a = self.A[idx]                                  # (n, b, d, d)
        r = jnp.einsum("wbij,j->wbi", a, w, precision=_F32) - self.b[idx]
        return jnp.einsum("wbij,wbi->wj", a, r, precision=_F32) / batch


def jax_quadratic(quad) -> JaxQuadratic:
    """Lift a numpy QuadraticProblem onto the device."""
    return JaxQuadratic(A=jnp.asarray(quad.A, jnp.float32),
                        b=jnp.asarray(quad.b, jnp.float32),
                        H=jnp.asarray(quad.H, jnp.float32),
                        w_star=jnp.asarray(quad.w_star, jnp.float32))


# --------------------------------------------------------------------------
# The engine
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static (compile-time) engine configuration."""

    n_ticks: int                 # market ticks to scan (≥ J + idle budget)
    batch: int = 16              # per-worker minibatch size (quad program)
    grad: str = "minibatch"      # "minibatch" | "full" (deterministic)
    snapshot_every: int = 0      # emit the full scan carry every k ticks
    #                              (0 = off) — preemption-safe checkpoints


@dataclasses.dataclass(frozen=True, eq=False)
class ModelProgram:
    """Pluggable model under the engine scan.

    ``step_fn(model, data, key, mask, j, alpha) -> (new_model, metric)``
    runs one training iteration: ``model`` is an arbitrary pytree (e.g.
    ``(params, opt_state)``), ``data`` a pytree of device arrays shared
    across all scenarios/seeds (problem constants, stacked batches),
    ``mask`` the (n_max,) float32 active-worker mask, ``j`` the traced
    iteration index, and ``alpha`` the scenario's step size (programs with
    their own LR schedule may ignore it). ``metric`` is the float32 scalar
    recorded in the per-iteration trajectory (error for the quadratic
    oracle, batch loss for real models).

    The engine gates the returned model on the iteration actually running
    (``jnp.where`` over every leaf), so the step need not handle the
    all-preempted / finished cases — idle ticks are true no-ops. Gating is
    *dtype-agnostic*: each gated leaf is cast back to the carry leaf's
    dtype (`_gate_model`), so mixed-precision models — bf16 params beside
    f32 optimizer masters, as `train.zoo_program` builds — cannot promote
    the scan carry even if a step leaks a weak f32 or promoted leaf; and
    ``metric`` is cast to f32 before it lands in the trajectory, so steps
    may return a bf16 loss. ``data`` is an arbitrary pytree threaded
    unbatched through both scan layouts (closed over in the vmapped path,
    a replicated `PartitionSpec()` prefix in the sharded path) — per-
    program batch streams ride along without engine changes.

    ``blocked=True`` selects the megabatched scan layout instead: the tick
    scan runs *outside* the grid vmap, the market logic is vmapped per
    (scenario, seed) cell, and ``step_fn`` is called ONCE per tick over the
    whole grid with leading (S, R) axes on every argument and the extra
    trailing ``running`` argument::

        step_fn(model, data, key, mask, j, alpha, running)
            model: pytree, leaves (S, R, ...);  key: (S, R) PRNG keys
            mask: (S, R, n_max) f32;  j/alpha/running: (S, R)
            -> (new_model, metric (S, R) f32)

    A blocked step must gate its own output on ``running`` (the engine
    skips its per-leaf ``where`` pass — the fused update does the gating
    element-for-element). ``train.trainer.make_megabatch_train_program``
    builds such programs over the flat replica-blocked parameter layout.

    Instances hash by identity (``eq=False``) and are jit static arguments:
    build them once (module constant / ``lru_cache``) or every call
    recompiles.
    """

    step_fn: Callable[..., Any]
    name: str = "program"
    blocked: bool = False


@functools.lru_cache(maxsize=None)
def quadratic_program(grad: str, batch: int) -> ModelProgram:
    """The Theorem-1 quadratic oracle as a ModelProgram: model = the (d,)
    SGD iterate, data = a JaxQuadratic, metric = error after the update."""

    def step_fn(w, quad: JaxQuadratic, key, mask, j, alpha):
        del j
        n_max = mask.shape[0]
        y = jnp.sum(mask)
        if grad == "full":
            g = quad.full_grad(w)
        else:
            gw = quad.minibatch_grads(key, w, n_max, batch)
            g = jnp.sum(gw * mask[:, None], 0) / jnp.maximum(y, 1.0)
        w_new = w - alpha * g
        return w_new, quad.error(w_new)

    return ModelProgram(step_fn=step_fn, name=f"quadratic-{grad}-{batch}")


class SimState(NamedTuple):
    """Per-(scenario, seed) scan carry."""

    t: jnp.ndarray               # wall clock
    j: jnp.ndarray               # iterations completed (i32)
    bucket: jnp.ndarray          # latched plan-table bucket (i32, -1=unset)
    total_cost: jnp.ndarray
    total_idle: jnp.ndarray
    model: Any                   # pytree under ModelProgram.step_fn
    err_traj: jnp.ndarray        # (J_max,) program metric after iteration j
    cost_traj: jnp.ndarray       # (J_max,) cumulative cost
    time_traj: jnp.ndarray       # (J_max,) wall clock
    y_traj: jnp.ndarray          # (J_max,) active workers


#: Engine-owned SimState fields and their mandatory dtypes. The model
#: subtree is program-defined; it only has to be weak-type-free.
_CARRY_DTYPES = {
    "t": jnp.float32, "j": jnp.int32, "bucket": jnp.int32,
    "total_cost": jnp.float32, "total_idle": jnp.float32,
    "err_traj": jnp.float32, "cost_traj": jnp.float32,
    "time_traj": jnp.float32, "y_traj": jnp.float32,
}


def canonicalize_model(model):
    """Strip weak types from a model pytree (Python scalars arrive as
    weakly-typed f32/i32, and a weak leaf in the scan carry promotes —
    i.e. recompiles — on the first tick). Leaf dtypes are preserved."""

    def strengthen(x):
        x = jnp.asarray(x)
        if getattr(x, "weak_type", False):
            x = lax.convert_element_type(x, x.dtype)
        return x

    return jax.tree.map(strengthen, model)


def assert_carry_dtypes(state: SimState) -> None:
    """Fail fast (at trace time) if the scan carry could promote: engine
    fields must be exactly their declared f32/i32 dtypes and no leaf —
    engine or model — may be weakly typed."""
    for name, want in _CARRY_DTYPES.items():
        leaf = getattr(state, name)
        if leaf.dtype != want or getattr(leaf, "weak_type", False):
            raise TypeError(
                f"SimState.{name} must be strong {jnp.dtype(want).name}, "
                f"got {leaf.dtype}"
                f"{' (weak)' if getattr(leaf, 'weak_type', False) else ''}")
    for path, leaf in jax.tree_util.tree_flatten_with_path(state.model)[0]:
        if getattr(leaf, "weak_type", False):
            raise TypeError(
                f"model leaf {jax.tree_util.keystr(path)} is weakly typed "
                f"({leaf.dtype}); pass it through canonicalize_model first")


def initial_state(scenarios: "ScenarioBatch | Sequence[Scenario]", model0,
                  n_seeds: int, sharding=None) -> SimState:
    """The batched (S, R) initial scan carry: every (scenario, seed) replica
    starts from ``model0`` at t=0 with empty trajectories.

    This is both what ``simulate_program`` starts from and the *restore
    template* for checkpointed runs (`train.checkpoint.restore` fills the
    values back in from disk).

    The model fan-out is materialized eagerly (``broadcast_to`` on device)
    so the buffers exactly match the scan carry — a donated call reuses
    them in place. ``sharding`` (a `NamedSharding` over the (S, R) grid
    axes) builds every leaf's shards on their own devices from a copy of
    ``model0``, so no device ever holds more than its own shard of the
    grid."""
    if not isinstance(scenarios, ScenarioBatch):
        scenarios = stack_scenarios(scenarios)
    grid = (scenarios.n_scenarios, int(n_seeds))
    j_max = scenarios.j_max

    def fan_out(x):
        shape = grid + x.shape
        if sharding is None:
            return jnp.broadcast_to(x, shape)
        shard = sharding.shard_shape(shape)
        return jax.make_array_from_single_device_arrays(
            shape, sharding,
            [jnp.broadcast_to(jax.device_put(x, d), shard)
             for d in sharding.addressable_devices_indices_map(shape)])

    nan_traj = jnp.full((j_max,), jnp.nan, jnp.float32)
    return SimState(
        t=fan_out(jnp.zeros((), jnp.float32)),
        j=fan_out(jnp.zeros((), jnp.int32)),
        bucket=fan_out(jnp.full((), -1, jnp.int32)),
        total_cost=fan_out(jnp.zeros((), jnp.float32)),
        total_idle=fan_out(jnp.zeros((), jnp.float32)),
        model=jax.tree.map(fan_out, canonicalize_model(model0)),
        err_traj=fan_out(nan_traj), cost_traj=fan_out(nan_traj),
        time_traj=fan_out(nan_traj), y_traj=fan_out(nan_traj))


@dataclasses.dataclass
class EngineResult:
    """Stacked trajectories, shape (S, R, J_max); invalid entries are NaN
    (iterations a scenario never ran within the tick budget)."""

    errors: np.ndarray
    costs: np.ndarray
    times: np.ndarray
    ys: np.ndarray
    iterations: np.ndarray       # (S, R) completed iterations
    total_time: np.ndarray       # (S, R) final wall clock (incl. idle)
    total_cost: np.ndarray       # (S, R)
    total_idle: np.ndarray       # (S, R)
    J: np.ndarray                # (S,) per-scenario targets
    final_state: Optional[SimState] = None  # device carry after the run,
    #                              leaves (S, R, ...) — a resume point
    snapshots: Any = None        # SimState pytree, leaves (S, R, n_snap, …)
    #                              — the full carry every cfg.snapshot_every
    #                              ticks (None when snapshots are off)
    snapshot_ticks: Optional[np.ndarray] = None  # (n_snap,) tick counts:
    #                              snapshot i is the carry after tick
    #                              snapshot_ticks[i] (resume passes this as
    #                              tick0)

    @property
    def final_model(self) -> Any:
        """The trained model pytree, leaves stacked (S, R, ...)."""
        return None if self.final_state is None else self.final_state.model

    @property
    def losses(self) -> np.ndarray:
        """Alias: for real-model programs the metric trajectory is the
        per-iteration batch loss, not a suboptimality gap."""
        return self.errors

    @property
    def completed(self) -> np.ndarray:
        """(S, R) bool: scenario finished all J iterations within n_ticks."""
        return self.iterations >= self.J[:, None]

    def summary(self) -> Dict[str, np.ndarray]:
        import warnings

        ys = np.where(np.isnan(self.ys), np.nan, np.maximum(self.ys, 1.0))
        with warnings.catch_warnings(), np.errstate(invalid="ignore"):
            # all-NaN rows (scenarios that never ran an iteration within
            # the tick budget) legitimately summarize to NaN — errstate
            # alone does not silence nanmean's RuntimeWarning
            warnings.simplefilter("ignore", RuntimeWarning)
            return {
                "iterations": self.iterations,
                "time": self.total_time,
                "cost": self.total_cost,
                "idle": self.total_idle,
                "mean_active": np.nanmean(self.ys, axis=-1),
                "mean_inv_y": np.nanmean(1.0 / ys, axis=-1),
            }


def _draw_price(sc: ScenarioBatch, key, k, seed, t) -> jnp.ndarray:
    """The price prevailing at tick ``k`` / wall clock ``t``; every kind is
    computed and the scenario's is picked (all branches are cheap)."""
    u = jax.random.uniform(key)
    p_unif = sc.price_lo + u * (sc.price_hi - sc.price_lo)
    lo_z = ndtr((sc.price_lo - sc.price_mu) / sc.price_sigma)
    hi_z = ndtr((sc.price_hi - sc.price_mu) / sc.price_sigma)
    p_gauss = jnp.clip(
        sc.price_mu + sc.price_sigma * ndtri(lo_z + u * (hi_z - lo_z)),
        sc.price_lo, sc.price_hi)
    # per-seed trace variation = deterministic index offset (≈ np.roll);
    # seed 0 replays the trace verbatim (the parity-pinned configuration)
    roll = seed * 1013
    # time-indexed replay (§V/fig4 fidelity): the entry whose timestamp is
    # the last one ≤ the wrapped wall clock — exact under stochastic
    # iteration durations, where tick count and elapsed time diverge
    t_eff = jnp.mod(t, sc.trace_period)
    idx_t = jnp.clip(
        jnp.searchsorted(sc.trace_times, t_eff, side="right") - 1,
        0, sc.trace_len - 1)
    p_time = sc.trace[(idx_t + roll) % sc.trace_len]
    # legacy tick-indexed replay (TickPrices consumption order)
    p_tick = sc.trace[(k + roll) % sc.trace_len]
    # empirical quantile: samples[int(u·len)] on the sorted trace
    p_emp = sc.trace[jnp.minimum((u * sc.trace_len).astype(jnp.int32),
                                 sc.trace_len - 1)]
    return jnp.where(
        sc.price_kind == PRICE_EMPIRICAL, p_emp,
        jnp.where(sc.price_kind == PRICE_TRACE, p_time,
                  jnp.where(sc.price_kind == PRICE_TRACE_TICK, p_tick,
                            jnp.where(sc.price_kind == PRICE_TRUNC_GAUSS,
                                      p_gauss, p_unif))))


class TickMarket(NamedTuple):
    """One cell's market outcome for one tick — everything `_sim_one.tick`
    needs besides the model step itself."""

    mask: jnp.ndarray            # (n_max,) bool active-worker mask
    y: jnp.ndarray               # Σ mask (f32)
    running: jnp.ndarray         # bool: the iteration actually runs
    idling: jnp.ndarray          # bool: alive but all-preempted
    bucket: jnp.ndarray          # updated plan-table bucket (i32)
    cost_inc: jnp.ndarray        # cost of this tick (0 unless running)
    idle_inc: jnp.ndarray        # idle-time increment (0 unless idling)
    dt: jnp.ndarray              # wall-clock advance
    k_grad: jnp.ndarray          # the model step's PRNG key


def _market_tick(sc: ScenarioBatch, base, seed, t, j, bucket0,
                 k) -> TickMarket:
    """Market/accounting logic for one (scenario, seed) cell at absolute
    tick ``k``: price draw, plan-table bucket latch, bid/preemption mask,
    runtime and cost. Single source of truth — `_sim_one` calls it inside
    its per-cell scan, `_sim_blocked` vmaps it over the whole grid — so the
    two layouts consume identical RNG streams and stay bit-exact."""
    j_max = sc.bid_table.shape[1]
    n_max = sc.bid_table.shape[2]
    kk = jax.random.fold_in(base, k)
    k_price, k_dur, k_grad, k_up = jax.random.split(kk, 4)
    price = _draw_price(sc, k_price, k, seed, t)

    # plan-table bucket: latched from the wall clock at the first tick
    # of iteration `replan_at` (cf. DynamicBids consulting the clock
    # once when it replans), 0 (the t=0 plan) before that
    cur_bucket = jnp.sum(t >= sc.bucket_starts).astype(jnp.int32) - 1
    bucket = jnp.where((bucket0 < 0) & (j >= sc.replan_at),
                       cur_bucket, bucket0)
    row = jnp.minimum(j, j_max - 1)
    bids = sc.bid_table[jnp.maximum(bucket, 0), row]         # (N,)
    mask_spot = spot_active_mask(bids, price)
    prov = sc.worker_schedule[row]
    mask_pre = (jnp.arange(n_max) < prov) & preemptible_active(
        jax.random.uniform(k_up, (n_max,)), sc.preempt_q)
    mask = jnp.where(sc.mode == PREEMPTIBLE, mask_pre, mask_spot)
    y = jnp.sum(mask.astype(jnp.float32))

    done = j >= sc.J
    running = (y >= 1.0) & ~done
    idling = ~running & ~done

    # runtime R(y): max of the active workers' exp(λ) draws + Δ, or R
    draws = jax.random.exponential(k_dur, (n_max,)) / sc.rt_lam
    dur_exp = jnp.max(jnp.where(mask, draws, 0.0)) + sc.rt_delta
    dur = jnp.where(sc.rt_kind == 1, sc.rt_const, dur_exp)
    price_paid = jnp.where(sc.mode == PREEMPTIBLE, sc.on_demand_price,
                           price)
    cost_inc = jnp.where(running, iteration_cost(y, price_paid, dur), 0.0)
    idle_inc = jnp.where(idling, sc.idle_step, 0.0)
    dt = jnp.where(running, dur, idle_inc)
    return TickMarket(mask=mask, y=y, running=running, idling=idling,
                      bucket=bucket, cost_inc=cost_inc, idle_inc=idle_inc,
                      dt=dt, k_grad=k_grad)


def _gate_model(running, stepped, old):
    """Land the stepped model only on running ticks, per leaf, preserving
    each carry leaf's dtype: a step that returns a promoted (or weak-f32)
    leaf — easy to do in a mixed-precision update — would otherwise change
    the scan carry's pytree dtypes mid-scan and fail to converge in
    ``lax.scan``'s fixed-point check."""
    return jax.tree.map(
        lambda new, o: jnp.where(running, new.astype(o.dtype), o),
        stepped, old)


def _sim_one(sc: ScenarioBatch, state0: SimState, data, seed,
             program: ModelProgram, n_run: int, k_snap: int, tick0):
    """Simulate one scenario × one seed (vmapped twice by `simulate`),
    running ``n_run`` ticks from carry ``state0`` at absolute tick ``tick0``
    (0 for a fresh run; a restored checkpoint resumes mid-trace — per-tick
    RNG keys are folded from the absolute tick index, so the continuation
    is bit-exact). ``tick0`` is *traced* (data, not a static shape), so
    host-chunked drivers replaying uniform ``n_run`` windows share one
    compiled program. ``sc`` holds per-scenario scalars/rows (leading S
    axis stripped). Returns ``(final_state, snapshots)``: with
    ``k_snap > 0`` the scan runs in k-tick chunks and stacks the full carry
    after each chunk (the checkpoint stream); otherwise snapshots is
    None."""
    j_max = sc.bid_table.shape[1]
    base = jax.random.fold_in(jax.random.PRNGKey(20), seed)
    assert_carry_dtypes(state0)

    def tick(state: SimState, k):
        m = _market_tick(sc, base, seed, state.t, state.j, state.bucket, k)

        # one model iteration; the update only lands when the iteration
        # actually ran — idle/finished ticks are true no-ops on every leaf
        stepped, metric = program.step_fn(
            state.model, data, m.k_grad, m.mask.astype(jnp.float32),
            state.j, sc.alpha)
        model = _gate_model(m.running, stepped, state.model)
        metric = jnp.asarray(metric).astype(jnp.float32)

        t_new = state.t + m.dt
        cost_new = state.total_cost + m.cost_inc
        idle_new = state.total_idle + m.idle_inc

        idx = jnp.minimum(state.j, j_max - 1)

        def put(traj, val):
            return traj.at[idx].set(jnp.where(m.running, val, traj[idx]))

        new = SimState(
            t=t_new, j=state.j + m.running.astype(jnp.int32),
            bucket=m.bucket,
            total_cost=cost_new, total_idle=idle_new, model=model,
            err_traj=put(state.err_traj, metric),
            cost_traj=put(state.cost_traj, cost_new),
            time_traj=put(state.time_traj, t_new),
            y_traj=put(state.y_traj, m.y))
        return new, None

    def run(state, ks):
        state, _ = lax.scan(tick, state, ks)
        return state

    ticks = tick0 + jnp.arange(n_run, dtype=jnp.int32)
    if k_snap and n_run >= k_snap:
        # chunked scan: the outer scan's per-step output is the whole carry
        # after each k_snap-tick chunk — every-k snapshots with no
        # per-tick memory cost; the remainder ticks run unsnapshotted
        n_chunks = n_run // k_snap
        head = ticks[:n_chunks * k_snap].reshape(n_chunks, k_snap)

        def chunk(state, ks):
            state = run(state, ks)
            return state, state

        final, snaps = lax.scan(chunk, state0, head)
        if n_run % k_snap:
            final = run(final, ticks[n_chunks * k_snap:])
        return final, snaps
    return run(state0, ticks), None


def _sim_blocked(batch: ScenarioBatch, state0: SimState, data, seeds,
                 tick0, program: ModelProgram, n_run: int, k_snap: int):
    """Megabatched scan for ``ModelProgram(blocked=True)``: the tick scan
    runs ONCE (outside any vmap); per tick the market logic is vmapped over
    the (S, R) grid — bit-identical RNG streams to `_sim_one`, via the
    shared `_market_tick` — and the blocked ``step_fn`` trains every
    replica in one call over (S, R)-leading leaves. The whole-model
    ``where`` gating pass is the step's own job (the fused update gates
    per element), which is the point: no per-replica small ops anywhere in
    the hot loop."""
    s_dim, r_dim = state0.t.shape
    j_max = batch.bid_table.shape[2]
    assert_carry_dtypes(state0)
    bases = jax.vmap(
        lambda s: jax.random.fold_in(jax.random.PRNGKey(20), s))(seeds)
    over_seeds = jax.vmap(_market_tick, in_axes=(None, 0, 0, 0, 0, 0, None))
    market_grid = jax.vmap(over_seeds, in_axes=(0, None, None, 0, 0, 0,
                                                None))
    alpha2 = jnp.broadcast_to(batch.alpha[:, None], (s_dim, r_dim))
    si = jnp.arange(s_dim)[:, None]
    ri = jnp.arange(r_dim)[None, :]

    def tick(state: SimState, k):
        m = market_grid(batch, bases, seeds, state.t, state.j,
                        state.bucket, k)
        # blocked steps gate on `running` internally (element-for-element
        # in the fused update) — no engine-side tree.map(where) pass
        model, metric = program.step_fn(
            state.model, data, m.k_grad, m.mask.astype(jnp.float32),
            state.j, alpha2, m.running)
        # blocked steps own the model gating, but the trajectory contract
        # is engine-owned either way: metrics land in f32 buffers
        metric = jnp.asarray(metric).astype(jnp.float32)

        t_new = state.t + m.dt
        cost_new = state.total_cost + m.cost_inc
        idle_new = state.total_idle + m.idle_inc

        idx = jnp.minimum(state.j, j_max - 1)

        def put(traj, val):
            return traj.at[si, ri, idx].set(
                jnp.where(m.running, val, traj[si, ri, idx]))

        new = SimState(
            t=t_new, j=state.j + m.running.astype(jnp.int32),
            bucket=m.bucket,
            total_cost=cost_new, total_idle=idle_new, model=model,
            err_traj=put(state.err_traj, metric),
            cost_traj=put(state.cost_traj, cost_new),
            time_traj=put(state.time_traj, t_new),
            y_traj=put(state.y_traj, m.y))
        return new, None

    def run(state, ks):
        state, _ = lax.scan(tick, state, ks)
        return state

    ticks = tick0 + jnp.arange(n_run, dtype=jnp.int32)
    if k_snap and n_run >= k_snap:
        n_chunks = n_run // k_snap
        head = ticks[:n_chunks * k_snap].reshape(n_chunks, k_snap)

        def chunk(state, ks):
            state = run(state, ks)
            return state, state

        final, snaps = lax.scan(chunk, state0, head)
        if n_run % k_snap:
            final = run(final, ticks[n_chunks * k_snap:])
        # scan stacks snapshots on axis 0; callers (snapshot_state) index
        # them at axis 2, the (S, R, n_snap, ...) layout of `_sim_one`
        snaps = jax.tree.map(lambda x: jnp.moveaxis(x, 0, 2), snaps)
        return final, snaps
    return run(state0, ticks), None


def _vmapped_sim(batch: ScenarioBatch, state0, data, seeds, tick0,
                 program: ModelProgram, n_run: int, k_snap: int):
    if program.blocked:
        return _sim_blocked(batch, state0, data, seeds, tick0, program,
                            n_run, k_snap)

    def one(sc, st, seed, t0):
        return _sim_one(sc, st, data, seed, program, n_run, k_snap, t0)

    over_seeds = jax.vmap(one, in_axes=(None, 0, 0, None))
    over_scenarios = jax.vmap(over_seeds, in_axes=(0, 0, None, None))
    return over_scenarios(batch, state0, seeds, tick0)


@functools.partial(jax.jit,
                   static_argnames=("program", "n_run", "k_snap"))
def _simulate_jit(batch, state0, data, seeds, tick0, program, n_run,
                  k_snap):
    return _vmapped_sim(batch, state0, data, seeds, tick0, program, n_run,
                        k_snap)


@functools.partial(jax.jit,
                   static_argnames=("program", "n_run", "k_snap"),
                   donate_argnames=("state0",))
def _simulate_jit_donated(batch, state0, data, seeds, tick0, program,
                          n_run, k_snap):
    # state0 leaves are materialized at the (S, R, ...) carry shapes
    # (`initial_state` broadcasts eagerly), so the donated buffers exactly
    # match the scan carry / final outputs and XLA reuses them in place
    return _vmapped_sim(batch, state0, data, seeds, tick0, program, n_run,
                        k_snap)


def simulate_program(scenarios, program: ModelProgram, model0, data, seeds,
                     cfg: SimConfig, donate: bool = False,
                     init_state: Optional[SimState] = None,
                     tick0: int = 0) -> EngineResult:
    """Run S scenarios × R seeds of an arbitrary ModelProgram in one
    compiled call.

    model0: initial model pytree, shared by every (scenario, seed) replica
    (``initial_state`` fans it out; ignored when ``init_state`` is given);
    data: device pytree visible to every step (problem constants / stacked
    batches); seeds: int count or explicit sequence. With ``donate=True``
    the initial-carry buffers are donated to the call (pass a fresh copy if
    you need them afterwards). The engine drops its own reference to
    ``model0`` once the carry is built, so a caller that passes a fresh
    model without keeping it never holds two copies on the device.

    Checkpointing: ``cfg.snapshot_every = k`` stacks the full scan carry
    every k ticks into ``EngineResult.snapshots`` (+ ``snapshot_ticks``);
    ``init_state``/``tick0`` resume a run from such a snapshot (same
    scenarios/seeds/cfg), continuing the per-tick RNG stream bit-exactly.

    Returns stacked (S, R, J_max) trajectories plus the per-replica final
    model (leaves shaped (S, R, ...), left on device).

    Reproducibility note: per-tick stochastic draws (runtime exponentials,
    preemption uniforms, minibatch indices) are shaped by the *batch-global*
    padded worker width ``n_max``, so a (scenario, seed) cell reproduces
    bit-exactly within the same stacked grid — checkpoint/resume included —
    but not across grids whose padding differs (stack with a wider scenario
    and the same seed consumes the key stream differently).
    """
    if not isinstance(scenarios, ScenarioBatch):
        scenarios = stack_scenarios(scenarios)
    if np.isscalar(seeds):
        seeds = np.arange(int(seeds))
    seeds = jnp.asarray(np.asarray(seeds, np.int32))
    tick0 = int(tick0)
    n_run = _check_run_window(cfg, tick0)
    if init_state is None:
        init_state = initial_state(scenarios, model0, len(seeds))
    del model0
    fn = _simulate_jit_donated if donate else _simulate_jit
    final, snaps = fn(scenarios, init_state, data, seeds,
                      jnp.asarray(tick0, jnp.int32), program, n_run,
                      cfg.snapshot_every)
    return _engine_result(final, snaps, scenarios, cfg, tick0, n_run)


def _check_run_window(cfg: SimConfig, tick0: int) -> int:
    """Validate the (tick0, n_ticks, snapshot_every) window; returns the
    number of ticks left to run."""
    if not 0 <= tick0 <= cfg.n_ticks:
        raise ValueError(f"tick0={tick0} outside [0, n_ticks={cfg.n_ticks}]")
    n_run = cfg.n_ticks - tick0
    if cfg.snapshot_every < 0:
        raise ValueError(f"snapshot_every={cfg.snapshot_every} must be ≥ 0")
    if cfg.snapshot_every and cfg.snapshot_every > n_run:
        # silently returning snapshots=None here would defeat the caller's
        # checkpointing intent — fail loudly instead
        raise ValueError(
            f"snapshot_every={cfg.snapshot_every} exceeds the remaining "
            f"tick budget ({n_run} ticks from tick0={tick0}): no snapshot "
            "would ever be emitted")
    return n_run


def _engine_result(final: SimState, snaps, scenarios: ScenarioBatch,
                   cfg: SimConfig, tick0: int, n_run: int) -> EngineResult:
    snap_ticks = None
    if snaps is not None:
        n_snap = n_run // cfg.snapshot_every
        snap_ticks = tick0 + cfg.snapshot_every * np.arange(1, n_snap + 1)
    return EngineResult(
        errors=np.asarray(final.err_traj),
        costs=np.asarray(final.cost_traj),
        times=np.asarray(final.time_traj),
        ys=np.asarray(final.y_traj),
        iterations=np.asarray(final.j),
        total_time=np.asarray(final.t),
        total_cost=np.asarray(final.total_cost),
        total_idle=np.asarray(final.total_idle),
        J=np.asarray(scenarios.J),
        final_state=final,
        snapshots=snaps,
        snapshot_ticks=snap_ticks)


# --------------------------------------------------------------------------
# Mesh execution: shard the (S, R) grid over devices
# --------------------------------------------------------------------------


def _pad_axis(x: jnp.ndarray, axis: int, target: int) -> jnp.ndarray:
    """Pad ``x`` along ``axis`` to length ``target`` by repeating the last
    slice (cells are independent, so duplicated rows never perturb real
    ones — they are sliced away after the run)."""
    n = x.shape[axis]
    if n == target:
        return x
    idx = jnp.full((target - n,), n - 1, jnp.int32)
    return jnp.concatenate([x, jnp.take(x, idx, axis=axis)], axis=axis)


def _padded_size(n: int, shards: int, platform: str) -> int:
    """Rows after padding ``n`` across ``shards`` devices: the smallest
    multiple of ``shards`` that is ≥ n — and, on the CPU, that gives every
    shard ≥ 2 rows.

    The CPU's ≥ 2 floor is its bit-exactness envelope: XLA:CPU compiles a
    size-1 vmap lane's dots/einsums with a different contraction order
    than the same cell inside a wider batch (observed ~1e-7 drift), while
    every width ≥ 2 reproduces the unsharded path bit-for-bit. On an
    accelerator the floor would double the models each chip holds, so it
    applies to the CPU only."""
    if shards <= 1:
        return n
    floor = 2 if platform == "cpu" else 1
    return shards * max(floor, -(-n // shards))


def _mesh_axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _grid_specs(mesh):
    """(scenario, grid, seed) PartitionSpecs for whichever of the
    ``data``/``replica`` axes the mesh actually has."""
    ds = "data" if "data" in mesh.axis_names else None
    rs = "replica" if "replica" in mesh.axis_names else None
    return PartitionSpec(ds), PartitionSpec(ds, rs), PartitionSpec(rs)


def _sharded_sim(batch, state0, data, seeds, tick0, mesh, program, n_run,
                 k_snap):
    sspec, gspec, seedspec = _grid_specs(mesh)

    def local(b, st, d, sd, t0):
        return _vmapped_sim(b, st, d, sd, t0, program, n_run, k_snap)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(sspec, gspec, PartitionSpec(), seedspec,
                  PartitionSpec()),
        out_specs=(gspec, gspec), check_vma=False)(
            batch, state0, data, seeds, tick0)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "program", "n_run", "k_snap"))
def _simulate_sharded_jit(batch, state0, data, seeds, tick0, mesh, program,
                          n_run, k_snap):
    return _sharded_sim(batch, state0, data, seeds, tick0, mesh, program,
                        n_run, k_snap)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "program", "n_run", "k_snap"),
                   donate_argnames=("state0",))
def _simulate_sharded_jit_donated(batch, state0, data, seeds, tick0, mesh,
                                  program, n_run, k_snap):
    return _sharded_sim(batch, state0, data, seeds, tick0, mesh, program,
                        n_run, k_snap)


def simulate_sharded(scenarios, program: ModelProgram, model0, data, seeds,
                     cfg: SimConfig, *, mesh=None, donate: bool = False,
                     init_state: Optional[SimState] = None,
                     tick0: int = 0) -> EngineResult:
    """`simulate_program` over a device mesh: the leading scenario axis of
    the stacked grid (``SimState`` carry, price traces, plan tables — every
    per-scenario row) is partitioned across the mesh's ``data`` axis, and
    the seed/replica axis across its ``replica`` axis when present, via
    ``shard_map``. Each device scans only its shard of the (S, R) grid;
    there is no cross-device communication inside the scan (cells are
    independent), so throughput scales with the mesh.

    Bit-exactness contract: per-cell RNG folds the seed *value* and the
    absolute tick index — never a device or shard position — so a sharded
    run is bit-identical to the single-device vmapped path, snapshots
    included. Non-divisible grids are handled by padding each sharded axis
    (repeating the last row) to a multiple of the axis size — with at
    least 2 rows per shard on the CPU (see `_padded_size` for why) — and
    slicing the padding back off the results. A fresh grid is built shard
    by shard on its own devices (`initial_state`'s ``sharding``).

    ``mesh``: a `jax.sharding.Mesh` whose sharded axes are named ``data``
    (scenarios) and/or ``replica`` (seeds) — `repro.launch.mesh` has
    constructors; defaults to a 1-D scenario mesh over every visible
    device. On a CPU host, force N virtual devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    initializes (the CI recipe; see scripts/ci.sh --devices).

    Checkpoints are mesh-portable: a snapshot from a sharded run restores
    through the same `train.checkpoint` path and can resume on a different
    mesh shape — or unsharded — bit-exactly.
    """
    if not isinstance(scenarios, ScenarioBatch):
        scenarios = stack_scenarios(scenarios)
    if np.isscalar(seeds):
        seeds = np.arange(int(seeds))
    seeds = jnp.asarray(np.asarray(seeds, np.int32))
    if mesh is None:
        from repro.launch.mesh import make_scenario_mesh
        mesh = make_scenario_mesh()
    bad = [a for a in mesh.axis_names if a not in ("data", "replica")]
    if bad:
        raise ValueError(
            f"mesh axes {bad} are not understood by the engine: the "
            "scenario grid shards over axes named 'data' (scenarios) "
            "and/or 'replica' (seeds) — build the mesh with "
            "repro.launch.mesh.make_scenario_mesh / "
            "make_scenario_replica_mesh")
    tick0 = int(tick0)
    n_run = _check_run_window(cfg, tick0)
    S, R = scenarios.n_scenarios, len(seeds)
    platform = mesh.devices.flat[0].platform
    s_pad = _padded_size(S, _mesh_axis_size(mesh, "data"), platform)
    r_pad = _padded_size(R, _mesh_axis_size(mesh, "replica"), platform)
    batch_p = (scenarios if s_pad == S else
               jax.tree.map(lambda x: _pad_axis(x, 0, s_pad), scenarios))
    seeds_p = _pad_axis(seeds, 0, r_pad)
    if init_state is None:
        state0 = initial_state(batch_p, model0, r_pad,
                               sharding=NamedSharding(mesh,
                                                      _grid_specs(mesh)[1]))
    else:
        state0 = jax.tree.map(
            lambda x: _pad_axis(_pad_axis(x, 0, s_pad), 1, r_pad),
            init_state)
    del model0
    fn = _simulate_sharded_jit_donated if donate else _simulate_sharded_jit
    final, snaps = fn(batch_p, state0, data, seeds_p,
                      jnp.asarray(tick0, jnp.int32), mesh, program, n_run,
                      cfg.snapshot_every)
    if (s_pad, r_pad) != (S, R):
        final = jax.tree.map(lambda x: x[:S, :R], final)
        if snaps is not None:
            snaps = jax.tree.map(lambda x: x[:S, :R], snaps)
    return _engine_result(final, snaps, scenarios, cfg, tick0, n_run)


def snapshot_state(result: EngineResult, index: int = -1):
    """Select one snapshot from a snapshotting run as a batched ``SimState``
    (leaves (S, R, ...)) plus its absolute tick count — the pair
    `train.checkpoint.save` persists and ``simulate_program(init_state=...,
    tick0=...)`` resumes from."""
    if result.snapshots is None:
        raise ValueError("run had no snapshots: set SimConfig.snapshot_every")
    tick = int(result.snapshot_ticks[index])
    state = jax.tree.map(lambda x: x[:, :, index], result.snapshots)
    return state, tick


def simulate(scenarios, quad, w0, seeds, cfg: SimConfig) -> EngineResult:
    """Run S scenarios × R seeds on the quadratic oracle in one compiled
    call (the original engine entry point; `simulate_program` is the
    general form).

    scenarios: ScenarioBatch or list[Scenario]; quad: QuadraticProblem or
    JaxQuadratic; seeds: int count or explicit sequence.
    Returns stacked (S, R, J_max) trajectories.
    """
    if not isinstance(quad, JaxQuadratic):
        quad = jax_quadratic(quad)
    return simulate_program(
        scenarios, quadratic_program(cfg.grad, cfg.batch),
        jnp.asarray(w0, jnp.float32), quad, seeds, cfg)


# --------------------------------------------------------------------------
# Strategy → Scenario builders
# --------------------------------------------------------------------------


def scenario_from_strategy(strategy, *, alpha: float, rt,
                           dist=None, q: Optional[float] = None,
                           on_demand_price: float = 1.0,
                           n_max: Optional[int] = None,
                           idle_step: Optional[float] = None,
                           J: Optional[int] = None,
                           price_spec: Optional[PriceSpec] = None,
                           name: str = "") -> Scenario:
    """Compile a core.strategies.Strategy into a batchable Scenario.

    Spot strategies (``bids``) become a precomputed plan table against the
    price distribution ``dist`` (or an explicit ``price_spec``, e.g. a
    time-indexed trace replay) — time-adaptive strategies (``DynamicBids``)
    resolve to one bid schedule per coarse elapsed-time bucket, latched by
    the engine at replan time; provisioning strategies (``workers``) become
    a worker schedule under exogenous preemption probability ``q``.
    """
    J = J or strategy.total_iterations
    name = name or getattr(strategy, "name", "")
    if q is None:
        table = strategy.plan_table(J, n_max=n_max)
        if idle_step is None:
            idle_step = rt.expected(max(table.bids.shape[2], 1))
        return Scenario.from_runtime(
            rt, price=price_spec or PriceSpec.from_dist(dist), alpha=alpha,
            bid_table=table.bids, bucket_starts=table.starts,
            replan_at=table.replan_at, idle_step=idle_step, name=name)
    wsched = strategy.worker_schedule(J)
    if n_max is not None:
        # match the legacy loop: provisioning never exceeds the fleet, and
        # the active mask is padded to the full fleet width (so e.g. the
        # elastic trainer's worker slices all get a mask entry)
        wsched = np.minimum(wsched, n_max)
    return Scenario.from_runtime(
        rt, price=PriceSpec.uniform(0.0, 1.0), alpha=alpha,
        worker_schedule=wsched, preempt_q=q, n_fleet=n_max,
        on_demand_price=on_demand_price,
        idle_step=idle_step if idle_step is not None else rt.expected(1),
        name=name)
