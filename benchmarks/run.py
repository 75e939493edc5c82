"""Benchmark harness — one function per paper table/figure, plus the
engine, trainer, service and supervisor paths. Prints
``name,us_per_call,derived`` CSV rows.

  fig3  — strategies under synthetic i.i.d. prices (uniform & Gaussian):
          cost to reach the target error, mean ± 95% CI over 8 seeds on the
          batched engine (paper Fig. 3).
  fig4  — strategies under the non-i.i.d. synthetic historical trace
          (paper Fig. 4; cost reduction % vs No-interruptions), 8 seeds.
  fig5a — Theorem-4 worker count vs naive choices (accuracy per dollar).
  fig5b — Theorem-5 dynamic workers vs static (accuracy per dollar).
  scenarios — vectorized engine vs legacy per-scenario loop throughput on a
          64-scenario fig3-style grid (scenarios/sec, speedup).
  trainer — scan-native trainer (train_batched: real reduced transformer
          inside the engine jit) vs the legacy per-strategy ElasticTrainer
          Python loop on an 8-strategy × 8-seed grid.
  sharded — engine ticks/sec under `simulate_sharded` over the first
          1/2/4/8 visible devices (cell-ticks/sec + speedup vs 1 device).
  serve — rolling-horizon bidding service (service.server) over the
          first 1/2/4 visible devices: replan latency p50/p95,
          decisions/sec, and per-job regret vs hindsight / best static
          paper plan.
  multibid — K=1..5 bid levels (core.multibid.optimize_multibid) on the
          engine: expected vs simulated cost curve (beyond-paper §VII).
  zoo  — the model zoo under preemption (trainer.train_zoo): tokens/sec
          for a small real reduced-qwen2 config under elastic masking,
          cost-vs-loss frontier across fixed-bid levels, and the bf16
          mixed-precision carry.
  chaos — recovery overhead of the self-healing supervisor: the same
          durable run unfailed vs under a seeded kill+corrupt fault plan
          (restarts, ticks lost, MTTR, wall overhead %).
          Its workers need the device, so it runs alone (--only chaos).

Run: PYTHONPATH=src python -m benchmarks.run [--only fig3,fig4] [--smoke]

--smoke shrinks every benchmark to a ~2-tick / 2-seed configuration so CI
can exercise all perf paths end-to-end in seconds (scripts/ci.sh
--smoke-bench); the numbers are meaningless, the code paths are real.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

ROWS = []
#: structured mirror of ROWS for --json output
RESULTS = []

#: --smoke: run each benchmark with a trivial tick/seed budget (CI mode).
SMOKE = False


def emit(name: str, us_per_call: float, derived: str):
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    RESULTS.append({"name": name, "us_per_call": round(us_per_call, 1),
                    "derived": derived})
    print(row, flush=True)


# --------------------------------------------------------------------------
# shared setup for the strategy benchmarks
# --------------------------------------------------------------------------


def _problem():
    from repro.sim.evaluate import calibrated_quadratic

    quad, w0, prob, _batch = calibrated_quadratic()
    return quad, w0, prob


def _strategies(prob, eps, theta, n, dist, rt):
    from repro.core import strategies as strat

    out = {
        "no-interruptions": strat.no_interruptions(prob, eps, n, dist, rt),
        "optimal-one-bid": strat.optimal_one_bid(prob, eps, theta, n, dist,
                                                 rt),
        "optimal-two-bids": strat.optimal_two_bids(prob, eps, theta, n, dist,
                                                   rt, n1=n // 2),
        "dynamic-bids": strat.DynamicBids(
            prob, eps, theta, dist, rt, stage1=(n // 4, n // 2),
            stage2=(n // 2, n), switch_at=2),
    }
    dyn = out["dynamic-bids"]
    dyn.switch_at = max(2, int(0.4 * dyn.total_iterations))
    return out


def _calibration(dist):
    """Shared fig3/fig4 planning calibration (ε above the Theorem-1 noise
    floor, 3×-slack deadline). Returns (quad, w0, prob, rt, strategies,
    eps_emp, n)."""
    from repro.core import convergence as conv
    from repro.core.cost_model import RuntimeModel

    quad, w0, prob = _problem()
    rt = RuntimeModel(kind="exp", lam=2.0, delta=0.05)
    n = 8
    # plan against the Theorem-1 bound: ε must sit above the noise floor
    # κ(n) = B/(1−β)/n even for the smallest intermediate fleet (n/4)
    floor = prob.B / (1 - prob.beta)
    eps = 5.0 * floor / n
    j_min = conv.phi_inverse(prob, eps, 1.0 / n)
    theta = 3.0 * j_min * rt.expected(n)
    strategies = _strategies(prob, eps, theta, n, dist, rt)
    # the bound is conservative: measure cost at an *empirical* error level
    # every strategy reaches (the paper measures accuracy targets likewise)
    return quad, w0, prob, rt, strategies, eps / 4, n


N_SEEDS = 8          # per-point seeds for the mean ± 95%-CI summaries


def _seeds() -> int:
    return 2 if SMOKE else N_SEEDS


def _ticks(full):
    """Tick budget: the real one (None = the engine default), or 2 in
    --smoke mode (the scan still compiles and runs — completion is not
    expected)."""
    return 2 if SMOKE else full


def _nanmean(x, axis=None):
    """Warning-silenced nan-stats (all-NaN slices are routine in --smoke
    mode, where nothing completes in 2 ticks)."""
    from repro.sim.evaluate import nanmean

    return nanmean(x, axis=axis)


def _nanstd(x, axis=None):
    from repro.sim.evaluate import nanstd

    return nanstd(x, axis=axis)


def _timed(fn):
    """(result, µs) of the *second* call — the first pays jit compilation,
    so the reported wall time is steady-state engine throughput."""
    fn()
    t0 = time.time()
    out = fn()
    return out, (time.time() - t0) * 1e6


def _timed_best(fn, n: int = 5):
    """(result, µs) best-of-n after a compile warmup — for sub-10ms calls,
    where a single sample is at the mercy of scheduler noise."""
    out = fn()
    best = float("inf")
    for _ in range(1 if SMOKE else n):
        t0 = time.perf_counter()
        fn()
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return out, best


def _emit_spot_grid(tag, bres, strategies, eps_emp, wall_us_per_scenario):
    """Per-strategy rows (cost-to-error mean ± CI over seeds) plus the
    vs-dynamic / vs-no-interruptions comparisons on the means."""
    results = {}
    for name, s in strategies.items():
        label = f"{name}@{tag}"
        run = bres.run(label)
        cost, ci, per_seed = bres.cost_to_error(label, eps_emp)
        if not np.isfinite(cost):   # never reached: report full mean cost
            cost, ci = run.summary["cost_mean"], run.summary["cost_ci"]
        results[name] = cost
        emit(f"{tag}_{name}", wall_us_per_scenario,
             f"J={s.total_iterations};seeds={bres.n_seeds};"
             f"cost_to_emp={cost:.2f};cost_to_emp_ci={ci:.2f};"
             f"time_total={run.summary['time_mean']:.1f}"
             f"±{run.summary['time_ci']:.1f};"
             f"final_err={run.summary['final_err_mean']:.4f}"
             f"±{run.summary['final_err_ci']:.4f}")
    ref = results.get("dynamic-bids") or min(results.values())
    for name, cost in results.items():
        if name != "dynamic-bids" and np.isfinite(cost) and ref > 0:
            emit(f"{tag}_{name}_vs_dynamic", 0.0,
                 f"extra_cost_pct={(cost / ref - 1) * 100:.1f}")
    no_int = results.get("no-interruptions")
    for name, cost in results.items():
        if name != "no-interruptions" and no_int:
            emit(f"{tag}_{name}_vs_nointerrupt", 0.0,
                 f"cost_saving_pct={(1 - cost / no_int) * 100:.1f}")


def bench_fig3():
    """Strategies × synthetic i.i.d. price dists, one jitted engine call per
    distribution, N_SEEDS seeds per point."""
    from repro.core.cost_model import TruncGaussianPrice, UniformPrice
    from repro.sim import engine
    from repro.sim.evaluate import evaluate_batch

    for tag, dist in [("fig3_uniform", UniformPrice(0.2, 1.0)),
                      ("fig3_gaussian",
                       TruncGaussianPrice(0.6, 0.175, 0.2, 1.0))]:
        quad, w0, prob, rt, strategies, eps_emp, n = _calibration(dist)
        # scenarios built once, outside the timed closure — the timed call
        # measures engine throughput, not host-side bid (re-)planning
        scenarios = [engine.scenario_from_strategy(
            s, alpha=prob.alpha, rt=rt, dist=dist, n_max=n,
            name=f"{name}@{tag}") for name, s in strategies.items()]
        bres, us = _timed(lambda: evaluate_batch(
            strategies, scenarios, _seeds(), quad=quad, w0=w0,
            alpha=prob.alpha, rt=rt, batch=16, n_ticks=_ticks(None)))
        _emit_spot_grid(tag, bres, strategies, eps_emp,
                        us / bres.n_scenarios)


def bench_fig4():
    """Strategies under the non-i.i.d. synthetic historical trace: planning
    sees the empirical F̂, the market replays the raw trace *time-indexed*
    (the wall clock selects the 5-minute-resolution entry, exactly as the
    legacy `TracePrices` loop does — correct under the stochastic `exp`
    iteration durations used here; per-seed index offsets stand in for
    np.roll)."""
    from repro.sim import engine
    from repro.sim.evaluate import evaluate_batch
    from repro.sim.spot_market import TracePrices, synthetic_history

    trace = synthetic_history(hours=24 * 30, seed=0)
    dist = TracePrices(trace, step=0.05).empirical_dist()
    quad, w0, prob, rt, strategies, eps_emp, n = _calibration(dist)
    tag = "fig4_trace"
    spec = engine.PriceSpec.from_trace(trace, step=0.05)
    scenarios = [engine.scenario_from_strategy(
        s, alpha=prob.alpha, rt=rt, n_max=n, price_spec=spec,
        name=f"{name}@{tag}") for name, s in strategies.items()]
    bres, us = _timed(lambda: evaluate_batch(
        strategies, scenarios, _seeds(), quad=quad, w0=w0, alpha=prob.alpha,
        rt=rt, batch=16, n_ticks=_ticks(None)))
    _emit_spot_grid(tag, bres, strategies, eps_emp, us / bres.n_scenarios)


def _problem5():
    """Fig-5 variant: label noise keeps gradient noise alive at the optimum
    so the empirical error floor is worker-count-dependent (as for the
    paper's CIFAR models); per-worker minibatch = 1."""
    from repro.sim.evaluate import calibrated_quadratic

    quad, w0, prob, _batch = calibrated_quadratic(label_noise=1.0)
    return quad, w0, prob


def bench_fig5a():
    from repro.core import provisioning as prov
    from repro.core import strategies as strat
    from repro.core.cost_model import RuntimeModel
    from repro.sim.evaluate import evaluate_batch

    quad, w0, prob = _problem5()
    rt = RuntimeModel(kind="det", r_const=1.0)
    eps, q = 0.5, 0.5
    plan = prov.optimal_n_and_j(prob, eps, 2000, d=1.0 / (1 - q))
    choices = {
        "theorem4": strat.StaticWorkers(plan),
        "half-n": strat.StaticWorkers(prov.ProvisionPlan(
            n=max(1, plan.n // 2), J=plan.J, expected_error=0,
            cost_proxy=0)),
        "double-n": strat.StaticWorkers(prov.ProvisionPlan(
            n=plan.n * 2, J=plan.J, expected_error=0, cost_proxy=0)),
    }
    # measure cost to an empirical error between the n and n/2 floors
    eps_emp = 0.02
    bres, us = _timed(lambda: evaluate_batch(
        choices, {"q": None}, _seeds(), quad=quad, w0=w0, alpha=prob.alpha,
        rt=rt, q=q, on_demand_price=0.5, batch=1, idle_step=0.1,
        n_ticks=_ticks(None)))
    wall = us / bres.n_scenarios
    for name, s in choices.items():
        run = bres.run(f"{name}@q")
        cost, ci, _ = bres.cost_to_error(f"{name}@q", eps_emp)
        emit(f"fig5a_{name}", wall,
             f"n={s.workers(0)};J={s.total_iterations};seeds={bres.n_seeds};"
             f"final_err={run.summary['final_err_mean']:.4f}"
             f"±{run.summary['final_err_ci']:.4f};"
             f"cost_to_emp={f'{cost:.1f}±{ci:.1f}' if np.isfinite(cost) else 'never'};"
             f"cost_total={run.summary['cost_mean']:.1f}")


def bench_fig5b():
    from repro.core import convergence as conv
    from repro.core import strategies as strat
    from repro.core.cost_model import RuntimeModel
    from repro.sim.evaluate import evaluate_batch

    quad, w0, prob = _problem5()
    rt = RuntimeModel(kind="det", r_const=1.0)
    q = 0.5
    # the paper's protocol (Fig. 5b): tiny η, Theorem-5-shortened horizon;
    # total instance-iterations (≈ cost) match the static baseline
    J_static, n0, eta = 3000, 1, 1.002
    Jp = conv.dynamic_iterations(J_static, eta, chi=1.0)
    runs = {
        "static_n1": strat.DynamicWorkers(n0=1, eta=1.0, J=J_static),
        "dynamic_eta": strat.DynamicWorkers(n0=n0, eta=eta, J=Jp),
    }
    bres, us = _timed(lambda: evaluate_batch(
        runs, {"q": None}, _seeds(), quad=quad, w0=w0, alpha=prob.alpha,
        rt=rt, q=q, on_demand_price=0.5, batch=1, idle_step=0.1,
        n_ticks=_ticks(None)))
    wall = us / bres.n_scenarios
    for name, s in runs.items():
        run = bres.run(f"{name}@q")
        i = bres.index(f"{name}@q")
        J_s = int(bres.result.J[i])
        # per-seed tail error; NaN-safe end to end so an incomplete seed is
        # dropped rather than poisoning the row
        errs = _nanmean(bres.result.errors[i, :, max(J_s - 20, 0):J_s],
                        axis=-1)
        n_ok = max(int(np.sum(~np.isnan(errs))), 1)
        err, err_ci = float(_nanmean(errs)), float(
            1.96 * _nanstd(errs) / np.sqrt(n_ok))
        err = max(err, 1e-9)
        cost = run.summary["cost_mean"]
        acc_per_dollar = (1.0 / err) / max(cost, 1e-9)
        emit(f"fig5b_{name}", wall,
             f"J={s.total_iterations};seeds={bres.n_seeds};"
             f"final_err={err:.4f}±{err_ci:.4f};cost={cost:.1f};"
             f"inv_err_per_dollar={acc_per_dollar:.4f}")


def bench_scenarios():
    """Engine vs legacy-loop throughput on a 64-scenario fig3-style grid
    (16 bid levels × 2 price dists × 2 fleet sizes, exact gradient so both
    paths do identical math). Reports scenarios/sec and the speedup."""
    from repro.core import bidding, strategies as strat
    from repro.core.cost_model import (RuntimeModel, TruncGaussianPrice,
                                       UniformPrice)
    from repro.data.synthetic import QuadraticProblem
    from repro.sim import engine
    from repro.sim.evaluate import run_spot_strategy
    from repro.sim.spot_market import IIDPrices, SpotMarket

    quad = QuadraticProblem(dim=10, n_samples=256, cond=8.0, noise=0.3,
                            seed=0)
    w0 = quad.w_star + 2.0 * np.ones(quad.dim) / np.sqrt(quad.dim)
    alpha = 0.5 / quad.L
    rt = RuntimeModel(kind="exp", lam=2.0, delta=0.05)
    J = 2 if SMOKE else 60
    dists = [UniformPrice(0.2, 1.0), TruncGaussianPrice(0.6, 0.175, 0.2,
                                                        1.0)]
    levels = np.linspace(0.45, 1.0, 2 if SMOKE else 16)
    grid = [(b, dist, n) for b in levels for dist in dists for n in (2, 4)]

    def fixed(b, n):
        return strat.FixedBids(bidding.BidPlan(
            n=n, n1=n, b1=float(b), b2=float(b), J=J, expected_cost=0,
            expected_time=0, expected_error=0))

    scenarios = [engine.scenario_from_strategy(
        fixed(b, n), alpha=alpha, rt=rt, dist=dist, n_max=4,
        name=f"b{b:.2f}_n{n}") for b, dist, n in grid]
    # tick budget covers the lowest-F(b) gaussian cell (F≈0.18 → ~6J ticks)
    cfg = engine.SimConfig(n_ticks=8 * J, grad="full")

    # engine: warm-up compiles, second call measures steady-state
    engine.simulate(scenarios, quad, w0, 1, cfg)
    t0 = time.time()
    res = engine.simulate(scenarios, quad, w0, 1, cfg)
    dt_engine = time.time() - t0
    eng_rate = len(grid) / dt_engine

    t0 = time.time()
    for i, (b, dist, n) in enumerate(grid):
        run_spot_strategy(quad, w0, alpha, fixed(b, n),
                          SpotMarket(IIDPrices(dist, seed=i)), rt,
                          grad="full", seed=i)
    dt_legacy = time.time() - t0
    leg_rate = len(grid) / dt_legacy

    emit("scenarios_engine", dt_engine * 1e6 / len(grid),
         f"scenarios={len(grid)};scenarios_per_sec={eng_rate:.1f};"
         f"completed={float(res.completed.mean()):.2f}")
    emit("scenarios_legacy", dt_legacy * 1e6 / len(grid),
         f"scenarios={len(grid)};scenarios_per_sec={leg_rate:.1f}")
    emit("scenarios_speedup", 0.0,
         f"engine_vs_legacy={eng_rate / leg_rate:.1f}x")


def _trainer_setup():
    """Shared grid for the trainer benchmark: a reduced transformer (1
    layer, d=16 — small enough that the legacy loop's per-step host
    overhead is the dominant cost, exactly the regime the scan removes)
    under 8 bid levels × 8 seeds."""
    from repro.configs import ARCHS
    from repro.configs.base import InputShape, JobConfig
    from repro.core import bidding, strategies as strat
    from repro.core.cost_model import RuntimeModel, UniformPrice
    from repro.sim import engine

    J = 4 if SMOKE else 30
    n_w = 4
    cfg = ARCHS["qwen2-7b"].reduced().with_(
        num_layers=1, d_model=16, num_heads=2, num_kv_heads=1, d_ff=32,
        vocab_size=64, head_dim=8)
    job = JobConfig(model=cfg, shape=InputShape("t", 8, 4, "train"),
                    n_workers=n_w, learning_rate=0.1)
    dist = UniformPrice(0.2, 1.0)
    rt = RuntimeModel(kind="exp", lam=2.0, delta=0.05)
    levels = np.linspace(0.75, 1.0, 2 if SMOKE else 8)

    def fixed(b):
        return strat.FixedBids(bidding.BidPlan(
            n=n_w, n1=n_w, b1=float(b), b2=float(b), J=J, expected_cost=0,
            expected_time=0, expected_error=0), name=f"b{b:.2f}")

    strategies = [fixed(b) for b in levels]
    scenarios = [engine.scenario_from_strategy(
        s, alpha=job.learning_rate, rt=rt, dist=dist, n_max=n_w,
        name=s.name) for s in strategies]
    return job, strategies, scenarios, dist, rt, J, n_w


def bench_trainer():
    """Scan-native trainer vs the legacy per-strategy ElasticTrainer loop:
    an 8-strategy × 8-seed grid trains a reduced transformer end to end
    under identical market/runtime models.

    Three rows: the batched engine path (one jit, donated buffers, no host
    sync inside the scan); the legacy Python loop with this PR's lru-cached
    train step (best-case loop); and the loop as seeded — one fresh
    ``jax.jit(make_train_step(...))`` per trainer instance, i.e. a
    recompile per grid cell, which is what a pre-batched-trainer grid sweep
    actually paid (measured on 2 cells, extrapolated)."""
    import jax

    from repro.sim.cluster import VolatileCluster
    from repro.sim.spot_market import IIDPrices, SpotMarket
    from repro.train.trainer import ElasticTrainer, train_batched
    from repro.train.train_step import make_train_step

    job, strategies, scenarios, dist, rt, J, n_w = _trainer_setup()
    n_seeds = _seeds()
    cells = len(strategies) * n_seeds
    n_ticks = _ticks(int(1.6 * J) + 6)

    bres, us_batched = _timed(lambda: train_batched(
        job, scenarios, seeds=n_seeds, n_ticks=n_ticks))
    final_losses = bres.losses[..., -1]
    emit("trainer_batched", us_batched / cells,
         f"grid={len(strategies)}x{n_seeds};J={J};n_ticks={n_ticks};"
         f"completed={float(bres.completed.mean()):.2f};"
         f"final_loss={_nanmean(final_losses):.3f}")

    # scan-native checkpointing overhead: same grid, full-carry snapshots
    # every quarter of the tick budget (the preemption-safe configuration)
    snap_k = max(n_ticks // 4, 1)
    bres_snap, us_snap = _timed(lambda: train_batched(
        job, scenarios, seeds=n_seeds, n_ticks=n_ticks,
        snapshot_every=snap_k))
    emit("trainer_batched_snapshots", us_snap / cells,
         f"snapshot_every={snap_k};"
         f"n_snapshots={len(bres_snap.snapshot_ticks)};"
         f"overhead_vs_plain_pct={(us_snap / us_batched - 1) * 100:.1f}")

    # megabatched layout (train.megabatch): the replica axis folded into
    # blocked flat params + a widened batch dim, hand-written backward,
    # Eq.-(5) renormalization fused into the update. Market trajectories
    # are bit-exact with the vmapped path (tests/test_megabatch.py).
    mres, us_mega = _timed(lambda: train_batched(
        job, scenarios, seeds=n_seeds, n_ticks=n_ticks, megabatch=True))
    emit("trainer_megabatch", us_mega / cells,
         f"speedup_vs_vmapped={us_batched / us_mega:.2f}x;"
         f"final_loss={_nanmean(mres.losses[..., -1]):.3f}")
    _, us_fused = _timed(lambda: train_batched(
        job, scenarios, seeds=n_seeds, n_ticks=n_ticks, megabatch=True,
        use_fused_update=True))
    emit("trainer_megabatch_fused", us_fused / cells,
         f"speedup_vs_vmapped={us_batched / us_fused:.2f}x")

    # step-level: one R-replica elastic update isolated from the engine
    # (no market draws / trajectory writes) — the apples-to-apples view of
    # the layout change itself
    import jax.numpy as jnp

    from repro.train import megabatch as mb
    from repro.train.train_step import init_train_state

    r_step = cells
    b_sz, s_len = job.shape.global_batch, job.shape.seq_len
    params, opt = init_train_state(job.model, job, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(
        rng.integers(0, job.model.vocab_size, (r_step, b_sz, s_len)),
        jnp.int32)
    labels = jnp.asarray(
        rng.integers(0, job.model.vocab_size, (r_step, b_sz, s_len)),
        jnp.int32)
    masks = jnp.asarray(rng.integers(0, 2, (r_step, n_w)), jnp.float32)
    jj = jnp.zeros((r_step,), jnp.int32)
    run_flags = jnp.ones((r_step,), bool)

    vstep_inner = make_train_step(job.model, job, remat="none")

    def vcell(p, o, tok, lab, m, j):
        np_, no, met = vstep_inner(p, o, {"tokens": tok, "labels": lab},
                                   m, j)
        return np_, no, met["loss"]

    tile = lambda x: jnp.tile(x[None], (r_step,) + (1,) * x.ndim)
    p_r, o_r = jax.tree.map(tile, params), jax.tree.map(tile, opt)
    vmapped_step = jax.jit(jax.vmap(vcell))
    _, us_vstep = _timed_best(lambda: jax.block_until_ready(
        vmapped_step(p_r, o_r, tokens, labels, masks, jj)))

    flat0 = mb.pack_state(params, opt, job.model, job.momentum)
    flat = jax.tree.map(tile, flat0)
    mstep = jax.jit(mb.make_megabatch_step(job.model, job))
    _, us_mstep = _timed_best(lambda: jax.block_until_ready(
        mstep(flat, tokens, labels, masks, jj, run_flags)))
    mfstep = jax.jit(
        mb.make_megabatch_step(job.model, job, use_fused_update=True))
    _, us_mfstep = _timed_best(lambda: jax.block_until_ready(
        mfstep(flat, tokens, labels, masks, jj, run_flags)))
    emit("trainer_vmapped_step", us_vstep,
         f"R={r_step};B={b_sz};S={s_len}")
    emit("trainer_megabatch_step", us_mstep, f"R={r_step};layout=flat")
    emit("trainer_megabatch_step_fused", us_mfstep,
         f"R={r_step};update=kernels.fused_elastic_update")
    emit("trainer_step_speedup", 0.0,
         f"megabatch_vs_vmapped={us_vstep / us_mstep:.2f}x;"
         f"fused_vs_vmapped={us_vstep / us_mfstep:.2f}x")

    def legacy_cell(strategy, seed, step_override=None):
        cluster = VolatileCluster(
            n_workers=n_w, runtime=rt, idle_step=rt.expected(n_w),
            market=SpotMarket(IIDPrices(dist, seed=seed)), seed=seed)
        tr = ElasticTrainer(job=job, cluster=cluster, strategy=strategy,
                            mode="spot", seed=0)
        if step_override is not None:
            tr._step_fn = step_override
        return tr.run(iterations=J)

    legacy_cell(strategies[0], 0)        # warm the shared cached step
    t0 = time.time()
    last = None
    for s in strategies:
        for seed in range(n_seeds):
            last = legacy_cell(s, seed)
    dt_cached = time.time() - t0
    emit("trainer_legacy_cached", dt_cached * 1e6 / cells,
         f"cells={cells};J={J};final_loss={last['final_loss']:.3f}")

    # as-seeded behavior: a fresh jit per trainer instance → one compile
    # per grid cell (2 cells measured, wall extrapolated to the grid)
    probe = 1 if SMOKE else 2
    t0 = time.time()
    for i in range(probe):
        step = jax.jit(make_train_step(job.model, job, remat="none"))
        legacy_cell(strategies[i % len(strategies)], i, step_override=step)
    per_cell_seed = (time.time() - t0) / probe
    dt_seed = per_cell_seed * cells
    emit("trainer_legacy_percell_jit", per_cell_seed * 1e6,
         f"measured_cells={probe};extrapolated_grid_s={dt_seed:.1f}")

    dt_batched = us_batched / 1e6
    emit("trainer_speedup", 0.0,
         f"batched_vs_legacy_loop={dt_seed / dt_batched:.1f}x;"
         f"batched_vs_cached_loop={dt_cached / dt_batched:.1f}x")


def bench_multibid():
    """BEYOND-PAPER multibid cost curve on the engine: K=1..5 optimized bid
    levels for the same n=8 fleet, deadline and ε-target — expected cost
    from the §VII-generalized model vs simulated cost (mean ± CI over
    seeds) from the batched engine."""
    from repro.core import convergence as conv, multibid
    from repro.core import strategies as strat
    from repro.core.cost_model import RuntimeModel, UniformPrice
    from repro.sim.evaluate import calibrated_quadratic, evaluate_batch

    quad, w0, prob, _batch = calibrated_quadratic()
    rt = RuntimeModel(kind="exp", lam=2.0, delta=0.05)
    dist = UniformPrice(0.2, 1.0)
    n = 8
    floor = prob.B / (1 - prob.beta)
    eps = 5.0 * floor / n
    j_min = conv.phi_inverse(prob, eps, 1.0 / n)
    J = j_min + 10
    theta = 3.0 * j_min * rt.expected(n)
    # nested splits (each refines the previous) so a larger K can always
    # represent the smaller-K optimum — the cost curve is monotone up to
    # optimizer/seed noise
    groups = {1: (8,), 2: (4, 4), 3: (4, 2, 2), 4: (4, 2, 1, 1),
              5: (4, 1, 1, 1, 1)}
    sweeps = 4 if SMOKE else 60
    plans = {k: multibid.optimize_multibid(prob, eps, theta, g, J, dist, rt,
                                           sweeps=sweeps)
             for k, g in groups.items()}
    strategies = {f"K{k}": strat.FixedBids(p, name=f"K{k}")
                  for k, p in plans.items()}
    f_min = min(dist.cdf(p.bid_levels[0]) for p in plans.values())
    bres, us = _timed(lambda: evaluate_batch(
        strategies, {"multibid": dist}, _seeds(), quad=quad, w0=w0,
        alpha=prob.alpha, rt=rt, batch=16, n_max=n,
        n_ticks=_ticks(int(3 * J / f_min) + 64)))
    costs = {}
    for k, plan in plans.items():
        run = bres.run(f"K{k}@multibid")
        costs[k] = run.summary["cost_mean"]
        emit(f"multibid_K{k}", us / bres.n_scenarios,
             f"groups={groups[k]};J={plan.J};seeds={bres.n_seeds};"
             f"expected_cost={plan.expected_cost:.2f};"
             f"sim_cost={run.summary['cost_mean']:.2f}"
             f"±{run.summary['cost_ci']:.2f};"
             f"completed={run.summary['completed']:.2f};"
             f"bids={','.join(f'{b:.3f}' for b in plan.bid_levels)}")
    base = costs[1]
    if np.isfinite(base) and base > 0:
        curve = ";".join(
            f"K{k}_saving_pct={(1 - c / base) * 100:.1f}"
            for k, c in costs.items() if k > 1)
        emit("multibid_curve", 0.0, curve)


# --------------------------------------------------------------------------
# sharded engine scaling across virtual devices
# --------------------------------------------------------------------------

def _device_counts(wanted):
    """The device counts of ``wanted`` this process can build a mesh
    over."""
    import jax

    return [n for n in wanted if n <= jax.device_count()]


def bench_sharded():
    """Engine throughput under `simulate_sharded` over meshes of the
    first 1/2/4/8 visible devices, in this process. Derived column reports
    cell-ticks/sec (S × R × n_ticks / wall) and the speedup over the
    1-device mesh. On a CPU host, force devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before the
    benchmark starts; counts beyond the visible devices are not run."""
    import jax

    from repro.data.synthetic import QuadraticProblem
    from repro.launch.mesh import make_scenario_mesh
    from repro.sim import engine

    S, R, n_ticks = (8, 2, 8) if SMOKE else (64, 8, 200)
    quad = QuadraticProblem(dim=16, n_samples=256, cond=5.0, noise=0.2,
                            seed=0)
    w0 = np.asarray(quad.w_star + 1.0, np.float32)
    scenarios = [engine.Scenario(
        price=engine.PriceSpec.uniform(0.2, 1.0), alpha=0.4 / quad.L,
        bid_schedule=np.tile([b, b, b, b], (max(2, n_ticks // 2), 1)),
        rt_kind="exp", rt_lam=2.0, idle_step=0.5, name=f"s{i}")
        for i, b in enumerate(np.linspace(0.4, 1.0, S))]
    batch = engine.stack_scenarios(scenarios)
    program = engine.quadratic_program("minibatch", 8)
    data = engine.jax_quadratic(quad)
    cfg = engine.SimConfig(n_ticks=n_ticks, batch=8)
    base_us = None
    for n_dev in _device_counts([1, 2] if SMOKE else [1, 2, 4, 8]):
        mesh = make_scenario_mesh(n_dev)

        def run():
            res = engine.simulate_sharded(batch, program, w0, data, R, cfg,
                                          mesh=mesh)
            jax.block_until_ready(res.final_model)

        _, us = _timed(run)
        if base_us is None:
            base_us = us
        ticks_per_sec = S * R * n_ticks / (us / 1e6)
        emit(f"sharded_d{n_dev}", us,
             f"grid={S}x{R};n_ticks={n_ticks};"
             f"cell_ticks_per_sec={ticks_per_sec:.0f};"
             f"speedup_vs_d1={base_us / us:.2f}x")


def bench_serve():
    """Rolling-horizon bidding service throughput over the first 1/2/4
    visible devices, in this process (d1 scores candidates vmapped, d>1
    shards scoring over a `make_scenario_mesh` — bit-exact either way,
    see tests/test_serve.py). Derived columns report replan latency
    p50/p95, decisions/sec, and — from the 1-device run — each job's
    regret vs the hindsight-optimal static bid and vs the best static
    paper plan."""
    from repro.core.cost_model import RuntimeModel
    from repro.launch.mesh import make_scenario_mesh
    from repro.service import BidServer, JobSpec, ServeConfig, synthetic_feed
    from repro.service.server import demo_problem

    ticks, horizon, warmup, score_ticks = \
        (24, 8, 8, 16) if SMOKE else (120, 24, 24, 0)
    quad, w0, prob = demo_problem(seed=0)
    feed = synthetic_feed(n_markets=2, n_ticks=ticks, seed=3)
    jobs = [JobSpec(name=f"job{i}", market=i % 2, eps=0.5, theta=60.0,
                    n_workers=4) for i in range(2)]
    cfg = ServeConfig(horizon=horizon, warmup=warmup, score_seeds=2, seed=0,
                      batch=4, idle_step=0.25, multibid_partitions=((2, 2),),
                      score_ticks=score_ticks or None)
    for n_dev in _device_counts([1] if SMOKE else [1, 2, 4]):
        mesh = make_scenario_mesh(n_dev) if n_dev > 1 else None
        t0 = time.perf_counter()
        rep = BidServer(feed, jobs, prob=prob, quad=quad, w0=w0,
                        alpha=prob.alpha,
                        rt_true=RuntimeModel(kind="exp", lam=2.0, delta=0.05),
                        cfg=cfg, mesh=mesh).run()
        wall = time.perf_counter() - t0
        s = rep["summary"]
        completed = sum(j["completed"] for j in s["jobs"].values())
        emit(f"serve_d{n_dev}", wall * 1e6,
             f"decisions={s['decisions']};"
             f"replan_p50_ms={s['replan_p50_ms']};"
             f"replan_p95_ms={s['replan_p95_ms']};"
             f"decisions_per_sec={s['decisions_per_sec']};"
             f"jobs_completed={completed}/2")
        if n_dev == 1:
            for name, j in s["jobs"].items():
                emit(f"serve_regret_{name}", 0.0,
                     f"cost={j['cost']};"
                     f"regret_vs_hindsight={j['regret_vs_hindsight']};"
                     f"regret_vs_static_paper="
                     f"{j['regret_vs_static_paper']}")


def bench_zoo():
    """Model zoo under preemption (trainer.train_zoo → zoo_program →
    engine): a small REAL reduced-qwen2 config trained through the batched
    engine under elastic masking.

    Rows: tokens/sec under the mask schedule (completed iterations ×
    global_batch × seq_len / steady-state wall); the cost-vs-loss frontier
    across three fixed-bid levels (per-level final loss vs total spot
    cost); and the bf16 mixed-precision zoo carry on the same grid."""
    from repro.configs import ARCHS
    from repro.configs.base import InputShape, JobConfig
    from repro.sim import engine
    from repro.train.trainer import train_zoo

    J = 4 if SMOKE else 12
    n_w = 4
    n_seeds = _seeds()
    n_ticks = _ticks(2 * J + 8)
    cfg = ARCHS["qwen2-7b"].reduced().with_(
        d_model=64, num_heads=2, num_kv_heads=1, d_ff=128,
        vocab_size=256, head_dim=32)
    job = JobConfig(model=cfg, shape=InputShape("zoo", 16, 4, "train"),
                    n_workers=n_w, learning_rate=0.1)
    levels = np.linspace(0.6, 1.0, 2 if SMOKE else 3)
    scenarios = [engine.Scenario(
        price=engine.PriceSpec.uniform(0.2, 1.0), alpha=job.learning_rate,
        bid_schedule=np.tile(np.full(n_w, b, np.float32), (J, 1)),
        rt_kind="exp", rt_lam=2.0, rt_delta=0.05, idle_step=0.5,
        name=f"b{b:.2f}") for b in levels]
    b_sz, s_len = job.shape.global_batch, job.shape.seq_len

    res, us_zoo = _timed(lambda: train_zoo(
        job, scenarios, seeds=n_seeds, n_ticks=n_ticks))
    iters = float(np.nansum(res.iterations))
    tokens = iters * b_sz * s_len
    cells = len(scenarios) * n_seeds
    emit("zoo_tokens_per_sec", us_zoo / cells,
         f"grid={len(scenarios)}x{n_seeds};J={J};n_ticks={n_ticks};"
         f"tokens_per_sec={tokens / (us_zoo / 1e6):.0f};"
         f"completed={float(res.completed.mean()):.2f}")

    # cost-vs-loss frontier: one row per bid level — lower bids buy fewer
    # active workers (noisier steps, cheaper ticks), the paper's trade
    for i, b in enumerate(levels):
        loss_traj = res.losses[i]            # (R, J_max)
        final_loss = _nanmean(loss_traj[:, -1] if np.isfinite(
            loss_traj[:, -1]).any() else loss_traj)
        emit(f"zoo_frontier_b{b:.2f}", 0.0,
             f"final_loss={final_loss:.3f};"
             f"total_cost={float(res.total_cost[i].mean()):.3f};"
             f"iterations={float(res.iterations[i].mean()):.1f}")

    # bf16 mixed-precision carry (bf16 params/activations, f32 masters)
    # through the identical grid — the zoo adapter's second dtype mode
    cfg16 = cfg.with_(dtype="bfloat16", param_dtype="bfloat16")
    job16 = JobConfig(model=cfg16, shape=job.shape, n_workers=n_w,
                      learning_rate=0.1)
    res16, us16 = _timed(lambda: train_zoo(
        job16, scenarios, seeds=n_seeds, n_ticks=n_ticks))
    tokens16 = float(np.nansum(res16.iterations)) * b_sz * s_len
    emit("zoo_bf16", us16 / cells,
         f"tokens_per_sec={tokens16 / (us16 / 1e6):.0f};"
         f"final_loss={_nanmean(res16.losses[..., -1]):.3f};"
         f"vs_f32={us_zoo / us16:.2f}x")


def bench_chaos():
    """Recovery overhead of the supervised durable loop: one unfailed
    supervised run vs the same workload under a seeded fault plan (a
    mid-chunk SIGKILL plus a corrupted newest-step checkpoint). Every
    attempt reads its compiled programs from the persistent compilation
    cache once the first has filled it, so the overhead column is the
    price of dying twice: restart latency + re-trace + lost-chunk
    recompute + fallback restore.

    The supervised workers are child processes that need the device, and
    a device belongs to the process that first touched it: run this
    benchmark alone (``--only chaos``)."""
    import tempfile

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "bench_chaos starts supervised workers that need the device, "
            "but this process already holds it (an earlier benchmark ran "
            "JAX here): run `python -m benchmarks.run --only chaos` on "
            "its own")

    from repro.chaos import Fault, FaultPlan
    from repro.launch import supervisor as sup
    from repro.launch.workload import WorkerSpec

    n_ticks, save_every = (8, 4) if SMOKE else (24, 6)
    spec = WorkerSpec(
        overrides=dict(d_model=16, num_heads=2, num_kv_heads=1, d_ff=32,
                       vocab_size=64, head_dim=8),
        bids=((0.9, 0.9, 0.5, 0.5), (0.8, 0.8, 0.6, 0.6)),
        seeds=2, n_ticks=n_ticks, save_every=save_every, keep_last=3)
    plan = FaultPlan((Fault("kill", at_tick=max(1, n_ticks // 3)),
                      Fault("corrupt", at_tick=max(2, 2 * n_ticks // 3),
                            mode="truncate_shard")), seed=5)
    cfg = dict(max_restarts=5, backoff_base=0.05, backoff_cap=0.5,
               hang_timeout=600.0, seed=5)

    def supervised(with_faults):
        d = tempfile.mkdtemp(prefix="bench_chaos_")
        spec.save(os.path.join(d, sup.SPEC_NAME))
        if with_faults:
            plan.save(os.path.join(d, sup.PLAN_NAME))
        t0 = time.perf_counter()
        summary = sup.Supervisor(
            d, sup.SupervisorConfig(**cfg)).run()
        if not summary["ok"]:
            raise RuntimeError(f"supervised bench run failed: {summary}")
        return summary, time.perf_counter() - t0

    base, base_s = supervised(with_faults=False)
    chaos, chaos_s = supervised(with_faults=True)
    emit("chaos_baseline", base_s * 1e6,
         f"n_ticks={n_ticks};save_every={save_every};"
         f"restarts={base['restarts']}")
    emit("chaos_recovery", chaos_s * 1e6,
         f"restarts={chaos['restarts']};ticks_lost={chaos['ticks_lost']};"
         f"mttr_s={chaos['mttr_s']:.2f};"
         f"overhead_vs_unfailed_pct={(chaos_s / base_s - 1) * 100:.1f}")


BENCHES = {
    "fig3": bench_fig3,
    "fig4": bench_fig4,
    "fig5a": bench_fig5a,
    "fig5b": bench_fig5b,
    "scenarios": bench_scenarios,
    "trainer": bench_trainer,
    "sharded": bench_sharded,
    "serve": bench_serve,
    "multibid": bench_multibid,
    "zoo": bench_zoo,
    "chaos": bench_chaos,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(BENCHES))
    ap.add_argument("--smoke", action="store_true",
                    help="2-tick/2-seed CI mode: exercise every perf path "
                         "in seconds; numbers are not meaningful")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as machine-readable JSON "
                         "(name/us_per_call/derived per row, plus the "
                         "backend and run configuration)")
    args = ap.parse_args()
    if args.smoke:
        global SMOKE
        SMOKE = True
    # chaos runs alone (its workers need the device this process takes)
    names = (args.only.split(",") if args.only
             else [n for n in BENCHES if n != "chaos"])
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        ap.error(f"unknown benchmark(s) {','.join(unknown)}; "
                 f"choose from {','.join(BENCHES)}")
    from repro.launch.jitcache import enable_persistent_cache
    enable_persistent_cache()
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()
    if args.json:
        import jax

        payload = {
            "benchmarks": names,
            "smoke": SMOKE,
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "generated_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                           time.gmtime()),
            "rows": RESULTS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"wrote {args.json} ({len(RESULTS)} rows)", flush=True)


if __name__ == '__main__':
    main()
