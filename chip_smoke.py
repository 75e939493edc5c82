"""Start the system's main paths once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: grid, bidding service, zoo
    python chip_smoke.py --chips 4    # four chips: the sharded zoo grid only

Phases, each run through the entry points a user calls:

* **grid** — the paper's own path: `engine.simulate` on a fig3-style grid
  of 64 scenarios × 8 seeds, plus a few cells replayed through the legacy
  `SpotMarket`/`VolatileCluster` loop on tick-replayed prices, which the
  engine must match to float32 tolerance.
* **serve** — the rolling-horizon `BidServer`, 2 jobs × 2 markets for a
  few horizons; it must compile exactly three engine programs.
* **zoo** — `trainer.train_zoo` on InternVL2-1B at its published widths
  and all 24 layers, bf16 params over f32 masters, 4 elastic workers,
  global batch 8 × 1024 tokens: an uninterrupted run, then a durable run
  stopped at half way, resumed with `resume_zoo` and run to the end. The
  losses and the final parameters' bits must equal the uninterrupted
  run's; the losses must be finite and fall.
* **zoo_mesh** (``--chips 4`` only) — `train_zoo` over 4 bid scenarios on
  `make_scenario_mesh(4)`, one full-width model per chip, against each
  scenario run alone on one chip in this process.

Each phase prints its wall and compile seconds and the device's peak
memory so far. The last line is a JSON object naming the device. With no
accelerator the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time
from typing import Dict

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: the zoo workers' bid vectors; the mesh phase takes one row per chip
BIDS = ((0.9, 0.9, 0.5, 0.5), (0.8, 0.8, 0.6, 0.6),
        (1.0, 1.0, 0.4, 0.4), (0.7, 0.7, 0.7, 0.7))


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Sizes of every phase. The defaults are what the chip runs;
    `reduced` is the CPU rehearsal of the same code."""

    arch: str = "internvl2-1b"
    full_width: bool = True        # published widths at all layers
    n_workers: int = 4
    global_batch: int = 8
    seq_len: int = 1024            # patch + text tokens per sequence
    zoo_ticks: int = 8             # N; the durable run stops at N/2
    learning_rate: float = 0.02
    grid_scenarios: int = 64
    grid_seeds: int = 8
    grid_iterations: int = 100
    legacy_ticks: int = 600
    serve_horizons: int = 4
    mesh_chips: int = 4

    def reduced(self) -> "SmokeConfig":
        return dataclasses.replace(
            self, full_width=False, seq_len=48, zoo_ticks=4,
            grid_scenarios=4, grid_seeds=2, grid_iterations=20,
            legacy_ticks=120, serve_horizons=2)

    def workload(self, n_bid_rows: int = 1):
        """(job, scenarios, seeds) through the supervisor's own workload
        builder: bf16 params, one seed, ``n_bid_rows`` bid scenarios."""
        from repro.configs import ARCHS
        from repro.launch.workload import WorkerSpec, build_workload

        spec = WorkerSpec(
            arch=self.arch, param_dtype="bfloat16", zoo=True,
            reduce_depth=(ARCHS[self.arch].num_layers if self.full_width
                          else None),
            n_workers=self.n_workers, seq_len=self.seq_len,
            global_batch=self.global_batch, bids=BIDS[:n_bid_rows],
            iterations=self.zoo_ticks, seeds=1, n_ticks=self.zoo_ticks,
            learning_rate=self.learning_rate)
        return build_workload(spec)


# ---------------------------------------------------------------- phases


class _Phase:
    """Wall time, compile time (trace + lowering + backend compile, from
    JAX's own monitoring events) and peak device memory of one phase."""

    compile_s = 0.0

    @classmethod
    def listen(cls):
        import jax
        from jax._src import dispatch

        events = {dispatch.BACKEND_COMPILE_EVENT, dispatch.JAXPR_TRACE_EVENT,
                  dispatch.JAXPR_TO_MLIR_MODULE_EVENT}

        def on_event(event, duration, **_):
            if event in events:
                cls.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), _Phase.compile_s
        print(f"[{self.name}] start", flush=True)
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            import jax

            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                     for d in jax.local_devices()]
            peak = (f"{max(peaks)}" if None not in peaks
                    else "not reported")
            print(f"[{self.name}] wall_s={time.perf_counter() - self.t0:.3f}",
                  flush=True)
            print(f"[{self.name}] compile_s="
                  f"{_Phase.compile_s - self.c0:.3f}", flush=True)
            print(f"[{self.name}] peak_bytes_in_use={peak}", flush=True)
        return False


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def fingerprint(tree, row=None) -> list:
    """Per leaf, a position-weighted wraparound sum of the bit patterns
    (of grid row ``row`` only, when given), reduced where the leaf lives:
    equal fingerprints mean equal bits, without copying a full-width model
    to the host or gathering a sharded one."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=1)
    def one(x, row):
        x = (x if row is None else x[row]).ravel()
        bits = jax.lax.bitcast_convert_type(
            x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[
                x.dtype.itemsize]).astype(jnp.uint32)
        pos = jax.lax.iota(jnp.uint32, x.size) % jnp.uint32(65521) + 1
        return jnp.sum(bits * pos, dtype=jnp.uint32)

    return [int(one(x, row)) for x in jax.tree.leaves(tree)]


def phase_grid(cfg: SmokeConfig) -> Dict:
    """The scenario grid on the quadratic oracle, and legacy-loop parity
    on tick-replayed prices (the recipe of tests/test_engine_parity.py)."""
    from repro.core import bidding, strategies as strat
    from repro.core.cost_model import (RuntimeModel, TruncGaussianPrice,
                                       UniformPrice)
    from repro.data.synthetic import QuadraticProblem
    from repro.sim import engine
    from repro.sim.evaluate import run_spot_strategy
    from repro.sim.spot_market import SpotMarket, TickPrices

    quad = QuadraticProblem(dim=16, n_samples=256, cond=5.0, noise=0.2,
                            seed=0)
    w0 = np.asarray(quad.w_star + 1.0, np.float32)
    alpha = 0.4 / quad.L
    J, S = cfg.grid_iterations, cfg.grid_scenarios
    prices = [engine.PriceSpec.uniform(0.2, 1.0),
              engine.PriceSpec.trunc_gaussian(0.6, 0.175, 0.2, 1.0)]
    bids = np.linspace(0.4, 1.0, S // 2)
    grid = [engine.Scenario(
        price=prices[i % 2], alpha=alpha,
        bid_schedule=np.tile(np.full(4, bids[i // 2], np.float32), (J, 1)),
        rt_kind="exp", rt_lam=2.0, idle_step=0.5, name=f"fig3-{i}")
        for i in range(S)]
    res = engine.simulate(grid, quad, w0, cfg.grid_seeds,
                          engine.SimConfig(n_ticks=4 * J, batch=16))
    _check(res.errors.shape == (S, cfg.grid_seeds, J),
           f"grid trajectory shape {res.errors.shape}")
    ran = ~np.isnan(res.errors)
    _check(bool(np.isfinite(res.errors[ran]).all()), "non-finite errors")
    done = res.completed
    _check(bool(done[-2:].all()), "the highest bids did not finish J")
    last = res.errors[done][:, -1]
    _check(bool((last < res.errors[done][:, 0]).all()),
           "error did not fall over a completed run")

    # legacy parity: one entry of a seeded price sequence per tick on
    # both sides, exact gradient, deterministic runtime
    rt = RuntimeModel(kind="det", r_const=1.0)
    worst = {"time": 0.0, "cost": 0.0, "error": 0.0}
    cells = [(UniformPrice(0.2, 1.0), (0.6, 0.6, 0.6)),
             (UniformPrice(0.2, 1.0), (0.8, 0.8, 0.45, 0.45)),
             (TruncGaussianPrice(0.6, 0.175, 0.2, 1.0), (0.85, 0.5, 0.5))]
    for k, (dist, b) in enumerate(cells):
        b = np.asarray(b, float)
        trace = dist.sample(np.random.default_rng(7 + k),
                            size=cfg.legacy_ticks).astype(np.float32)
        plan = bidding.BidPlan(n=len(b), n1=int(np.sum(b == b[0])),
                               b1=float(b[0]), b2=float(b[-1]), J=J,
                               expected_cost=0, expected_time=0,
                               expected_error=0)
        legacy = run_spot_strategy(
            quad, quad.w_star + 1.0, alpha, strat.FixedBids(plan),
            SpotMarket(TickPrices(trace)), rt, iterations=J, grad="full",
            seed=3, idle_step=0.5)
        cell = engine.simulate(
            [engine.Scenario(price=engine.PriceSpec.from_trace_ticks(trace),
                             alpha=alpha, bid_schedule=np.tile(b, (J, 1)),
                             rt_kind="det", rt_const=1.0, idle_step=0.5)],
            quad, w0, [0],
            engine.SimConfig(n_ticks=cfg.legacy_ticks, grad="full"))
        _check(int(cell.iterations[0, 0]) == J, f"legacy cell {k} unfinished")
        for key, got, want, rtol, atol in (
                ("time", cell.times, legacy.times, 1e-5, 1e-4),
                ("cost", cell.costs, legacy.costs, 1e-4, 1e-4),
                # float32 iterate drift accumulates over J steps
                ("error", cell.errors, legacy.errors, 5e-3, 1e-6)):
            got = got[0, 0, :J]
            np.testing.assert_allclose(got, want, rtol=rtol, atol=atol)
            worst[key] = max(worst[key], float(np.max(
                np.abs(got - want) / (atol + rtol * np.abs(want)))))
    return {"grid": f"{S}x{cfg.grid_seeds}", "iterations": J,
            "completed_share": float(done.mean()),
            "legacy_cells": len(cells),
            "legacy_worst_share_of_tolerance": worst}


def phase_serve(cfg: SmokeConfig) -> Dict:
    """The bidding service, 2 jobs × 2 markets: it runs to its summary and
    compiles exactly three engine programs."""
    from repro.core.cost_model import RuntimeModel
    from repro.service import BidServer, JobSpec, ServeConfig, synthetic_feed
    from repro.service.server import demo_problem
    from repro.sim import engine

    horizon = warmup = 16
    programs = (engine._simulate_jit, engine._simulate_jit_donated,
                engine._simulate_sharded_jit,
                engine._simulate_sharded_jit_donated)
    before = sum(f._cache_size() for f in programs)
    quad, w0, prob = demo_problem(seed=0)
    feed = synthetic_feed(n_markets=2, seed=3,
                          n_ticks=warmup + cfg.serve_horizons * horizon)
    jobs = [JobSpec(name=f"job{i}", market=i, eps=0.5, theta=60.0,
                    n_workers=4) for i in range(2)]
    rep = BidServer(
        feed, jobs, prob=prob, quad=quad, w0=w0, alpha=prob.alpha,
        rt_true=RuntimeModel(kind="exp", lam=2.0, delta=0.05),
        cfg=ServeConfig(horizon=horizon, warmup=warmup, score_seeds=2,
                        batch=4, idle_step=0.25)).run()
    compiled = sum(f._cache_size() for f in programs) - before
    s = rep["summary"]
    _check(compiled == 3, f"the service compiled {compiled} engine programs")
    _check(s["decisions"] >= 2 * cfg.serve_horizons - 2,
           f"only {s['decisions']} decisions")
    costs = [j["cost"] for j in s["jobs"].values()]
    _check(bool(np.isfinite(costs).all()), f"non-finite job costs {costs}")
    return {"decisions": s["decisions"], "engine_programs": compiled,
            "replan_p50_ms": s["replan_p50_ms"]}


class _Preempted(Exception):
    """Raised from the durable loop's after-save hook: the run dies right
    after its first checkpoint lands, as a preempted trainer would."""


class _StopAfterFirstSave:
    def after_save(self, tick, path):
        raise _Preempted(tick)


def _losses_fall(losses: np.ndarray) -> bool:
    ran = losses[~np.isnan(losses)]
    return ran.size >= 2 and bool(np.isfinite(ran).all()) \
        and bool(ran[-1] < ran[0])


def phase_zoo(cfg: SmokeConfig) -> Dict:
    """Full-width zoo training: uninterrupted vs preempted-and-resumed,
    bit for bit."""
    from repro.train.trainer import resume_zoo, train_zoo

    job, scenarios, seeds = cfg.workload()
    n = cfg.zoo_ticks

    full = train_zoo(job, scenarios, seeds, n_ticks=n)
    losses, iters = full.losses, full.iterations
    prints = fingerprint(full.final_model)
    del full                       # free the device carry before the rerun
    _check(_losses_fall(losses[0, 0]),
           f"losses not finite or not falling: {losses[0, 0]}")

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "zoo.npz")
        try:
            train_zoo(job, scenarios, seeds, n_ticks=n,
                      checkpoint_path=path, save_every=n // 2,
                      hooks=_StopAfterFirstSave())
        except _Preempted:
            pass
        else:
            raise AssertionError("the durable run did not stop at N/2")
        state, tick = resume_zoo(path, job, scenarios, seeds)
    _check(tick == n // 2, f"checkpoint at tick {tick}, not {n // 2}")
    iters_before = int(np.asarray(state.j).sum())
    t0 = time.perf_counter()
    resumed = train_zoo(job, scenarios, seeds, n_ticks=n, init_state=state,
                        tick0=tick)
    wall = time.perf_counter() - t0      # its chunk program is compiled
    del state
    prints_resumed = fingerprint(resumed.final_model)
    _check(np.array_equal(resumed.losses, losses, equal_nan=True),
           f"resumed losses {resumed.losses} != {losses}")
    _check(prints_resumed == prints, "resumed parameters differ in bits")
    tokens = (int(resumed.iterations.sum()) - iters_before) \
        * job.shape.global_batch * job.shape.seq_len
    return {"arch": job.model.name, "layers": job.model.num_layers,
            "d_model": job.model.d_model, "vocab": job.model.vocab_size,
            "iterations": int(iters.sum()),
            "loss_first_last": [float(x) for x in
                                losses[0, 0][~np.isnan(losses[0, 0])][[0, -1]]],
            "resume_bit_equal": True,
            "tokens_per_s_resumed_half": tokens / wall}


def phase_zoo_mesh(cfg: SmokeConfig) -> Dict:
    """One full-width model per chip over a 4-scenario mesh, against each
    scenario run on one chip in the layout a chip of the mesh holds it:
    alone, as one cell. (The CPU pads each device of a mesh to two cells,
    `engine._padded_size`, so there each scenario runs beside a copy of
    itself: one cell is computed on per-worker slices, a wider grid on
    the whole batch.) Equal bits pass; so do losses within float32
    rounding (relative 1e-5), reported with the difference found, since a
    sharded program may order a reduction differently."""
    import jax

    from repro.launch.mesh import make_scenario_mesh
    from repro.sim import engine
    from repro.train.trainer import train_zoo

    k = cfg.mesh_chips
    job, scenarios, seeds = cfg.workload(n_bid_rows=k)
    per_chip = engine._padded_size(k, k, jax.devices()[0].platform) // k
    res = train_zoo(job, scenarios, seeds, n_ticks=cfg.zoo_ticks,
                    mesh=make_scenario_mesh(k))
    losses = res.losses
    prints = [fingerprint(res.final_model, i) for i in range(k)]
    del res
    _check(all(_losses_fall(losses[i, 0]) for i in range(k)),
           f"losses not finite or not falling: {losses}")
    bits_equal, worst = True, 0.0
    for i in range(k):
        alone = train_zoo(job, [scenarios[i]] * per_chip, seeds,
                          n_ticks=cfg.zoo_ticks)
        _check(np.array_equal(np.isnan(alone.losses[0]),
                              np.isnan(losses[i])),
               f"scenario {i} ran other iterations alone than on the mesh")
        ran = ~np.isnan(losses[i])
        worst = max(worst, float(np.max(
            np.abs(alone.losses[0][ran] - losses[i][ran])
            / np.abs(losses[i][ran]), initial=0.0)))
        bits_equal &= fingerprint(alone.final_model, 0) == prints[i]
        del alone
    _check(worst <= 1e-5, f"mesh losses differ from one-chip runs by a "
           f"relative {worst}")
    return {"chips": k, "scenarios": k, "params_bit_equal": bits_equal,
            "losses_bit_equal": worst == 0.0,
            "max_relative_loss_difference": worst}


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded zoo phase over 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu":
        print(f"chip_smoke: JAX found no accelerator (platform "
              f"{platform!r}); this check runs on a TPU only",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.jitcache import enable_persistent_cache

    enable_persistent_cache()
    _Phase.listen()
    cfg = SmokeConfig()
    phases = ([("zoo_mesh", phase_zoo_mesh)] if args.chips == 4 else
              [("grid", phase_grid), ("serve", phase_serve),
               ("zoo", phase_zoo)])
    for name, fn in phases:
        with _Phase(name):
            print(f"[{name}] {json.dumps(fn(cfg))}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
