"""Mesh-sharded execution must be *bit-exact* with the single-device
vmapped path.

`engine.simulate_sharded` / `train_batched(mesh=...)` shard the (S, R)
scenario × replica grid over a device mesh with ``shard_map``; per-cell
RNG folds the seed value and the absolute tick — never a device index —
so sharding must not change a single bit of any trajectory, snapshot, or
trained parameter. These tests pin that contract under 8 forced host
devices (subprocess, so the forced XLA_FLAGS never leak into this
process's jax backend), including:

* uneven shard counts — S = 11 scenarios over 8/4/2-way meshes, and a
  replica axis of 3 over a 2-way ``replica`` mesh axis (the padded
  cells are sliced off; see `engine._padded_size` for the width-≥2 rule
  that keeps XLA:CPU's contraction order identical);
* the fig3 regime (uniform + truncated-Gaussian i.i.d. prices) and the
  fig4 regime (time-indexed synthetic-history trace replay);
* real-model training — vmapped and megabatched layouts — losses, final
  params/opt state, cost/time accounting, and mid-run snapshots.

An in-process `multidevice` check runs natively when the host already
has ≥ 2 devices (e.g. `scripts/ci.sh --devices 8`) and skips cleanly
otherwise.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_PRELUDE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax
from repro.sim import engine
from repro.launch.mesh import make_scenario_mesh, make_scenario_replica_mesh

if jax.device_count() < 8:
    print("RESULT " + json.dumps({"skip": f"{jax.device_count()} devices"}))
    raise SystemExit(0)


def tree_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(la, lb))


def result_equal(res, ref):
    return {
        "errors": bool(np.array_equal(res.errors, ref.errors,
                                      equal_nan=True)),
        "costs": bool(np.array_equal(res.costs, ref.costs, equal_nan=True)),
        "times": bool(np.array_equal(res.times, ref.times, equal_nan=True)),
        "total_cost": bool(np.array_equal(res.total_cost, ref.total_cost)),
        "total_time": bool(np.array_equal(res.total_time, ref.total_time)),
        "iterations": bool(np.array_equal(res.iterations, ref.iterations)),
        "model": tree_equal(res.final_model, ref.final_model),
        "snapshots": (res.snapshots is None) == (ref.snapshots is None)
        and (res.snapshots is None
             or tree_equal(res.snapshots, ref.snapshots)),
    }


MESHES = [("d8", lambda: make_scenario_mesh(8)),
          ("d4", lambda: make_scenario_mesh(4)),
          ("d2", lambda: make_scenario_mesh(2)),
          ("d4xr2", lambda: make_scenario_replica_mesh(4, 2)),
          ("d2xr2", lambda: make_scenario_replica_mesh(2, 2))]
"""

# S = 11 is coprime with every mesh width used (8, 4, 2) and R = 3 is
# odd against the 2-wide replica axis — every shard boundary is uneven.
_ENGINE_SCRIPT = _PRELUDE + r"""
from repro.data.synthetic import QuadraticProblem
from repro.sim.spot_market import synthetic_history

quad = QuadraticProblem(dim=6, n_samples=64, cond=5.0, noise=0.2, seed=0)
w0 = np.asarray(quad.w_star + 1.0, np.float32)
alpha = 0.4 / quad.L

# fig3 regime: i.i.d. uniform + truncated-Gaussian prices, 11 scenarios
fig3_specs = [engine.PriceSpec.uniform(0.2, 1.0),
              engine.PriceSpec.trunc_gaussian(0.6, 0.175, 0.2, 1.0)]
fig3 = [engine.Scenario(
    price=fig3_specs[i % 2], alpha=alpha,
    bid_schedule=np.tile([b, b, b], (16, 1)), rt_kind="exp", rt_lam=2.0,
    idle_step=0.5, name=f"fig3-{i}")
    for i, b in enumerate(np.linspace(0.4, 1.0, 11))]

# fig4 regime: time-indexed replay of the synthetic history trace
trace = synthetic_history(hours=24, seed=0)
fig4 = [engine.Scenario(
    price=engine.PriceSpec.from_trace(trace, step=0.05), alpha=alpha,
    bid_schedule=np.tile([b, b, b], (16, 1)), rt_kind="exp", rt_lam=2.0,
    idle_step=0.5, name=f"fig4-{i}")
    for i, b in enumerate([0.5, 0.7, 0.9, 1.0, 0.6])]

program = engine.quadratic_program("minibatch", 4)
data = engine.jax_quadratic(quad)
cfg = engine.SimConfig(n_ticks=40, batch=4, snapshot_every=20)

out = {}
for tag, scenarios in [("fig3", fig3), ("fig4", fig4)]:
    batch = engine.stack_scenarios(scenarios)
    ref = engine.simulate_program(batch, program, w0, data, 3, cfg)
    for mname, make in MESHES:
        res = engine.simulate_sharded(batch, program, w0, data, 3, cfg,
                                      mesh=make())
        out[f"{tag}:{mname}"] = result_equal(res, ref)
print("RESULT " + json.dumps(out))
"""

_TRAINER_SCRIPT = _PRELUDE + r"""
from repro.configs import ARCHS
from repro.configs.base import InputShape, JobConfig
from repro.core import bidding, strategies as strat
from repro.core.cost_model import RuntimeModel, UniformPrice
from repro.train import trainer

J, N_W = 8, 4
cfg = ARCHS["qwen2-7b"].reduced().with_(
    d_model=16, num_heads=2, num_kv_heads=1, d_ff=32, vocab_size=64,
    head_dim=8)
job = JobConfig(model=cfg, shape=InputShape("t", 8, 4, "train"),
                n_workers=N_W, learning_rate=0.1)


def fixed(bids, name):
    bids = np.asarray(bids, float)
    return strat.FixedBids(bidding.BidPlan(
        n=len(bids), n1=int(np.sum(bids == bids[0])), b1=float(bids[0]),
        b2=float(bids[-1]), J=J, expected_cost=0, expected_time=0,
        expected_error=0), name=name)


scen = [engine.scenario_from_strategy(
    fixed([b, b, 0.5, 0.5], name=f"g{i}"), alpha=0.1,
    rt=RuntimeModel(kind="exp", lam=2.0, delta=0.05),
    dist=UniformPrice(0.2, 1.0), n_max=N_W, idle_step=0.5,
    name=f"g{i}") for i, b in enumerate([0.9, 0.8, 0.7])]

out = {}
for tag, mb in [("vmapped", False), ("megabatch", True)]:
    ref = trainer.train_batched(job, scen, [0, 1, 2], n_ticks=14,
                                snapshot_every=7, donate=False,
                                megabatch=mb)
    for mname, make in [("d8", lambda: make_scenario_mesh(8)),
                        ("d2xr2", lambda: make_scenario_replica_mesh(2, 2))]:
        res = trainer.train_batched(job, scen, [0, 1, 2], n_ticks=14,
                                    snapshot_every=7, donate=False,
                                    megabatch=mb, mesh=make())
        out[f"{tag}:{mname}"] = result_equal(res, ref)
print("RESULT " + json.dumps(out))
"""


def _run_subprocess(script):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    rec = json.loads(line[len("RESULT "):])
    if "skip" in rec:
        pytest.skip(f"cannot force 8 host devices: {rec['skip']}")
    return rec


@pytest.mark.slow
def test_simulate_sharded_bitexact_fig3_fig4_uneven_shards():
    """Engine sharding is bit-exact on every mesh shape for both figure
    regimes — S = 11 (fig3) and S = 5 (fig4) never divide evenly."""
    rec = _run_subprocess(_ENGINE_SCRIPT)
    bad = {k: v for k, v in rec.items()
           if not all(v.values())}
    assert not bad, f"sharded run diverged from vmapped: {bad}"


@pytest.mark.slow
def test_train_batched_sharded_bitexact():
    """Sharded real-model training (vmapped and megabatched layouts) is
    bit-exact: losses, snapshots, cost/time, and every model leaf."""
    rec = _run_subprocess(_TRAINER_SCRIPT)
    bad = {k: v for k, v in rec.items() if not all(v.values())}
    assert not bad, f"sharded training diverged from vmapped: {bad}"


@pytest.mark.multidevice
@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs ≥ 2 devices (scripts/ci.sh --devices N)")
def test_simulate_sharded_bitexact_native_devices():
    """In-process variant for hosts that already expose ≥ 2 devices: the
    default scenario mesh reproduces the vmapped run bit-exactly."""
    from repro.data.synthetic import QuadraticProblem
    from repro.sim import engine

    quad = QuadraticProblem(dim=4, n_samples=32, cond=5.0, noise=0.2,
                            seed=0)
    w0 = np.asarray(quad.w_star + 1.0, np.float32)
    scenarios = [engine.Scenario(
        price=engine.PriceSpec.uniform(0.2, 1.0), alpha=0.4 / quad.L,
        bid_schedule=np.tile([b, b], (10, 1)), rt_kind="exp", rt_lam=2.0,
        idle_step=0.5, name=f"b={b}") for b in [0.5, 0.7, 0.9]]
    batch = engine.stack_scenarios(scenarios)
    program = engine.quadratic_program("minibatch", 4)
    data = engine.jax_quadratic(quad)
    cfg = engine.SimConfig(n_ticks=20, batch=4)
    ref = engine.simulate_program(batch, program, w0, data, 2, cfg)
    res = engine.simulate_sharded(batch, program, w0, data, 2, cfg)
    np.testing.assert_array_equal(res.errors, ref.errors)
    np.testing.assert_array_equal(res.total_cost, ref.total_cost)
    np.testing.assert_array_equal(res.total_time, ref.total_time)


def test_simulate_sharded_rejects_unknown_mesh_axes():
    """A mesh whose sharded axes aren't named data/replica is a usage
    error, not a silent wrong-answer."""
    from repro.data.synthetic import QuadraticProblem
    from repro.sim import engine

    quad = QuadraticProblem(dim=4, n_samples=32, cond=5.0, noise=0.2,
                            seed=0)
    sc = engine.Scenario(price=engine.PriceSpec.uniform(0.2, 1.0),
                         alpha=0.1, bid_schedule=np.tile([0.9], (4, 1)))
    mesh = jax.make_mesh((1,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    with pytest.raises(ValueError, match="data"):
        engine.simulate_sharded(
            engine.stack_scenarios([sc]),
            engine.quadratic_program("full", 4),
            np.zeros(4, np.float32), engine.jax_quadratic(quad), 2,
            engine.SimConfig(n_ticks=4), mesh=mesh)
