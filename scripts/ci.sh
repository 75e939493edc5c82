#!/usr/bin/env bash
# Tier-1 CI: fast test suite + a 5-scenario engine smoke sweep.
# Run from anywhere: scripts/ci.sh [--smoke-bench] [--devices N] [--chaos]
#                                   [--serve-smoke] [--zoo-smoke]
#
# --smoke-bench additionally runs every benchmark in --smoke mode (2-tick /
# 2-seed budgets) so perf-path regressions — import errors, shape breaks,
# jit failures in benchmarks/run.py — fail CI instead of rotting silently.
# (This includes the sharded engine bench, which smoke-runs at 1 and 2
# forced host devices in its own subprocesses.)
#
# --devices N forces N virtual host devices for the whole run
# (XLA_FLAGS=--xla_force_host_platform_device_count=N, set before any jax
# import) so the `multidevice`-marked sharded tests run natively instead
# of skipping.
#
# --chaos additionally runs the fast chaos-marked tests plus one supervised
# end-to-end smoke: a durable run on forced host devices that survives a
# mid-chunk SIGKILL and a corrupted newest checkpoint and still finishes.
#
# --serve-smoke additionally runs the fast serve-marked tests (the
# rolling-horizon bidding service: stream -> posterior -> batched replan)
# plus the serve benchmark in --smoke mode.
#
# --zoo-smoke additionally runs the zoo-marked tests (the zoo<->engine
# adapter: engine-vs-plain-loop parity, the weighted_mean convention at the
# train-step denominator, bf16 checkpoint kill-and-resume) plus the zoo
# benchmark in --smoke mode (tokens/sec under elastic masking, cost-vs-loss
# frontier, the bf16 carry).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

SMOKE_BENCH=0
DEVICES=0
CHAOS=0
SERVE=0
ZOO=0
while [ "$#" -gt 0 ]; do
  case "$1" in
    --smoke-bench) SMOKE_BENCH=1; shift ;;
    --chaos) CHAOS=1; shift ;;
    --serve-smoke) SERVE=1; shift ;;
    --zoo-smoke) ZOO=1; shift ;;
    --devices)
      [ "$#" -ge 2 ] || { echo "--devices needs a count" >&2; exit 2; }
      DEVICES="$2"; shift 2 ;;
    *) echo "unknown option: $1" >&2; exit 2 ;;
  esac
done

if [ "$DEVICES" -gt 0 ]; then
  export XLA_FLAGS="--xla_force_host_platform_device_count=$DEVICES${XLA_FLAGS:+ $XLA_FLAGS}"
  echo "== forcing $DEVICES virtual host devices (XLA_FLAGS=$XLA_FLAGS) =="
fi

echo "== tier-1 tests (excluding slow) =="
python -m pytest -x -q -m "not slow"

echo "== engine smoke sweep (5 scenarios x 2 seeds) =="
python - <<'PY'
import numpy as np
from repro.data.synthetic import QuadraticProblem
from repro.sim import engine

quad = QuadraticProblem(dim=6, n_samples=64, cond=5.0, noise=0.2, seed=0)
w0 = quad.w_star + 1.0
alpha = 0.4 / quad.L
scenarios = [engine.Scenario(
    price=engine.PriceSpec.uniform(0.2, 1.0), alpha=alpha,
    bid_schedule=np.tile([b, b, b], (40, 1)), rt_kind="exp", rt_lam=2.0,
    idle_step=0.5, name=f"b={b}") for b in [0.5, 0.6, 0.7, 0.85, 1.0]]
res = engine.simulate(scenarios, quad, w0, 2,
                      engine.SimConfig(n_ticks=250, batch=4))
assert res.completed.all(), "smoke sweep failed to complete"
assert np.isfinite(res.total_cost).all()
print("smoke sweep OK:",
      [f"{s.name}:cost={c:.1f}" for s, c in
       zip(scenarios, res.total_cost.mean(axis=1))])
PY

if [ "$SMOKE_BENCH" = 1 ]; then
  echo "== benchmark smoke (--smoke: 2-tick budgets) =="
  python -m benchmarks.run --smoke

  echo "== checkpoint smoke (save one snapshot + resume, bit-exact) =="
  python - <<'PY'
import numpy as np, tempfile, os
from repro.data.synthetic import QuadraticProblem
from repro.sim import engine
from repro.train import checkpoint as ck

quad = QuadraticProblem(dim=6, n_samples=64, cond=5.0, noise=0.2, seed=0)
sc = engine.stack_scenarios([engine.Scenario(
    price=engine.PriceSpec.uniform(0.2, 1.0), alpha=0.4 / quad.L,
    bid_schedule=np.tile([0.7, 0.7], (10, 1)), rt_kind="exp", rt_lam=2.0,
    idle_step=0.5)])
program = engine.quadratic_program("full", 4)
data = engine.jax_quadratic(quad)
w0 = np.asarray(quad.w_star + 1.0, np.float32)
cfg = engine.SimConfig(n_ticks=24, grad="full", snapshot_every=8)
full = engine.simulate_program(sc, program, w0, data, [0, 1], cfg)
state, tick = engine.snapshot_state(full, 0)
path = os.path.join(tempfile.mkdtemp(prefix="ci_ckpt_"), "smoke.npz")
ck.save(path, state, tick)
restored, tick = ck.restore(path, engine.initial_state(sc, w0, 2))
res = engine.simulate_program(
    sc, program, None, data, [0, 1],
    engine.SimConfig(n_ticks=24, grad="full"),
    init_state=restored, tick0=tick)
assert np.array_equal(res.costs, full.costs, equal_nan=True)
assert np.array_equal(res.errors, full.errors, equal_nan=True)
assert np.array_equal(res.total_time, full.total_time)
print(f"checkpoint smoke OK: saved tick {tick}, resumed 16 ticks, "
      "bit-exact")
PY

  echo "== fig4 trace-parity + kill-and-resume tests =="
  python -m pytest -q \
    "tests/test_engine_parity.py::test_fig4_trace_replay_matches_legacy_under_exp_runtimes" \
    "tests/test_trainer_batched.py::test_kill_and_resume_batched_is_bitexact"

  echo "== megabatch kernel-on smoke (Pallas interpret parity vs ref) =="
  python - <<'PY'
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import ARCHS
from repro.configs.base import InputShape, JobConfig
from repro.kernels import ref
from repro.kernels.elastic_update import elastic_sgd_update
from repro.train import megabatch as mb

cfg = ARCHS["qwen2-7b"].reduced().with_(
    num_layers=1, d_model=16, num_heads=2, num_kv_heads=1, d_ff=32,
    vocab_size=64, head_dim=8)
job = JobConfig(model=cfg, shape=InputShape("t", 8, 4, "train"),
                n_workers=4, learning_rate=0.1)
assert mb.supports_megabatch(cfg, job) is None
r = 4
model = jax.tree.map(
    lambda x: jnp.tile(x[None], (r,) + (1,) * x.ndim),
    mb.init_megabatch_state(cfg, job, jax.random.PRNGKey(0)))
key = jax.random.PRNGKey(1)
tokens = jax.random.randint(key, (r, 4, 8), 0, cfg.vocab_size)
labels = jax.random.randint(jax.random.fold_in(key, 1), (r, 4, 8), 0,
                            cfg.vocab_size)
masks = jnp.ones((r, 4)).at[0].set(0.0)
run = jnp.ones(r, bool).at[-1].set(False)

# one step through the fused Pallas kernel, interpret=True (kernel-on path)
step_k = jax.jit(mb.make_megabatch_step(cfg, job, use_fused_update=True,
                                        fused_interpret=True))
mk, lk = step_k(model, tokens, labels, masks, jnp.zeros(r, jnp.int32), run)
# same step through the pure-jnp inline update
step_i = jax.jit(mb.make_megabatch_step(cfg, job, use_fused_update=False))
mi, li = step_i(model, tokens, labels, masks, jnp.zeros(r, jnp.int32), run)
np.testing.assert_allclose(np.asarray(lk), np.asarray(li), rtol=1e-6)
np.testing.assert_allclose(np.asarray(mk["p"]), np.asarray(mi["p"]),
                           atol=1e-6)
# raw kernel vs reference on an odd-sized padded block
p = jax.random.normal(key, (3, 517))
g = jax.random.normal(jax.random.fold_in(key, 2), (3, 517))
v = jnp.zeros_like(p)
w = jnp.array([0.0, 2.5, 4.0]); lr = jnp.full(3, 0.1)
running = jnp.array([True, True, False])
pk, vk = elastic_sgd_update(p, v, g, w, running, lr, block_p=128,
                            interpret=True)
pr, vr = ref.elastic_update_reference(p, v, g, w, running, lr)
np.testing.assert_allclose(np.asarray(pk), np.asarray(pr), atol=1e-6)
np.testing.assert_allclose(np.asarray(vk), np.asarray(vr), atol=1e-6)
print("megabatch kernel-on smoke OK: fused step == inline step, "
      "Pallas(interpret) == ref on 3x517 @ block 128")
PY
fi

if [ "$CHAOS" = 1 ]; then
  echo "== chaos tests (fast subset) =="
  python -m pytest -q -m "chaos and not slow"

  echo "== chaos supervised smoke (kill + corrupt shard on 2 forced devices) =="
  python - <<'PY'
import json, os, tempfile
from repro.chaos import Fault, FaultPlan
from repro.launch import supervisor as sup
from repro.launch.workload import WorkerSpec

run_dir = tempfile.mkdtemp(prefix="ci_chaos_")
WorkerSpec(
    overrides=dict(d_model=16, num_heads=2, num_kv_heads=1, d_ff=32,
                   vocab_size=64, head_dim=8),
    bids=((0.9, 0.9, 0.5, 0.5), (0.8, 0.8, 0.6, 0.6)),
    seeds=2, n_ticks=12, save_every=4, save_shards=2, keep_last=3,
    mesh=2).save(os.path.join(run_dir, sup.SPEC_NAME))
FaultPlan((Fault("kill", at_tick=5),
           Fault("corrupt", at_tick=9, mode="truncate_shard")),
          seed=3).save(os.path.join(run_dir, sup.PLAN_NAME))
summary = sup.Supervisor(run_dir, sup.SupervisorConfig(
    max_restarts=5, backoff_base=0.05, backoff_cap=0.5,
    hang_timeout=600.0, devices=2, seed=3)).run()
assert summary["ok"], summary
assert summary["restarts"] == 2, summary
assert summary["final_tick"] == 12, summary
assert summary["ticks_lost"] <= 8, summary
print("chaos smoke OK:", json.dumps(summary))
PY
fi

if [ "$SERVE" = 1 ]; then
  echo "== serve tests (fast subset) =="
  python -m pytest -q -m "serve and not slow"

  echo "== serve benchmark smoke (replayed feed, tiny budgets) =="
  python -m benchmarks.run --only serve --smoke
fi

if [ "$ZOO" = 1 ]; then
  echo "== zoo tests (parity, weighted_mean convention, bf16 resume) =="
  python -m pytest -q -m "zoo and not slow"

  echo "== zoo benchmark smoke (real reduced config, tiny budgets) =="
  python -m benchmarks.run --only zoo --smoke
fi
echo "CI OK"
