"""What the elastic training path tells a profiler (`repro.obs`), on a tiny
zoo model on the CPU: the device scopes reach the chunk program's op
metadata, the engine's counters agree with a known mask, the host spans
nest inside their caller's, and none of it changes a bit of the run."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro import obs
from repro.configs import ARCHS
from repro.configs.base import InputShape, JobConfig
from repro.launch.mesh import make_scenario_mesh
from repro.sim import engine
from repro.sim.market_core import spot_active_mask
from repro.train import trainer, zoo_program

pytestmark = pytest.mark.zoo

SCOPES = ("repro.market", "repro.record", "repro.gate", "repro.step",
          "repro.loss_head", "repro.update")
SPANS = ("repro.prepare.scenarios", "repro.prepare.batches",
         "repro.engine.init", "repro.engine.dispatch", "repro.engine.fetch")
COUNTERS = ("engine.ticks", "engine.running_ticks", "engine.shard_slots",
            "engine.active_shards")
N_W = 4
BIDS = np.asarray([0.9, 0.9, 0.5, 0.5], np.float32)
# prices per tick: all four workers up, the two high bidders, nobody
TRACE = np.asarray([0.3, 0.7, 0.95, 0.45, 0.7, 0.3, 0.95, 0.6,
                    0.3, 0.7, 0.45, 0.3, 0.7, 0.3, 0.45, 0.3], np.float32)
CHUNK = 8
J = 12


def _job(dtype="bfloat16"):
    cfg = ARCHS["qwen2-7b"].reduced().with_(
        d_model=64, num_heads=2, num_kv_heads=1, d_ff=128, vocab_size=256,
        head_dim=32, dtype=dtype, param_dtype=dtype)
    return JobConfig(model=cfg, shape=InputShape("t", 16, 4, "train"),
                     n_workers=N_W, learning_rate=0.1)


def _scenario():
    return engine.Scenario(
        price=engine.PriceSpec.from_trace_ticks(TRACE), alpha=0.1,
        bid_schedule=np.tile(BIDS, (J, 1)), rt_kind="det", rt_const=1.0,
        idle_step=0.5, name="trace")


def _two_chunks(job, seeds=(0,), **kw):
    """Ticks [0, CHUNK) from a fresh carry, then [CHUNK, 2 CHUNK) from the
    first call's carry: (second result, counter deltas of each call)."""
    deltas, state, res = [], None, None
    for tick0 in (0, CHUNK):
        before = obs.snapshot()
        res = trainer.train_zoo(job, [_scenario()], list(seeds),
                                n_ticks=tick0 + CHUNK, n_batches=J,
                                init_state=state, tick0=tick0, **kw)
        after = obs.snapshot()
        deltas.append({k: after.get(k, 0) - before.get(k, 0)
                       for k in COUNTERS})
        state = res.final_state
    return res, deltas


def _innermost_scope(op_name):
    found = [s for s in re.findall(r"(?:^|[/(])(repro\.[a-z_]+)(?=[/):]|$)",
                                   op_name) if s in SCOPES]
    return found[-1] if found else None


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_every_scope_names_ops_of_the_chunk_program(dtype):
    """Both carry layouts of the zoo step; the head's backward matmul
    carries its forward scope."""
    job = _job(dtype)
    batch = engine.stack_scenarios([_scenario()])
    model0 = zoo_program.init_zoo_state(job.model, job,
                                        jax.random.PRNGKey(0))
    program = zoo_program.make_zoo_program(job.model, job, J)
    hlo = engine._simulate_jit.lower(
        batch, engine.initial_state(batch, model0, 1),
        trainer.stack_batches(job, J), jnp.zeros((1,), jnp.int32),
        jnp.int32(0), program=program, n_run=CHUNK,
        k_snap=0).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert set(SCOPES) <= {_innermost_scope(name) for name in names}
    head_backward = [name for name in names
                     if _innermost_scope(name) == "repro.loss_head" and
                     "transpose(jvp(" in name and
                     name.endswith("dot_general")]
    assert head_backward, "no backward matmul of the head in its scope"


def test_a_grid_program_gates_under_its_scope():
    """A vmapped grid lands the stepped model under ``repro.gate``: the
    tick's ``cond`` on a batched predicate is a select over the carry."""
    job = _job("float32")
    batch = engine.stack_scenarios([_scenario()])
    model0 = zoo_program.init_zoo_state(job.model, job,
                                        jax.random.PRNGKey(0))
    program = zoo_program.make_zoo_program(job.model, job, J)
    hlo = engine._simulate_jit.lower(
        batch, engine.initial_state(batch, model0, 2),
        trainer.stack_batches(job, J), jnp.zeros((2,), jnp.int32),
        jnp.int32(0), program=program, n_run=CHUNK,
        k_snap=0).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert "repro.gate" in {_innermost_scope(name) for name in names}


@pytest.mark.parametrize("sharded", [False, True])
def test_counters_follow_the_mask(sharded):
    """On the trace's known mask, over two calls (the second resumes the
    first's carry): ticks, running ticks, Σ y and ticks × workers."""
    seeds = (0, 1)
    kw = {"mesh": make_scenario_mesh(1)} if sharded else {}
    res, deltas = _two_chunks(_job(), seeds, **kw)
    want = np.zeros((len(seeds), len(TRACE), 2))   # (running, y) per tick
    for r, seed in enumerate(seeds):
        j = 0
        for k in range(len(TRACE)):
            # a seed replays the trace rotated by 1013 ticks per unit
            price = TRACE[(k + 1013 * seed) % len(TRACE)]
            y = float(spot_active_mask(BIDS, price).sum())
            running = y >= 1 and j < J
            j += running
            want[r, k] = running, y * running
    for call, delta in enumerate(deltas):
        running, y = want[:, call * CHUNK:(call + 1) * CHUNK].sum((0, 1))
        assert delta == {
            "engine.ticks": CHUNK * len(seeds),
            "engine.running_ticks": int(running),
            "engine.shard_slots": CHUNK * len(seeds) * N_W,
            "engine.active_shards": int(y)}
    assert sum(d["engine.active_shards"] for d in deltas) == \
        int(np.nansum(res.ys))
    assert sum(d["engine.running_ticks"] for d in deltas) == \
        int(res.iterations.sum())


@pytest.mark.parametrize("seeds", [(0,), (0, 1)], ids=["one-cell", "grid"])
def test_skipped_shards_follow_the_path(seeds):
    """One cell branches on the mask and skips every slot it does not
    train; a vmapped grid computes every slot."""
    before = obs.snapshot()
    _two_chunks(_job(), seeds)
    after = obs.snapshot()
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in COUNTERS + ("engine.skipped_shards",)}
    skipped = delta["engine.shard_slots"] - delta["engine.active_shards"]
    assert skipped > 0
    assert delta["engine.skipped_shards"] == (skipped if len(seeds) == 1
                                              else 0)


def _host_spans(log_dir):
    path = next(iter(sorted(log_dir.rglob("*.xplane.pb"))))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("repro.", "caller"))]


def test_host_spans_nest_inside_the_callers(tmp_path):
    job = _job()
    trainer.train_zoo(job, [_scenario()], [0], n_ticks=CHUNK,
                      n_batches=J)   # compiled outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("caller"):
            trainer.train_zoo(job, [_scenario()], [0], n_ticks=CHUNK,
                              n_batches=J)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    caller = next(s for s in spans if s[0] == "caller")
    first = {}
    for name, a, b in spans:
        if name != "caller":
            assert caller[1] <= a <= b <= caller[2], name
            first.setdefault(name, a)
    assert list(sorted(first, key=first.get)) == list(SPANS)


def test_the_profiler_changes_no_bits(tmp_path):
    job = _job()
    plain, _ = _two_chunks(job)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced, _ = _two_chunks(job)
    finally:
        jax.profiler.stop_trace()
    assert plain.losses.tobytes() == traced.losses.tobytes()
    for a, b in zip(jax.tree.leaves(plain.final_state),
                    jax.tree.leaves(traced.final_state)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
