"""The zoo ↔ engine adapter (train/zoo_program.py), pinned three ways.

1. Parity: a real (tiny) transformer trained through the batched engine's
   scan (`trainer.train_zoo` → `make_zoo_program`) must reproduce a
   hand-rolled host loop over the same update rule under the same
   deterministic mask schedule — pinned at float32-ulp tolerance in f32
   (where the engine carry is literally `init_train_state`'s
   ``(params, opt_state)``), and atol-pinned for the bf16 mixed-precision
   carry.
2. Convention: the train-step loss/grads follow
   `core.elastic.weighted_mean`'s exact-zero convention — an all-preempted
   step is exactly 0 in value AND gradient, and the normal-path loss IS
   the weighted mean of per-token nll under the elastic token weights.
3. Durability: a bf16 zoo run killed mid-scan and resumed through the
   durable checkpoint path (`train_zoo(checkpoint_path=...)`) lands
   bit-for-bit where the uninterrupted run lands — the uint16-view bf16
   leaf round-trip in train/checkpoint.py included.
"""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.configs.base import DtypeError, InputShape, JobConfig, \
    resolve_dtype
from repro.core import elastic
from repro.models import model_zoo
from repro.sim import engine
from repro.sim.market_core import spot_active_mask
from repro.train.loss import elastic_token_weights
from repro.train.train_step import init_train_state, make_loss_grad, \
    make_train_step, worker_slices
from repro.train.trainer import resume_zoo, stack_batches, train_zoo
from repro.train.zoo_program import init_zoo_state, is_mixed_precision, \
    make_zoo_step

pytestmark = pytest.mark.zoo

J = 8
N_W = 4
BIDS = np.asarray([0.9, 0.9, 0.5, 0.5], np.float32)
# price per tick: 0.3 → all 4 active; 0.7 → the two 0.9-bidders; 0.95 →
# nobody (idle tick, must be a true no-op); cycles so the schedule mixes
# full, partial and preempted ticks
TRACE = np.asarray([0.3, 0.7, 0.95, 0.45, 0.7, 0.3, 0.95, 0.6,
                    0.3, 0.7, 0.45, 0.3, 0.7, 0.3, 0.45, 0.3], np.float32)
N_TICKS = len(TRACE)


def _tiny_cfg(**over):
    cfg = ARCHS["qwen2-7b"].reduced().with_(
        d_model=64, num_heads=2, num_kv_heads=1, d_ff=128, vocab_size=256,
        head_dim=32)
    return cfg.with_(**over) if over else cfg


def _job(cfg, b=4, s=16):
    return JobConfig(model=cfg, shape=InputShape("t", s, b, "train"),
                     n_workers=N_W, learning_rate=0.1)


def _trace_scenario():
    """Deterministic everything: tick-replayed prices (seed 0 replays the
    trace verbatim), det runtime — the mask schedule is a pure function
    of (trace, bids), so the host loop below knows it exactly."""
    return engine.Scenario(
        price=engine.PriceSpec.from_trace_ticks(TRACE), alpha=0.1,
        bid_schedule=np.tile(BIDS, (J, 1)),
        rt_kind="det", rt_const=1.0, idle_step=0.5, name="trace")


def _hand_masks():
    """The (running, mask) schedule the engine will realize on TRACE."""
    sched = []
    j = 0
    for price in TRACE:
        mask = spot_active_mask(BIDS, price).astype(np.float32)
        running = bool(mask.sum() >= 1) and j < J
        sched.append((running, mask))
        j += int(running)
    return sched


def test_trace_schedule_mixes_full_partial_idle():
    """The parity fixture actually exercises all three tick kinds."""
    ys = [m.sum() for run, m in _hand_masks() if run]
    idle = [1 for run, _ in _hand_masks() if not run]
    assert 4.0 in ys and 2.0 in ys and idle


def test_zoo_engine_matches_plain_loop_f32():
    """f32 zoo carry through the engine scan == a hand-rolled
    make_train_step loop under the same mask schedule, pinned at
    float32-ulp tolerance (the engine's scan refuses the exact fusion
    order of the host loop, so last-ulp drift is the floor). A one-cell run
    steps on per-worker slices, so the loop feeds the same slices."""
    cfg = _tiny_cfg()
    job = _job(cfg)
    res = train_zoo(job, [_trace_scenario()], seeds=[0], n_ticks=N_TICKS,
                    donate=False)

    params, opt_state = init_train_state(cfg, job, jax.random.PRNGKey(0))
    data = stack_batches(job, J, seed=0)
    step = jax.jit(make_train_step(cfg, job, remat="none"))
    losses = []
    j = 0
    for running, mask in _hand_masks():
        if not running:
            continue
        batch = worker_slices(
            jax.tree.map(lambda x: np.asarray(x)[j % J], data), N_W)
        params, opt_state, metrics = step(params, opt_state, batch,
                                          jnp.asarray(mask),
                                          jnp.asarray(j, jnp.int32))
        losses.append(float(metrics["loss"]))
        j += 1

    assert int(res.iterations[0, 0]) == j == J
    np.testing.assert_allclose(res.losses[0, 0, :j],
                               np.asarray(losses, np.float32),
                               rtol=1e-6, atol=1e-6)
    eng_params = jax.tree.map(lambda x: np.asarray(x)[0, 0],
                              res.final_model[0])
    for a, b in zip(jax.tree.leaves(eng_params), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_zoo_engine_matches_plain_loop_bf16():
    """bf16 mixed-precision carry: the engine run is pinned (small atol —
    the only difference is scan batching of bf16 ops) against an
    independent host loop over the same `make_zoo_step` update rule, fed
    the per-worker slices a one-cell run steps on."""
    cfg = _tiny_cfg(dtype="bfloat16", param_dtype="bfloat16")
    assert is_mixed_precision(cfg)
    job = _job(cfg)
    res = train_zoo(job, [_trace_scenario()], seeds=[0], n_ticks=N_TICKS,
                    donate=False)

    model = init_zoo_state(cfg, job, jax.random.PRNGKey(0))
    data = stack_batches(job, J, seed=0)
    step = jax.jit(make_zoo_step(cfg, job))
    losses = []
    j = 0
    for running, mask in _hand_masks():
        if not running:
            continue
        batch = worker_slices(
            jax.tree.map(lambda x: np.asarray(x)[j % J], data), N_W)
        model, loss = step(model, batch, jnp.asarray(mask),
                           jnp.asarray(j, jnp.int32))
        losses.append(float(loss))
        j += 1

    assert int(res.iterations[0, 0]) == j == J
    np.testing.assert_allclose(res.losses[0, 0, :j],
                               np.asarray(losses, np.float32),
                               rtol=0, atol=1e-5)
    assert jax.tree.leaves(model["params"])[0].dtype == jnp.bfloat16
    assert jax.tree.leaves(model["master"])[0].dtype == jnp.float32
    eng = jax.tree.map(lambda x: np.asarray(x)[0, 0],
                       res.final_model["master"])
    for a, b in zip(jax.tree.leaves(eng),
                    jax.tree.leaves(model["master"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# the weighted_mean convention, pinned at the train-step denominator
# ---------------------------------------------------------------------------


def _one_batch(job):
    return jax.tree.map(lambda x: np.asarray(x)[0],
                        stack_batches(job, 1, seed=3))


def test_all_preempted_step_is_exact_zero():
    """Σw = 0: loss AND every gradient leaf are exactly 0 — the same
    convention as `core.elastic.weighted_mean`, not an ε-scaled residue."""
    cfg = _tiny_cfg()
    job = _job(cfg)
    params, _ = init_train_state(cfg, job, jax.random.PRNGKey(1))
    grad_step = make_loss_grad(cfg, job, remat="none")
    grads, loss, _ = grad_step(params, _one_batch(job),
                               jnp.zeros((N_W,), jnp.float32))
    assert float(loss) == 0.0
    for g in jax.tree.leaves(grads):
        assert float(jnp.abs(g).max()) == 0.0


@pytest.mark.parametrize("mask", [(1, 1, 1, 1), (1, 1, 0, 0),
                                  (0.5, 0.25, 0.0, 1.0)])
def test_loss_is_weighted_mean_of_token_nll(mask):
    """The train-step loss IS elastic.weighted_mean(per-token nll, elastic
    token weights) — including fractional (importance-scaled) masks, where
    an ε-clamped denominator would silently rescale."""
    cfg = _tiny_cfg()
    job = _job(cfg)
    params, _ = init_train_state(cfg, job, jax.random.PRNGKey(1))
    batch = _one_batch(job)
    m = jnp.asarray(mask, jnp.float32)
    _, loss, _ = make_loss_grad(cfg, job, remat="none")(params, batch, m)

    logits, _ = model_zoo.forward(params, cfg, batch, remat="none")
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32),
                               batch["labels"][..., None], axis=-1)[..., 0]
    b, s = batch["tokens"].shape
    w = elastic_token_weights(m, b, s).astype(jnp.float32)
    np.testing.assert_allclose(float(loss),
                               float(elastic.weighted_mean(lse - gold, w)),
                               rtol=0, atol=1e-6)


def test_microbatch_path_shares_the_convention():
    """Gradient accumulation normalizes by the same Σw-or-1 denominator:
    microbatched and single-shot grads/loss agree, and the all-preempted
    microbatch run is still exactly 0."""
    import dataclasses

    cfg = _tiny_cfg()
    job1 = _job(cfg)
    job = dataclasses.replace(job1, microbatch=2)
    params, _ = init_train_state(cfg, job1, jax.random.PRNGKey(1))
    batch = _one_batch(job1)
    m = jnp.asarray([1, 0, 1, 1], jnp.float32)
    g2, l2, _ = make_loss_grad(cfg, job, remat="none")(params, batch, m)
    g1, l1, _ = make_loss_grad(cfg, job1, remat="none")(params, batch, m)
    np.testing.assert_allclose(float(l2), float(l1), rtol=0, atol=1e-6)
    for a, b in zip(jax.tree.leaves(g2), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-5)
    _, l0, _ = make_loss_grad(cfg, job, remat="none")(
        params, batch, jnp.zeros((N_W,), jnp.float32))
    assert float(l0) == 0.0


def test_resolve_dtype_raises_named_error():
    with pytest.raises(DtypeError, match="bfloat17"):
        resolve_dtype("bfloat17", where="test")
    with pytest.raises(DtypeError):
        is_mixed_precision(_tiny_cfg(param_dtype="not-a-dtype"))


# ---------------------------------------------------------------------------
# durable bf16 checkpoints: kill, resume, land bit-exact
# ---------------------------------------------------------------------------


def _assert_results_bitexact(a, b):
    np.testing.assert_array_equal(a.losses, b.losses)
    np.testing.assert_array_equal(a.costs, b.costs)
    np.testing.assert_array_equal(a.iterations, b.iterations)
    np.testing.assert_array_equal(a.total_cost, b.total_cost)
    for la, lb in zip(jax.tree.leaves(a.final_model),
                      jax.tree.leaves(b.final_model)):
        assert np.asarray(la).dtype == np.asarray(lb).dtype
        np.testing.assert_array_equal(
            np.asarray(jnp.asarray(la).astype(jnp.float32)),
            np.asarray(jnp.asarray(lb).astype(jnp.float32)))


def _uniform_grid():
    return [engine.Scenario(
        price=engine.PriceSpec.uniform(0.2, 1.0), alpha=0.1,
        bid_schedule=np.tile(BIDS, (J, 1)), rt_kind="exp", rt_lam=2.0,
        rt_delta=0.05, idle_step=0.5, name=f"g{i}") for i in range(2)]


def test_zoo_bf16_kill_and_resume_is_bitexact(tmp_path):
    """A bf16 zoo run driven through the durable path, killed after a
    truncated tick budget, resumes from its .npz (bf16 leaves stored as
    uint16 views) and finishes bit-identical to the uninterrupted run."""
    cfg = _tiny_cfg(dtype="bfloat16", param_dtype="bfloat16")
    job = _job(cfg)
    scenarios, seeds, n_ticks = _uniform_grid(), [0, 1], 20

    full = train_zoo(job, scenarios, seeds=seeds, n_ticks=n_ticks,
                     donate=False)

    # durable single pass lands where the plain call lands
    path = str(tmp_path / "zoo.npz")
    durable = train_zoo(job, scenarios, seeds=seeds, n_ticks=n_ticks,
                        checkpoint_path=path, save_every=6)
    _assert_results_bitexact(durable, full)

    # "kill" after 8 ticks, then resume to the full budget
    path2 = str(tmp_path / "killed.npz")
    train_zoo(job, scenarios, seeds=seeds, n_ticks=8,
              checkpoint_path=path2, save_every=4)
    state, tick = resume_zoo(path2, job, scenarios, seeds)
    assert tick == 8
    # restored carry kept its mixed dtypes through the npz round-trip
    assert jax.tree.leaves(state.model["params"])[0].dtype == jnp.bfloat16
    assert jax.tree.leaves(state.model["master"])[0].dtype == jnp.float32
    resumed = train_zoo(job, scenarios, seeds=seeds, n_ticks=n_ticks,
                        checkpoint_path=path2, save_every=4)
    _assert_results_bitexact(resumed, full)


def test_train_zoo_requires_cadence_with_checkpoint():
    cfg = _tiny_cfg()
    with pytest.raises(ValueError, match="save_every"):
        train_zoo(_job(cfg), _uniform_grid(), seeds=[0],
                  checkpoint_path="/tmp/nope.npz")


# ---------------------------------------------------------------------------
# per-worker slices: only the active workers' shards are computed
# ---------------------------------------------------------------------------

MASKS = {"all": (1, 1, 1, 1), "half": (1, 1, 0, 0), "one": (0, 0, 1, 0),
         "none": (0, 0, 0, 0)}
#: (rtol, atol): the file's f32 pin, and its bf16 atol with an rtol of two
#: bf16 roundings (machine epsilon 2^-7) for quantities rounded once
TOL = {"float32": (1e-6, 1e-6), "bfloat16": (2 ** -6, 1e-5)}


def _assert_leaf_close(a, b, rtol, atol):
    """|a − b| ≤ rtol · max|b| + atol over one leaf. The scale is the
    leaf's largest entry, not each entry: slices of opposite sign cancel,
    and an entry near zero keeps the rounding of the larger terms."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    assert np.max(np.abs(a - b)) <= rtol * np.max(np.abs(b)) + atol, (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


def _worst_error(tree, ref):
    """The largest over leaves of max|a − r| / max|r|."""
    return max(float(np.max(np.abs(np.asarray(a, np.float32) - r)) /
                     max(float(np.max(np.abs(r))), 1e-30))
               for a, r in zip(jax.tree.leaves(tree),
                               jax.tree.leaves(ref)))


@functools.lru_cache(maxsize=None)
def _slice_programs(dtype):
    """(cfg, job, grad step, zoo step), jitted once per dtype: the mask is
    data, and the batch's layout picks the path."""
    cfg = _tiny_cfg(dtype=dtype, param_dtype=dtype)
    job = _job(cfg)
    return (cfg, job, jax.jit(make_loss_grad(cfg, job, remat="none")),
            jax.jit(make_zoo_step(cfg, job)))


@pytest.mark.parametrize("mask", list(MASKS), ids=list(MASKS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_worker_slices_match_whole_batch(dtype, mask):
    """Gradient, loss and one zoo update from the per-worker conditional
    slices against the whole batch's, for every active-worker pattern: in
    float32 equal to the file's ulp pin. In bf16 each path's backward
    rounds its own partial sums, so both are held to the float32 gradient
    at the same bf16 parameters, and the slices (rounded per slice, summed
    in float32) must be as near it as the whole batch, within a quarter."""
    cfg, job, grad_step, zoo_step = _slice_programs(dtype)
    rtol, atol = TOL[dtype]
    model = init_zoo_state(cfg, job, jax.random.PRNGKey(1))
    params = model[0] if dtype == "float32" else model["params"]
    batch = _one_batch(job)
    slices = worker_slices(batch, N_W)
    m = jnp.asarray(MASKS[mask], jnp.float32)

    grads, loss, _ = grad_step(params, slices, m)
    ref_grads, ref_loss, _ = grad_step(params, batch, m)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=rtol,
                               atol=atol)
    assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(grads))
    if dtype == "bfloat16":
        _, _, f32_step, _ = _slice_programs("float32")
        want, _, _ = f32_step(
            jax.tree.map(lambda p: p.astype(jnp.float32), params), batch, m)
        assert (_worst_error(grads, want) <=
                1.25 * _worst_error(ref_grads, want) + atol)
        return
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        _assert_leaf_close(a, b, rtol, atol)

    # the update the engine lands, against the whole batch's: the
    # parameters' change, so the tolerance applies to lr · g
    j0 = jnp.asarray(0, jnp.int32)
    (got, _), (want, _) = [zoo_step(model, b, m, j0)
                           for b in (slices, batch)]
    for a, b, o in zip(jax.tree.leaves(got[0]), jax.tree.leaves(want[0]),
                       jax.tree.leaves(model[0])):
        _assert_leaf_close(a - o, b - o, rtol, atol)


@pytest.mark.parametrize("layout", ["whole", "slices"])
def test_only_worker_slices_branch(layout):
    """A dict batch is one forward and backward with no conditional (the
    dry run and vmapped grids lower it, where a per-worker loop would run
    the workers one after another); `WorkerSlices` branch per worker."""
    cfg, job, _, _ = _slice_programs("float32")
    params, _ = init_train_state(cfg, job, jax.random.PRNGKey(1))
    batch = _one_batch(job)
    if layout == "slices":
        batch = worker_slices(batch, N_W)
    text = jax.jit(make_loss_grad(cfg, job, remat="none")).lower(
        params, batch, jnp.ones((N_W,), jnp.float32)).as_text()
    assert ("stablehlo.case" in text) == (layout == "slices")


def _tiny_moe_cfg():
    return ARCHS["qwen2-moe-a2.7b"].reduced().with_(
        d_model=64, num_heads=2, num_kv_heads=1, d_ff=64, vocab_size=256,
        head_dim=32, moe=dataclasses.replace(
            ARCHS["qwen2-moe-a2.7b"].moe, num_experts=4,
            num_experts_unpadded=4, top_k=2, d_ff_expert=32,
            num_shared_experts=1, d_ff_shared=32))


@pytest.mark.parametrize("mask", ["all", "half", "one"])
def test_moe_slices_route_alone(mask):
    """An MoE model's slices each route alone: the sliced loss and
    gradient are the Σw-weighted mean of each active slice's own
    whole-batch step, and ``aux`` the mean of those slices' aux terms."""
    cfg = _tiny_moe_cfg()
    job = _job(cfg, b=8)
    params, _ = init_train_state(cfg, job, jax.random.PRNGKey(1))
    batch = _one_batch(job)
    m = np.asarray(MASKS[mask], np.float32)
    grad_step = jax.jit(make_loss_grad(cfg, job, remat="none"))
    grads, loss, aux = grad_step(params, worker_slices(batch, N_W),
                                 jnp.asarray(m))

    per = 8 // N_W
    terms = []
    for i in np.flatnonzero(m):
        one = jax.tree.map(lambda x: x[i * per:(i + 1) * per], batch)
        g_i, l_i, a_i = grad_step(params, one, jnp.asarray(m[i:i + 1]))
        w_i = float(elastic_token_weights(jnp.asarray(m[i:i + 1]), per,
                                          one["labels"].shape[1]).sum())
        terms.append((w_i, g_i, float(l_i), float(a_i)))
    w_sum = sum(t[0] for t in terms)
    np.testing.assert_allclose(
        float(loss), sum(w * l for w, _, l, _ in terms) / w_sum, rtol=1e-6)
    np.testing.assert_allclose(float(aux),
                               np.mean([a for *_, a in terms]), rtol=1e-6)
    want = jax.tree.map(lambda *g: sum(w * x for (w, *_), x in
                                       zip(terms, g)) / w_sum,
                        *[t[1] for t in terms])
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        _assert_leaf_close(a, b, *TOL["float32"])


@pytest.mark.parametrize("vmapped", [False, True], ids=["cond", "select"])
def test_masked_out_slice_keeps_non_finite_input_out(vmapped):
    """A NaN in a preempted worker's patches reaches neither the gradient
    nor the loss of the sliced step, under a real ``cond`` and under
    vmap's ``select``."""
    cfg = ARCHS["internvl2-1b"].reduced().with_(
        d_model=64, num_heads=2, num_kv_heads=1, d_ff=128, vocab_size=256,
        head_dim=32, vision=dataclasses.replace(
            ARCHS["internvl2-1b"].vision, num_patches=4))
    job = _job(cfg)
    params, _ = init_train_state(cfg, job, jax.random.PRNGKey(1))
    batch = _one_batch(job)
    m = jnp.asarray([1, 1, 0, 1], jnp.float32)
    poisoned = dict(batch, patches=np.asarray(batch["patches"]).copy())
    poisoned["patches"][2] = np.nan          # worker 2's only row
    grad_step = make_loss_grad(cfg, job, remat="none")
    if vmapped:
        # a batched mask: each slice's ``cond`` becomes a select
        two = jax.jit(jax.vmap(
            lambda p, b, mm: grad_step(p, worker_slices(b, N_W), mm),
            in_axes=(None, 0, 0)))
        (g, loss, _), (g0, loss0, _) = [
            jax.tree.map(lambda x: x[0], two(
                params, jax.tree.map(lambda *x: np.stack(x), b, batch),
                jnp.stack([m, m])))
            for b in (poisoned, batch)]
    else:
        g, loss, _ = jax.jit(grad_step)(params,
                                        worker_slices(poisoned, N_W), m)
        g0, loss0, _ = jax.jit(grad_step)(params,
                                          worker_slices(batch, N_W), m)
    assert np.isfinite(float(loss)) and float(loss) == float(loss0)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("seeds", [[0], [0, 1]], ids=["one-cell", "grid"])
def test_idle_ticks_leave_the_carry_bit_equal(seeds):
    """Prices above every bid: no tick runs, and the carry comes back
    bit-equal, whether the tick branches (one cell) or selects (a grid)."""
    cfg = _tiny_cfg(dtype="bfloat16", param_dtype="bfloat16")
    job = _job(cfg)
    sc = engine.Scenario(
        price=engine.PriceSpec.from_trace_ticks(np.full(4, 0.95,
                                                        np.float32)),
        alpha=0.1, bid_schedule=np.tile(BIDS, (J, 1)), rt_kind="det",
        rt_const=1.0, idle_step=0.5, name="idle")
    res = train_zoo(job, [sc], seeds=seeds, n_ticks=4, donate=False)
    assert int(res.iterations.sum()) == 0
    model = init_zoo_state(cfg, job, jax.random.PRNGKey(job.seed))
    for a, b in zip(jax.tree.leaves(res.final_model),
                    jax.tree.leaves(model)):
        for r in range(len(seeds)):
            assert np.asarray(a)[0, r].tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_cell_run_matches_its_cell_in_a_grid(dtype):
    """S = R = 1 runs unvmapped on per-worker slices; the same (scenario,
    seed) inside a 2-seed grid is vmapped over the whole batch. On the
    parity trace both realize the same masks and iterations exactly, and
    the first loss agrees within the slices' tolerances; in float32 so
    does the carry after the first tick. (Later steps start from carries
    that differ by that rounding, so they are pinned against host loops
    instead.)"""
    cfg = _tiny_cfg(dtype=dtype, param_dtype=dtype)
    job = _job(cfg)
    rtol, atol = TOL[dtype]
    one, grid = [train_zoo(job, [_trace_scenario()], seeds=seeds,
                           n_ticks=N_TICKS, donate=False)
                 for seeds in ([0], [0, 1])]
    np.testing.assert_array_equal(one.ys[0, 0], grid.ys[0, 0])
    np.testing.assert_array_equal(one.iterations[0, 0],
                                  grid.iterations[0, 0])
    np.testing.assert_allclose(one.losses[0, 0, 0], grid.losses[0, 0, 0],
                               rtol=rtol, atol=atol)
    if dtype == "bfloat16":
        return          # the bf16 step's precision: the test above
    one, grid = [train_zoo(job, [_trace_scenario()], seeds=seeds,
                           n_ticks=1, donate=False)
                 for seeds in ([0], [0, 1])]
    assert int(one.iterations[0, 0]) == 1
    model0 = init_zoo_state(cfg, job, jax.random.PRNGKey(job.seed))
    for a, b, o in zip(jax.tree.leaves(one.final_model),
                       jax.tree.leaves(grid.final_model),
                       jax.tree.leaves(model0)):
        _assert_leaf_close(np.asarray(a)[0, 0] - o,
                           np.asarray(b)[0, 0] - o, rtol, atol)
