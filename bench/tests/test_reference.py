"""The plain references against the program at a small size on the CPU,
in float32: one step of the program's zoo update from the benchmark's
weights lands where one reference step does."""
import copy

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import traffic, train_cell
from bench.reference import common, mamba2
from bench.tests.tiny import CELLS, tiny_cell


@pytest.mark.parametrize("name", CELLS)
def test_reference_step_matches_zoo_step(name):
    from repro.train.zoo_program import make_zoo_step

    cell = tiny_cell(name)
    config = copy.deepcopy(cell.config)
    config["model"].update(dtype="float32", param_dtype="float32")
    plan = train_cell.make_plan(config, cell.traffic, 11)
    job = plan.job
    mod = train_cell.reference_module(config)
    w = common.make_params(plan.spec, common.seed_key(11))
    batch = plan.feed[0]
    mask = np.array([1, 1, 0, 1], np.float32)
    step = make_zoo_step(job.model, job)
    opt = jax.tree.map(jnp.zeros_like, w)
    (new, _), loss = jax.jit(step)((w, opt), batch, mask, 0)

    lr = config["layout"]["learning_rate"]
    rows_per = plan.rows_per_worker
    rows = [r for r in range(len(mask) * rows_per) if mask[r // rows_per]]
    loss_fn = mod.row_loss(config["model"])
    with jax.default_matmul_precision("highest"):
        total, grads = 0.0, jax.tree.map(jnp.zeros_like, w)
        for r in rows:
            nll, g = jax.value_and_grad(loss_fn)(
                w, {k: jnp.asarray(v[r]) for k, v in batch.items()})
            total += nll
            grads = jax.tree.map(jnp.add, grads, g)
    denom = len(rows) * batch["labels"].shape[1]
    assert float(loss) == pytest.approx(float(total) / denom, rel=1e-5)
    want = jax.tree.map(lambda p, g: p - lr * g / denom, w, grads)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(new)[0],
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6,
                                   err_msg=jax.tree_util.keystr(path))


def test_minimal_ssd_equals_the_recurrence():
    rng = np.random.default_rng(0)
    length, heads, p, n = 12, 2, 3, 4
    x = rng.normal(size=(length, heads, p)).astype(np.float32)
    a = -rng.uniform(0.1, 1.0, (length, heads)).astype(np.float32)
    b = rng.normal(size=(length, 1, n)).astype(np.float32)
    c = rng.normal(size=(length, 1, n)).astype(np.float32)
    got = mamba2.ssd(jnp.asarray(x), jnp.asarray(a), jnp.asarray(b),
                     jnp.asarray(c), block=4)
    h = np.zeros((heads, p, n))
    want = np.zeros_like(x)
    for t in range(length):
        h = h * np.exp(a[t])[:, None, None] + x[t][..., None] * b[t, 0]
        want[t] = h @ c[t, 0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_fp8_rounds_and_saturates():
    x = jnp.asarray([1.0, 1.0626, 1e6, -1e6], jnp.float32)
    got = np.asarray(common.fp8(x))
    assert got[0] == 1.0 and got[1] in (1.0, 1.125)
    assert got[2] == 448.0 and got[3] == -448.0


def test_seed_keys_cover_large_seeds():
    a = common.seed_key(2 ** 31 + 5)
    b = common.seed_key(2 ** 40 + 5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        common.seed_key(-1)


def test_prices_stay_off_the_bids():
    mix = tiny_cell("internvl2-1b.spot").traffic
    prices = traffic.price_trace(mix, 3, 800)
    gap = np.min(np.abs(prices[:, None] - np.asarray(mix["bids"])))
    assert gap >= traffic.EDGE
