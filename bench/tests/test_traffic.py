"""The spot mix's stratification and the active masks it gives."""
import numpy as np
import pytest

from bench import traffic
from bench.spec import load_cell

SEEDS = (0, 1, 12345, 2 ** 31 + 17, 2 ** 33 + 5)


@pytest.mark.parametrize("seed", SEEDS)
def test_spot_blocks_hold_exact_shares(seed):
    mix = load_cell("internvl2-1b.spot").traffic
    prices = traffic.price_trace(mix, seed, 8 * 64).reshape(-1, 8)
    y = traffic.active_masks(mix, prices.reshape(-1)).sum(1).reshape(-1, 8)
    assert np.all((y == 0).sum(1) == 1)        # one idle tick per block
    assert np.all((y == 2).sum(1) == 4)        # four half-preempted
    assert np.all((y == 4).sum(1) == 3)        # three with every worker
    assert prices.min() > 0.2 and prices.max() < 1.0


def test_seed_orders_the_ticks_and_repeats():
    mix = load_cell("internvl2-1b.spot").traffic
    a = traffic.price_trace(mix, 5, 64)
    assert np.array_equal(a, traffic.price_trace(mix, 5, 64))
    assert not np.array_equal(a, traffic.price_trace(mix, 6, 64))


def test_ondemand_never_preempts():
    mix = load_cell("internvl2-1b.spot").traffic
    mix = dict(mix, bids=[1.0] * 4)
    masks = traffic.active_masks(mix, traffic.price_trace(mix, 3, 256))
    assert masks.all()


def test_strata_must_match_the_uniform_distribution():
    mix = load_cell("internvl2-1b.spot").traffic
    bad = dict(mix, price=dict(mix["price"], strata=[
        {"lo": 0.2, "hi": 0.5, "count": 4},
        {"lo": 0.5, "hi": 0.9, "count": 3},
        {"lo": 0.9, "hi": 1.0, "count": 1}]))
    with pytest.raises(ValueError):
        traffic.check_mix(bad)


def test_iteration_masks_follow_chunk_targets():
    masks = np.array([[0, 0], [1, 0], [1, 1], [0, 0],
                      [1, 1], [0, 0], [1, 0], [1, 1]], bool)
    got = traffic.iteration_masks(masks, (1, 3), chunk=4)
    assert [m.tolist() for m in got] == [[True, False], [True, True],
                                         [True, False]]


def test_batches_repeat_by_seed_and_index():
    cell = load_cell("internvl2-1b.spot")
    a = traffic.batch(cell.config["model"], cell.config["layout"], 9, 2)
    b = traffic.batch(cell.config["model"], cell.config["layout"], 9, 2)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert a["tokens"].shape == (8, 768) and a["patches"].shape == (8, 256,
                                                                    896)
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
