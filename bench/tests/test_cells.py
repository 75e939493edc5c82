"""Each cell's set-up, chunk loop and token count at a reduced size,
through the harness's own functions."""
import numpy as np
import pytest

from bench import check, train_cell
from bench.spans import CompileClock
from bench.tests.tiny import CELLS, tiny_cell

CLOCK = CompileClock()


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_counts_only_useful_tokens(name):
    cell = tiny_cell(name)
    plan = train_cell.make_plan(cell.config, cell.traffic, 2 ** 31 + 99)
    run, ref = train_cell.run_cell(plan, 0.0, lambda: CLOCK.compiles, 0.0)
    lay = cell.config["layout"]
    # a window of one whole chunk, after the two set-up chunks
    assert run.chunks == 1
    expected = train_cell.expected_iterations(plan, 2 * plan.chunk,
                                              3 * plan.chunk)
    assert np.array_equal(run.y_window, expected)
    assert run.iterations == len(expected)
    rows = lay["global_batch"] // lay["n_workers"]
    assert run.tokens == int(expected.sum()) * rows * lay["seq_len"]
    assert run.compiles_in_window == 0
    assert run.nonfinite == 0
    assert run.counters is None       # taken in a traced run only
    assert check.y_mismatch(run) == 0
    gaps = check.gaps(run.readings, ref)
    assert max(gaps.values()) < 0.02, gaps


def test_spot_counts_differ_from_every_shard_counted():
    cell = tiny_cell("internvl2-1b.spot")
    plan = train_cell.make_plan(cell.config, cell.traffic, 7)
    y = train_cell.expected_iterations(plan, 0, plan.chunk)
    # 7 of 8 ticks run; 4 with half the workers, 3 with all of them
    assert sorted(y.tolist()) == [2, 2, 2, 2, 4, 4, 4]


def test_traced_window_counts_its_own_shard_slots(tmp_path):
    """A traced run takes the program's counters around the window alone:
    one chunk of 8 ticks × 4 workers, none of the set-up's."""
    cell = tiny_cell("internvl2-1b.spot")
    plan = train_cell.make_plan(cell.config, cell.traffic, 2 ** 31 + 98)
    run, _ = train_cell.run_cell(plan, 0.0, lambda: CLOCK.compiles, 0.0,
                                 trace_dir=str(tmp_path))
    expected = train_cell.expected_iterations(plan, 2 * plan.chunk,
                                              3 * plan.chunk)
    c = run.counters
    assert c["engine.ticks"] == plan.chunk
    assert c["engine.shard_slots"] == plan.chunk * 4
    assert c["engine.running_ticks"] == len(expected)
    assert c["engine.active_shards"] == int(expected.sum())
    assert c["engine.skipped_shards"] == \
        c["engine.shard_slots"] - c["engine.active_shards"]
