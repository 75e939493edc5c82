"""The control — the reference computed with every matmul operand rounded
to float8, put in the program's place — and the half-batch fault come out
not correct under each cell's limits, and read above the program:
`bench.calibrate`'s readings at a size the CPU holds (the chip readings at
the cells' own sizes, from which the limits were set, are in PERF.md)."""
import io

import pytest

from bench import calibrate, check
from bench.tests.tiny import CELLS, tiny_cell


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_where_the_program_passes(name):
    cell = tiny_cell(name)
    recs = {r["variant"]: r for r in calibrate.readings(
        cell, [2 ** 31 + 41], 1, out=io.StringIO())}
    assert set(recs) == {"program", "fp8", "half"}

    def correct(rec):
        numbers = {k: rec[k] for k in ("loss_gap", "grad_gap",
                                       "change_gap")}
        return check.decide(numbers, cell.limits)[0]

    assert recs["program"]["y_first"] == recs["program"]["y_expected"]
    for k in ("loss_gap", "grad_gap", "change_gap"):
        assert recs["fp8"][k] > recs["program"][k], (k, recs)
    assert not correct(recs["fp8"]), recs["fp8"]
    assert not correct(recs["half"]), recs["half"]


def test_a_null_limit_is_not_compared_and_a_missing_one_fails():
    numbers = {"loss_gap": 0.5, "grad_gap": 0.01, "change_gap": 0.01,
               "y_mismatch": 0}
    limits = {"loss_gap": None, "grad_gap": 0.02, "change_gap": 0.02,
              "y_mismatch": 0}
    ok, table = check.decide(numbers, limits)
    assert ok and "loss_gap" not in table and set(table) == {
        "grad_gap", "change_gap", "y_mismatch"}
    ok, table = check.decide(numbers, {k: v for k, v in limits.items()
                                       if k != "grad_gap"})
    assert not ok and table["grad_gap"]["limit"] is None
