"""A run with the timed path broken underneath comes out not correct: the
harness's look for a chip is skipped and the rest of a run is driven at a
size the CPU holds, with the cell's own limits, once for each fault a
one-chip training cell can have: a step that returns its state unchanged,
half of the batch left out with the mean taken over the rest, and a
gradient altered where it is produced. (The exchange between chips is not
on a one-chip cell's path.)"""
import contextlib
import dataclasses

import pytest

import jax
import jax.numpy as jnp

from bench import run as bench_run
from bench.spans import CompileClock
from bench.tests.tiny import CELLS, tiny_cell

CLOCK = CompileClock()
PEAKS = {"bf16_flops_per_s": 1e12}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


@contextlib.contextmanager
def patched(module, name, wrap):
    """Replace ``module.name`` by ``wrap(original)`` with the program's
    cached chunk programs dropped, so the next run traces the fault."""
    from repro.train import zoo_program

    original = getattr(module, name)
    setattr(module, name, wrap(original))
    zoo_program.make_zoo_program.cache_clear()
    try:
        yield
    finally:
        setattr(module, name, original)
        zoo_program.make_zoo_program.cache_clear()


def unchanged_state(make_zoo_step):
    def make(cfg, job):
        step = make_zoo_step(cfg, job)

        def zoo_step(model, batch, mask, j):
            _, loss = step(model, batch, mask, j)
            return model, loss

        return zoo_step

    return make


def half_the_batch(elastic_token_weights):
    """Keep only the first half of the active rows; the mean is then taken
    over those."""

    def weights(active_mask, batch_size, seq_len, label_mask=None):
        w = elastic_token_weights(active_mask, batch_size, seq_len,
                                  label_mask)
        active = w[:, 0] > 0
        rank = jnp.cumsum(active)
        keep = active & (rank <= jnp.sum(active) // 2)
        return w * keep[:, None].astype(w.dtype)

    return weights


def altered_gradient(make_loss_grad):
    """The gradient of the final norm's scale comes out 1.25 times what it
    is, where the loss and gradient are produced."""

    def make(cfg, job, remat="full"):
        grad_step = make_loss_grad(cfg, job, remat)

        def altered(params, batch, mask):
            grads, loss, aux = grad_step(params, batch, mask)
            return dict(grads, ln_f=grads["ln_f"] * 1.25), loss, aux

        return altered

    return make


def _measure(name, seed):
    cell = tiny_cell(name)
    assert cell.limits is not None, f"{name} has no limits file"
    return bench_run.measure(cell, seed, 0.0, False, CLOCK, PEAKS, DEVICE,
                             t_start=0.0)[0]


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_reads_below_the_faults(name):
    """The unbroken run at this size: the active-worker counts match, and
    the gradient and change read below the cell's limits. (Its first loss
    is not held to the limit set at published widths: a 64-wide model
    rounds its loss to bf16 more coarsely.)"""
    line = _measure(name, 21)
    checks = line["checks"]
    assert list(line)[-1] == "checks"
    assert checks["y_mismatch"]["value"] == 0
    for k in ("grad_gap", "change_gap"):
        assert checks[k]["value"] < checks[k]["limit"], checks


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["unchanged_state", "half_the_batch",
                                   "altered_gradient"])
def test_fault_is_not_correct(name, fault):
    from repro.train import train_step, zoo_program

    target = {"unchanged_state": (zoo_program, "make_zoo_step"),
              "half_the_batch": (train_step, "elastic_token_weights"),
              "altered_gradient": (zoo_program, "make_loss_grad")}[fault]
    with patched(*target, globals()[fault]):
        line = _measure(name, 22)
    assert not line["correct"], line["checks"]
