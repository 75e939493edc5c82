"""Elastic train/serve step builders — shared by the CPU trainer, the smoke
tests, and the multi-pod dry-run (which lowers these exact functions)."""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import JobConfig, ModelConfig
from repro.models import model_zoo
from repro.models.common import shard
from repro.optim.sgd import constant_lr, get_optimizer
from repro.train.loss import elastic_token_weights, next_token_loss


class WorkerSlices(NamedTuple):
    """A global batch cut into one contiguous slice per elastic worker
    (`core.elastic.example_weights`' layout): every leaf of ``batch`` has
    a leading ``(n_workers,)`` axis. `make_loss_grad`'s step computes such
    a batch slice by slice, each under ``lax.cond`` on its worker's mask,
    so a preempted worker's slice is not computed at all. Only an
    unvmapped caller gains from it: under ``vmap`` a ``cond`` on a batched
    mask is a select that computes both branches."""

    batch: Dict


def worker_slices(batch: Dict, n_workers: int) -> WorkerSlices:
    """``batch`` cut into `WorkerSlices` of its ``B // n_workers`` rows."""
    def cut(x):
        assert x.shape[0] % n_workers == 0, (x.shape, n_workers)
        return x.reshape((n_workers, x.shape[0] // n_workers) + x.shape[1:])

    return WorkerSlices({k: cut(v) for k, v in batch.items()})


def make_loss_grad(cfg: ModelConfig, job: JobConfig, remat: str = "full"):
    """Returns grad_step(params, batch, active_mask) -> (grads, loss, aux).

    The loss/grad core shared by ``make_train_step`` (f32 training) and
    ``train/zoo_program.make_zoo_program`` (mixed-precision engine path):
    per-worker token weights from the elastic ``active_mask``, masked-mean
    normalization with `core.elastic.weighted_mean`'s exact-zero convention
    (Σw=0 → loss 0, grads 0; denominator ``where(Σw>0, Σw, 1)``), and
    optional gradient accumulation over ``job.microbatch`` micro-slices.

    A ``batch`` given as `WorkerSlices` runs the same accumulation with one
    slice per worker, each under ``lax.cond`` on its worker's mask: only
    the active workers' slices are computed, and nothing a skipped slice
    holds (a non-finite input included) reaches the result. The gradient
    sums in float32, and ``job.microbatch`` adds nothing: a worker's slice
    is no larger than any microbatch, which holds whole workers. An MoE
    model then routes each slice alone, with its expert capacity from the
    slice's tokens; the loss adds each slice's aux term weighted by its Σw,
    and the returned ``aux`` is the mean over the slices that ran (0 when
    none did), where the whole batch routes once.
    """
    n_micro = max(job.microbatch, 1)
    aux_w = cfg.moe.aux_loss_weight if cfg.moe is not None else 0.0

    def _losses(p, batch, active_mask, b):
        """(weighted nll sum, weight sum, aux) for one (micro)batch —
        sum-form so microbatch accumulation is exactly the full-batch
        masked mean of Eq. (5)."""
        logits, aux = model_zoo.forward(p, cfg, batch, remat=remat)
        with obs.scope("repro.loss_head"):
            if cfg.family == "vlm":
                logits_txt = logits[:, cfg.vision.num_patches:]
            else:
                logits_txt = logits
            labels = batch["labels"]
            s = labels.shape[1]
            w = elastic_token_weights(active_mask, b, s,
                                      batch.get("label_mask"))
            w = shard(w, "batch", None)
            lse = jax.nn.logsumexp(logits_txt.astype(jnp.float32), axis=-1)
            gold = jnp.take_along_axis(logits_txt.astype(jnp.float32),
                                       labels[..., None], axis=-1)[..., 0]
            nll_sum = ((lse - gold) * w.astype(jnp.float32)).sum()
            w_sum = w.astype(jnp.float32).sum()
        return nll_sum, w_sum, aux

    def accumulate(params, micro, mask_micro, branch):
        """Scan the slices of ``micro`` (leading slice axis), summing the
        gradient of the SUM; normalization by Σw comes at the end. With
        ``branch`` a slice whose workers are all preempted is skipped."""
        mb = micro["labels"].shape[1]

        def run(carry, mbatch, mmask):
            g_acc, nll_acc, w_acc, aux_acc, n_ran = carry

            def f(p):
                nll, w_sum, aux = _losses(p, mbatch, mmask, mb)
                # fold the aux loss in sum-form (× w_sum) so dividing by
                # the global Σw yields CE + aux_w·weighted-mean(aux)
                return nll + aux_w * aux * w_sum, (w_sum, aux)

            (obj, (w_sum, aux)), g = jax.value_and_grad(
                f, has_aux=True)(params)
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            return (g_acc, nll_acc + obj, w_acc + w_sum, aux_acc + aux,
                    n_ran + 1.0)

        def scan_body(carry, xs):
            mbatch, mmask = xs
            if branch:
                return jax.lax.cond(jnp.any(mmask > 0),
                                    lambda c: run(c, mbatch, mmask),
                                    lambda c: c, carry), None
            return run(carry, mbatch, mmask), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        f32 = jnp.zeros((), jnp.float32)
        (g_sum, nll_sum, w_sum, aux_sum, n_ran), _ = jax.lax.scan(
            scan_body, (zeros, f32, f32, f32, f32), (micro, mask_micro))
        # weighted_mean's exact-zero convention: at Σw=0 the nll/grad
        # sums are identically 0, and denom 1 keeps them exactly 0
        denom = jnp.where(w_sum > 0, w_sum, 1.0)
        grads = jax.tree.map(lambda g: g / denom, g_sum)
        aux = aux_sum / jnp.maximum(n_ran, 1.0)
        loss = jnp.where(w_sum > 0, nll_sum / denom, 0.0)
        return grads, loss, aux

    def grad_step(params, batch: Dict, active_mask):
        if isinstance(batch, WorkerSlices):
            return accumulate(params, batch.batch, active_mask[:, None],
                              branch=True)

        tokens = batch["tokens"]
        b = tokens.shape[0]

        if n_micro == 1:
            def loss_fn(p):
                nll_sum, w_sum, aux = _losses(p, batch, active_mask, b)
                loss = nll_sum / jnp.where(w_sum > 0, w_sum, 1.0)
                if cfg.moe is not None:
                    loss = loss + cfg.moe.aux_loss_weight * aux
                # exact 0 (value and grads, incl. the MoE router through
                # the aux term) when every worker is preempted — the
                # mechanism behind core.elastic.weighted_mean, and the
                # same semantics as the microbatch path's aux·w_sum fold
                return jnp.where(w_sum > 0, loss, 0.0), aux

            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params)
            return grads, loss, aux

        assert b % n_micro == 0, (b, n_micro)
        mb = b // n_micro
        micro = {k: v.reshape((n_micro, mb) + v.shape[1:])
                 for k, v in batch.items()}
        n_w = active_mask.shape[0]
        assert n_w % n_micro == 0, (
            "n_workers must split evenly across microbatches so worker "
            "slices stay contiguous", n_w, n_micro)
        mask_micro = active_mask.reshape(n_micro, n_w // n_micro)
        return accumulate(params, micro, mask_micro, branch=False)

    return grad_step


def make_train_step(cfg: ModelConfig, job: JobConfig,
                    lr_fn: Optional[Callable] = None, remat: str = "full"):
    """Returns train_step(params, opt_state, batch, active_mask, step).

    batch: tokens (B,S), labels (B,S), optional label_mask (B,S), frames /
    patches for encdec / vlm. active_mask: (n_workers,) float — the elastic
    worker mask (Eq. (5) with y_j = Σ mask).
    """
    opt = get_optimizer(job.optimizer, job.momentum)
    lr_fn = lr_fn or constant_lr(job.learning_rate)
    grad_step = make_loss_grad(cfg, job, remat)

    def train_step(params, opt_state, batch: Dict, active_mask, step):
        grads, loss, aux = grad_step(params, batch, active_mask)
        lr = lr_fn(step)
        new_params, new_opt = opt.update(grads, opt_state, params, lr)
        metrics = {
            "loss": loss,
            "moe_aux": aux,
            "active_workers": active_mask.sum(),
            "lr": lr,
        }
        return new_params, new_opt, metrics

    return train_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        logits, _ = model_zoo.forward(params, cfg, batch, remat="none")
        if cfg.family == "vlm":
            logits = logits[:, cfg.vision.num_patches:]
        return next_token_loss(logits, batch["labels"],
                               batch.get("label_mask"))

    return eval_step


def make_serve_step(cfg: ModelConfig):
    """One decode step: greedy next token + updated caches. This is the
    function the decode_* dry-run shapes lower."""

    def serve_step(params, caches, tokens, pos):
        logits, new_caches = model_zoo.decode_step(params, cfg, tokens,
                                                   caches, pos)
        next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return next_tok, new_caches

    return serve_step


def init_train_state(cfg: ModelConfig, job: JobConfig, key):
    """(params, opt_state) for CPU-scale runs (tests/examples)."""
    from repro.models.common import init_params

    defs = model_zoo.param_defs(cfg)
    params = init_params(defs, key, cfg.resolved_param_dtype())
    opt = get_optimizer(job.optimizer, job.momentum)
    return params, opt.init(params)
