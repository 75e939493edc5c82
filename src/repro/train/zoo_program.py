"""Zoo ↔ engine adapter: any ``ModelConfig`` as an engine ModelProgram.

This is the bridge that collapses the two training stacks into one: the
scan-native batched engine (`sim.engine.simulate_program`) previously only
trained reduced toy models through `trainer.make_train_program`;
`make_zoo_program` wraps the same `train_step.make_loss_grad` core so any
architecture in ``configs.ARCHS`` — qwen2 / deepseek-MLA / mamba2 / hybrids,
at any depth — trains inside the engine's ``lax.scan`` under elastic
worker masking, with:

* **mixed precision**: when ``cfg.param_dtype`` resolves to a sub-f32 dtype
  the carry holds bf16 params (what the forward/backward consumes) beside
  f32 optimizer *master* copies and f32 momentum — grads are computed
  against the bf16 params, cast to f32, applied to the masters, and the
  masters are cast back down to refresh the bf16 params. Loss stays f32
  end to end (the CE core upcasts logits before logsumexp). With an f32
  ``param_dtype`` the carry is exactly `init_train_state`'s
  ``(params, opt_state)`` and the program reproduces a plain
  `make_train_step` loop to float32-ulp tolerance (pinned in
  tests/test_zoo_program.py; the engine's vmap batching changes fusion
  order at the last ulp, nothing more).
* **elastic masking**: the engine's (n_max,) active-worker mask drives
  per-worker microbatch shard weights inside `make_loss_grad`, renormalized
  with `core.elastic.weighted_mean`'s exact-zero convention — preempted
  workers' shards contribute nothing, all-preempted ticks are gated to
  true no-ops by the engine. A grid of one cell, which the engine runs
  unvmapped, takes the program's ``cell_step_fn``: the same step fed the
  batch as `train_step.WorkerSlices`, so only the active workers' slices
  are computed.
* **Pallas kernels**: ``cfg.use_flash_attention`` routes full-sequence
  self-attention through `kernels.ops.flash_mha` (and SSM configs already
  route SSD through the chunked kernel) — nothing extra to wire here; the
  flag is part of the (hashable) config, so kernel-on and kernel-off
  programs cache separately.
* **donated buffers**: the program's carry is an ordinary engine model
  pytree, so `simulate_program(..., donate=True)` (the default) donates
  params/masters/momentum into the scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import JobConfig, ModelConfig
from repro.models import model_zoo
from repro.models.common import abstract_params, init_params
from repro.optim.sgd import constant_lr, get_optimizer
from repro.sim import engine
from repro.train.train_step import init_train_state, make_loss_grad, \
    worker_slices


def is_mixed_precision(cfg: ModelConfig) -> bool:
    """True when the config's param dtype is narrower than f32 — selects
    the master-copy carry layout. A bad dtype string raises the named
    `configs.base.DtypeError` here, before anything is traced."""
    return cfg.resolved_param_dtype() != jnp.dtype(jnp.float32)


def init_zoo_state(cfg: ModelConfig, job: JobConfig, key):
    """The zoo program's initial model carry.

    f32 configs: exactly ``init_train_state`` — ``(params, opt_state)``.
    Mixed-precision configs: ``{"params": bf16, "master": f32, "opt": f32}``
    where the bf16 params are the f32 masters cast down leaf-for-leaf
    (identical values to initializing at bf16 directly: `init_params` draws
    in f32 and casts last), and the optimizer state is initialized over the
    f32 masters so momentum accumulates at full precision.
    """
    if not is_mixed_precision(cfg):
        return init_train_state(cfg, job, key)
    defs = model_zoo.param_defs(cfg)
    master = init_params(defs, key, jnp.float32)
    # per-leaf target dtypes, honoring per-ParamSpec overrides (int32
    # buffers etc. keep their declared dtype, not the param dtype)
    like = abstract_params(defs, cfg.resolved_param_dtype())
    params = jax.tree.map(lambda m, l: m.astype(l.dtype), master, like)
    opt = get_optimizer(job.optimizer, job.momentum)
    return {"params": params, "master": master, "opt": opt.init(master)}


def make_zoo_step(cfg: ModelConfig, job: JobConfig):
    """One zoo training iteration over the `init_zoo_state` carry:
    ``zoo_step(model, batch, mask, j) -> (new_model, loss)``. Activations
    are recomputed per ``job.sharding.remat`` (default ``"full"``: at
    published widths the saved per-layer activations would not fit one
    chip).

    Shared by the engine program below and by the plain-loop side of the
    parity tests (so the bf16 pin compares the engine against an
    independent host loop over the *same* update rule, not against
    itself)."""
    grad_step = make_loss_grad(cfg, job, job.sharding.remat)
    opt = get_optimizer(job.optimizer, job.momentum)
    lr_fn = constant_lr(job.learning_rate)

    if not is_mixed_precision(cfg):
        def zoo_step(model, batch, mask, j):
            params, opt_state = model
            grads, loss, _ = grad_step(params, batch, mask)
            with obs.scope("repro.update"):
                new_params, new_opt = opt.update(grads, opt_state, params,
                                                 lr_fn(j))
            return (new_params, new_opt), loss

        return zoo_step

    def zoo_step(model, batch, mask, j):
        grads, loss, _ = grad_step(model["params"], batch, mask)
        with obs.scope("repro.update"):
            g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            master, opt_state = opt.update(g32, model["opt"],
                                           model["master"], lr_fn(j))
            # refresh the low-precision working copy from the masters
            params = jax.tree.map(lambda m, p: m.astype(p.dtype), master,
                                  model["params"])
        return {"params": params, "master": master, "opt": opt_state}, loss

    return zoo_step


@functools.lru_cache(maxsize=32)
def make_zoo_program(cfg: ModelConfig, job: JobConfig,
                     n_batches: int) -> engine.ModelProgram:
    """Any zoo ``ModelConfig`` as an engine-runnable ModelProgram.

    ``data`` is the `trainer.stack_batches` pytree (leading (n_batches,)
    axis), indexed ``j % n_batches`` inside the scan. The scenario ``alpha``
    is ignored — the LR comes from the job, as everywhere in the trainer.
    Cached on the hashable (cfg, job, n_batches) so repeated grids
    share one compilation (ModelProgram hashes by identity and is a jit
    static argument)."""
    step = make_zoo_step(cfg, job)

    def step_on(sliced):
        def step_fn(model, data, key, mask, j, alpha):
            del key, alpha
            batch = jax.tree.map(lambda x: x[j % n_batches], data)
            if sliced:
                batch = worker_slices(batch, mask.shape[0])
            new_model, loss = step(model, batch, mask, j)
            return new_model, loss

        return step_fn

    mode = "mixed" if is_mixed_precision(cfg) else "f32"
    return engine.ModelProgram(
        step_fn=step_on(False), cell_step_fn=step_on(True),
        name=f"zoo-{cfg.name}-{n_batches}-{mode}")
