"""The elastic trainer: wires the spot-market/cluster simulator, the paper's
strategies, the elastic train step, and checkpointing into one loop.

Two execution paths share the same step function:

* ``ElasticTrainer.run`` — the legacy per-iteration Python loop over the
  discrete-event ``VolatileCluster``. Kept as the exact-semantics path
  (per-iteration checkpointing, serve parity, dynamic strategies consulting
  the real clock).
* ``train_batched`` / ``ElasticTrainer.run_batched`` — the scan-native
  path: the elastic masked train step is folded into the batched engine's
  per-tick step, so an S-strategy × R-seed grid trains real (reduced)
  models end-to-end inside ONE ``lax.scan``+``vmap`` jit — price draw,
  bid→active-mask, masked-renormalized SGD update, and time/cost/idle
  accounting all on device, with donated model buffers and no host sync
  between ticks. Checkpointing is scan-native too: ``snapshot_every=k``
  emits the full batched carry every k ticks, `save_batched` /
  `restore_batched` persist it through ``train/checkpoint.py``, and
  ``ElasticTrainer.resume_batched`` restarts a preempted grid bit-exactly
  mid-trace.

Runs real (reduced) models on CPU for tests/examples/benchmarks; on hardware
the same loop drives the full mesh (the step function is identical — the
dry-run compiles it for the production mesh).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import JobConfig
from repro.core.strategies import Strategy
from repro.data.synthetic import lm_batch
from repro.sim import engine
from repro.sim.cluster import VolatileCluster
from repro.train import checkpoint as ckpt_mod
from repro.train import megabatch as megabatch_mod
from repro.train.train_step import init_train_state, make_train_step


@functools.lru_cache(maxsize=32)
def jit_train_step(job: JobConfig):
    """Jitted elastic train step, cached on the (hashable) JobConfig so
    trainers over the same job share one compilation instead of paying it
    per ElasticTrainer instance."""
    return jax.jit(make_train_step(job.model, job, remat="none"))


@dataclasses.dataclass
class TrainLogEntry:
    j: int
    time: float
    cost: float
    loss: float
    y: int


@dataclasses.dataclass
class ElasticTrainer:
    job: JobConfig
    cluster: VolatileCluster
    strategy: Strategy
    mode: str = "spot"                 # "spot" | "preemptible"
    checkpoint_path: Optional[str] = None
    checkpoint_every: int = 0
    seed: int = 0

    def __post_init__(self):
        cfg = self.job.model
        self._step_fn = jit_train_step(self.job)
        key = jax.random.PRNGKey(self.job.seed)
        self.params, self.opt_state = init_train_state(cfg, self.job, key)
        self.log: List[TrainLogEntry] = []
        self._j = 0

    # ---------------------------------------------------------------- loop

    def run(self, iterations: Optional[int] = None,
            batch_fn: Optional[Callable[[int], Dict]] = None) -> Dict:
        cfg = self.job.model
        total = iterations or self.strategy.total_iterations
        shape = self.job.shape
        n_w = self.job.n_workers

        for j in range(self._j, total):
            if self.mode == "spot":
                bids = self.strategy.bids(self.cluster.t, j)
                assert len(bids) == n_w, (len(bids), n_w)
                mask = self.cluster.next_iteration_spot(j, np.asarray(bids))
            else:
                prov = min(self.strategy.workers(j), n_w)
                mask = self.cluster.next_iteration_preemptible(j, prov)
                mask = np.pad(mask, (0, n_w - len(mask)))[:n_w]

            batch = batch_fn(j) if batch_fn else lm_batch(
                cfg, shape.global_batch, shape.seq_len, j, seed=self.seed)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            self.params, self.opt_state, metrics = self._step_fn(
                self.params, self.opt_state, batch, jnp.asarray(mask),
                jnp.asarray(j, jnp.int32))
            self.log.append(TrainLogEntry(
                j=j, time=self.cluster.t, cost=self.cluster.total_cost,
                loss=float(metrics["loss"]), y=int(mask.sum())))
            self._j = j + 1
            if (self.checkpoint_path and self.checkpoint_every
                    and (j + 1) % self.checkpoint_every == 0):
                ckpt_mod.save(self.checkpoint_path,
                              {"params": self.params,
                               "opt": self.opt_state}, j + 1)

        return self.summary()

    def restore(self):
        assert self.checkpoint_path
        state, step = ckpt_mod.restore(
            self.checkpoint_path, {"params": self.params,
                                   "opt": self.opt_state})
        self.params, self.opt_state = state["params"], state["opt"]
        self._j = step

    def summary(self) -> Dict:
        s = self.cluster.summary()
        s["final_loss"] = self.log[-1].loss if self.log else float("nan")
        s["log"] = self.log
        return s

    # ------------------------------------------------------- batched path

    def run_batched(self, seeds: Union[int, Sequence[int]] = 8,
                    iterations: Optional[int] = None,
                    strategies: Optional[Mapping[str, Strategy]] = None,
                    n_ticks: Optional[int] = None,
                    n_batches: Optional[int] = None,
                    batch_fn: Optional[Callable[[int], Dict]] = None,
                    snapshot_every: int = 0,
                    megabatch: bool = False,
                    use_fused_update: bool = False,
                    mesh=None):
        """Scan-native training: the trainer's market/runtime plus a grid of
        strategies (default: its own) × seeds, every configuration training
        a real model end-to-end in one compiled call.

        Each (strategy, seed) replica starts from the job's deterministic
        init (``PRNGKey(job.seed)``) — the same state a fresh ``run()``
        would start from — and consumes the same deterministic batch stream
        (``lm_batch`` indexed by iteration, or ``batch_fn``). Returns a
        `repro.sim.evaluate.BatchResult` whose per-iteration "errors" are
        the batch losses.

        With ``snapshot_every = k`` the run emits the full batched carry
        every k ticks; if the trainer has a ``checkpoint_path`` the latest
        snapshot is persisted there *when the compiled call returns*, and
        `resume_batched` restarts the grid from it bit-exactly. Note the
        snapshots of a single jit call only reach the host at call return —
        to survive a kill at any moment (losing at most k ticks), use
        `train_batched_durable`, which persists every chunk as it runs.
        """
        from repro.sim.evaluate import BatchResult

        strategies = strategies or {self.strategy.name: self.strategy}
        scenarios = [self._scenario(s, iterations, name)
                     for name, s in strategies.items()]
        res = train_batched(
            self.job, scenarios, seeds, n_ticks=n_ticks,
            n_batches=n_batches, batch_fn=batch_fn, batch_seed=self.seed,
            snapshot_every=snapshot_every, megabatch=megabatch,
            use_fused_update=use_fused_update, mesh=mesh)
        if self.checkpoint_path and res.snapshots is not None:
            save_batched(self.checkpoint_path, res)
        return BatchResult(names=[s.name for s in scenarios], result=res)

    def resume_batched(self, seeds: Union[int, Sequence[int]] = 8,
                       iterations: Optional[int] = None,
                       strategies: Optional[Mapping[str, Strategy]] = None,
                       n_ticks: Optional[int] = None,
                       n_batches: Optional[int] = None,
                       batch_fn: Optional[Callable[[int], Dict]] = None,
                       snapshot_every: int = 0,
                       mesh=None):
        """Restart a preempted `run_batched` from ``checkpoint_path``: the
        batched carry (every replica's params/opt_state/clock/cost and the
        loss trajectories so far) is restored and the scan continues from
        the checkpointed tick — with the same grid/seeds/tick budget the
        final state is bit-exact with the uninterrupted run."""
        if not self.checkpoint_path:
            raise ValueError(
                "resume_batched needs a checkpoint_path on the trainer")
        from repro.sim.evaluate import BatchResult

        strategies = strategies or {self.strategy.name: self.strategy}
        scenarios = [self._scenario(s, iterations, name)
                     for name, s in strategies.items()]
        batch = engine.stack_scenarios(scenarios)
        state, tick = restore_batched(self.checkpoint_path, self.job, batch,
                                      seeds)
        res = train_batched(
            self.job, batch, seeds, n_ticks=n_ticks, n_batches=n_batches,
            batch_fn=batch_fn, batch_seed=self.seed, donate=False,
            snapshot_every=snapshot_every, init_state=state, tick0=tick,
            mesh=mesh)
        if self.checkpoint_path and res.snapshots is not None:
            save_batched(self.checkpoint_path, res)
        return BatchResult(names=[s.name for s in scenarios], result=res)

    def _scenario(self, strategy: Strategy, iterations: Optional[int],
                  name: str) -> engine.Scenario:
        """Compile one strategy against this trainer's cluster (market,
        runtime, idle step) into a batchable Scenario."""
        cl = self.cluster
        if self.mode == "spot":
            return engine.scenario_from_strategy(
                strategy, alpha=self.job.learning_rate, rt=cl.runtime,
                price_spec=price_spec_from_market(cl.market),
                n_max=self.job.n_workers, idle_step=cl.idle_step,
                J=iterations, name=name)
        return engine.scenario_from_strategy(
            strategy, alpha=self.job.learning_rate, rt=cl.runtime,
            q=cl.preempt_q or 0.0, on_demand_price=cl.on_demand_price,
            n_max=self.job.n_workers, idle_step=cl.idle_step, J=iterations,
            name=name)


def price_spec_from_market(market) -> engine.PriceSpec:
    """Map a legacy SpotMarket's price process onto a batchable PriceSpec:
    IIDPrices → its distribution; TracePrices → *time-indexed* replay at
    the trace's resolution (`PriceSpec.from_trace(..., step=proc.step)` —
    exact under stochastic iteration durations); TickPrices → legacy
    tick-replay (one entry per engine tick, for tick-exact parity)."""
    from repro.sim.spot_market import TickPrices, TracePrices

    proc = market.process
    if hasattr(proc, "dist"):
        return engine.PriceSpec.from_dist(proc.dist)
    if isinstance(proc, TracePrices):
        return engine.PriceSpec.from_trace(proc.trace, step=proc.step)
    if isinstance(proc, TickPrices):
        return engine.PriceSpec.from_trace_ticks(proc.trace)
    raise TypeError(f"no batchable PriceSpec for {type(proc).__name__}")


@functools.lru_cache(maxsize=32)
def make_train_program(job: JobConfig, n_batches: int) -> engine.ModelProgram:
    """The elastic masked train step as an engine ModelProgram.

    model = (params, opt_state); data = the batch stream stacked on a
    leading (n_batches,) axis, indexed by ``j % n_batches`` inside the scan
    (deterministic — matches the legacy loop's ``lm_batch(..., index=j)``
    when ``n_batches >= J``). The scenario's ``alpha`` is ignored: the LR
    comes from the job, exactly as in ``ElasticTrainer.run``. Cached so
    repeated grids over the same job reuse one compilation.
    """
    step = make_train_step(job.model, job, remat="none")

    def step_fn(model, data, key, mask, j, alpha):
        del key, alpha
        params, opt_state = model
        batch = jax.tree.map(lambda x: x[j % n_batches], data)
        new_params, new_opt, metrics = step(params, opt_state, batch, mask,
                                            j)
        return (new_params, new_opt), metrics["loss"]

    return engine.ModelProgram(step_fn=step_fn,
                               name=f"train-{job.model.name}-{n_batches}")


@functools.lru_cache(maxsize=32)
def make_megabatch_train_program(job: JobConfig, n_batches: int,
                                 use_fused_update: bool = False
                                 ) -> engine.ModelProgram:
    """The megabatched elastic train step as a *blocked* engine program.

    model = ``train.megabatch``'s flat replica-blocked state ({"p", "v"}
    (S, R, P) buffers); per tick the whole (S, R) grid trains in ONE step
    call — each replica's batch gathered by its own ``j % n_batches``, the
    grid flattened to a single widened replica axis, and Eq. (5)'s
    renormalization + the gated SGD apply fused over the flat blocks
    (through the Pallas kernel when ``use_fused_update``). Semantically
    identical to `make_train_program` (see tests/test_megabatch.py);
    raises NotImplementedError for configs outside the megabatch envelope
    (`megabatch.supports_megabatch` names the reason).
    """
    cfg = job.model
    reason = megabatch_mod.supports_megabatch(cfg, job)
    if reason:
        raise NotImplementedError(f"megabatch path unsupported: {reason}")
    step = megabatch_mod.make_megabatch_step(
        cfg, job, use_fused_update=use_fused_update)

    def step_fn(model, data, key, mask, j, alpha, running):
        del key, alpha
        s, r = j.shape
        rt = s * r
        b = j % n_batches
        tokens = data["tokens"][b].reshape((rt,) + data["tokens"].shape[1:])
        labels = data["labels"][b].reshape((rt,) + data["labels"].shape[1:])
        label_mask = data.get("label_mask")
        if label_mask is not None:
            label_mask = label_mask[b].reshape(
                (rt,) + label_mask.shape[1:])
        flat = jax.tree.map(
            lambda x: x.reshape((rt,) + x.shape[2:]), model)
        new, loss = step(flat, tokens, labels, mask.reshape(rt, -1),
                         j.reshape(rt), running.reshape(rt), label_mask)
        new = jax.tree.map(
            lambda x: x.reshape((s, r) + x.shape[1:]), new)
        return new, loss.reshape(s, r)

    name = f"train-mega-{job.model.name}-{n_batches}"
    if use_fused_update:
        name += "-fused"
    return engine.ModelProgram(step_fn=step_fn, name=name, blocked=True)


def unpack_batched_model(final_model, job: JobConfig):
    """A megabatched run's ``EngineResult.final_model`` ({"p", "v"} flat
    (S, R, P) buffers) back to the standard (params, opt_state) pytrees
    with (S, R, ...) leading axes — the layout the vmapped path returns."""
    return megabatch_mod.unpack_state(final_model, job.model,
                                      float(job.momentum))


def stack_batches(job: JobConfig, n_batches: int, seed: int = 0,
                  batch_fn: Optional[Callable[[int], Dict]] = None):
    """Device-stack the first ``n_batches`` training batches on a leading
    axis — the engine data pytree the scan indexes by iteration."""
    shape = job.shape
    batches = [batch_fn(j) if batch_fn else
               lm_batch(job.model, shape.global_batch, shape.seq_len, j,
                        seed=seed)
               for j in range(n_batches)]
    return {k: jnp.asarray(np.stack([np.asarray(b[k]) for b in batches]))
            for k in batches[0]}


def train_batched(job: JobConfig,
                  scenarios: Union[engine.ScenarioBatch,
                                   Sequence[engine.Scenario]],
                  seeds: Union[int, Sequence[int]] = 8, *,
                  n_ticks: Optional[int] = None,
                  n_batches: Optional[int] = None,
                  batch_fn: Optional[Callable[[int], Dict]] = None,
                  batch_seed: int = 0,
                  donate: bool = True,
                  snapshot_every: int = 0,
                  init_state: Optional[engine.SimState] = None,
                  tick0: int = 0,
                  megabatch: bool = False,
                  use_fused_update: bool = False,
                  mesh=None,
                  program=None,
                  init_model=None) -> engine.EngineResult:
    """Train a real model under every scenario × seed in one compiled call.

    Folds the elastic masked train step into the batched engine: the whole
    run — price draw, bid→active-mask, masked-renormalized SGD update,
    time/cost/idle accounting — executes inside one ``lax.scan``, vmapped
    over stacked scenarios and seeds. The initial (params, opt_state) is
    donated to the call by default (it is rebuilt per call from
    ``PRNGKey(job.seed)``, so nothing is lost).

    Checkpointing: ``snapshot_every = k`` emits the full batched carry
    (params, opt_state, clock, cost, trajectories — everything) every k
    ticks into ``EngineResult.snapshots``; ``init_state``/``tick0`` resume
    from a restored snapshot (same scenarios/seeds/tick budget), continuing
    bit-exactly. See `save_batched` / `restore_batched`.

    Returns an EngineResult whose ``errors``/``losses`` trajectory holds
    the per-iteration batch loss and whose ``final_model`` stacks the
    trained (params, opt_state) per replica on a leading (S, R) axis.

    Reproducibility note (inherited from the engine's padded batching):
    per-tick stochastic draws are shaped by the *batch-global* padded
    worker width, so a (scenario, seed) cell is bit-reproducible within
    the same stacked grid — not across grids padded to different widths.

    ``megabatch=True`` selects the replica-blocked layout (see
    `train.megabatch`): the same market draws and update semantics with
    the replica axis folded into blocked parameters and a widened batch
    dimension — market trajectories stay bit-exact, losses/params agree
    to float tolerance (test_megabatch pins both). ``final_model`` then
    holds the flat {"p", "v"} buffers; `unpack_batched_model` converts
    back. ``use_fused_update`` additionally routes the elastic SGD apply
    through the fused Pallas kernel (`kernels.ops.fused_elastic_update`).

    ``program`` / ``init_model`` swap in a caller-built ModelProgram
    factory (``n_batches -> ModelProgram``) and a zero-argument builder of
    the matching initial model carry — the hook `train_zoo` uses to run
    full zoo configs (mixed-precision carries included) through this exact
    machinery. The initial model is built only when the grid's carry is,
    and nothing keeps it afterwards: at published widths one model is a
    large share of a chip's memory.

    ``mesh`` routes execution through `engine.simulate_sharded`: the
    scenario axis of the grid shards over the mesh's ``data`` axis and
    the seed axis over its ``replica`` axis (when present), each device
    scanning only its shard — bit-exact with the single-device path
    (`launch.mesh.make_scenario_mesh` / `make_scenario_replica_mesh`
    build the mesh; see tests/test_sharded_parity.py).
    """
    scenarios, program, data, n_ticks = _prepare_batched(
        job, scenarios, n_ticks=n_ticks, n_batches=n_batches,
        batch_fn=batch_fn, batch_seed=batch_seed, megabatch=megabatch,
        use_fused_update=use_fused_update, program=program)
    init_model = init_model or _default_init(job, megabatch)
    cfg = engine.SimConfig(n_ticks=n_ticks, snapshot_every=snapshot_every)
    fresh = init_state is None
    # the initial model is built in the call so that only the engine holds
    # it, and drops it once the grid's carry is built
    if mesh is not None:
        return engine.simulate_sharded(
            scenarios, program, init_model() if fresh else None, data,
            seeds, cfg, mesh=mesh, donate=donate, init_state=init_state,
            tick0=tick0)
    return engine.simulate_program(
        scenarios, program, init_model() if fresh else None, data, seeds,
        cfg, donate=donate, init_state=init_state, tick0=tick0)


def _default_init(job: JobConfig, megabatch: bool = False):
    """The zero-argument initial-model builder of the reduced-model
    programs: `init_train_state`, or the flat replica-blocked carry."""
    key = jax.random.PRNGKey(job.seed)
    if megabatch:
        return lambda: megabatch_mod.init_megabatch_state(job.model, job,
                                                          key)
    return lambda: init_train_state(job.model, job, key)


def _prepare_batched(job: JobConfig, scenarios, *, n_ticks, n_batches,
                     batch_fn, batch_seed, megabatch: bool = False,
                     use_fused_update: bool = False, program=None):
    """Shared setup of the scan-native training paths (`train_batched` and
    `train_batched_durable` must stay bit-exact equivalents): stack +
    fleet-width check, batch stream, program, tick-budget default.

    ``program`` overrides the default reduced-model train program with a
    caller-built `engine.ModelProgram` factory — called with the resolved
    ``n_batches`` so the program's batch indexing matches the stacked data
    stream (this is how `train_zoo` plugs `zoo_program.make_zoo_program`
    in). Pass a callable ``n_batches -> ModelProgram``."""
    if not isinstance(scenarios, engine.ScenarioBatch):
        scenarios = engine.stack_scenarios(scenarios)
    if scenarios.n_max != job.n_workers:
        raise ValueError(
            f"scenario fleet width {scenarios.n_max} != job.n_workers "
            f"{job.n_workers}: the elastic mask must cover every worker "
            "slice")
    j_max = scenarios.j_max
    n_batches = n_batches or j_max
    data = stack_batches(job, n_batches, seed=batch_seed, batch_fn=batch_fn)
    if program is not None:
        program = program(n_batches)
    elif megabatch:
        program = make_megabatch_train_program(job, n_batches,
                                               use_fused_update)
    else:
        program = make_train_program(job, n_batches)
    return scenarios, program, data, n_ticks or 2 * j_max + 16


def batched_init_state(job: JobConfig,
                       scenarios: Union[engine.ScenarioBatch,
                                        Sequence[engine.Scenario]],
                       seeds: Union[int, Sequence[int]],
                       megabatch: bool = False,
                       init_model=None) -> engine.SimState:
    """The (S, R) initial carry a batched training run starts from — and
    therefore the *restore template* for `checkpoint.restore` (same model
    init ``PRNGKey(job.seed)``, same trajectory shapes). ``megabatch`` /
    ``init_model`` must match the run being restored: the flat replica-
    blocked carry, the (params, opt_state) tree, and a zoo mixed-precision
    carry are all different pytrees."""
    n_seeds = int(seeds) if np.isscalar(seeds) else len(seeds)
    init_model = init_model or _default_init(job, megabatch)
    return engine.initial_state(scenarios, init_model(), n_seeds)


def _restore_template(job: JobConfig, scenarios, seeds,
                      megabatch: bool = False, init_model=None):
    """`batched_init_state`'s shapes and dtypes, built without touching a
    device — all a restore needs of its template."""
    return jax.eval_shape(lambda: batched_init_state(
        job, scenarios, seeds, megabatch=megabatch, init_model=init_model))


def save_batched(path: str, result: engine.EngineResult,
                 index: int = -1, *, shards: Optional[int] = None,
                 writer: Optional[ckpt_mod.AsyncCheckpointWriter] = None
                 ) -> int:
    """Persist one snapshot of a ``snapshot_every`` run as a durable
    checkpoint; returns the snapshot's absolute tick count (the ``tick0``
    a resume passes back).

    ``shards=n`` writes a *sharded* checkpoint — n per-scenario-slice
    .npz files plus a JSON manifest at ``path`` (`checkpoint.save_sharded`)
    instead of one flat .npz; natural for mesh runs (one shard per
    ``data``-axis device) and for carries too large to serialize in one
    file. Either format restores through `restore_batched` on any mesh
    shape, bit-exactly. ``writer`` offloads the serialization to an
    `AsyncCheckpointWriter` background thread — the call returns as soon
    as the snapshot is enqueued (do not donate the result's buffers
    before ``writer.wait()``)."""
    if writer is not None and result.snapshots is not None:
        # the snapshot is sliced out of the stream in the writer's thread,
        # so the submit only enqueues
        tick = int(result.snapshot_ticks[index])
        writer.submit(path, lambda: engine.snapshot_state(result, index)[0],
                      tick, n_shards=shards)
        return tick
    state, tick = engine.snapshot_state(result, index)
    if shards:
        ckpt_mod.save_sharded(path, state, tick, shards)
    else:
        ckpt_mod.save(path, state, tick)
    return tick


def restore_batched(path: str, job: JobConfig,
                    scenarios: Union[engine.ScenarioBatch,
                                     Sequence[engine.Scenario]],
                    seeds: Union[int, Sequence[int]],
                    megabatch: bool = False,
                    init_model=None):
    """Load a `save_batched` checkpoint back into a batched carry. Returns
    ``(state, tick)`` for ``train_batched(init_state=state, tick0=tick)``;
    raises a key-naming ValueError if the job/scenario grid drifted from
    the one that was checkpointed. Pass ``megabatch=True`` for checkpoints
    written by a megabatched run (flat replica-blocked carry), or
    ``init_model`` for a caller-built carry (zoo runs — see
    `resume_zoo`).

    Both checkpoint formats are accepted (flat .npz or sharded manifest,
    sniffed by `checkpoint.restore_any`), and neither records a mesh: a
    grid saved from an 8-device run resumes on 4 devices, 1 device, or
    the plain vmapped path bit-exactly — re-sharding is just
    ``train_batched(init_state=..., mesh=...)`` on the new mesh."""
    like = _restore_template(job, scenarios, seeds, megabatch=megabatch,
                             init_model=init_model)
    return ckpt_mod.restore_any(path, like)


def state_is_finite(state: engine.SimState) -> bool:
    """The in-scan NaN guard's predicate: every float leaf of the carry's
    model, plus the cost/clock accumulators, is finite. (Trajectory
    buffers are excluded — their not-yet-run entries are NaN by design.)
    Reduced where the carry lives, so a device carry is never copied to
    the host for the check."""
    # jnp.issubdtype, not np: ml_dtypes' bfloat16 is NOT a np.floating
    # subtype, so the numpy predicate would silently skip exactly the
    # mixed-precision leaves this guard exists to check
    leaves = [x for x in jax.tree.leaves(state.model)
              if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)]
    return all(bool(jnp.isfinite(x).all())
               for x in leaves + [state.total_cost, state.t])


def train_batched_durable(job: JobConfig,
                          scenarios: Union[engine.ScenarioBatch,
                                           Sequence[engine.Scenario]],
                          seeds: Union[int, Sequence[int]] = 8, *,
                          checkpoint_path: str,
                          save_every: int,
                          n_ticks: Optional[int] = None,
                          n_batches: Optional[int] = None,
                          batch_fn: Optional[Callable[[int], Dict]] = None,
                          batch_seed: int = 0,
                          resume: bool = True,
                          mesh=None,
                          save_shards: Optional[int] = None,
                          async_save: bool = False,
                          keep_last: Optional[int] = None,
                          strict_resume: bool = True,
                          nan_guard: bool = False,
                          max_rollbacks: int = 3,
                          hooks=None,
                          program=None,
                          init_model=None) -> engine.EngineResult:
    """Preemption-*durable* batched training: the scan executes in
    ``save_every``-tick jitted chunks on the host, persisting the full
    batched carry to ``checkpoint_path`` after every chunk — so a process
    killed at any moment loses at most ``save_every`` ticks of work, and
    rerunning the same call (``resume=True``) picks up from the file.

    This is the host-loop complement of ``train_batched(snapshot_every=k)``
    (whose snapshots only reach the host when the single compiled call
    returns): durability costs one host sync + .npz write per chunk.
    The chunk start enters the jit as *data*, so every full-size chunk
    shares one compiled program, and the chunked execution is bit-exact
    with the single-call run (absolute-tick RNG folding).

    Returns the final EngineResult — identical to the equivalent
    ``train_batched(job, scenarios, seeds, n_ticks=n_ticks)``.

    ``mesh`` runs each chunk through `engine.simulate_sharded` (grid
    sharded over the mesh, bit-exact). ``save_shards=n`` writes each
    checkpoint as n per-shard files + manifest (`checkpoint.save_sharded`)
    instead of one flat .npz; ``async_save=True`` hands serialization to
    a background `AsyncCheckpointWriter` thread so the next chunk's scan
    launches without waiting for disk — the last write is always joined
    (and its errors surfaced) before the function returns.

    Each chunk donates the carry to its call and emits no snapshot: the
    chunk's final carry is the checkpoint. So the device holds one carry
    at a time, which is what lets a published-width model train durably
    on one chip. What outlives a chunk on the host — the async writer's
    snapshot, the NaN guard's rollback point — is a host copy.

    ``keep_last=n`` switches checkpointing to *step-directory* mode:
    ``checkpoint_path`` names a root directory holding one
    ``step_{tick:08d}/`` per retained checkpoint (`checkpoint.save_step`),
    GC'd to the newest n. Resume then goes through
    `checkpoint.restore_newest` — with ``strict_resume=False`` a corrupt
    newest step is quarantined and the previous valid one used instead,
    so a torn write never bricks the run.

    ``nan_guard=True`` validates the carry after every chunk
    (`state_is_finite`): a non-finite model/cost rolls the carry back to
    the chunk's start (kept as a host copy) and re-runs it, never
    checkpointing poison; more
    than ``max_rollbacks`` consecutive failures raise ``FloatingPointError``.

    ``hooks`` is an optional object observing (and, for fault injection,
    perturbing) the chunk loop; all methods are optional and resolved by
    ``getattr``: ``on_resume(tick, path)``, ``before_chunk(tick, state)
    -> state|None``, ``before_save(tick)``, ``after_save(tick, path)``,
    ``on_rollback(tick, reason)``. `chaos.FaultInjector` implements this
    protocol; the supervisor's heartbeat writer piggybacks on it too.
    """
    if save_every < 1:
        raise ValueError(f"save_every={save_every} must be ≥ 1")
    if keep_last is not None and keep_last < 1:
        raise ValueError(f"keep_last={keep_last} must be ≥ 1")
    scenarios, program, data, n_ticks = _prepare_batched(
        job, scenarios, n_ticks=n_ticks, n_batches=n_batches,
        batch_fn=batch_fn, batch_seed=batch_seed, program=program)

    def hook(name, *args):
        fn = getattr(hooks, name, None) if hooks is not None else None
        return fn(*args) if fn is not None else None

    step_mode = keep_last is not None
    resumed_from = None
    if resume and step_mode and ckpt_mod.list_steps(checkpoint_path):
        like = _restore_template(job, scenarios, seeds,
                                 init_model=init_model)
        state, tick, resumed_from = ckpt_mod.restore_newest(
            checkpoint_path, like, strict=strict_resume)
    elif resume and not step_mode and os.path.exists(checkpoint_path):
        state, tick = restore_batched(checkpoint_path, job, scenarios,
                                      seeds, init_model=init_model)
        resumed_from = checkpoint_path
    else:
        state, tick = batched_init_state(job, scenarios, seeds,
                                         init_model=init_model), 0
    if tick > n_ticks:
        raise ValueError(
            f"checkpoint {resumed_from} is at tick {tick}, beyond "
            f"this run's n_ticks={n_ticks}")
    hook("on_resume", tick, resumed_from)

    def run_chunk(end, state, tick):
        cfg = engine.SimConfig(n_ticks=end)
        if mesh is not None:
            return engine.simulate_sharded(scenarios, program, None, data,
                                           seeds, cfg, mesh=mesh,
                                           donate=True, init_state=state,
                                           tick0=tick)
        return engine.simulate_program(scenarios, program, None, data,
                                       seeds, cfg, donate=True,
                                       init_state=state, tick0=tick)

    def save(state, tick):
        # sync writes get the same transient-OSError retry the async
        # writer applies — a disk hiccup should cost milliseconds, not
        # a crash-and-restart cycle
        if step_mode:
            path = ckpt_mod.step_path(checkpoint_path, tick)
            if writer is not None:
                writer.submit_step(checkpoint_path, state, tick,
                                   n_shards=save_shards,
                                   keep_last=keep_last)
            else:
                ckpt_mod.retry_io(ckpt_mod.save_step, checkpoint_path,
                                  state, tick, save_shards, keep_last)
            return path
        if writer is not None:
            writer.submit(checkpoint_path, state, tick,
                          n_shards=save_shards)
        elif save_shards:
            ckpt_mod.retry_io(ckpt_mod.save_sharded, checkpoint_path,
                              state, tick, save_shards)
        else:
            ckpt_mod.retry_io(ckpt_mod.save, checkpoint_path, state, tick)
        return checkpoint_path

    has_after_save = hooks is not None and \
        getattr(hooks, "after_save", None) is not None
    writer = ckpt_mod.AsyncCheckpointWriter() if async_save else None
    # host copies: the rollback point (the pre-hook carry of the chunk
    # being run) and what the async writer serializes — the device carry
    # itself is donated to the next chunk
    host = jax.device_get(state) if nan_guard else None
    rollbacks = 0
    try:
        res = None
        while tick < n_ticks:
            hooked = hook("before_chunk", tick, state)
            if hooked is not None:
                state = hooked
            new_tick = min(tick + save_every, n_ticks)
            res = run_chunk(new_tick, state, tick)
            # the chunk's final carry is the checkpoint — persist it before
            # advancing (atomic write; a kill between chunks re-runs at
            # most this chunk)
            state = res.final_state
            if nan_guard and not state_is_finite(state):
                rollbacks += 1
                hook("on_rollback", tick,
                     f"non-finite carry after chunk ending at tick "
                     f"{new_tick} (rollback {rollbacks}/{max_rollbacks})")
                if rollbacks > max_rollbacks:
                    raise FloatingPointError(
                        f"carry still non-finite after {max_rollbacks} "
                        f"rollbacks of the chunk starting at tick {tick}")
                state, res = jax.device_put(host), None
                continue
            rollbacks = 0
            tick = new_tick
            if nan_guard or writer is not None:
                host = jax.device_get(state)
            hook("before_save", tick)
            path = save(state if host is None else host, tick)
            if has_after_save:
                if writer is not None:
                    writer.wait()        # hook must see the landed file
                hook("after_save", tick, path)
        if res is None:
            # checkpoint already at n_ticks (or the last chunk rolled
            # back): materialize the result from the carry with a
            # zero-tick call
            res = run_chunk(n_ticks, state, tick)
    finally:
        if writer is not None:
            writer.close()
    return res


def _zoo_setup(job: JobConfig):
    """(program factory, initial-carry builder) for a zoo run — the two
    hooks that turn the generic batched paths into full-zoo training."""
    from repro.train import zoo_program as zoo_mod

    cfg = job.model

    def program(n_batches: int) -> engine.ModelProgram:
        return zoo_mod.make_zoo_program(cfg, job, n_batches)

    def init_model():
        return zoo_mod.init_zoo_state(cfg, job, jax.random.PRNGKey(job.seed))

    return program, init_model


def train_zoo(job: JobConfig,
              scenarios: Union[engine.ScenarioBatch,
                               Sequence[engine.Scenario]],
              seeds: Union[int, Sequence[int]] = 8, *,
              checkpoint_path: Optional[str] = None,
              save_every: Optional[int] = None,
              **kw) -> engine.EngineResult:
    """Train ``job.model`` — any zoo config, full or reduced, f32 or
    mixed-precision — under every scenario × seed through the batched
    engine.

    A thin front over `train_batched` (and, when ``checkpoint_path`` +
    ``save_every`` are given, over `train_batched_durable` — the same
    durable chunk loop, step-directory GC, async writers, NaN guard and
    chaos hooks all apply) with the model program swapped for
    `zoo_program.make_zoo_program` and the initial carry for
    `zoo_program.init_zoo_state`. Activations are recomputed per
    ``job.sharding.remat``. Mixed-precision configs train with bf16
    params/activations over f32 optimizer masters; checkpoints then carry
    bf16 leaves (see `checkpoint`'s bit-view encoding) and resume
    bit-consistently. Remaining keyword arguments pass through to the
    underlying path (``n_ticks``, ``n_batches``, ``mesh``,
    ``snapshot_every``, ``keep_last``, ``nan_guard`` ...)."""
    program, init_model = _zoo_setup(job)
    if checkpoint_path is not None:
        if not save_every:
            raise ValueError(
                "train_zoo(checkpoint_path=...) needs save_every ≥ 1")
        return train_batched_durable(
            job, scenarios, seeds, checkpoint_path=checkpoint_path,
            save_every=save_every, program=program, init_model=init_model,
            **kw)
    return train_batched(job, scenarios, seeds, program=program,
                         init_model=init_model, **kw)


def resume_zoo(path: str, job: JobConfig,
               scenarios: Union[engine.ScenarioBatch,
                                Sequence[engine.Scenario]],
               seeds: Union[int, Sequence[int]]):
    """Load a zoo run's checkpoint back into its (possibly mixed-precision)
    carry: ``(state, tick)`` for ``train_zoo(..., init_state=state,
    tick0=tick)``. The restore template is rebuilt from the job exactly as
    `train_zoo` built it, so structure drift is named, not silent."""
    _, init_model = _zoo_setup(job)
    return restore_batched(path, job, scenarios, seeds,
                           init_model=init_model)
