"""One run of a training cell: set-up, the timed window, and the readings
that `check` compares with the reference.

The window drives the user's entry, `repro.train.trainer.train_zoo`, in
``chunk_ticks``-tick calls that carry ``init_state``/``tick0`` forward, and
ends on a whole chunk. Set-up builds the one carry the window trains: the
benchmark's weights from the seed, made on the device in one jitted call,
then two chunks through the window's own call and feed that stop after the
first and the third iteration (the scenario's iteration target is data, so
they run the window's compiled program). What those chunks leave is what
the reference is compared with.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import json
import time
import typing
from typing import Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from bench import traffic as traffic_mod
from bench.reference import common as ref
from bench.spec import load_module
from bench.spans import BATCH, CHUNK, INIT, REFERENCE, span

#: the plan and price trace the engine holds, in ticks: far more than any
#: window runs, and a whole number of every mix's blocks
PLAN_TICKS = 8192
#: iteration targets of the two set-up chunks
SETUP_TARGETS = (1, 3)


def reference_module(config: Dict):
    return load_module("reference", config["reference"])


def config_groups() -> List[str]:
    """The fields of the program's `ModelConfig` that hold a group of
    their own, a dataclass such as ``ssm`` or ``moe``."""
    from repro.configs.base import ModelConfig

    hints = typing.get_type_hints(ModelConfig)
    return [f.name for f in dataclasses.fields(ModelConfig)
            if any(dataclasses.is_dataclass(k) for k in
                   typing.get_args(hints[f.name]) or (hints[f.name],))]


def model_config(config: Dict):
    """The program's `ModelConfig` for a configuration file: the arch of
    ``configs.ARCHS`` with every field of the file's ``model`` set, and
    every nested group merged into the arch's own group of that name."""
    from repro.configs import ARCHS

    base = ARCHS[config["arch"]]
    groups = config_groups()
    kw = {}
    for key, value in config["model"].items():
        if not isinstance(value, dict):
            kw[key] = value
        elif key not in groups:
            raise ValueError(f"model.{key} is a group, but ModelConfig's "
                             f"{key!r} is not one (groups: "
                             f"{sorted(groups)})")
        elif getattr(base, key) is None:
            raise ValueError(f"model.{key}: the architecture "
                             f"{config['arch']!r} has no {key!r} group")
        else:
            kw[key] = dataclasses.replace(getattr(base, key), **value)
    return base.with_(**kw)


def job_config(config: Dict):
    from repro.configs.base import InputShape, JobConfig, ShardingConfig

    lay = config["layout"]
    return JobConfig(
        model=model_config(config),
        shape=InputShape("bench", seq_len=int(lay["seq_len"]),
                         global_batch=int(lay["global_batch"]),
                         kind="train"),
        sharding=ShardingConfig(remat=lay["remat"]),
        n_workers=int(lay["n_workers"]),
        learning_rate=float(lay["learning_rate"]),
        momentum=float(lay["momentum"]), optimizer=lay["optimizer"])


@dataclasses.dataclass
class Plan:
    """Everything a run of a cell derives from its files and the seed."""

    config: Dict
    mix: Dict
    seed: int
    job: object
    prices: np.ndarray            # (PLAN_TICKS,)
    masks: np.ndarray             # (PLAN_TICKS, n_workers) bool
    feed: List[Dict]              # host batches, index j % n_batches
    spec: Dict                    # reference parameter tree

    @property
    def layout(self) -> Dict:
        return self.config["layout"]

    @property
    def chunk(self) -> int:
        return int(self.layout["chunk_ticks"])

    @property
    def rows_per_worker(self) -> int:
        return int(self.layout["global_batch"]) // int(
            self.layout["n_workers"])

    @property
    def positions(self) -> int:
        """Sequence positions of one row, patch and text."""
        return int(self.layout["seq_len"])

    def scenarios(self, j_target: int):
        from repro.sim import engine

        rt = self.mix["runtime"]
        sc = engine.Scenario(
            price=engine.PriceSpec.from_trace_ticks(self.prices),
            alpha=float(self.layout["learning_rate"]),
            bid_schedule=np.tile(np.asarray(self.mix["bids"], np.float32),
                                 (PLAN_TICKS, 1)),
            J_target=j_target, rt_kind=rt["kind"], rt_lam=rt["lam"],
            rt_delta=rt["delta"], idle_step=rt["idle_step"])
        return engine.stack_scenarios([sc])

    def batch_fn(self, j: int) -> Dict:
        with span(BATCH):
            return self.feed[j]


def make_plan(config: Dict, mix: Dict, seed: int) -> Plan:
    if config["layout"]["chunk_ticks"] % mix["price"]["block"]:
        raise ValueError("a chunk must hold whole price blocks")
    prices = traffic_mod.price_trace(mix, seed, PLAN_TICKS)
    n_batches = int(config["layout"]["n_batches"])
    return Plan(config=config, mix=mix, seed=seed, job=job_config(config),
                prices=prices,
                masks=traffic_mod.active_masks(mix, prices),
                feed=[traffic_mod.batch(config["model"], config["layout"],
                                        seed, j) for j in range(n_batches)],
                spec=reference_module(config).spec(config["model"]))


# ------------------------------------------------------------- the carry


@functools.lru_cache(maxsize=None)
def _programs(config_json: str):
    """The jitted set-up programs of one configuration, built once per
    process: the carry from the seed, and the masters' change from it."""
    from repro.sim import engine
    from repro.train import zoo_program

    config = json.loads(config_json)
    spec = reference_module(config).spec(config["model"])
    job = job_config(config)
    like = jax.eval_shape(lambda: zoo_program.init_zoo_state(
        job.model, job, jax.random.PRNGKey(0)))
    if not (isinstance(like, dict) and set(like) == {"params", "master",
                                                     "opt"}):
        raise TypeError("expected the mixed-precision carry "
                        "{params, master, opt}; the configuration must "
                        "state a parameter dtype below float32")
    want = jax.tree.map(lambda x: x.shape, ref.shapes(spec))
    for part in ("params", "master", "opt"):
        got = jax.tree.map(lambda x: x.shape, like[part])
        if got != want:
            raise ValueError(f"the program's {part} tree differs from the "
                             f"reference's:\n{got}\n{want}")

    @jax.jit
    def build(key, scenarios):
        master = ref.make_params(spec, key)
        params = jax.tree.map(lambda w, l: w.astype(l.dtype), master,
                              like["params"])
        opt = jax.tree.map(jnp.zeros_like, master)
        return engine.initial_state(
            scenarios, {"params": params, "master": master, "opt": opt}, 1)

    @jax.jit
    def change(master, key):
        return jax.tree.map(
            lambda m, w: jnp.sqrt(jnp.sum(jnp.square(m[0, 0] - w))), master,
            ref.make_params(spec, key))

    return build, change


def _config_key(config: Dict) -> str:
    return json.dumps(config, sort_keys=True)


def initial_state(plan: Plan, scenarios):
    """The engine carry of a fresh run, built on the device in one jitted
    call: the seed's weights as float32 masters, the working copy in the
    program's parameter dtype, momentum zero. The program's own init gives
    only the carry's structure, which has to match the reference's tree."""
    build, _ = _programs(_config_key(plan.config))
    return build(ref.seed_key(plan.seed), scenarios)


# ---------------------------------------------------------------- a run


@dataclasses.dataclass
class Run:
    """What one run measured and read."""

    setup_s: float = 0.0
    window_s: float = 0.0
    chunks: int = 0
    iterations: int = 0           # iterations that ran in the window
    shard_steps: int = 0          # Σ y_j over them
    tokens: int = 0               # useful tokens: Σ y_j · rows · positions
    y_window: Optional[np.ndarray] = None
    expected_y: Optional[np.ndarray] = None
    first_y: Optional[np.ndarray] = None
    expected_first_y: Optional[np.ndarray] = None
    readings: Optional[ref.Readings] = None
    memory_peak_bytes: Optional[int] = None
    compiles_in_window: int = 0
    chunk_s: Optional[List[float]] = None   # wall seconds of each chunk
    gc_s: float = 0.0             # Python's collector, inside the window
    host_cpu_s: float = 0.0       # this process's CPU time in the window
    nonfinite: int = 0            # window iterations with a non-finite loss
    reference_s: float = 0.0
    counters: Optional[Dict[str, int]] = None   # the program's, over a
    #                                             traced window


def _train(plan: Plan, scenarios, state, tick: int):
    from repro.train.trainer import train_zoo

    return train_zoo(plan.job, scenarios, [0], n_ticks=tick + plan.chunk,
                     init_state=state, tick0=tick,
                     n_batches=len(plan.feed), batch_fn=plan.batch_fn)


def expected_iterations(plan: Plan, tick0: int, tick1: int) -> np.ndarray:
    """The active-worker count of each iteration the ticks [tick0, tick1)
    run under the full plan: one per tick on which some worker is up."""
    y = plan.masks[tick0:tick1].sum(1)
    return y[y > 0]


def setup(plan: Plan, run: Run):
    """Build the carry and drive it through the first three iterations.
    Returns (state, tick, scenarios of the window)."""
    key = ref.seed_key(plan.seed)
    window_sc = plan.scenarios(PLAN_TICKS)
    with span(INIT):
        state = initial_state(plan, plan.scenarios(SETUP_TARGETS[0]))
    tick, grad_norms = 0, None
    for target in SETUP_TARGETS:
        with span(CHUNK):
            res = _train(plan, plan.scenarios(target), state, tick)
        state, tick = res.final_state, tick + plan.chunk
        if target == 1:
            grad_norms = ref.norms(state.model["opt"])
    _, change_fn = _programs(_config_key(plan.config))
    change = ref.leaf_norms(change_fn(state.model["master"], key))
    losses = np.asarray(res.losses[0, 0, :3], np.float64)
    run.first_y = np.asarray(res.ys[0, 0, :3])
    run.expected_first_y = np.asarray([m.sum() for m in
                                       traffic_mod.iteration_masks(
                                           plan.masks, SETUP_TARGETS,
                                           plan.chunk)[:3]], np.float32)
    run.readings = ref.Readings(losses, grad_norms, change)
    return state, tick, window_sc


def window(plan: Plan, run: Run, state, tick: int, scenarios,
           seconds: float, compile_count: Callable[[], int]):
    """Train whole chunks until ``seconds`` have passed; the time counted
    runs to the end of the chunk that crosses it."""
    j0 = int(np.asarray(state.j).reshape(-1)[0])
    tick0, c0 = tick, compile_count()
    run.chunk_s = []
    gc_clock = _GcClock()
    cpu0 = time.process_time()
    t0 = t = time.perf_counter()
    while True:
        with span(CHUNK):
            res = _train(plan, scenarios, state, tick)
        state, tick = res.final_state, tick + plan.chunk
        run.chunks += 1
        run.chunk_s.append(time.perf_counter() - t)
        t = time.perf_counter()
        if t - t0 >= seconds:
            break
    run.window_s = time.perf_counter() - t0
    run.host_cpu_s = time.process_time() - cpu0
    run.gc_s = gc_clock.stop()
    run.compiles_in_window = compile_count() - c0
    j1 = int(res.iterations.reshape(-1)[0])
    ys = np.asarray(res.ys[0, 0, j0:j1])
    run.iterations = j1 - j0
    run.y_window = ys
    run.nonfinite = int(np.sum(~np.isfinite(res.losses[0, 0, j0:j1])))
    run.expected_y = expected_iterations(plan, tick0, tick)
    run.shard_steps = int(round(float(ys.sum())))
    run.tokens = run.shard_steps * plan.rows_per_worker * plan.positions
    if j1 >= PLAN_TICKS - plan.chunk:
        raise RuntimeError("the window ran past the engine's plan")
    return state


class _GcClock:
    """Seconds Python's cyclic collector runs until `stop`."""

    def __init__(self):
        self.seconds, self._t = 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds += time.perf_counter() - self._t

    def stop(self) -> float:
        gc.callbacks.remove(self._on)
        return self.seconds


def peak_bytes() -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    return None if None in peaks else int(max(peaks))


@functools.lru_cache(maxsize=None)
def _stepper(config_json: str, variant: str) -> ref.Stepper:
    config = json.loads(config_json)
    mod = reference_module(config)
    q8 = ref.fp8 if variant == "fp8" else ref.identity
    return ref.Stepper(mod.spec(config["model"]),
                       mod.row_loss(config["model"], q8),
                       float(config["layout"]["learning_rate"]),
                       float(config["layout"]["momentum"]))


def reference_readings(plan: Plan, variant: str = "f32") -> ref.Readings:
    """The reference's three steps on this run's seed, inputs and masks.
    ``variant``: "f32" (the reference), "fp8" (the control: every matmul
    operand rounded to float8), or "half" (the fault: half of the active
    rows left out, the mean taken over the rest)."""
    stepper = _stepper(_config_key(plan.config),
                       "fp8" if variant == "fp8" else "f32")
    masks = traffic_mod.iteration_masks(plan.masks, SETUP_TARGETS,
                                        plan.chunk)[:3]
    batches = [plan.feed[j % len(plan.feed)] for j in range(3)]
    labels = batches[0]["labels"].shape[1]
    keep = (lambda rows: rows[:len(rows) // 2]) if variant == "half" else None
    with span(REFERENCE):
        return stepper.run(ref.seed_key(plan.seed), batches, masks,
                           plan.rows_per_worker, labels, keep)


def run_cell(plan: Plan, seconds: float, compile_count: Callable[[], int],
             t_start: float, trace_dir: Optional[str] = None,
             on_setup_done: Optional[Callable[[], None]] = None):
    """A whole run: set-up, window (traced into ``trace_dir`` when given,
    with the program's counters taken around it), peak memory, then the
    reference once the program's state is gone.
    Returns (Run, the reference's readings)."""
    run = Run()
    state, tick, scenarios = setup(plan, run)
    jax.block_until_ready(state)
    # set-up's garbage is collected in set-up, and what it leaves is not
    # scanned again by collections inside the window
    gc.collect()
    gc.freeze()
    run.setup_s = time.perf_counter() - t_start
    if on_setup_done is not None:
        on_setup_done()
    if trace_dir is not None:
        from repro import obs

        before = obs.snapshot()
        jax.profiler.start_trace(trace_dir)
    try:
        state = window(plan, run, state, tick, scenarios, seconds,
                       compile_count)
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    if trace_dir is not None:
        run.counters = {k: v - before.get(k, 0)
                        for k, v in obs.snapshot().items()}
    run.memory_peak_bytes = peak_bytes()
    del state
    gc.unfreeze()
    gc.collect()
    t0 = time.perf_counter()
    reading = reference_readings(plan)
    run.reference_s = time.perf_counter() - t0
    return run, reading
