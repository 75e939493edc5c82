"""Compile the main path for a described TPU v5e, without the chip.

The TPU compiler is installed with JAX and compiles for a topology that
is described, not attached: it refuses kernels whose blocks break the
(8, 128) tiling rule and programs that do not fit the chip's memory,
which interpret mode and the CPU backend never do. Nothing runs here, so
these tests say nothing about results or times.

The topology is described inside module-scoped fixtures, never at import:
only one process may load the TPU library, and every test worker imports
this file. The persistent compilation cache is off around the compiles,
since a compile for a described chip cannot be read back from it.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

#: usable HBM of one v5e, as the compiler reports it
HBM_BYTES = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def test_flash_attention_compiles(one_chip):
    """bf16, 14 query / 2 kv heads, 1024 tokens (InternVL2-1B's LM)."""
    from repro.kernels import ops

    q = jax.ShapeDtypeStruct((8, 1024, 14, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 1024, 2, 64), jnp.bfloat16,
                              sharding=one_chip)
    c = jax.jit(lambda q, k, v: ops.flash_mha(
        q, k, v, causal=True, interpret=False)).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    """mamba2-1.3b: 64 heads of P=64, state N=128, chunk 256."""
    from repro.kernels import ops

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    c = jax.jit(lambda *a: ops.ssd_chunked_pallas(
        *a, chunk=256, interpret=False)).lower(
            s((1, 2048, 64, 64), jnp.bfloat16), s((1, 2048, 64)), s((64,)),
            s((1, 2048, 1, 128), jnp.bfloat16),
            s((1, 2048, 1, 128), jnp.bfloat16)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("r,p", [(8, 4432), (1, 629_663_872)],
                         ids=["megabatch-grid", "flat-zoo-carry"])
def test_elastic_update_compiles(one_chip, r, p):
    """The trainer-bench flat layout, and InternVL2-1B's whole f32 carry
    flattened to one replica row (updated in place)."""
    from repro.kernels.elastic_update import elastic_sgd_update

    rows = jax.ShapeDtypeStruct((r, p), jnp.float32, sharding=one_chip)
    col = jax.ShapeDtypeStruct((r,), jnp.float32, sharding=one_chip)
    c = jax.jit(lambda *a: elastic_sgd_update(*a, interpret=False),
                donate_argnums=(0, 1)).lower(
                    rows, rows, rows, col, col, col).compile()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().peak_memory_in_bytes <= HBM_BYTES


def _engine_args(sharding, job, scenarios, seeds, n_ticks, **kw):
    """(program, arguments as shapes) of `train_zoo`'s engine call."""
    from repro.train import trainer, zoo_program

    batch, program, data, _ = trainer._prepare_batched(
        job, scenarios, n_ticks=n_ticks, batch_seed=0,
        program=lambda n: zoo_program.make_zoo_program(job.model, job, n),
        **kw)
    state = jax.eval_shape(lambda: trainer.batched_init_state(
        job, batch, seeds, init_model=trainer._zoo_setup(job)[1]))
    args = (_shapes(batch, sharding), _shapes(state, sharding),
            _shapes(data, sharding),
            jax.ShapeDtypeStruct((len(seeds),), jnp.int32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding))
    return program, args


def _repo_root_importable():
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    if root not in sys.path:
        sys.path.insert(0, root)


@pytest.fixture(scope="module")
def zoo_program_args(one_chip):
    """The engine arguments of chip_smoke.py's zoo phase, as shapes."""
    _repo_root_importable()
    import chip_smoke

    cfg = chip_smoke.SmokeConfig()
    job, scenarios, seeds = cfg.workload()
    program, args = _engine_args(one_chip, job, scenarios, seeds,
                                 cfg.zoo_ticks, n_batches=None,
                                 batch_fn=None)
    return cfg, program, args


@pytest.fixture(scope="module")
def mamba2_program_args(one_chip):
    """The engine arguments of one window chunk of the benchmark's
    `mamba2-1.3b.spot` cell (24 layers, 8 × 2048 tokens), as shapes."""
    _repo_root_importable()
    from bench import spec, train_cell

    cell = spec.load_cell("mamba2-1.3b.spot")
    plan = train_cell.make_plan(cell.config, cell.traffic, seed=0)
    program, args = _engine_args(
        one_chip, plan.job, plan.scenarios(train_cell.PLAN_TICKS), [0],
        plan.chunk, n_batches=len(plan.feed), batch_fn=plan.batch_fn)
    return plan.chunk, program, args


def _compile_chunk(program, args, n_run):
    from repro.sim import engine

    return engine._simulate_jit_donated.lower(
        *args, program=program, n_run=n_run, k_snap=0).compile()


def _assert_fits_in_place(c):
    mem = c.memory_analysis()
    # the donated carry is updated in place, not copied
    assert mem.alias_size_in_bytes >= 0.99 * mem.output_size_in_bytes
    assert mem.peak_memory_in_bytes <= HBM_BYTES, mem.peak_memory_in_bytes


@pytest.mark.parametrize("segment", ["uninterrupted", "durable-chunk"])
def test_full_width_zoo_program_fits_one_chip(zoo_program_args, segment):
    """InternVL2-1B at published widths, 24 layers, bf16 over f32 masters,
    global batch 8 × 1024 tokens: the donated scan `train_zoo` runs for N
    ticks, and the N/2-tick chunk its durable loop and resume run."""
    cfg, program, args = zoo_program_args
    n_run = cfg.zoo_ticks if segment == "uninterrupted" \
        else cfg.zoo_ticks // 2
    c = _compile_chunk(program, args, n_run)
    _assert_fits_in_place(c)
    if segment == "uninterrupted":
        _assert_branches_on_the_mask(c, args[1].model)
    n_params = sum(int(np.prod(x.shape[2:]))
                   for x in jax.tree.leaves(args[1].model["params"]))
    assert n_params > 600e6


def test_mamba2_bench_chunk_fits_one_chip(mamba2_program_args):
    """Mamba-2 1.3B at the benchmark's 24 layers, bf16 over f32 masters,
    global batch 8 × 2048 tokens: one 8-tick chunk of the window."""
    n_run, program, args = mamba2_program_args
    c = _compile_chunk(program, args, n_run)
    _assert_fits_in_place(c)
    _assert_branches_on_the_mask(c, args[1].model)
    n_params = sum(int(np.prod(x.shape[2:]))
                   for x in jax.tree.leaves(args[1].model["params"]))
    assert n_params > 650e6


def _assert_branches_on_the_mask(c, model):
    """A one-cell chunk program runs its step under conditionals (the
    tick's and each worker slice's), and selects over no array shaped
    like a leaf of the model carry: nothing is computed to be dropped."""
    import re

    text = c.as_text()
    assert len(re.findall(r" conditional\(", text)) >= 2
    carry = {tuple(x.shape[2:]) for x in jax.tree.leaves(model)
             if np.prod(x.shape[2:]) >= 2 ** 20}
    selected = {tuple(int(d) for d in dims.split(",") if d)
                for dims in re.findall(
                    r"= \w+\[([\d,]*)\]\S* select\(", text)}
    assert not carry & selected, carry & selected


def test_four_chip_scenario_mesh_branches_per_chip(topo, one_chip):
    """`chip_smoke.py --chips 4`: four bid scenarios of the full-width
    InternVL2-1B program over a 4-chip scenario mesh, one cell per chip.
    Each chip takes the one-cell branching tick and fits its memory."""
    _repo_root_importable()
    import chip_smoke
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.sim import engine

    cfg = chip_smoke.SmokeConfig()
    job, scenarios, seeds = cfg.workload(n_bid_rows=cfg.mesh_chips)
    mesh = Mesh(np.asarray(topo.devices[:cfg.mesh_chips]), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
    program, args = _engine_args(one_chip, job, scenarios, seeds,
                                 cfg.zoo_ticks, n_batches=None,
                                 batch_fn=None)
    sspec, gspec, seedspec = engine._grid_specs(mesh)
    specs = (sspec, gspec, PartitionSpec(), seedspec, PartitionSpec())
    args = tuple(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), a)
        for a, spec in zip(args, specs))
    c = engine._simulate_sharded_jit_donated.lower(
        *args, mesh=mesh, program=program, n_run=cfg.zoo_ticks,
        k_snap=0).compile()
    _assert_fits_in_place(c)
    _assert_branches_on_the_mask(c, args[1].model)
