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


@pytest.fixture(scope="module")
def zoo_program_args(one_chip):
    """The engine arguments of chip_smoke.py's zoo phase, as shapes."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import chip_smoke
    from repro.sim import engine
    from repro.train import trainer, zoo_program

    cfg = chip_smoke.SmokeConfig()
    job, scenarios, seeds = cfg.workload()
    batch, program, data, _ = trainer._prepare_batched(
        job, scenarios, n_ticks=cfg.zoo_ticks, n_batches=None,
        batch_fn=None, batch_seed=0,
        program=lambda n: zoo_program.make_zoo_program(job.model, job, n))
    state = jax.eval_shape(lambda: trainer.batched_init_state(
        job, batch, seeds, init_model=trainer._zoo_setup(job)[1]))
    args = (_shapes(batch, one_chip), _shapes(state, one_chip),
            _shapes(data, one_chip),
            jax.ShapeDtypeStruct((len(seeds),), jnp.int32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    return cfg, program, args


@pytest.mark.parametrize("segment", ["uninterrupted", "durable-chunk"])
def test_full_width_zoo_program_fits_one_chip(zoo_program_args, segment):
    """InternVL2-1B at published widths, 24 layers, bf16 over f32 masters,
    global batch 8 × 1024 tokens: the donated scan `train_zoo` runs for N
    ticks, and the N/2-tick chunk its durable loop and resume run."""
    from repro.sim import engine

    cfg, program, args = zoo_program_args
    n_run = cfg.zoo_ticks if segment == "uninterrupted" \
        else cfg.zoo_ticks // 2
    c = engine._simulate_jit_donated.lower(
        *args, program=program, n_run=n_run, k_snap=0).compile()
    mem = c.memory_analysis()
    # the donated carry is updated in place, not copied
    assert mem.alias_size_in_bytes >= 0.99 * mem.output_size_in_bytes
    assert mem.peak_memory_in_bytes <= HBM_BYTES, mem.peak_memory_in_bytes
    n_params = sum(int(np.prod(x.shape[2:]))
                   for x in jax.tree.leaves(args[1].model["params"]))
    assert n_params > 600e6
