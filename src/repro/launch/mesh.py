"""Production mesh construction. A FUNCTION (not a module-level constant) so
importing this module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the engine and the models
    place data with ``shard_map`` and sharding constraints, not with the
    ``Explicit`` axes ``make_mesh`` defaults to."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; multi_pod adds a leading 2-pod axis
    (512 chips). Axes: ("data", "model") / ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU tests (1×1)."""
    return _mesh((1, 1), ("data", "model"))


def make_scenario_mesh(n_devices: int | None = None):
    """1-D mesh over the scenario axis of the batched engine grid.

    The single axis is named ``data`` — `sim.engine.simulate_sharded`
    partitions the leading scenario axis of the stacked grid across it.
    Spans the first ``n_devices`` visible devices (default: all of them);
    on a CPU host, force N virtual devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
    initializes (``scripts/ci.sh --devices N`` does this)."""
    if n_devices is None:
        n_devices = jax.device_count()
    return _mesh((n_devices,), ("data",), jax.devices()[:n_devices])


def make_scenario_replica_mesh(n_scenario: int | None = None,
                               n_replica: int | None = None):
    """2-D mesh sharding scenarios over ``data`` and seeds over
    ``replica``. With only one size given, the other takes the remaining
    devices; with neither, all devices go to the scenario axis."""
    total = jax.device_count()
    if n_scenario is None and n_replica is None:
        n_scenario, n_replica = total, 1
    elif n_scenario is None:
        n_scenario = total // n_replica
    elif n_replica is None:
        n_replica = total // n_scenario
    if n_scenario * n_replica > total:
        raise ValueError(
            f"mesh shape ({n_scenario}, {n_replica}) needs "
            f"{n_scenario * n_replica} devices but only {total} are "
            "visible")
    return _mesh((n_scenario, n_replica), ("data", "replica"),
                 jax.devices()[:n_scenario * n_replica])


def data_parallel_workers(mesh) -> int:
    """Number of elastic worker slices = product of the batch axes."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pod", 1) * sizes.get("data", 1)
