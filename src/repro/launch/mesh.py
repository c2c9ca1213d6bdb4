"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state; the dry-run entrypoint sets XLA_FLAGS *before* any jax import.

Mesh geometry (TPU v5e pods): one pod = 256 chips as (data=16, model=16);
multi-pod = 2 pods → (pod=2, data=16, model=16) with the `pod` axis mapped
across DCN. Axis roles: `data` = batch/FSDP/vertex shards, `model` = tensor/
expert/landmark parallel, `pod` = extra data parallelism across pods.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Host-device mesh for CPU runs: (data = n_devices // model, model).

    With the default `model=1` every local device lands on the `data` axis
    (the historical degenerate shape). Pass `model>1` to split off a
    landmark-parallel axis — e.g. under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, `model=4`
    yields a (data=2, model=4) mesh. `core/shard.py` runs the BatchHL
    stack on this mesh; `launch/serve.py --mesh host --shards M` wires it
    into the serving loop.
    """
    n = len(jax.devices())
    if model < 1 or n % model:
        raise ValueError(
            f"model-axis size {model} must divide the {n} local devices")
    # Auto axes: `core/shard.py` places its outputs with shard_map specs
    # and lets the compiler propagate shardings outside it; Explicit axes
    # (the make_mesh default) would type-check every op on them instead.
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
