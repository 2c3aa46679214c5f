"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state. The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else (smoke tests, benches) sees the real single device.

Topology: one TPU v5e pod = 16x16 = 256 chips. Single-pod mesh is
("data", "model") = (16, 16); the multi-pod mesh adds a leading "pod" axis
(DCN between pods): ("pod", "data", "model") = (2, 16, 16) = 512 chips.
TP ("model") stays intra-pod on ICI; batch/ZeRO sharding spans pod x data.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape, axes) -> Mesh:
    # Auto axes: sharding constraints and shard_map partition these meshes;
    # jax.make_mesh would default them to Explicit.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh_for_devices(n_devices: int, model_parallel: int = 1) -> Mesh:
    """Elastic helper: an (n/model, model) mesh over however many devices the
    runtime currently has (used by the fault-tolerance / resize paths)."""
    assert n_devices % model_parallel == 0, (n_devices, model_parallel)
    return _mesh((n_devices // model_parallel, model_parallel), ("data", "model"))


def make_local_mesh() -> Mesh:
    """1-device mesh with production axis names: smoke tests exercise the
    exact sharded code paths with every constraint a no-op."""
    return _mesh((1, 1), ("data", "model"))
