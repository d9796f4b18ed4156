"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required because the dry-run
launcher must set XLA_FLAGS before any jax initialization.

Single pod : (16, 16)      axes (data, model)   = 256 chips (v5e pod)
Multi-pod  : (2, 16, 16)   axes (pod, data, model) = 512 chips; the "pod"
axis composes with "data" for data parallelism and is the fault-isolation /
gradient-compression boundary (cross-pod links are the slow DCN/ICI hops).
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: GSPMD partitions from the param specs (make_mesh's
    # default is Explicit axes, which demand sharding-typed programs)
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh_from_devices(devices, model_parallel: int
                           ) -> jax.sharding.Mesh:
    """Elastic-scaling path: build the best (data, model) mesh from an
    explicit device list (e.g. survivors after a failure)."""
    n = len(devices)
    while n % model_parallel and model_parallel > 1:
        model_parallel //= 2
    data = n // model_parallel
    import numpy as np
    arr = np.asarray(devices)[: data * model_parallel].reshape(
        data, model_parallel)
    return jax.sharding.Mesh(arr, ("data", "model"))


def dp_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """The data-parallel axes of a production mesh."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def dp_size(mesh: jax.sharding.Mesh) -> int:
    out = 1
    for a in dp_axes(mesh):
        out *= mesh.shape[a]
    return out
