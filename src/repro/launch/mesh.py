"""Production mesh builders (functions, not module constants — importing this
module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (TPU v5e); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


#: axis name of the fleet-execution mesh (instance axis of a job bin)
FLEET_AXIS = "fleet"

_FLEET_MESHES: dict = {}


def make_fleet_mesh(n_devices: int | None = None):
    """1-D mesh over the local devices for sharding a fleet bin's instance
    axis. Returns None with fewer than 2 devices (nothing to shard over).
    Memoized per device count: FleetExecutor asks once per bin and jit
    caches key on mesh identity."""
    n = n_devices if n_devices is not None else jax.device_count()
    if n < 2:
        return None
    mesh = _FLEET_MESHES.get(n)
    if mesh is None:
        mesh = _FLEET_MESHES[n] = make_mesh((n,), (FLEET_AXIS,))
    return mesh


def dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
