"""Production mesh construction (multi-pod dry-run §MULTI-POD).

``make_production_mesh`` is a FUNCTION so importing this module never
touches jax device state.  Shapes:

* single-pod: (16, 16)    axes ("data", "model")  — 256 chips
* multi-pod:  (2, 16, 16) axes ("pod", "data", "model") — 512 chips

``submesh`` builds the single-pod mesh out of the first 256 of 512 host
devices so one dry-run process can exercise both meshes.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) == ndev:
        return jax.make_mesh(shape, axes,
                             axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
    if len(devs) > ndev:  # e.g. single-pod mesh on a 512-device host platform
        arr = np.asarray(devs[:ndev]).reshape(shape)
        return Mesh(arr, axes)
    raise RuntimeError(
        f"need {ndev} devices for mesh {shape}, have {len(devs)} — run under "
        f"XLA_FLAGS=--xla_force_host_platform_device_count={ndev} (dry-run) "
        f"or on real hardware")


def make_debug_mesh(shape=(2, 2), axes=("data", "model")) -> Mesh:
    """Small mesh for CPU multi-device tests (8 host devices)."""
    ndev = int(np.prod(shape))
    arr = np.asarray(jax.devices()[:ndev]).reshape(shape)
    return Mesh(arr, axes)


def mesh_from_spec(spec) -> Mesh:
    """Reconstruct a device mesh from a pure-data
    :class:`repro.distributed.plan.MeshSpec` (or a jax ``Mesh``, passed
    through).  This is the only place a :class:`ShardingPlan` touches
    device state, so an exported plan reloads on any machine with enough
    devices — CPU CI included."""
    if isinstance(spec, Mesh):
        return spec
    from repro.distributed.plan import MeshSpec
    spec = MeshSpec.of(spec)
    devs = jax.devices()
    if len(devs) < spec.size:
        raise RuntimeError(
            f"sharding plan needs {spec.size} devices for mesh "
            + "x".join(f"{n}:{s}" for n, s in spec.axes)
            + f", have {len(devs)} {devs[0].platform} device(s) — run on "
            f"a host with {spec.size} chips, or for a CPU rehearsal under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={spec.size}")
    shape = tuple(s for _, s in spec.axes)
    arr = np.asarray(devs[:spec.size]).reshape(shape)
    return Mesh(arr, spec.names)


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (pod included when present)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis(mesh: Mesh) -> str:
    return "model"
