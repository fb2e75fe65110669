"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — smoke tests must keep seeing a
single CPU device; only the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh", "make_eval_mesh",
           "mesh_axes"]


def _auto_mesh(shape, axes, **kw):
    """``jax.make_mesh`` with ``Auto`` axes: the model code places
    activations with ``with_sharding_constraint``, which ``Explicit``
    axes (the default since jax 0.7) reject."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model); the leading pod
    axis carries the AFarePart pipeline stages."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over the real local device(s) for CPU tests."""
    return _auto_mesh(shape, axes)


def make_eval_mesh(n_devices: int):
    """(data=n, model=1) mesh over the first ``n_devices`` LOCAL
    devices — the evaluation engine's device pool
    (``core/eval_engine.DeviceScheduler``).  Unlike
    :func:`make_test_mesh` this may enumerate a subset of the host's
    devices (``devices=N`` on the evaluator with more chips present),
    so the device list is passed explicitly; the mesh is the one
    agreement between the eval engines and the launch stack on device
    order."""
    return _auto_mesh((n_devices, 1), ("data", "model"),
                      devices=jax.local_devices()[:n_devices])


def mesh_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)
