"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (a v5e pod-slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis extends
the data-parallel domain across the inter-pod (DCN/ICI) boundary.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state -- required because the
dry-run forces 512 host devices while tests/benches must see 1.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """A device mesh whose axes are all ``Auto``: GSPMD partitions what
    the sharding annotations leave open."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def activate_mesh(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple:
    """The axes forming the data-parallel domain."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def dp_size(mesh) -> int:
    return int(jax.numpy.prod(jax.numpy.asarray(
        [mesh.shape[a] for a in data_axes(mesh)])))


def tp_size(mesh) -> int:
    return mesh.shape["model"]
