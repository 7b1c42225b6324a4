"""Production meshes (per the multi-pod dry-run spec).

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state.
"""

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for tests run under --xla_force_host_platform_device_count."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )
