"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16)."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (TPU v5e pod slice); 2 pods when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh_for_devices(devices: list, model_axis: int = 16,
                          pod_axis: int = 1):
    """Elastic variant: biggest legal mesh for a surviving device list."""
    from ..runtime.fault_tolerance import plan_mesh
    import numpy as np
    shape = plan_mesh(len(devices), model_axis, pod_axis)
    n = int(np.prod(shape))
    axes = (("pod", "data", "model") if len(shape) == 3
            else ("data", "model"))
    devs = np.asarray(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(devs, axes)
