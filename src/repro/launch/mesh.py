"""Mesh construction.  Importing this module never touches jax device state;
meshes are built inside functions only (dry-run requirement)."""
from __future__ import annotations

from typing import Optional, Sequence, Tuple


def make_production_mesh(*, multi_pod: bool = False):
    """Production mesh: (data=16, model=16) single pod = 256 chips;
    (pod=2, data=16, model=16) = 512 chips across two pods."""
    import numpy as np
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes)
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; "
            "run under dryrun.py (XLA_FLAGS=--xla_force_host_platform_device_count=512)")
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_host_mesh(model_parallel: int = 1, n_devices: int = 0):
    """Mesh over the first ``n_devices`` devices (all when 0) of this host."""
    import numpy as np
    import jax
    devs = jax.devices()[:n_devices or None]
    dp = len(devs) // model_parallel
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:dp * model_parallel]).reshape(dp, model_parallel),
                ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
