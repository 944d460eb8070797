"""Device meshes for production and LTFB runs.

Functions, not module-level constants — importing this module never
touches jax device state (required so smoke tests see 1 CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256-chip pod, or 2x16x16 = 512-chip two-pod mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    assert len(devices) == n, (
        f"need {n} devices, have {len(devices)} (to emulate them, set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
        "importing jax)")
    import numpy as np
    return Mesh(np.asarray(devices).reshape(shape), axes)


def make_ltfb_mesh(trainers: int, per_trainer_model: int = 16) -> Mesh:
    """LTFB population mesh: ('trainer', 'model').

    The paper's production point is 64 trainers x 16 GPUs; on a 512-chip
    2-pod system the analogue is 32 trainers x 16-way model/data
    parallelism per trainer.
    """
    n = trainers * per_trainer_model
    devices = jax.devices()[:n]
    assert len(devices) == n, f"need {n} devices, have {len(devices)}"
    import numpy as np
    return Mesh(np.asarray(devices).reshape(trainers, per_trainer_model),
                ("trainer", "model"))


def make_host_mesh(axes=("data",)) -> Mesh:
    """All visible devices on one axis (tests / small runs)."""
    import numpy as np
    devs = np.asarray(jax.devices())
    return Mesh(devs.reshape((len(devs),) + (1,) * (len(axes) - 1)), axes)
