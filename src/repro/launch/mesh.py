"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.  The dry-run launcher
sets XLA_FLAGS --xla_force_host_platform_device_count=512 before any jax
import; everything else sees the real device count.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType


def _auto(axes: Tuple[str, ...]):
    """GSPMD-propagated axes: the model places activations with sharding
    hints and leaves the rest to the compiler.  ``jax.make_mesh`` now
    defaults to explicit axes, under which the embedding gather of a
    sharded table is refused."""
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(axes),
                         devices=jax.devices()[: int(np.prod(shape))])


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[list] = None):
    """Arbitrary mesh factorization (the tuner's dp/tp knob).

    shape like (dp, tp) with axes ("data", "model"), or (pods, dp, tp).
    """
    n = int(np.prod(shape))
    devices = devices if devices is not None else jax.devices()[:n]
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    return jax.make_mesh(shape, axes, _auto(axes), devices=devices[:n])


def single_device_mesh():
    axes = ("data", "model")
    return jax.make_mesh((1, 1), axes, _auto(axes), devices=jax.devices()[:1])
