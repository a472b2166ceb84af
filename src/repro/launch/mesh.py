"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — required because the dry-run must set
XLA_FLAGS before the first jax initialization.  Every mesh in the repo (and
in the subprocess test scripts) is built through :func:`make_mesh`.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(devices, axes: tuple[str, ...]) -> Mesh:
    """Mesh constructor: all axes auto-sharded."""
    return Mesh(np.asarray(devices), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    assert len(devices) >= n, \
        f"need {n} devices, have {len(devices)} — run under dryrun.py " \
        f"(XLA_FLAGS=--xla_force_host_platform_device_count=512)"
    return make_mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_local_mesh(model_parallel: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    devices = jax.devices()
    mp = max(1, min(model_parallel, len(devices)))
    dp = len(devices) // mp
    dev = np.asarray(devices[: dp * mp]).reshape(dp, mp)
    return make_mesh(dev, ("data", "model"))
