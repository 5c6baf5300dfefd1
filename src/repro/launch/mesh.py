"""Production mesh definitions (functions — importing never touches jax
device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    # Auto axes: the sharding rules (parallel/sharding.py) place arrays with
    # NamedSharding + with_sharding_constraint and let GSPMD propagate — JAX
    # 0.9's make_mesh default (Explicit) rejects those constraints
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1, devices=None):
    """(data, model) mesh over `devices` (default: every local device)."""
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    assert n % model_parallel == 0, (n, model_parallel)
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"), devices=devices)
