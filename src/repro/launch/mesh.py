"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (tests run with 1 CPU device; dryrun.py runs
with 512 forced host devices).

Axes:
* "pod"   — pure data parallelism across pods (gradient all-reduce over
  DCI only; no weight shard crosses a pod boundary);
* "data"  — FSDP/ZeRO-3 weight sharding + batch within a pod (ICI);
* "model" — tensor parallelism (+ sequence parallelism between blocks).

The same rule table (parallel/sharding.py) drives any pod count — scale
out = grow the leading "pod" axis.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "make_serve_mesh",
           "POD_SHAPE"]

POD_SHAPE = (16, 16)   # 256 chips per pod


def make_mesh(shape, axes, devices=None):
    """Build a mesh of ``shape`` over ``devices`` (default: all of this
    process's devices, in order).  An explicit device list is how the
    elastic path rebuilds on the survivors after a loss — the dead
    device must not appear in the new mesh."""
    n = int(np.prod(shape))
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)} — the "
            f"dry-run must set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=512 before importing jax")
    # Auto axes: the model code places arrays through sharding rules and
    # with_sharding_constraint, not through explicitly-typed mesh axes.
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2,) + POD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has (tests/examples): (1, N) mesh."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def make_serve_mesh(model=None, data=1, devices=None):
    """(data, model) mesh for the low-bit serving engine
    (``ServeConfig(mesh=...)``): ``model`` defaults to whatever fills
    the available devices.  CPU-tested by spawning a process with
    ``--xla_force_host_platform_device_count=N``."""
    devs = list(devices) if devices is not None else jax.devices()
    if model is None:
        model = len(devs) // data
    return make_mesh((data, model), ("data", "model"), devices=devs)
