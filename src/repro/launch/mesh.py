"""Mesh construction — every mesh in the repo is built here.

Functions, not module-level constants — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).

All axes are ``AxisType.Auto``: the model code shards through
``with_sharding_constraint`` hints and ``shard_map`` and leaves the rest to
GSPMD. (``jax.make_mesh`` defaults every axis to ``Explicit``, under which
the untyped reshapes of the MoE dispatch are refused.)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with Auto axes; ``devices`` defaults to all."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_ep_mesh(n_devices: int, devices=None):
    """Flat EP mesh for the distributed serving engines: all devices on the
    ``model`` axis (so any expert count divisible by the device count
    shards), a singleton ``data`` axis to satisfy the sharding rule table."""
    return make_mesh((1, n_devices), ("data", "model"), devices=devices)


def force_host_device_count(n: int) -> None:
    """Split the host platform into ``n`` XLA devices (CI / laptop meshes).

    Must run BEFORE the jax backend initializes (first device query) — this
    is why ``repro.launch.serve`` handles ``--mesh`` before importing jax
    for real work, and why the mesh test tier sets
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in the
    environment instead. A no-op when the flag is already present.
    """
    import os
    cur = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in cur:
        os.environ["XLA_FLAGS"] = (
            f"{cur} --xla_force_host_platform_device_count={n}".strip())
