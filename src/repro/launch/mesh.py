"""Mesh construction.

Functions (never module-level constants) so importing this module touches
no jax device state; the dry-run sets the 512-placeholder-device XLA flag
before jax initializes, and only then calls these.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """The repo's one mesh constructor: ``jax.make_mesh`` with ``Auto`` axis
    types.

    The sharded paths (``ServingEngine.topk_sharded``, the owner-compute
    ``mf.train_step_shard_map``, the dry-run cells) are written for
    GSPMD-propagated shardings: explicit ``shard_map`` bodies plus ordinary
    jnp slicing/gathers on their outputs.  ``jax.make_mesh`` defaults to
    ``Explicit`` axes, under which those slices and the donated scan carries
    fail sharding-in-types checks, so every mesh goes through here.
    ``devices`` overrides ``jax.devices()`` (e.g. described, not attached,
    TPU devices for compile-only rehearsals).
    """
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """Tiny mesh with the same axis names for CI-scale SPMD tests (needs >= 8
    host devices via --xla_force_host_platform_device_count)."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
