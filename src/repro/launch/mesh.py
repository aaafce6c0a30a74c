"""Mesh construction — the one place outside ``repro/backend/`` that builds
meshes (lint LN004).

Every mesh has Auto axes: ``with_sharding_constraint`` (the model's
``constrain``) only accepts specs over Auto axes, and ``jax.make_mesh``
defaults to Explicit ones.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any device query).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices: Optional[Sequence] = None):
    """Arbitrary mesh over ``devices`` (default: all), Auto axes."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod ("data","model"); 2 pods = 512 ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist, as a 1-D 'data' mesh (CPU examples/tests)."""
    return make_mesh((len(jax.devices()),), ("data",))
