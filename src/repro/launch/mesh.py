"""Mesh builders.

Every mesh in the repo is built by :func:`make_mesh`.  The builders are
FUNCTIONS (not module-level constants) so that importing this module never
touches jax device state.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A mesh over the first ``prod(shape)`` devices whose axes are all
    ``Auto``.  Model code places activations with
    ``with_sharding_constraint`` (``sharding/ctx.py``), which accepts only
    Auto axes; ``jax.make_mesh`` makes Explicit axes by default."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; 2 pods when multi_pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Every device of this host as a (data, model) = (n, 1) mesh
    (1 device -> 1x1 mesh)."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))
