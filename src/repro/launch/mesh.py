"""Mesh construction.

Functions, not module-level constants, so importing never touches jax device
state (required by the dry-run protocol).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto``.

    In jax 0.9 ``jax.make_mesh`` defaults to ``Explicit`` axes, on which
    ``with_sharding_constraint`` (the model's sharding hints) and the
    propagation-driven layouts of this repo are refused.  Every mesh here is
    built through this one function."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod: (data=16, model=16); two pods: (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model = min(model, n)
    return make_mesh((n // model, model), ("data", "model"))
