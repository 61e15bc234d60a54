"""Mesh construction (as functions — importing this module never touches
jax device state).

Every mesh in the repo is built by :func:`make_mesh`, with ``Auto`` axes:
the sharding design is GSPMD propagation plus
``distributed/axes.py::constrain``, not explicit-sharding types.

Single pod: (data=16, model=16) = 256 chips (v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; only batch/grad traffic
crosses the `pod` (DCN) axis.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None):
    """A mesh of ``shape`` named ``axes`` whose axes are all ``Auto``;
    ``devices`` defaults to ``jax.devices()``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, devices=None):
    """Small (data, model) mesh over the first ``data*model`` devices —
    tests, examples and the chip smoke."""
    return make_mesh((data, model), ("data", "model"), devices=devices)
