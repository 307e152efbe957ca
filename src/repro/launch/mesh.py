"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  The dry-run forces 512 host-platform devices
before importing jax; real deployments get the same mesh over TPU chips.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Elastic variant: any (data, model) / (pod, data, model) shape, used by
    the trainer for small CPU runs and by elastic restarts."""
    return _mesh(shape, axes)
