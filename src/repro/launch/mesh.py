"""Production mesh definitions.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (v5e pod),
axes (data, model).  Multi-pod: 2 pods = 512 chips, axes (pod, data, model);
'pod' is an outer data-parallel axis (params replicated per pod, hierarchical
gradient all-reduce).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """A device mesh whose axes are all Auto: the compiler propagates
    shardings from the jit in/out shardings and constraints, which is what
    the train step, the shard_map paths and the checkpoint restore are
    written against (``jax.make_mesh`` alone gives Explicit axes)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"need {need} devices, have {len(devices)}; launch through "
            f"launch/dryrun.py (it forces 512 host devices) or a real fleet")
    return make_mesh(shape, axes, devices=devices[:need])
