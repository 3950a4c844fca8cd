"""Device meshes. Functions, not module constants — importing this
module never touches jax device state (dry-run sets device flags first).

Every mesh here has Auto axes: XLA propagates shardings through them
and ``parallel.act_sharding`` may pin activations on them. (JAX's own
``jax.make_mesh`` defaults to Explicit axes, on which
``with_sharding_constraint`` refuses a bare ``PartitionSpec``.) Enter a
mesh with ``jax.set_mesh(mesh)`` so traced code sees it.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — batch
shards over (pod, data); parameters replicate across pods (gradient
all-reduce over the pod axis is the cross-pod DCI collective).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
