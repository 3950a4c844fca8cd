"""Activation sharding constraints (Megatron-style pinning).

XLA SPMD propagation occasionally resolves conflicts catastrophically —
e.g. batch-unsharding the (B, S, V) logits when the head contraction dim
carries the ZeRO 'data' shard, or padding 14 attention heads onto a
16-way 'model' axis. These helpers pin the canonical activation layout:

    tokens/activations: batch over ('pod','data'), features unsharded
    q/k/v:              batch over dp, heads over 'model' iff divisible
    mlp hidden:         batch over dp, d_ff over 'model'
    logits:             batch over dp, vocab over 'model'

They are no-ops outside a mesh context (single-device smoke tests) and
silently drop axes that do not divide the dimension. The context is the
mesh entered with ``jax.set_mesh`` (``launch.mesh`` builds Auto axes);
only Auto axes are pinned — Manual axes inside a ``shard_map`` body and
Explicit axes already carry their sharding in the types.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

DP = "__dp__"        # sentinel: the data-parallel axes ('pod','data')
MDL = "__model__"    # sentinel: the tensor-parallel axis


def _ambient_mesh():
    """The mesh set by ``jax.set_mesh``, with its axis types (inside a
    shard_map body the mapped axes are Manual); None outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def _usable(mesh, name) -> bool:
    return (name in mesh.axis_names
            and mesh.axis_types[mesh.axis_names.index(name)]
            == AxisType.Auto)


def _resolve(axis, mesh):
    if axis == DP:
        axes = tuple(a for a in ("pod", "data") if _usable(mesh, a))
        return axes if len(axes) > 1 else (axes[0] if axes else None)
    if axis == MDL:
        return "model" if _usable(mesh, "model") else None
    return axis


def _size(mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def constrain(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint(x, P(axes...)) with sentinel resolution,
    divisibility checks, and no-op without an ambient mesh."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    spec = []
    for dim, ax in zip(x.shape, axes):
        r = _resolve(ax, mesh)
        spec.append(r if r is not None and dim % _size(mesh, r) == 0
                    else None)
    spec += [None] * (x.ndim - len(spec))
    return jax.lax.with_sharding_constraint(x, P(*spec))


def batch_seq(x: jax.Array) -> jax.Array:
    """(B, S, ...) block-boundary activations: batch over dp and, by
    default, sequence over 'model' (Megatron-style sequence parallelism —
    cuts the scan-carry residual memory by the TP degree; attention
    all-gathers the sequence internally). REPRO_SP=0 disables the
    sequence axis for A/B measurements (§Perf)."""
    import os
    if x.ndim >= 2 and os.environ.get("REPRO_SP", "1") == "1":
        return constrain(x, DP, MDL)
    return constrain(x, DP)


def heads(x: jax.Array) -> jax.Array:
    """(B, S, H, D): batch over dp, heads over model iff divisible."""
    return constrain(x, DP, None, MDL, None)


def ffn_hidden(x: jax.Array) -> jax.Array:
    """(B, S, F): batch over dp, d_ff over model."""
    return constrain(x, DP, None, MDL)


def logits(x: jax.Array) -> jax.Array:
    """(B, S, V) or (B, V): batch over dp, vocab over model."""
    if x.ndim == 3:
        return constrain(x, DP, None, MDL)
    return constrain(x, DP, MDL)


def expert_parallel(x: jax.Array) -> jax.Array:
    """(E, C, d) MoE expert-major activations: experts over model."""
    return constrain(x, MDL, DP, None)
