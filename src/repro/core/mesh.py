"""The fleet mesh: the solver core's one device axis (DESIGN.md §14).

``run_batch_sharded`` shards the instance/seed axis of a stacked
``Problem``/``SolverState`` pytree across a 1-D device mesh.
:func:`fleet_mesh` builds that mesh, :func:`fleet_axis` names its axis,
:func:`fleet_specs` builds the leading-axis PartitionSpec tree, and
:func:`pad_fleet`/:func:`unpad_fleet` implement uneven-shard padding
with exact masking (pad lanes replicate the last real instance — a
feasible solve whose rows are sliced off afterwards, so
unpad(pad(x)) is bit-identical to x).

Functions, not module constants: importing this module never touches
jax device state.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

FLEET_AXIS = "fleet"


def fleet_axis(mesh) -> str:
    """The mesh axis carrying the instance/seed dimension.

    A fleet mesh is 1-D (:func:`fleet_mesh`); for convenience any
    mesh with a ``"fleet"`` axis qualifies.  Raises on meshes where the
    fleet axis is ambiguous — sharding the instance axis over a silently
    guessed axis would be an invisible wrong answer.
    """
    if FLEET_AXIS in mesh.axis_names:
        return FLEET_AXIS
    if len(mesh.axis_names) == 1:
        return mesh.axis_names[0]
    raise ValueError(
        f"mesh axes {mesh.axis_names} have no '{FLEET_AXIS}' axis and are "
        "not 1-D: name the instance axis explicitly (fleet_mesh "
        "builds the canonical 1-D fleet mesh)")


def fleet_spec(ndim: int, axis: str = FLEET_AXIS) -> P:
    """Leading-axis PartitionSpec for one rank-``ndim`` leaf.

    Rank-0 leaves (scalars) have no instance axis and replicate.
    """
    if ndim == 0:
        return P()
    return P(axis, *([None] * (ndim - 1)))


def fleet_specs(tree: Any, axis: str = FLEET_AXIS, *,
                shard: bool = True) -> Any:
    """PartitionSpec tree sharding every leaf's leading axis over ``axis``.

    ``shard=False`` replicates the whole tree (the broadcast-bank /
    scalar-demand case).  Works on concrete arrays and on
    ``ShapeDtypeStruct`` trees from ``jax.eval_shape`` alike.
    """
    def spec_for(leaf):
        ndim = len(leaf.shape) if hasattr(leaf, "shape") else jnp.ndim(leaf)
        return fleet_spec(ndim, axis) if shard else P()

    return jax.tree_util.tree_map(spec_for, tree)


def fleet_padded_size(size: int, n_shards: int) -> int:
    """The smallest multiple of ``n_shards`` that is ≥ ``size``."""
    if size < 1 or n_shards < 1:
        raise ValueError(f"need size ≥ 1 and n_shards ≥ 1, got "
                         f"({size}, {n_shards})")
    return -(-size // n_shards) * n_shards


def pad_fleet(tree: Any, n_shards: int) -> Any:
    """Pad every leaf's leading axis up to a multiple of ``n_shards``.

    Pad lanes replicate the **last real instance**, so they carry a
    feasible problem (no NaN-generating zero masks enter the solve) and
    every shard runs the same program.  Exactness comes from masking on
    the way out: :func:`unpad_fleet` slices the pad lanes off, making
    ``unpad_fleet(pad_fleet(x, n), B)`` bit-identical to ``x``.
    """
    def pad_leaf(leaf):
        leaf = jnp.asarray(leaf)
        b = leaf.shape[0]
        extra = fleet_padded_size(b, n_shards) - b
        if extra == 0:
            return leaf
        fill = jnp.broadcast_to(leaf[-1:], (extra,) + leaf.shape[1:])
        return jnp.concatenate([leaf, fill], axis=0)

    return jax.tree_util.tree_map(pad_leaf, tree)


def unpad_fleet(tree: Any, size: int) -> Any:
    """Slice every leaf's leading axis back to the true fleet ``size``."""
    return jax.tree_util.tree_map(lambda leaf: leaf[:size], tree)


def shard_map_compat(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled.

    The fleet engine's per-shard solves are embarrassingly parallel (no
    cross-shard collectives), which the checker cannot always prove
    through a scanned solver body.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def fleet_mesh(n_devices: int | None = None, devices=None):
    """The solver core's 1-D instance-axis mesh: axes ``("fleet",)``.

    ``run_batch_sharded`` (core/batch.py, DESIGN.md §14) shards the
    stacked instance/seed axis of a fleet solve over this mesh.  Uses
    every visible device by default; a 1-device fleet mesh is valid (and
    bit-identical to the plain vmap path — the parity tier asserts it),
    so callers never need a device-count special case.
    """
    devices = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if not 1 <= n_devices <= len(devices):
            raise ValueError(
                f"n_devices={n_devices} outside [1, {len(devices)}]")
        devices = devices[:n_devices]
    return jax.sharding.Mesh(np.array(devices), (FLEET_AXIS,))
