"""Link/compute cost functions D_ij(F_ij, C_ij) (paper §II-D).

Every cost is increasing, continuously differentiable and convex in F for
fixed C.  All are implemented with smooth linear extensions past a clip point
so that gradients stay finite when an iterate momentarily overloads a link
(the optimum is always in the well-behaved region).  The ``where``/``where``
pattern avoids NaN cotangents from saturated branches.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax.numpy as jnp

Array = jnp.ndarray


class CostFn(NamedTuple):
    """value(F, C) -> D_ij elementwise; deriv(F, C) -> dD/dF elementwise.

    ``floor`` is value(0, C), the same for every edge (1 for the exp cost).
    """

    name: str
    value: Callable[[Array, Array], Array]
    deriv: Callable[[Array, Array], Array]
    floor: float = 0.0


def total(cost: CostFn, F: Array, C: Array, mask: Array) -> Array:
    """Σ mask·value(F, C), summed as excess over the floor plus the floor.

    The exp cost charges every edge at least exp(0) = 1, so its total
    carries the edge count as a large constant.  Summed directly in f32,
    the rounding at that magnitude is several ulps of the total, which is
    the size of the two-point gradient's D(Λ+δe) − D(Λ−δe).  Summing the
    small excesses first and adding the exact edge count once leaves one
    rounding at the total's magnitude.  Plain jnp, so kernel bodies call
    it too.
    """
    if cost.floor == 0.0:
        return jnp.sum(mask * cost.value(F, C))
    return (jnp.sum(mask * (cost.value(F, C) - cost.floor))
            + cost.floor * jnp.sum(mask))


_EXP_CLIP = 25.0     # exp cost linearized beyond z = F/C = 25
_MM1_CLIP = 0.95     # M/M/1 cost quadratically extended beyond 95% load


def _exp_value(F: Array, C: Array) -> Array:
    z = F / C
    zs = jnp.minimum(z, _EXP_CLIP)
    ev = jnp.exp(zs)
    return jnp.where(z <= _EXP_CLIP, ev, ev * (1.0 + (z - zs)))


def _exp_deriv(F: Array, C: Array) -> Array:
    z = F / C
    zs = jnp.minimum(z, _EXP_CLIP)
    return jnp.exp(zs) / C


def _mm1_value(F: Array, C: Array) -> Array:
    z = F / C
    zs = jnp.minimum(z, _MM1_CLIP)
    base = zs / (1.0 - zs)
    # C¹ quadratic extension: value' and value'' continuous at the clip point
    g = 1.0 / (1.0 - _MM1_CLIP) ** 2
    h = 2.0 / (1.0 - _MM1_CLIP) ** 3
    dz = jnp.maximum(z - _MM1_CLIP, 0.0)
    return jnp.where(z <= _MM1_CLIP, base, base + g * dz + 0.5 * h * dz * dz)


def _mm1_deriv(F: Array, C: Array) -> Array:
    z = F / C
    zs = jnp.minimum(z, _MM1_CLIP)
    base = 1.0 / (1.0 - zs) ** 2
    h = 2.0 / (1.0 - _MM1_CLIP) ** 3
    dz = jnp.maximum(z - _MM1_CLIP, 0.0)
    return jnp.where(z <= _MM1_CLIP, base, base + h * dz) / C


EXP = CostFn("exp", _exp_value, _exp_deriv, floor=1.0)            # paper §IV
MM1 = CostFn("mm1", _mm1_value, _mm1_deriv)                       # paper eq. (5)
LINEAR = CostFn("linear", lambda F, C: F / C, lambda F, C: 1.0 / C)
QUADRATIC = CostFn("quad", lambda F, C: F * F / C, lambda F, C: 2.0 * F / C)

REGISTRY = {c.name: c for c in (EXP, MM1, LINEAR, QUADRATIC)}


def get(name: str) -> CostFn:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown cost {name!r}: registered costs are "
            f"{sorted(REGISTRY)}") from None
