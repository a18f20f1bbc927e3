"""Marginal-cost broadcast (paper eq. (19)–(21), Gallager's recursion).

∂D/∂r_j(w) — the marginal network cost of one extra unit of session-w traffic
arriving at node j — satisfies the reverse recursion

    ∂D/∂r_{D_w} = 0
    ∂D/∂r_i(w)  = Σ_j φ_ij(w) · [ D'_ij(F_ij) + ∂D/∂r_j(w) ]

In a deployment this is the hop-by-hop "marginal cost broadcast" protocol
(paper §III-B): each node piggybacks its scalar on traffic towards its
upstream neighbours.  Here the same recursion is a ``lax.scan`` on the
reversed DAG, exact after ``depth_max`` steps.  The full marginal routing
cost (eq. (19)) and the gradient w.r.t. φ (eq. (18)) follow elementwise.

``tests/test_core_flow.py`` property-checks this recursion against
``jax.grad`` through the forward propagation — the distributed protocol and
autodiff must agree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .costs import CostFn
from .flow import HIGHEST
from .graph import CECGraph, CECGraphSparse

Array = jnp.ndarray


def marginals(graph: CECGraph | CECGraphSparse, cost: CostFn, phi, t: Array,
              F) -> tuple:
    """Returns (delta, dDdr).

    delta[w,i,j] = D'_ij + ∂D/∂r_j(w)  — marginal routing cost (eq. 19)
    dDdr[w,i]    = ∂D/∂r_i(w)          — broadcast scalar    (eq. 21)

    Sparse graphs take the edge-list recursion (core/sparse.py): φ, F and
    the returned delta are then in the slot layout; dDdr is [W, Nb] in
    both representations.
    """
    if isinstance(graph, CECGraphSparse):
        from . import sparse

        return sparse.marginals(graph, cost, phi, t, F)
    Dp = graph.edge_mask * cost.deriv(F, graph.capacity)   # [Nb, Nb]
    mask = graph.out_mask

    def step(r, _):
        # r_i(w) = Σ_j φ_ij (Dp_ij + r_j);  sinks have no out-edges → stay 0
        nxt = jnp.einsum("wij,wij->wi", phi, mask * (Dp[None] + r[:, None, :]),
                         precision=HIGHEST)
        return nxt, None

    zero = jnp.zeros_like(t)
    dDdr, _ = jax.lax.scan(step, zero, None, length=graph.depth_max)
    delta = mask * (Dp[None] + dDdr[:, None, :])
    return delta, dDdr


def phi_gradient(t: Array, delta: Array) -> Array:
    """∂D/∂φ_ij(w) = t_i(w) · δφ_ij(w) (paper eq. (18))."""
    return t[:, :, None] * delta
