"""Sparse edge-list execution path for the solver stack (DESIGN.md §12).

Every hot-path quantity of the dense solver — flow propagation (paper
eq. (1)/(2)), link flows (eq. (4)), total cost, the marginal-cost
broadcast (eq. (19)–(21)) and the exponentiated-gradient routing update
(eq. (22)) — re-expressed over a :class:`~repro.core.graph.CECGraphSparse`
padded edge-list layout, O(E) state and FLOPs instead of O(N̄²).

The formulation is gather-only in the relaxation loop (TPU-friendly —
no scatters inside the scan):

* per-step relay inflow is a CSC gather + row sum
  (``t[:, in_src] · φ[:, in_src, in_slot]``);
* the virtual source's contribution is **constant across relaxation
  steps** (S has no in-edges, so t_S(w) ≡ λ_w) and is scattered once per
  ``propagate`` into the injection vector (:func:`source_inflow`);
* sink inflow — the one true hub of the augmented graph (in-degree
  Θ(N/W)) — is accumulated analytically as W masked reductions over the
  compute-edge slots, never via padded in-lists.

``core.flow`` / ``core.marginal`` / ``core.routing`` dispatch here on the
graph type, so ``solve_routing``, ``gs_oma``/``omad``, the vmapped batch
solvers and ``CECRouter`` run either representation transparently; when
``core.dispatch.use_kernels`` holds, the inner steps route through the
segment Pallas kernels ``kernels.flow_step_sparse`` /
``kernels.omd_update_sparse`` (interpret mode off-TPU).  Dense↔sparse
parity is property-tested to 1e-5 in ``tests/test_sparse_parity.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import costs, dispatch
from .costs import CostFn
from .flow import HIGHEST
from .graph import CECGraph, CECGraphSparse, SparsePhi

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# φ layout conversions
# ---------------------------------------------------------------------------

def phi_to_sparse(graph: CECGraphSparse, phi: Array) -> SparsePhi:
    """Gather a dense [W, Nb, Nb] routing tensor into the slot layout."""
    W = graph.n_sessions
    idx = jnp.broadcast_to(graph.nbr[None], (W,) + graph.nbr.shape)
    rows = jnp.take_along_axis(phi, idx, axis=2) * graph.out_mask
    src = phi[:, graph.src, graph.src_nbr] * graph.src_out_mask
    return SparsePhi(rows=rows, src=src)


def remap_phi(old: CECGraphSparse, new: CECGraphSparse,
              phi: SparsePhi) -> SparsePhi:
    """Re-express a :class:`SparsePhi` on another graph's slot layout.

    Matches slots by **edge identity** (tail, head), not position: after
    churn the CSR packing can shift even when ``d_max`` is unchanged, so
    positional reuse would silently hand edge (a→b)'s mass to (a→e).
    Edges absent from ``old`` start at zero — exactly what
    ``warm_start_phi``'s exploration mix expects to revive.  Python-level
    numpy (it runs at topology-change time, never inside a trace); both
    graphs must share the augmented index space (``n_bar``).
    """
    if old.n_bar != new.n_bar:
        raise ValueError(f"index spaces differ: {old.n_bar} != {new.n_bar}")

    def match(old_nbr, old_vals, old_mask, new_nbr, new_mask):
        # hit[..., d_new, d_old] — same head ⇒ same edge (rows share tails)
        hit = (np.asarray(new_nbr)[..., :, None]
               == np.asarray(old_nbr)[..., None, :])
        hit &= (np.asarray(old_mask) > 0)[..., None, :]
        hit &= (np.asarray(new_mask) > 0)[..., None]
        found = hit.any(-1)
        slot = hit.argmax(-1)
        vals = np.take_along_axis(np.asarray(old_vals), slot[None], -1)
        return jnp.asarray(np.where(found[None], vals, 0.0))

    rows = match(old.nbr, phi.rows, old.edge_mask, new.nbr, new.edge_mask)
    src = match(old.src_nbr, phi.src, old.src_edge_mask,
                new.src_nbr, new.src_edge_mask)
    return SparsePhi(rows=rows * new.out_mask, src=src * new.src_out_mask)


def phi_to_dense(graph: CECGraphSparse, phi: SparsePhi) -> Array:
    """Scatter a :class:`SparsePhi` back to the dense [W, Nb, Nb] layout."""
    W, n_bar = graph.n_sessions, graph.n_bar
    out = jnp.zeros((W, n_bar, n_bar), phi.rows.dtype)
    rows_i = jnp.broadcast_to(jnp.arange(n_bar)[:, None], graph.nbr.shape)
    out = out.at[:, rows_i, graph.nbr].add(phi.rows * graph.out_mask)
    return out.at[:, graph.src, graph.src_nbr].add(phi.src * graph.src_out_mask)


# ---------------------------------------------------------------------------
# flow propagation (paper eq. (1)/(2)) and cost
# ---------------------------------------------------------------------------

def source_inflow(graph: CECGraphSparse, phi: SparsePhi, lam: Array) -> Array:
    """[W, Nb] per-step constant inflow: exogenous injection at S plus the
    admission flow λ_w·φ_S over the S→D(1) fan-out (t_S(w) ≡ λ_w)."""
    admit = lam[:, None] * phi.src * graph.src_out_mask
    return graph.injection(lam).at[:, graph.src_nbr].add(admit)


def _relay_inflow(graph: CECGraphSparse, rows: Array, t: Array) -> Array:
    """[W, Nb] physical relay inflow: CSC gather + row sum (jnp path)."""
    tv = t[:, graph.in_src]                          # [W, Nb, Din]
    pv = rows[:, graph.in_src, graph.in_slot]        # [W, Nb, Din]
    return (tv * pv * graph.in_mask).sum(-1)


def _sink_inflow(graph: CECGraphSparse, rows: Array, t: Array) -> Array:
    """[W] compute-edge inflow per sink: Σ_{i∈D(w)} t_i(w)·φ_{i,D_w}."""
    tphys = t[:, : graph.n_phys]
    psink = jnp.take_along_axis(
        rows[:, : graph.n_phys], graph.sink_slot[None, :, None], axis=2)[..., 0]
    return (graph.deploy * tphys * psink).sum(-1)


def propagate(graph: CECGraphSparse, phi: SparsePhi, lam: Array) -> Array:
    """Session rates t[W, Nb]: ``depth_max`` Jacobi steps over edge lists.

    Bit-for-bit the dense recursion re-associated over slots: each step is
    ``t' = base + relay_gather(t)`` with the W sink entries overlaid from
    :func:`_sink_inflow` (old ``t``, Jacobi semantics).  Size-dispatched
    like the dense path: past ``dispatch.use_kernels(n_bar)`` the gather
    step runs the Pallas ``flow_step_sparse`` kernel.
    """
    inject = graph.injection(lam)
    base = source_inflow(graph, phi, lam)
    wi, sinks = jnp.arange(graph.n_sessions), graph.sinks

    if dispatch.use_kernels(graph.n_bar):
        from repro.kernels.ops import flow_step_sparse_op

        interpret = dispatch.kernel_interpret()

        def relay(t):
            return flow_step_sparse_op(t, phi.rows, base, graph.in_src,
                                       graph.in_slot, graph.in_mask,
                                       interpret=interpret)
    else:
        def relay(t):
            return base + _relay_inflow(graph, phi.rows, t)

    def step(t, _):
        t_new = relay(t).at[wi, sinks].set(_sink_inflow(graph, phi.rows, t))
        return t_new, None

    t, _ = jax.lax.scan(step, inject, None, length=graph.depth_max)
    return t


def link_flow_slots(graph: CECGraphSparse, phi: SparsePhi,
                    t: Array) -> SparsePhi:
    """Per-edge total flow F (eq. (4)) in the slot layout."""
    rows = jnp.einsum("wi,wid->id", t, phi.rows, precision=HIGHEST)
    src = jnp.einsum("w,wd->d", t[:, graph.src], phi.src,
                     precision=HIGHEST)
    return SparsePhi(rows=rows, src=src)


def _slot_cost(graph: CECGraphSparse, cost: CostFn, F: SparsePhi) -> Array:
    return (costs.total(cost, F.rows, graph.capacity, graph.edge_mask)
            + costs.total(cost, F.src, graph.src_capacity,
                          graph.src_edge_mask))


def total_cost(graph: CECGraphSparse, cost: CostFn, phi: SparsePhi,
               lam: Array) -> Array:
    """Σ_{e∈Ē} D_e(F_e, C_e) — identical edge set to the dense sum."""
    t = propagate(graph, phi, lam)
    F = link_flow_slots(graph, phi, t)
    return _slot_cost(graph, cost, F)


def cost_and_state(graph: CECGraphSparse, cost: CostFn, phi: SparsePhi,
                   lam: Array):
    """(total cost, t, F-slots) in one pass — the routing-iteration bundle."""
    t = propagate(graph, phi, lam)
    F = link_flow_slots(graph, phi, t)
    return _slot_cost(graph, cost, F), t, F


# ---------------------------------------------------------------------------
# marginal-cost broadcast (paper eq. (19)–(21))
# ---------------------------------------------------------------------------

def marginals(graph: CECGraphSparse, cost: CostFn, phi: SparsePhi, t: Array,
              F: SparsePhi) -> tuple[SparsePhi, Array]:
    """Returns (delta, dDdr) — Gallager's reverse recursion over edge lists.

    ``delta`` is the marginal routing cost δφ (eq. 19) in the slot layout;
    ``dDdr[w, i]`` the broadcast scalar ∂D/∂r_i(w) (eq. 21), covering the
    virtual source row (its own slot set) exactly like the dense scan.
    """
    Dp = graph.edge_mask * cost.deriv(F.rows, graph.capacity)      # [Nb, D]
    Dp_src = graph.src_edge_mask * cost.deriv(F.src, graph.src_capacity)
    mask = graph.out_mask

    def step(r, _):
        nxt = (phi.rows * mask * (Dp[None] + r[:, graph.nbr])).sum(-1)
        r_src = (phi.src * graph.src_out_mask
                 * (Dp_src[None] + r[:, graph.src_nbr])).sum(-1)
        return nxt.at[:, graph.src].set(r_src), None

    zero = jnp.zeros_like(t)
    dDdr, _ = jax.lax.scan(step, zero, None, length=graph.depth_max)
    delta = SparsePhi(
        rows=mask * (Dp[None] + dDdr[:, graph.nbr]),
        src=graph.src_out_mask * (Dp_src[None] + dDdr[:, graph.src_nbr]))
    return delta, dDdr


# ---------------------------------------------------------------------------
# exponentiated-gradient update (eq. (22)) + optimality residual
# ---------------------------------------------------------------------------

def eg_update(phi: Array, delta: Array, mask: Array, eta: float) -> Array:
    """Row-stabilized exponentiated-gradient step on the last axis.

    Shape-generic (the row is whatever the trailing axis holds), so the
    dense [W, Nb, Nb] path (``routing.omd_step``), the sparse [W, Nb, D]
    rows and the [W, Ds] source row all share this one jnp definition.
    ``kernels/ref.py::omd_update_ref`` keeps an intentionally independent
    copy — it is the oracle the Pallas kernels are tested against, and an
    oracle that delegates to the code under test verifies nothing.
    """
    logits = jnp.where(mask > 0, -eta * delta, -1e30)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    w = phi * jnp.exp(logits) * mask
    s = w.sum(-1, keepdims=True)
    return jnp.where(s > 0, w / jnp.where(s > 0, s, 1.0), phi)


def omd_phi_update(graph: CECGraphSparse, phi: SparsePhi, delta: SparsePhi,
                   eta: float) -> SparsePhi:
    """Apply eq. (22) to both φ parts (kernel-dispatched like the dense path)."""
    if dispatch.use_kernels(graph.n_bar):
        from repro.kernels.ops import omd_update_sparse_op

        interpret = dispatch.kernel_interpret()
        rows = omd_update_sparse_op(phi.rows, delta.rows, graph.out_mask,
                                    float(eta), interpret=interpret)
        src = omd_update_sparse_op(phi.src[:, None], delta.src[:, None],
                                   graph.src_out_mask[:, None], float(eta),
                                   interpret=interpret)[:, 0]
        return SparsePhi(rows=rows, src=src)
    return SparsePhi(
        rows=eg_update(phi.rows, delta.rows, graph.out_mask, eta),
        src=eg_update(phi.src, delta.src, graph.src_out_mask, eta))


def kkt_residual(graph: CECGraphSparse, cost: CostFn, phi: SparsePhi,
                 lam: Array) -> Array:
    """Theorem 3 residual in the slot layout (mirrors the dense metric)."""
    D, t, F = cost_and_state(graph, cost, phi, lam)
    delta, _ = marginals(graph, cost, phi, t, F)

    def row_residual(d, p, m, tt):
        on = (p > 1e-6) & (m > 0)
        big = jnp.where(on, d, -jnp.inf).max(-1)
        small = jnp.where(m > 0, d, jnp.inf).min(-1)
        active = (tt > 1e-6) & (m.sum(-1) > 0)
        return jnp.where(active, jnp.maximum(big - small, 0.0), 0.0).max()

    r_rows = row_residual(delta.rows, phi.rows, graph.out_mask, t)
    r_src = row_residual(delta.src, phi.src, graph.src_out_mask,
                         t[:, graph.src])
    return jnp.maximum(r_rows, r_src)


def state_nbytes(graph: CECGraphSparse | CECGraph, phi) -> int:
    """Total bytes of the graph + routing-state pytree (bench_sparse)."""
    leaves = jax.tree_util.tree_leaves((graph, phi))
    return int(sum(x.size * x.dtype.itemsize for x in leaves))
