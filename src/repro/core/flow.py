"""Flow propagation and total network cost (paper §II-C/D).

Given routing variables φ (row-stochastic over each node's allowed
out-edges) and allocation Λ, the per-node session rates are the linear fixed
point of paper eq. (1)/(2):

    t_j(w) = inject_j(w) + Σ_i t_i(w) · φ_ij(w)

Because φ is loop-free by construction (DAG orientation — see graph.py), the
fixed point is reached exactly after ``depth_max`` Jacobi relaxation steps,
implemented as a ``lax.scan`` of masked batched mat-vecs.  This is the
control-plane hot loop, and it is size-dispatched (core/dispatch.py): when
``dispatch.use_kernels(n_bar)`` holds — graph clears the threshold (default
256, env ``REPRO_KERNEL_NBAR_THRESHOLD``) on a TPU backend, or under an
explicit override like ``dispatch.kernel_dispatch(n)`` — each relaxation
step runs through the Pallas ``flow_step`` kernel, operands zero-padded to
the kernel's 128-lane blocks by ``kernels/ops.py`` and sliced back
(``interpret=True`` off-TPU).  Otherwise graphs keep the fused einsum.  The
dispatch keys on static metadata at trace time, so both branches jit, scan
and vmap (the batched multi-instance path in core/batch.py goes through the
same code).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.lax import Precision

from . import costs, dispatch
from .costs import CostFn
from .graph import CECGraph, CECGraphSparse

# full f32 contractions: a TPU runs a default-precision f32 dot as one
# bf16 pass, far coarser than the control plane's f32 parity contract
HIGHEST = Precision.HIGHEST

Array = jnp.ndarray


def propagate(graph: CECGraph | CECGraphSparse, phi, lam: Array) -> Array:
    """Session rates t[W, Nb] induced by routing φ and allocation Λ.

    Accepts either representation: a dense ``CECGraph`` with φ
    ``[W, Nb, Nb]``, or a ``CECGraphSparse`` with a ``SparsePhi`` — the
    sparse branch (core/sparse.py) runs the same Jacobi recursion over
    padded edge lists in O(E) per step.
    """
    if isinstance(graph, CECGraphSparse):
        from . import sparse

        return sparse.propagate(graph, phi, lam)
    inject = graph.injection(lam)

    if dispatch.use_kernels(graph.n_bar):
        from repro.kernels.ops import flow_step_op

        interpret = dispatch.kernel_interpret()

        def step(t, _):
            return flow_step_op(t, phi, inject, interpret=interpret), None
    else:
        def step(t, _):
            return inject + jnp.einsum("wi,wij->wj", t, phi,
                                       precision=HIGHEST), None

    t, _ = jax.lax.scan(step, inject, None, length=graph.depth_max)
    return t


def link_flows(graph: CECGraph | CECGraphSparse, phi, t: Array):
    """Total flow per augmented link: F_ij = Σ_w t_i(w)·φ_ij(w) (eq. (4)).

    Dense graphs return [Nb, Nb]; sparse graphs return the flows in the
    slot layout (a ``SparsePhi``-shaped container).
    """
    if isinstance(graph, CECGraphSparse):
        from . import sparse

        return sparse.link_flow_slots(graph, phi, t)
    return jnp.einsum("wi,wij->ij", t, phi, precision=HIGHEST)


def total_cost(graph: CECGraph | CECGraphSparse, cost: CostFn, phi,
               lam: Array) -> Array:
    """Σ_{(i,j)∈Ē} D_ij(F_ij, C_ij): communication + computation cost."""
    if isinstance(graph, CECGraphSparse):
        from . import sparse

        return sparse.total_cost(graph, cost, phi, lam)
    t = propagate(graph, phi, lam)
    F = link_flows(graph, phi, t)
    return costs.total(cost, F, graph.capacity, graph.edge_mask)


def cost_and_state(graph: CECGraph | CECGraphSparse, cost: CostFn, phi,
                   lam: Array):
    """(total cost, t, F) in one pass — used by the routing iteration."""
    if isinstance(graph, CECGraphSparse):
        from . import sparse

        return sparse.cost_and_state(graph, cost, phi, lam)
    t = propagate(graph, phi, lam)
    F = link_flows(graph, phi, t)
    D = costs.total(cost, F, graph.capacity, graph.edge_mask)
    return D, t, F
