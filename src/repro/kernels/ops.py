"""Jit'd public wrappers around the Pallas kernels.

Each op pads its operands to hardware-aligned tiles, invokes the kernel
and slices the padding back off.  ``interpret`` is a required keyword:
``core.dispatch.kernel_interpret()`` is the one place that decides it
(True off-TPU), and an op that defaulted it would run the Pallas
interpreter on the chip for any caller that forgot it.  The control
plane consults ``core.dispatch``: ``flow_step_op`` / ``omd_update_op``
are invoked by ``core.flow.propagate`` and ``core.routing.omd_step``
whenever ``dispatch.use_kernels(n_bar)`` holds (threshold cleared on
TPU, or an explicit override).  Padding rules: both node axes go to
multiples of 128 with zeros — zero-padded φ rows contribute nothing to
``flow_step`` accumulation, and all-zero-mask rows in ``omd_update`` fall
through to the input φ before being sliced off, so padding is exact.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .control_megakernel import control_step_dense, control_step_sparse
from .flow_step import flow_step
from .flow_step_sparse import flow_step_sparse
from .omd_update import omd_update
from .omd_update_sparse import omd_update_sparse


def _pad_to(x, axis: int, mult: int, value=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _pad_axis_to(x, axis: int, size: int, value=0.0):
    """Pad ``axis`` to exactly ``size`` entries (≥ current length).

    The megakernel needs *one* padded node width shared by arrays whose
    native node axes differ (``sink_slot``/``deploy`` run over ``n_phys``,
    everything else over ``n_bar``) — a per-array multiple-of-128 pad
    would disagree whenever the two cross different 128 boundaries.
    """
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _round_up(n: int, mult: int = 128) -> int:
    return ((n + mult - 1) // mult) * mult


@partial(jax.jit, static_argnames=("interpret",))
def flow_step_op(t, phi, inject, *, interpret):
    N = t.shape[1]
    tp = _pad_to(t, 1, 128)[:, None, :]
    ip = _pad_to(inject, 1, 128)[:, None, :]
    pp = _pad_to(_pad_to(phi, 1, 128), 2, 128)
    return flow_step(tp, pp, ip, interpret=interpret)[:, 0, :N]


@partial(jax.jit, static_argnames=("eta", "interpret"))
def omd_update_op(phi, delta, mask, eta, *, interpret):
    N = phi.shape[1]
    pp = _pad_to(_pad_to(phi, 1, 128), 2, 128)
    dp = _pad_to(_pad_to(delta, 1, 128), 2, 128)
    mp = _pad_to(_pad_to(mask, 1, 128), 2, 128)
    out = omd_update(pp, dp, mp, eta, interpret=interpret)
    return out[:, :N, :N]


@jax.jit
def flow_in_edges_sparse(rows, in_src, in_slot, in_mask):
    """The sparse relaxation step's in-edge operands (see
    flow_step_sparse.py), for a routing φ that holds over many steps.

    Gathers the masked in-edge routing shares pv [W, N, Din] in XLA, then
    lays the in-lists out nodes-on-lanes ([Din, N], Din padded to 8, N to
    128) with each tail split into its lane and 128-node chunk.  Padded
    in-slots carry pv 0 and point at node 0.  The gather runs under the
    scope ``edge_pv_gather``: XLA names its fusion ``fusion.N`` all the
    same, so only the op's metadata carries the scope.  Returns
    (pv, lane, chunk) for :func:`flow_step_sparse_op`.
    """
    with jax.named_scope("edge_pv_gather"):
        pv = rows[:, in_src, in_slot] * in_mask               # [W, N, Din]
    pvt = _pad_to(_pad_to(jnp.swapaxes(pv, 1, 2), 1, 8), 2, 128)
    src = _pad_to(_pad_to(in_src.T, 0, 8), 1, 128)
    return pvt, src % 128, src // 128


@partial(jax.jit, static_argnames=("interpret",))
def flow_step_sparse_op(t, base, in_edges, *, interpret):
    """Padded/sliced sparse relaxation step (see flow_step_sparse.py) on
    the in-edge operands :func:`flow_in_edges_sparse` lays out once for
    all the steps of a relaxation."""
    N = t.shape[1]
    pv, lane, chunk = in_edges
    tp = _pad_to(t, 1, 128)[:, None, :]
    bp = _pad_to(base, 1, 128)[:, None, :]
    out = flow_step_sparse(tp, pv, bp, lane, chunk, interpret=interpret)
    return out[:, 0, :N]


@partial(jax.jit, static_argnames=("eta", "interpret"))
def omd_update_sparse_op(phi, delta, mask, eta, *, interpret):
    """Padded/sliced sparse EG update over [W, R, C] edge-slot rows."""
    R, C = phi.shape[1], phi.shape[2]
    pp = _pad_to(_pad_to(phi, 1, 128), 2, 128)
    dp = _pad_to(_pad_to(delta, 1, 128), 2, 128)
    mp = _pad_to(_pad_to(mask, 1, 128), 2, 128)
    out = omd_update_sparse(pp, dp, mp, eta, interpret=interpret)
    return out[:, :R, :C]


@partial(jax.jit, static_argnames=("k_iters", "delta", "eta_outer",
                                   "eta_inner", "cost", "phi_dtype",
                                   "interpret"))
def control_step_op(lam, phi, task_u, lam_total, graph, k_iters, delta,
                    eta_outer, eta_inner, cost, phi_dtype="float32", *,
                    interpret):
    """Padded/sliced one-kernel fused control step, dense layout.

    ``lam`` [W], ``phi`` [W, Nb, Nb], ``task_u`` [2W] (the measured task
    utilities in ``perturbed_allocations`` row order), ``lam_total`` a
    traced scalar, ``graph`` a ``CECGraph`` pytree.  η's, δ, ``k_iters``
    (the oracle's OMD iteration count) and the ``CostFn`` are static
    kernel parameters.  Capacity pads with 1.0 — a zero-capacity pad
    entry would put NaN into cost derivatives that the mask multiply
    cannot kill.  Returns (Λ' [W], φ' [W, Nb, Nb], ĝ [W], D scalar).
    """
    W, N, _ = phi.shape
    lp = _pad_to(lam[None, :], 1, 128)
    taup = _pad_to(task_u[None, :], 1, 128)
    tot = jnp.zeros_like(lp) + lam_total
    pp = _pad_to(_pad_to(phi, 1, 128), 2, 128)
    mp = _pad_to(_pad_to(graph.out_mask, 1, 128), 2, 128)
    ep = _pad_to(_pad_to(graph.edge_mask, 0, 128), 1, 128)
    cp = _pad_to(_pad_to(graph.capacity, 0, 128, 1.0), 1, 128, 1.0)
    dt = jnp.bfloat16 if phi_dtype == "bfloat16" else jnp.float32
    lam_o, phi_o, g_o, d_o = control_step_dense(
        lp, pp, mp, ep, cp, taup, tot, depth_max=graph.depth_max,
        src=graph.src, k_iters=k_iters, delta=delta, eta_outer=eta_outer,
        eta_inner=eta_inner, cost=cost, phi_dtype=dt, interpret=interpret)
    return lam_o[0, :W], phi_o[:, :N, :N], g_o[0, :W], d_o[0, 0]


@partial(jax.jit, static_argnames=("k_iters", "delta", "eta_outer",
                                   "eta_inner", "cost", "phi_dtype",
                                   "interpret"))
def control_step_sparse_op(lam, rows, src_phi, task_u, lam_total, graph,
                           k_iters, delta, eta_outer, eta_inner, cost,
                           phi_dtype="float32", *, interpret):
    """Padded/sliced one-kernel fused control step, sparse slot layout.

    ``rows``/``src_phi`` are the ``SparsePhi`` parts, ``graph`` a
    ``CECGraphSparse``.  The node axis of *every* operand pads to one
    shared width (``_pad_axis_to`` — ``sink_slot``/``deploy`` natively
    run over ``n_phys``, not ``n_bar``); slot axes pad to 128 multiples
    and slot ids stay valid because the kernel flattens with the padded
    stride (the ``flow_step_sparse`` convention).  The S→D(1) admission
    scatter is pre-built here as a (Ds, Np) 0/1 matrix so the kernel
    scatters by matmul.  Returns (Λ' [W], rows' , src', ĝ [W], D).
    """
    W, N, D = rows.shape
    Ds = src_phi.shape[1]
    Np = _round_up(N)
    lp = _pad_to(lam[None, :], 1, 128)
    taup = _pad_to(task_u[None, :], 1, 128)
    tot = jnp.zeros_like(lp) + lam_total
    rp = _pad_axis_to(_pad_to(rows, 2, 128), 1, Np)
    sp = _pad_to(src_phi, 1, 128)[:, None, :]
    omp = _pad_axis_to(_pad_to(graph.out_mask, 2, 128), 1, Np)
    smp = _pad_to(graph.src_out_mask, 1, 128)[:, None, :]
    dep = _pad_axis_to(graph.deploy.astype(jnp.float32), 1, Np)[:, None, :]
    emp = _pad_axis_to(_pad_to(graph.edge_mask, 1, 128), 0, Np)
    cap = _pad_axis_to(_pad_to(graph.capacity, 1, 128, 1.0), 0, Np, 1.0)
    semp = _pad_to(graph.src_edge_mask[None, :], 1, 128)
    scap = _pad_to(graph.src_capacity[None, :], 1, 128, 1.0)
    nbr = _pad_axis_to(_pad_to(graph.nbr, 1, 128), 0, Np)
    snbr = _pad_to(graph.src_nbr[None, :], 1, 128)
    sink = _pad_axis_to(graph.sink_slot[None, :], 1, Np)
    isrc = _pad_axis_to(_pad_to(graph.in_src, 1, 128), 0, Np)
    islot = _pad_axis_to(_pad_to(graph.in_slot, 1, 128), 0, Np)
    imask = _pad_axis_to(_pad_to(graph.in_mask, 1, 128), 0, Np)
    # matmul scatter: admit (1, Ds) @ smat (Ds, Np) sums λ_w·φ_S·mask onto
    # the fan-out heads — duplicate heads accumulate exactly like .at.add
    smat = jnp.zeros((Ds, Np), jnp.float32).at[
        jnp.arange(Ds), graph.src_nbr].add(1.0)
    smat = _pad_to(smat, 0, 128)
    dt = jnp.bfloat16 if phi_dtype == "bfloat16" else jnp.float32
    lam_o, rows_o, src_o, g_o, d_o = control_step_sparse(
        lp, rp, sp, omp, smp, dep, emp, cap, semp, scap, nbr, snbr, sink,
        isrc, islot, imask, smat, taup, tot, depth_max=graph.depth_max,
        src=graph.src, n_phys=graph.n_phys, k_iters=k_iters, delta=delta,
        eta_outer=eta_outer, eta_inner=eta_inner, cost=cost, phi_dtype=dt,
        interpret=interpret)
    return (lam_o[0, :W], rows_o[:, :N, :D], src_o[:, 0, :Ds], g_o[0, :W],
            d_o[0, 0])


__all__ = ["control_step_op", "control_step_sparse_op",
           "flow_in_edges_sparse", "flow_step_op", "flow_step_sparse_op",
           "omd_update_op", "omd_update_sparse_op", "ref"]
