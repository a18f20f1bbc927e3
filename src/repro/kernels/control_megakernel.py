"""One-kernel fused control step (paper Alg. 1/3) — dense and sparse.

``solver.step``'s sampled path is a scan of 2W+1 oracle observations,
each stitched from separate flow-propagation / marginal / EG kernels
with the iterates bouncing through HBM between phases.  This module
instantiates the *entire* outer iteration as a single ``pallas_call`` —
perturb Λ by ±δ·e_w, propagate flows to the fixed point, price the
links, form the two-point gradient, mirror-ascent + exact box-simplex
projection, committed observation — so φ, F and the gradient accumulator
never leave VMEM between phases (DESIGN.md §17).

Grid layout (§17.1): ``(P, K+1, 2, W)`` with P = 2W+1 observations,
K oracle iterations plus the pricing pass, a propagate/update phase
pair, and the session sweep innermost.  TPU grids execute sequentially
(lexicographic, last axis fastest), so VMEM scratch carries state across
grid steps exactly like the jnp scan carries (g, φ):

* phase 0 (``ph==0``), per session w: load φ_w from the VMEM-resident
  scratch, run ``depth_max`` Jacobi relaxations ``t ← inject + t·φ_w``,
  accumulate link flows F += tᵀ·φ_w (the w==0 step zeroes F).
* phase 1 (``ph==1``), ``k < K``: at w==0 price the links once
  (D' = mask·cost.deriv(F, C)); every w then runs Gallager's reverse
  recursion in column form and the exponentiated-gradient update,
  storing φ_w back to scratch (bf16 when ``phi_dtype`` says so — §17.3).
* phase 1, ``k == K``: at w==0 evaluate D = Σ mask·cost.value(F, C) and
  fold the two-point term sign·(u_w − D)/(2δ)·e_w into the gradient
  scratch; no φ update (this is the observation's pricing pass —
  ``routing.oracle_observe`` prices the *post*-update iterate).
* observation boundary (``k==0, ph==0, w==0``): perturbed admissions
  Λ ± δ·e_w for p < 2W (always from the *unperturbed* Λ), and for the
  final observation the mirror-ascent + exact projection commit
  (:func:`_mirror_project`).

φ lives in a ``[W, Nb, Nb]`` (dense) or ``[W, Nb, D]``+``[W, Ds]``
(sparse) VMEM scratch for the whole kernel — the VMEM residency
contract (§17.2) is enforced by ``dispatch.megakernel_fits``.  With
``phi_dtype="bfloat16"`` only this φ *storage* narrows: every load
upcasts to f32 before any arithmetic, every store rounds once per EG
update, and flows/prices/gradient/Λ stay f32 (§17.3 has the measured
error bounds against the golden trace).

All transposes are emulated with iota-eye contractions (Mosaic has no
cheap 2D transpose for these shapes) and the sort inside the exact
projection is an O(M²) stable rank sort — ``jnp.sort`` does not lower
inside a TPU kernel body.  The sparse variant keeps the session rate
vector 1-D and gathers with ``jnp.take`` over flattened (node·stride +
slot) ids.  Mosaic lowers a gather only within one vreg, so the sparse
variant runs in interpret mode only: ``dispatch.use_megakernel`` never
picks it on a TPU, where sparse graphs take the stitched kernels.
Every kernel compiles with ``vmem_limit_bytes=dispatch.VMEM_BYTES``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.costs import total
from repro.core.dispatch import VMEM_BYTES

NEG = -1e30
BIG = 1e30


# ---------------------------------------------------------------------------
# Mosaic-safe helpers (no 1-D iota, no transpose, no sort)
# ---------------------------------------------------------------------------

def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _eye(m, dtype):
    return (_iota((m, m), 0) == _iota((m, m), 1)).astype(dtype)


def _col(row):
    """[1, M] → [M, 1] via an iota-eye contraction (transpose emulation)."""
    return jnp.sum(_eye(row.shape[1], row.dtype) * row, axis=1, keepdims=True)


def _row(col):
    """[M, 1] → [1, M] (same trick, other axis)."""
    return jnp.sum(_eye(col.shape[0], col.dtype) * col, axis=0, keepdims=True)


def _mm(a, b):
    """[M, K] @ [K, N] in full f32 (a TPU's default precision would run
    an f32 dot as one bf16 pass)."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _eg(phi, delta, mask, eta):
    """Row-stabilized exponentiated-gradient step (eq. (22)), last axis.

    Mirrors ``core.sparse.eg_update`` term for term: all-zero-mask rows
    fall through to the input φ, so padded rows stay exactly zero.
    """
    logits = jnp.where(mask > 0, -eta * delta, NEG)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    w = phi * jnp.exp(logits) * mask
    s = w.sum(-1, keepdims=True)
    return jnp.where(s > 0, w / jnp.where(s > 0, s, 1.0), phi)


def _mirror_project(lam, g, lam_total, n_real, eta_outer, delta):
    """Mirror ascent + exact box-simplex projection on a padded (1, Wp) row.

    Replicates ``solver._mirror_ascent`` → ``solver.project_box_simplex``
    with the ``jnp.sort`` over the 2W breakpoints replaced by an O(M²)
    stable rank sort (strict-less count plus earlier-index tie-break) —
    variadic sorts do not lower inside a kernel body.  Padded entries
    ride as +BIG breakpoints and are excluded from the bracketing count,
    so the real entries project exactly as the unpadded jnp expression.
    """
    wp = lam.shape[1]
    real = (_iota((1, wp), 1) < n_real).astype(lam.dtype)
    z = jnp.where(real > 0, eta_outer * g, NEG)
    z = z - jnp.max(z)
    wgt = lam * jnp.exp(z) * real
    y = lam_total * wgt / jnp.sum(wgt)
    lo = delta
    hi = lam_total - delta
    bp = jnp.concatenate(
        [jnp.where(real > 0, y - lo, BIG), jnp.where(real > 0, y - hi, BIG)],
        axis=1)                                              # (1, M)
    m = bp.shape[1]
    bcol = _col(bp)                                          # (M, 1)
    less = (bp < bcol).astype(lam.dtype)                     # a_j < a_i
    tie = ((bp == bcol)
           & (_iota((m, m), 1) < _iota((m, m), 0))).astype(lam.dtype)
    rank = jnp.sum(less + tie, axis=1, keepdims=True)        # (M, 1) unique
    srt = jnp.sum(jnp.where(rank == _iota((1, m), 1), bcol, 0.0),
                  axis=0, keepdims=True)                     # ascending sort
    # Σ_w clip(y_w − bp, lo, hi) at every sorted breakpoint, then the
    # bracketing segment / linear interpolation of project_box_simplex
    scol = jnp.sum(jnp.clip(y - _col(srt), lo, hi) * real, axis=1,
                   keepdims=True)                            # (M, 1)
    kcol = _iota((m, 1), 0)
    n_bp = 2 * n_real
    count = jnp.sum(((scol >= lam_total) & (kcol < n_bp))
                    .astype(jnp.float32))
    k = jnp.clip(count - 1.0, 0.0, float(n_bp - 2))
    krow = _iota((1, m), 1).astype(jnp.float32)
    t0 = jnp.sum(jnp.where(krow == k, srt, 0.0))
    t1 = jnp.sum(jnp.where(krow == k + 1.0, srt, 0.0))
    kcf = kcol.astype(jnp.float32)
    s0 = jnp.sum(jnp.where(kcf == k, scol, 0.0))
    s1 = jnp.sum(jnp.where(kcf == k + 1.0, scol, 0.0))
    drop = jnp.where(s0 > s1, s0 - s1, 1.0)
    frac = jnp.where(s0 > s1, (s0 - lam_total) / drop, 0.0)
    tau = t0 + frac * (t1 - t0)
    return jnp.clip(y - tau, lo, hi) * real


def _sign_dir(p, widx):
    """Observation p's (sign, e_w row): rows (2w, 2w+1) = (+e_w, −e_w)."""
    sign = jnp.where(p % 2 == 0, 1.0, -1.0)
    ew = (widx == p // 2).astype(jnp.float32)
    return sign, ew


def _task_u(tau_ref, p):
    """Scalar u(Λ ± δe_w) for observation p via a one-hot contraction."""
    tidx = _iota((1, tau_ref.shape[1]), 1)
    return jnp.sum(jnp.where(tidx == p, tau_ref[...], 0.0))


# ---------------------------------------------------------------------------
# dense kernel
# ---------------------------------------------------------------------------

def _dense_kernel(lam_ref, phi0_ref, omask_ref, emask_ref, cap_ref, tau_ref,
                  tot_ref, lam_o, phi_o, g_o, d_o,
                  phi_s, f_s, dp_s, g_s, lam_s, d_s, *,
                  n_sessions, k_iters, depth, src, delta, eta_outer,
                  eta_inner, cost):
    W, K = n_sessions, k_iters
    p = pl.program_id(0)
    k = pl.program_id(1)
    ph = pl.program_id(2)
    w = pl.program_id(3)
    P = pl.num_programs(0)
    np_ = f_s.shape[0]
    wp = lam_s.shape[1]
    lam_total = jnp.max(tot_ref[...])
    widx = _iota((1, wp), 1)
    wsl = (pl.ds(w, 1), slice(None), slice(None))

    # --- first visit: seed the VMEM-resident φ and the gradient scratch
    @pl.when((p == 0) & (k == 0) & (ph == 0))
    def _seed_phi():
        phi_s[wsl] = phi0_ref[...].astype(phi_s.dtype)

    @pl.when((p == 0) & (k == 0) & (ph == 0) & (w == 0))
    def _seed_g():
        g_s[...] = jnp.zeros_like(g_s)

    # --- observation boundary: perturbed admission, or the commit
    @pl.when((k == 0) & (ph == 0) & (w == 0))
    def _admit():
        @pl.when(p < P - 1)
        def _perturb():
            sign, ew = _sign_dir(p, widx)
            lam_s[...] = lam_ref[...] + sign * delta * ew

        @pl.when(p == P - 1)
        def _commit():
            lam_s[...] = _mirror_project(lam_ref[...], g_s[...], lam_total,
                                         W, eta_outer, delta)

    # --- phase 0: Jacobi flow relaxation + link-flow accumulation
    @pl.when(ph == 0)
    def _flow():
        phi_w = phi_s[wsl][0].astype(jnp.float32)
        lam_w = jnp.sum(jnp.where(widx == w, lam_s[...], 0.0))
        inject = jnp.where(_iota((1, np_), 1) == src, lam_w, 0.0)

        def relax(_, t):
            return inject + _mm(t, phi_w)

        t = jax.lax.fori_loop(0, depth, relax, inject)

        @pl.when(w == 0)
        def _zero_f():
            f_s[...] = jnp.zeros_like(f_s)

        f_s[...] += _col(t) * phi_w                  # F_ij += t_i·φ_ij

    # --- phase 1, k < K: price once, then marginals + EG per session
    @pl.when((ph == 1) & (w == 0) & (k < K))
    def _prices():
        dp_s[...] = emask_ref[...] * cost.deriv(f_s[...], cap_ref[...])

    @pl.when((ph == 1) & (k < K))
    def _update():
        phi_w = phi_s[wsl][0].astype(jnp.float32)
        mask_w = omask_ref[0]
        pm = phi_w * mask_w
        dp = dp_s[...]
        ones = jnp.ones((np_, 1), jnp.float32)
        b = _mm(pm * dp, ones)

        def back(_, r):
            return b + _mm(pm, r)

        r = jax.lax.fori_loop(0, depth, back, jnp.zeros_like(b))
        delta_w = mask_w * (dp + _row(r))
        phi_s[wsl] = _eg(phi_w, delta_w, mask_w, eta_inner)[None].astype(
            phi_s.dtype)

    # --- phase 1, k == K: observe the cost, fold the two-point term
    @pl.when((ph == 1) & (w == 0) & (k == K))
    def _observe():
        D = total(cost, f_s[...], cap_ref[...], emask_ref[...])
        d_s[...] = jnp.zeros_like(d_s) + D

        @pl.when(p < P - 1)
        def _grad():
            sign, ew = _sign_dir(p, widx)
            g_s[...] += sign * ((_task_u(tau_ref, p) - D)
                                / (2.0 * delta)) * ew

    # --- emit: per-w blocked φ at its last visit, rows at the final step
    @pl.when((p == P - 1) & (k == K) & (ph == 1))
    def _emit_phi():
        phi_o[...] = phi_s[wsl].astype(phi_o.dtype)

    @pl.when((p == P - 1) & (k == K) & (ph == 1) & (w == W - 1))
    def _emit_rows():
        lam_o[...] = lam_s[...]
        g_o[...] = g_s[...]
        d_o[...] = d_s[...]


def control_step_dense(lam, phi, out_mask, edge_mask, capacity, task_u, tot,
                       *, depth_max, src, k_iters, delta, eta_outer,
                       eta_inner, cost, phi_dtype=jnp.float32,
                       interpret):
    """Padded-operand dense megakernel (callers go through ``ops``).

    ``lam``/``tot`` (1, Wp); ``phi``/``out_mask`` [W, Np, Np];
    ``edge_mask``/``capacity`` (Np, Np); ``task_u`` (1, Pp).  Returns
    (Λ' (1, Wp), φ' [W, Np, Np] f32, ĝ (1, Wp), D (1, Wp) broadcast).
    """
    W, np_, _ = phi.shape
    wp = lam.shape[1]
    grid = (2 * W + 1, k_iters + 1, 2, W)
    row = pl.BlockSpec(lam.shape, lambda p, k, ph, w: (0, 0))
    tau_row = pl.BlockSpec(task_u.shape, lambda p, k, ph, w: (0, 0))
    per_w = pl.BlockSpec((1, np_, np_), lambda p, k, ph, w: (w, 0, 0))
    full = pl.BlockSpec((np_, np_), lambda p, k, ph, w: (0, 0))
    kernel = functools.partial(
        _dense_kernel, n_sessions=W, k_iters=k_iters, depth=depth_max,
        src=src, delta=delta, eta_outer=eta_outer, eta_inner=eta_inner,
        cost=cost)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row, per_w, per_w, full, full, tau_row, row],
        out_specs=[row, per_w, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((1, wp), jnp.float32),
            jax.ShapeDtypeStruct((W, np_, np_), jnp.float32),
            jax.ShapeDtypeStruct((1, wp), jnp.float32),
            jax.ShapeDtypeStruct((1, wp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((W, np_, np_), phi_dtype),    # φ — the resident state
            pltpu.VMEM((np_, np_), jnp.float32),     # F accumulator
            pltpu.VMEM((np_, np_), jnp.float32),     # link prices D'
            pltpu.VMEM((1, wp), jnp.float32),        # gradient ĝ
            pltpu.VMEM((1, wp), jnp.float32),        # admission Λ_p
            pltpu.VMEM((1, wp), jnp.float32),        # observed cost D
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
    )(lam, phi, out_mask, edge_mask, capacity, task_u, tot)


# ---------------------------------------------------------------------------
# sparse (padded-CSR slot layout) kernel
# ---------------------------------------------------------------------------

def _sparse_kernel(lam_ref, rows0_ref, src0_ref, omask_ref, smask_ref,
                   dep_ref, emask_ref, cap_ref, semask_ref, scap_ref,
                   nbr_ref, snbr_ref, sink_ref, insrc_ref, inslot_ref,
                   inmask_ref, smat_ref, tau_ref, tot_ref,
                   lam_o, rows_o, src_o, g_o, d_o,
                   rows_s, srcphi_s, f_s, fsrc_s, dp_s, dpsrc_s, g_s,
                   lam_s, d_s, *,
                   n_sessions, k_iters, depth, src, n_phys, delta,
                   eta_outer, eta_inner, cost):
    W, K = n_sessions, k_iters
    p = pl.program_id(0)
    k = pl.program_id(1)
    ph = pl.program_id(2)
    w = pl.program_id(3)
    P = pl.num_programs(0)
    np_, dmax = f_s.shape
    wp = lam_s.shape[1]
    lam_total = jnp.max(tot_ref[...])
    widx = _iota((1, wp), 1)
    nidx = _iota((1, np_), 1)[0]                     # [Np] node ids (2D-born)
    wsl = (pl.ds(w, 1), slice(None), slice(None))

    @pl.when((p == 0) & (k == 0) & (ph == 0))
    def _seed_phi():
        rows_s[wsl] = rows0_ref[...].astype(rows_s.dtype)
        srcphi_s[wsl] = src0_ref[...].astype(srcphi_s.dtype)

    @pl.when((p == 0) & (k == 0) & (ph == 0) & (w == 0))
    def _seed_g():
        g_s[...] = jnp.zeros_like(g_s)

    @pl.when((k == 0) & (ph == 0) & (w == 0))
    def _admit():
        @pl.when(p < P - 1)
        def _perturb():
            sign, ew = _sign_dir(p, widx)
            lam_s[...] = lam_ref[...] + sign * delta * ew

        @pl.when(p == P - 1)
        def _commit():
            lam_s[...] = _mirror_project(lam_ref[...], g_s[...], lam_total,
                                         W, eta_outer, delta)

    # --- phase 0: Jacobi relaxation over edge lists (cf. sparse.propagate)
    @pl.when(ph == 0)
    def _flow():
        rows_w = rows_s[wsl][0].astype(jnp.float32)           # [Np, D]
        src_w = srcphi_s[wsl][0].astype(jnp.float32)           # (1, Ds)
        lam_w = jnp.sum(jnp.where(widx == w, lam_s[...], 0.0))
        # base inflow: exogenous injection at S plus the admission flow
        # λ_w·φ_S scattered onto the S→D(1) heads by the (Ds, Np) matmul
        # scatter built in ops.py (no in-kernel scatter on TPU)
        admit = lam_w * src_w * smask_ref[0]                   # (1, Ds)
        scat = _mm(admit, smat_ref[...])
        inject = jnp.where(nidx == src, lam_w, 0.0)            # [Np]
        base = inject + scat[0]
        flat = rows_w.reshape(-1)
        pv = jnp.take(flat, insrc_ref[...] * dmax + inslot_ref[...])
        psink = jnp.take(flat, nidx * dmax + sink_ref[0])      # [Np]
        dep_w = dep_ref[0][0]                                  # [Np]
        on_sink = nidx == (n_phys + 1 + w)

        def relax(_, t):
            sval = jnp.sum(dep_w * t * psink)                  # old-t Jacobi
            tn = base + (jnp.take(t, insrc_ref[...]) * pv
                         * inmask_ref[...]).sum(-1)
            return jnp.where(on_sink, sval, tn)

        t = jax.lax.fori_loop(0, depth, relax, inject)

        @pl.when(w == 0)
        def _zero_f():
            f_s[...] = jnp.zeros_like(f_s)
            fsrc_s[...] = jnp.zeros_like(fsrc_s)

        f_s[...] += t[:, None] * rows_w              # F_slots += t_i·φ_i,d
        t_src = jnp.sum(jnp.where(nidx == src, t, 0.0))
        fsrc_s[...] += t_src * src_w

    @pl.when((ph == 1) & (w == 0) & (k < K))
    def _prices():
        dp_s[...] = emask_ref[...] * cost.deriv(f_s[...], cap_ref[...])
        dpsrc_s[...] = semask_ref[...] * cost.deriv(fsrc_s[...],
                                                    scap_ref[...])

    @pl.when((ph == 1) & (k < K))
    def _update():
        rows_w = rows_s[wsl][0].astype(jnp.float32)
        src_w = srcphi_s[wsl][0].astype(jnp.float32)
        mask_w = omask_ref[0]                                  # [Np, D]
        smask_w = smask_ref[0]                                 # (1, Ds)
        nbr = nbr_ref[...]
        snbr = snbr_ref[...]
        dpr = dp_s[...]
        dps = dpsrc_s[...]

        def back(_, r):
            rn = (rows_w * mask_w * (dpr + jnp.take(r, nbr))).sum(-1)
            rs = jnp.sum(src_w * smask_w * (dps + jnp.take(r, snbr)))
            return jnp.where(nidx == src, rs, rn)

        r = jax.lax.fori_loop(0, depth, back,
                              jnp.zeros((np_,), jnp.float32))
        delta_rows = mask_w * (dpr + jnp.take(r, nbr))
        delta_src = smask_w * (dps + jnp.take(r, snbr))
        rows_s[wsl] = _eg(rows_w, delta_rows, mask_w, eta_inner)[None].astype(
            rows_s.dtype)
        srcphi_s[wsl] = _eg(src_w, delta_src, smask_w, eta_inner)[None].astype(
            srcphi_s.dtype)

    @pl.when((ph == 1) & (w == 0) & (k == K))
    def _observe():
        D = (total(cost, f_s[...], cap_ref[...], emask_ref[...])
             + total(cost, fsrc_s[...], scap_ref[...], semask_ref[...]))
        d_s[...] = jnp.zeros_like(d_s) + D

        @pl.when(p < P - 1)
        def _grad():
            sign, ew = _sign_dir(p, widx)
            g_s[...] += sign * ((_task_u(tau_ref, p) - D)
                                / (2.0 * delta)) * ew

    @pl.when((p == P - 1) & (k == K) & (ph == 1))
    def _emit_phi():
        rows_o[...] = rows_s[wsl].astype(rows_o.dtype)
        src_o[...] = srcphi_s[wsl].astype(src_o.dtype)

    @pl.when((p == P - 1) & (k == K) & (ph == 1) & (w == W - 1))
    def _emit_rows():
        lam_o[...] = lam_s[...]
        g_o[...] = g_s[...]
        d_o[...] = d_s[...]


def control_step_sparse(lam, rows, src_phi, out_mask, src_out_mask, deploy,
                        edge_mask, capacity, src_edge_mask, src_capacity,
                        nbr, src_nbr, sink_slot, in_src, in_slot, in_mask,
                        smat, task_u, tot, *, depth_max, src, n_phys,
                        k_iters, delta, eta_outer, eta_inner, cost,
                        phi_dtype=jnp.float32, interpret):
    """Padded-operand sparse megakernel (callers go through ``ops``).

    Slot layout follows ``CECGraphSparse``; ``smat`` is the (Ds, Np)
    matmul-scatter of the S→D(1) fan-out heads.  Returns (Λ', φ'.rows,
    φ'.src, ĝ, D-row), all f32.
    """
    W, np_, dmax = rows.shape
    dsp = src_phi.shape[2]
    wp = lam.shape[1]
    grid = (2 * W + 1, k_iters + 1, 2, W)
    row = pl.BlockSpec(lam.shape, lambda p, k, ph, w: (0, 0))
    tau_row = pl.BlockSpec(task_u.shape, lambda p, k, ph, w: (0, 0))
    per_w3 = pl.BlockSpec((1, np_, dmax), lambda p, k, ph, w: (w, 0, 0))
    per_w_src = pl.BlockSpec((1, 1, dsp), lambda p, k, ph, w: (w, 0, 0))
    per_w_node = pl.BlockSpec((1, 1, np_), lambda p, k, ph, w: (w, 0, 0))
    full = pl.BlockSpec((np_, dmax), lambda p, k, ph, w: (0, 0))
    full_src = pl.BlockSpec((1, dsp), lambda p, k, ph, w: (0, 0))
    full_node = pl.BlockSpec((1, np_), lambda p, k, ph, w: (0, 0))
    full_in = pl.BlockSpec(in_src.shape, lambda p, k, ph, w: (0, 0))
    full_smat = pl.BlockSpec(smat.shape, lambda p, k, ph, w: (0, 0))
    kernel = functools.partial(
        _sparse_kernel, n_sessions=W, k_iters=k_iters, depth=depth_max,
        src=src, n_phys=n_phys, delta=delta, eta_outer=eta_outer,
        eta_inner=eta_inner, cost=cost)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row, per_w3, per_w_src, per_w3, per_w_src, per_w_node,
                  full, full, full_src, full_src, full, full_src, full_node,
                  full_in, full_in, full_in, full_smat, tau_row, row],
        out_specs=[row, per_w3, per_w_src, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((1, wp), jnp.float32),
            jax.ShapeDtypeStruct((W, np_, dmax), jnp.float32),
            jax.ShapeDtypeStruct((W, 1, dsp), jnp.float32),
            jax.ShapeDtypeStruct((1, wp), jnp.float32),
            jax.ShapeDtypeStruct((1, wp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((W, np_, dmax), phi_dtype),   # φ rows — resident
            pltpu.VMEM((W, 1, dsp), phi_dtype),      # φ source row
            pltpu.VMEM((np_, dmax), jnp.float32),    # F slot accumulator
            pltpu.VMEM((1, dsp), jnp.float32),       # F source slots
            pltpu.VMEM((np_, dmax), jnp.float32),    # slot prices D'
            pltpu.VMEM((1, dsp), jnp.float32),       # source prices
            pltpu.VMEM((1, wp), jnp.float32),        # gradient ĝ
            pltpu.VMEM((1, wp), jnp.float32),        # admission Λ_p
            pltpu.VMEM((1, wp), jnp.float32),        # observed cost D
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
    )(lam, rows, src_phi, out_mask, src_out_mask, deploy, edge_mask,
      capacity, src_edge_mask, src_capacity, nbr, src_nbr, sink_slot,
      in_src, in_slot, in_mask, smat, task_u, tot)
