"""Implicit fixed-point layer: VJP correctness and learned-mode parity.

The contracts of DESIGN.md §16.1–16.2:

* ``fixed_point_solve``'s implicit-function-theorem VJP matches central
  finite differences to ≤1e-4 through the full oracle (``oracle_observe``
  → ``solve_routing_implicit``), dense AND sparse.  The comparison runs
  in float64 (``jax.enable_x64``): in f32 the FD reference
  itself carries ~1e-3 of roundoff, which would swamp the bar.
* the layer composes with jit and vmap (the learned solver path wraps it
  in both).
* ``grad_mode="learned"`` with an *exact* surrogate reproduces the
  sampled controller's converged utility to ≤1e-3, and a *fitted*
  surrogate stays within the same bar once its holdout error is small —
  the golden migration check.
* at the oracle fixed point the implicit gradient equals the
  envelope-theorem genie gradient ``core.allocation.
  exact_allocation_gradient`` (Theorem 1's marginal form).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (build_random_cec, get_cost, make_bank,
                        paper_defaults, sparsify, total_cost)
from repro.core import solver as _solver
from repro.core.implicit import fixed_point_solve
from repro.core.problem import Problem
from repro.core.routing import oracle_observe, solve_routing_implicit
from repro.topo import connected_er

ETA = 0.2
FD_ETA = 0.5           # hotter OMD step for the FD check (fast contraction)
FD_WARM = 6000         # warm-start depth: φ0 ≈ φ*, so the N-step implicit
FD_ITERS = 800         # solve is *at* the fixed point the IFT assumes


def _graph():
    return build_random_cec(connected_er(10, 0.4, seed=2), 3, 10.0, seed=0)


def _f64(tree):
    def cast(x):
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(jnp.float64)
        return x

    return jax.tree_util.tree_map(cast, tree)


def _fd_vs_implicit(graph, phi_init):
    """max |implicit grad − central FD| of Λ ↦ D(Λ, φ*(Λ)).

    The IFT VJP is exact only *at* the fixed point, so φ0 is first
    warm-started to convergence (a λ-independent constant — FD and the
    implicit gradient differentiate the same map either way); without
    the warm start the FD reference measures the truncated iteration's
    gradient instead, and the two differ by the forward truncation.
    """
    from repro.core.routing import solve_routing

    cost = get_cost("exp")
    W = graph.n_sessions
    lam = jnp.full((W,), 4.0, jnp.float64)
    phi0, _ = solve_routing(graph, cost, lam, phi_init, FD_ETA, FD_WARM)

    def D(lam):
        phi = solve_routing_implicit(graph, cost, lam, phi0, FD_ETA,
                                     FD_ITERS, bwd_iters=FD_ITERS)
        return total_cost(graph, cost, phi, lam)

    g = jax.grad(D)(lam)
    eps = 1e-4
    fd = np.zeros(W)
    for w in range(W):
        e = jnp.zeros(W, jnp.float64).at[w].set(eps)
        fd[w] = (float(D(lam + e)) - float(D(lam - e))) / (2 * eps)
    return float(jnp.max(jnp.abs(g - np.asarray(fd))))


def test_implicit_vjp_matches_fd_dense():
    with jax.enable_x64(True):
        g = _f64(_graph())
        err = _fd_vs_implicit(g, _f64(g.uniform_phi()))
    assert err <= 1e-4, err


def test_implicit_vjp_matches_fd_sparse():
    with jax.enable_x64(True):
        gs = _f64(sparsify(_graph()))
        err = _fd_vs_implicit(gs, _f64(gs.uniform_phi()))
    assert err <= 1e-4, err


def test_fixed_point_forward_matches_plain_scan():
    """The implicit layer's forward is the same scan ``solve_routing``
    runs — bit-identical φ* (the golden-trace guarantee)."""
    from repro.core.routing import solve_routing

    g = _graph()
    cost = get_cost("exp")
    lam = jnp.full((g.n_sessions,), 4.0, jnp.float32)
    phi_ref, _ = solve_routing(g, cost, lam, g.uniform_phi(), ETA, 60)
    phi_imp = solve_routing_implicit(g, cost, lam, g.uniform_phi(), ETA, 60)
    np.testing.assert_array_equal(np.asarray(phi_ref), np.asarray(phi_imp))


def test_implicit_jit_and_vmap_compose():
    g = _graph()
    cost = get_cost("exp")
    W = g.n_sessions
    phi0 = g.uniform_phi()

    def D(lam):
        phi, d = oracle_observe(g, cost, lam, phi0, ETA, 80)
        return d

    lam = jnp.full((W,), 4.0, jnp.float32)
    g_eager = jax.grad(D)(lam)
    g_jit = jax.jit(jax.grad(D))(lam)
    np.testing.assert_allclose(np.asarray(g_eager), np.asarray(g_jit),
                               rtol=1e-5, atol=1e-6)
    lams = jnp.stack([lam, lam * 1.2, lam * 0.8])
    g_vmap = jax.vmap(jax.grad(D))(lams)
    assert g_vmap.shape == (3, W)
    assert bool(jnp.isfinite(g_vmap).all())
    np.testing.assert_allclose(np.asarray(g_vmap[0]), np.asarray(g_eager),
                               rtol=1e-4, atol=1e-5)


def test_fixed_point_solve_simple_contraction():
    """Sanity on a closed-form fixed point: x* = a/(1−c) for
    x ← c·x + a, with dx*/da = 1/(1−c) exactly."""
    c = 0.5

    def f(x, a):
        return c * x + a

    def xstar(a):
        return fixed_point_solve(f, jnp.float32(0.0), a, n_iters=60)

    a = jnp.float32(1.5)
    np.testing.assert_allclose(float(xstar(a)), 3.0, rtol=1e-5)
    np.testing.assert_allclose(float(jax.grad(xstar)(a)), 2.0, rtol=1e-4)


def test_learned_gradient_matches_envelope_at_fixed_point(small_cec):
    """∇_Λ[Σu − D(Λ, φ*(Λ))] from the implicit layer equals the
    envelope/Theorem-1 genie gradient at the oracle fixed point."""
    from repro.core.allocation import exact_allocation_gradient
    from repro.core.routing import solve_routing

    g = small_cec
    cost = get_cost("exp")
    W = g.n_sessions
    bank = make_bank("log", W, seed=0)
    lam = jnp.full((W,), 5.0, jnp.float32)
    phi_star, _ = solve_routing(g, cost, lam, g.uniform_phi(), ETA, 1500)

    def net_u(lam):
        phi, d = oracle_observe(g, cost, lam, phi_star, ETA, 400)
        return bank.total(lam) - d

    g_imp = jax.grad(net_u)(lam)
    phi_end = solve_routing_implicit(g, cost, lam, phi_star, ETA, 400)
    g_env = exact_allocation_gradient(g, cost, bank, lam, phi_end)
    np.testing.assert_allclose(np.asarray(g_imp), np.asarray(g_env),
                               rtol=2e-3, atol=2e-3)


def _run_modes(problem_sampled, problem_learned, iters=40):
    cfg_s = paper_defaults().replace(inner_iters=20)
    cfg_l = cfg_s.replace(grad_mode="learned")
    res_s = _solver.run(problem_sampled, cfg_s, iters=iters)
    res_l = _solver.run(problem_learned, cfg_l, iters=iters)
    return res_s, res_l


def test_learned_with_exact_surrogate_reproduces_sampled(small_cec):
    """grad_mode="learned" with the true bank as surrogate converges to
    the sampled controller's utility (≤1e-3 relative) — the analytic
    gradient path is the same optimization, minus the perturbation
    sweep."""
    bank = make_bank("log", small_cec.n_sessions, seed=0)
    prob = Problem.create(small_cec, bank, lam_total=20.0)
    res_s, res_l = _run_modes(prob, prob)
    u_s, u_l = float(res_s.utility_traj[-1]), float(res_l.utility_traj[-1])
    assert abs(u_l - u_s) / abs(u_s) <= 1e-3, (u_s, u_l)


def test_learned_with_fitted_surrogate_golden(small_cec):
    """The golden migration check: a log-family surrogate fitted to
    box-sampled bank observations drives the learned controller to the
    sampled controller's converged utility (≤1e-3 relative)."""
    from repro.core.utility import fit_utilities, get_family

    W = small_cec.n_sessions
    bank = make_bank("log", W, seed=0)
    fam = get_family("log")
    rng = np.random.default_rng(0)
    lams = jnp.asarray(rng.uniform(0.3, 19.0, size=(256, W)), jnp.float32)
    utils = jax.vmap(bank.total)(lams)
    params = fam.init_params(W, seed=0)
    for _ in range(3):
        params, _ = fit_utilities(fam, params, lams, utils,
                                  steps=2000, lr=0.1)
    prob_s = Problem.create(small_cec, bank, lam_total=20.0)
    prob_l = prob_s.with_utilities("log", params)
    res_s, res_l = _run_modes(prob_s, prob_l)
    u_s = float(res_s.utility_traj[-1])
    # price the learned trajectory's final Λ with the TRUE bank — the
    # surrogate must land the controller at the same operating point
    from repro.core.flow import total_cost as _tc

    cost = get_cost("exp")
    u_l = float(bank.total(res_l.lam)
                - _tc(small_cec, cost, res_l.phi, res_l.lam))
    assert abs(u_l - u_s) / abs(u_s) <= 1e-3, (u_s, u_l)


def test_learned_mode_without_surrogate_or_bank_errors(small_cec):
    cfg = paper_defaults().replace(grad_mode="learned")
    prob = Problem.create(small_cec, None, lam_total=20.0)
    with pytest.raises(ValueError, match="learned"):
        _solver.run(prob, cfg, iters=2)
