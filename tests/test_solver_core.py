"""Shim-parity suite for the solver core (DESIGN.md §13).

Every legacy entry point (``solve_jowr``, ``gs_oma``, ``omad``,
``solve_jowr_batch``, ``CECRouter``) is a projection of the one
``Problem``/``SolverConfig``/``SolverState`` engine — these tests pin
that claim *bit-exactly*: the old call and the equivalent first-class
call must produce identical trajectories (tolerance 1e-12, in practice
0.0 — they execute the same compiled program), on the dense and the
auto-sparsified path alike.  The golden trace
(tests/golden/fig7_gs_oma_traj.npz, tests/test_golden_trace.py) pins the
engine itself across time; this module pins the facade against the
engine.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CECGraphBatch, Problem, SolverConfig, SolverState,
                        build_random_cec, dispatch, get_cost, gs_oma,
                        make_bank, omad, paper_defaults, resolve_cost,
                        run_batch, serving_defaults, solve_jowr,
                        solve_jowr_batch)
from repro.core import solver as S
from repro.topo import connected_er

LAM_TOTAL = 30.0


def _instance(n=12, p=0.35, seed=1, W=3):
    g = build_random_cec(connected_er(n, p, seed=seed), W, 10.0, seed=0)
    bank = make_bank("log", W, seed=0, lam_total=LAM_TOTAL)
    return g, bank


def _assert_traj_equal(old, new):
    """Bit-level parity (≤1e-12) across every shared result field."""
    for name in ("utility_traj", "lam_traj", "lam", "phi"):
        a = np.asarray(getattr(old, name), np.float64)
        b = np.asarray(getattr(new, name), np.float64)
        np.testing.assert_allclose(a, b, rtol=0.0, atol=1e-12, err_msg=name)


# ---------------------------------------------------------------------------
# old call → new call, bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method,inner", [("nested", 4), ("single", 1)])
def test_solve_jowr_is_a_shim_over_run(method, inner):
    g, bank = _instance()
    old = solve_jowr(g, bank, LAM_TOTAL, method=method, eta_inner=3.0,
                     outer_iters=8, inner_iters=inner)
    problem = Problem.create(g, bank, lam_total=LAM_TOTAL, cost="exp")
    config = SolverConfig(method=method, eta_inner=3.0, inner_iters=inner)
    new = S.run(problem, config, iters=8)
    _assert_traj_equal(old, new)


def test_gs_oma_and_omad_are_shims_over_run():
    g, bank = _instance()
    cost = get_cost("exp")
    problem = Problem.create(g, bank, lam_total=LAM_TOTAL, cost=cost)
    old_nested = gs_oma(g, cost, bank, LAM_TOTAL, eta_inner=3.0,
                        outer_iters=6, inner_iters=3)
    new_nested = S.run(problem, SolverConfig(method="nested", eta_inner=3.0,
                                             inner_iters=3), iters=6)
    _assert_traj_equal(old_nested, new_nested)

    old_single = omad(g, cost, bank, LAM_TOTAL, eta_inner=3.0, outer_iters=6)
    new_single = S.run(problem, SolverConfig(method="single", eta_inner=3.0),
                       iters=6)
    _assert_traj_equal(old_single, new_single)


def test_solve_jowr_batch_is_a_shim_over_run_batch():
    graphs = [build_random_cec(connected_er(12, 0.35, seed=3 + b), 3, 10.0,
                               seed=b) for b in range(3)]
    banks = [make_bank("log", 3, seed=b, lam_total=LAM_TOTAL)
             for b in range(3)]
    batch = CECGraphBatch.from_graphs(graphs)
    old = solve_jowr_batch(batch, banks, LAM_TOTAL, method="single",
                           eta_inner=3.0, outer_iters=6)
    new = run_batch(batch, banks, LAM_TOTAL,
                    SolverConfig(method="single", eta_inner=3.0), iters=6)
    _assert_traj_equal(old, new)
    # ... and the batched engine is the single-instance engine, lane-wise
    solo = S.run(Problem.create(graphs[1], banks[1], lam_total=LAM_TOTAL),
                 SolverConfig(method="single", eta_inner=3.0), iters=6)
    np.testing.assert_allclose(np.asarray(new.utility_traj[1]),
                               np.asarray(solo.utility_traj),
                               rtol=1e-5, atol=1e-5)


def test_sparse_path_shim_parity():
    """The auto-sparsified representation goes through the same single
    conversion point (Problem.canonical) for old and new calls."""
    g, bank = _instance(n=16, p=0.3)
    with dispatch.sparse_dispatch(1, 1.0):
        old = solve_jowr(g, bank, LAM_TOTAL, method="single", eta_inner=3.0,
                         outer_iters=5)
        new = S.run(Problem.create(g, bank, lam_total=LAM_TOTAL),
                    SolverConfig(method="single", eta_inner=3.0), iters=5)
    _assert_traj_equal(old, new)
    # the representation never leaks: dense in → dense out
    assert new.phi.shape == g.out_mask.shape
    assert new.state.phi.shape == g.out_mask.shape


def test_router_control_steps_match_fused_step_exactly():
    """CECRouter == Problem + SolverConfig + SolverState: driving
    solver.fused_step by hand with the same measured utilities reproduces
    the router's trajectory bit-for-bit (same executable, same inputs)."""
    from repro.serve import CECRouter

    g, _ = _instance(n=10, p=0.4, seed=2)
    quality = np.array([1.0, 1.5, 2.0], np.float32)

    def measured(lams):
        return np.atleast_2d(np.asarray(lams)) @ quality

    router = CECRouter(g, lam_total=12.0)
    recs = [router.control_step(measured) for _ in range(4)]

    config = serving_defaults()
    problem = Problem(graph=g, bank=None, lam_total=jnp.float32(12.0),
                      cost=resolve_cost("exp"))
    state = S.init(problem, config)
    for rec in recs:
        pert = S.perturbed_allocations(state.lam, config.delta)
        task_u = jnp.asarray(np.asarray(measured(np.asarray(pert)),
                                        np.float32))
        state, info = S.fused_step(config)(problem, state, task_u)
        np.testing.assert_array_equal(np.asarray(state.lam), rec["lam"])
        np.testing.assert_array_equal(float(info.cost), rec["cost"])
        np.testing.assert_array_equal(np.asarray(info.grad), rec["grad"])
    assert int(router.state.t) == int(state.t) == 4


def test_run_scenario_accepts_config(monkeypatch):
    """run_scenario(config=...) ≡ run_scenario(legacy knobs)."""
    from repro.core import Scenario, run_scenario

    sc = Scenario("steady", horizon=6, topo_kwargs={"n": 12, "p": 0.35},
                  mean_capacity=10.0, lam_total=LAM_TOTAL)
    legacy = run_scenario(sc, seeds=(0, 1), eta_inner=3.0)
    cfg = SolverConfig(method="single", eta_inner=3.0)
    first_class = run_scenario(sc, seeds=(0, 1), config=cfg)
    _assert_traj_equal(legacy, first_class)


# ---------------------------------------------------------------------------
# the engine itself: init/step/run contract
# ---------------------------------------------------------------------------

def test_run_equals_manual_step_loop():
    g, bank = _instance()
    problem = Problem.create(g, bank, lam_total=LAM_TOTAL)
    config = SolverConfig(method="single", eta_inner=3.0)
    res = S.run(problem, config, iters=5)

    state = S.init(problem, config)
    for k in range(5):
        task_u = jax.vmap(bank.total)(
            S.perturbed_allocations(state.lam, config.delta))
        state, info = S.step(problem, config, state, task_u)
        np.testing.assert_allclose(np.asarray(res.lam_traj[k]),
                                   np.asarray(state.lam), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(float(res.cost_traj[k]), float(info.cost),
                                   rtol=1e-5, atol=1e-5)
    assert int(state.t) == 5


def test_run_threads_state_across_calls():
    """run(10) == run(5) ∘ run(5, state=...) — the scenario-segment
    contract."""
    g, bank = _instance()
    problem = Problem.create(g, bank, lam_total=LAM_TOTAL)
    config = SolverConfig(method="single", eta_inner=3.0)
    whole = S.run(problem, config, iters=10)
    first = S.run(problem, config, iters=5)
    second = S.run(problem, config, iters=5, state=first.state)
    np.testing.assert_allclose(
        np.asarray(whole.utility_traj),
        np.concatenate([np.asarray(first.utility_traj),
                        np.asarray(second.utility_traj)]),
        rtol=1e-5, atol=1e-5)
    assert int(second.state.t) == 10


def test_result_unifies_the_legacy_records():
    """Result carries the JOWRResult fields plus the ControlStep/history
    diagnostics (cost, grad) per iteration."""
    g, bank = _instance()
    res = S.run(Problem.create(g, bank, lam_total=LAM_TOTAL),
                SolverConfig(method="single", eta_inner=3.0), iters=4)
    T, W = 4, g.n_sessions
    assert res.utility_traj.shape == (T,)
    assert res.lam_traj.shape == (T, W)
    assert res.cost_traj.shape == (T,)
    assert res.grad_traj.shape == (T, W)
    assert isinstance(res.state, SolverState)
    # the recorded utility decomposes as bank.total(Λ^t) − cost^t
    task = np.asarray(jax.vmap(bank.total)(res.lam_traj))
    np.testing.assert_allclose(np.asarray(res.utility_traj),
                               task - np.asarray(res.cost_traj),
                               rtol=1e-5, atol=1e-5)


def test_run_is_jit_and_vmap_compatible():
    """Problem is a pytree: run jits with lam_total traced (demand shifts
    reuse the executable)."""
    g, bank = _instance()
    config = SolverConfig(method="single", eta_inner=3.0)

    @jax.jit
    def solve(lam_total):
        problem = Problem(graph=g, bank=bank, lam_total=lam_total,
                          cost=get_cost("exp"))
        return S.run(problem, config, iters=3).utility_traj

    u1 = solve(jnp.float32(LAM_TOTAL))
    eager = S.run(Problem.create(g, bank, lam_total=LAM_TOTAL), config,
                  iters=3).utility_traj
    np.testing.assert_allclose(np.asarray(u1), np.asarray(eager), rtol=1e-5,
                               atol=1e-5)
    u2 = solve(jnp.float32(LAM_TOTAL * 1.25))      # no retrace, new demand
    assert not np.allclose(np.asarray(u1), np.asarray(u2))


# ---------------------------------------------------------------------------
# validation / presets
# ---------------------------------------------------------------------------

def test_problem_validate_errors():
    g, bank = _instance()
    with pytest.raises(TypeError, match="CECGraph"):
        Problem(graph=np.zeros((3, 3)), bank=bank,
                lam_total=LAM_TOTAL).validate()
    with pytest.raises(ValueError, match="sessions"):
        Problem(graph=g, bank=make_bank("log", 5, seed=0),
                lam_total=LAM_TOTAL).validate()
    with pytest.raises(ValueError, match="positive"):
        Problem(graph=g, bank=bank, lam_total=0.0).validate()
    with pytest.raises(TypeError, match="CostFn"):
        Problem(graph=g, bank=bank, lam_total=LAM_TOTAL,
                cost="exp").validate()          # names go through create()
    with pytest.raises(KeyError, match="registered costs"):
        Problem.create(g, bank, lam_total=LAM_TOTAL, cost="expo")


def test_solver_config_validation_and_presets():
    with pytest.raises(ValueError, match="valid methods"):
        SolverConfig(method="bogus")
    with pytest.raises(ValueError, match="delta"):
        SolverConfig(delta=0.0)
    with pytest.raises(ValueError, match="inner_iters"):
        SolverConfig(inner_iters=0)
    paper, serving = paper_defaults(), serving_defaults()
    # the documented (intentional) divergence, pinned: the serving plane
    # runs the hot K=1 oracle, the offline evaluation the gentle nested one
    assert (paper.method, paper.eta_inner, paper.inner_iters) == \
        ("nested", 0.05, 50)
    assert (serving.method, serving.eta_inner, serving.oracle_iters) == \
        ("single", 3.0, 1)
    assert SolverConfig(method="single", inner_iters=50).oracle_iters == 1
    # configs are hashable jit-cache keys
    assert hash(paper) != hash(serving)
    assert dataclasses.replace(paper, method="single") != paper


def test_run_continuation_recanonicalizes_sparse():
    """A carried dense state must not pin a continuation to the dense
    path: run(state=...) re-applies the representation policy (the φ is
    re-laid-out onto the edge slots), and split == whole bit-exactly."""
    g, bank = _instance(n=16, p=0.3)
    problem = Problem.create(g, bank, lam_total=LAM_TOTAL)
    config = SolverConfig(method="single", eta_inner=3.0)
    with dispatch.sparse_dispatch(1, 1.0):
        whole = S.run(problem, config, iters=6)
        first = S.run(problem, config, iters=3)
        assert first.state.phi.shape == g.out_mask.shape   # dense contract
        second = S.run(problem, config, iters=3, state=first.state)
    np.testing.assert_allclose(
        np.asarray(whole.utility_traj, np.float64),
        np.concatenate([np.asarray(first.utility_traj, np.float64),
                        np.asarray(second.utility_traj, np.float64)]),
        rtol=0.0, atol=1e-12)
    _assert_traj_equal(
        whole, second._replace(
            utility_traj=whole.utility_traj,
            lam_traj=jnp.concatenate([first.lam_traj, second.lam_traj])))


def test_run_rejects_state_plus_warm_start_overrides():
    """state= and phi0=/lam0= are mutually exclusive — silently dropping
    a caller's warm-start override would be an invisible wrong answer."""
    g, bank = _instance()
    problem = Problem.create(g, bank, lam_total=LAM_TOTAL)
    config = SolverConfig(method="single", eta_inner=3.0)
    prev = S.run(problem, config, iters=2)
    with pytest.raises(ValueError, match="not both"):
        S.run(problem, config, iters=2, state=prev.state,
              phi0=g.uniform_phi())


def test_run_without_bank_points_at_step():
    g, _ = _instance()
    with pytest.raises(ValueError, match="solver.step"):
        S.run(Problem(graph=g, bank=None, lam_total=LAM_TOTAL),
              SolverConfig(), iters=2)


def test_paper_preset_module():
    from repro.configs import cec_paper

    cfg = cec_paper.solver_config()
    assert cfg.eta_inner == 3.0 and cfg.method == "single"
    assert cec_paper.solver_config(method="nested").inner_iters == 50
    problem = cec_paper.build_problem()
    assert problem.n_sessions == 3
    assert float(np.asarray(problem.lam_total)) == 60.0


@pytest.mark.parametrize("lead", [(), (5,)], ids=["one", "K"])
@pytest.mark.parametrize("delta", [0.5, 0.1, 1e-3])
@pytest.mark.parametrize("W", [1, 2, 3, 8])
def test_perturbed_allocations_host_matches_device_bits(W, delta, lead):
    """The host sweep the serving entries build equals the eager
    (vmapped) ``perturbed_allocations`` bit for bit, float32, in the
    observation order: row 2w = Λ + δ·e_w, row 2w+1 = Λ − δ·e_w."""
    rng = np.random.default_rng(W)
    lam = rng.uniform(0.0, 60.0, lead + (W,)).astype(np.float32)
    lam.reshape(-1)[0] = 0.0                 # a tenant at its box edge

    def dev(lam):
        return S.perturbed_allocations(lam, delta)

    want = np.asarray((jax.vmap(dev) if lead else dev)(jnp.asarray(lam)))
    got = S.perturbed_allocations_host(lam, delta)
    assert got.dtype == np.float32 and got.shape == lead + (2 * W, W)
    np.testing.assert_array_equal(got.view(np.uint32),
                                  want.view(np.uint32))
    for w in range(W):
        e = np.zeros(W, np.float32)
        e[w] = np.float32(delta)
        np.testing.assert_array_equal(got[..., 2 * w, :], lam + e)
        np.testing.assert_array_equal(got[..., 2 * w + 1, :], lam - e)
