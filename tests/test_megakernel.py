"""One-kernel fused control step: parity, precision, compat (DESIGN.md §17).

The megakernel (``kernels/control_megakernel.py``) replaces the whole
``lax.scan``-of-observations control iteration — perturbation sweep,
K-iteration routing oracle, cost evaluation, two-point gradient, mirror
ascent, exact box-simplex projection, committed observation — with one
``pallas_call``.  This suite pins it against the stitched jnp reference
(``solver._sampled_step``) on both layouts, checks the bf16 storage mode
against the committed golden trace within the §17.3 bounds, and proves
the dispatch wiring composes with jit / vmap / shard_map.  Everything
runs in Pallas interpret mode on CPU (``dispatch.kernel_interpret``), so
the fused path is validated wherever CI runs, not just on TPU.
"""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_random_cec, dispatch
from repro.core import solver as S
from repro.core.graph import sparsify
from repro.core.problem import Problem
from repro.topo import connected_er

_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:          # scripts/ is a namespace package
    sys.path.insert(0, str(_ROOT))

PARITY_TOL = 1e-5          # f32 storage vs the stitched jnp reference
LAM_TOTAL = 8.0


def _setup(n=12, n_sessions=3, k_iters=3, seed=3, sparse=False):
    g = build_random_cec(connected_er(n, 0.35, seed=seed), n_sessions,
                         10.0, seed=0)
    if sparse:
        g = sparsify(g)
    problem = Problem.create(g, lam_total=LAM_TOTAL, cost="exp")
    config = S.SolverConfig(method="nested", delta=0.5, eta_outer=0.05,
                            eta_inner=0.05, inner_iters=k_iters,
                            grad_mode="sampled")
    state = S.init(problem, config)
    tau = jnp.asarray(
        np.random.default_rng(0).uniform(1.0, 5.0, 2 * g.n_sessions),
        jnp.float32)
    return problem, config, state, tau


def _ref_and_mega(problem, config, state, tau):
    ref = S.step(problem, config, state, tau)
    with dispatch.megakernel_dispatch(1):
        mega = S.step(problem, config, state, tau)
    return ref, mega


# ---------------------------------------------------------------------------
# f32 parity vs the stitched reference — dense and sparse layouts
# ---------------------------------------------------------------------------

def test_dense_parity_f32():
    problem, config, state, tau = _setup()
    (rs, ri), (ms, mi) = _ref_and_mega(problem, config, state, tau)
    np.testing.assert_allclose(ms.lam, rs.lam, atol=PARITY_TOL)
    np.testing.assert_allclose(ms.phi, rs.phi, atol=PARITY_TOL)
    np.testing.assert_allclose(mi.grad, ri.grad, atol=PARITY_TOL)
    np.testing.assert_allclose(float(mi.cost), float(ri.cost),
                               rtol=PARITY_TOL, atol=PARITY_TOL)
    assert int(ms.t) == int(rs.t) == int(state.t) + 1


def test_sparse_parity_f32():
    problem, config, state, tau = _setup(sparse=True)
    (rs, ri), (ms, mi) = _ref_and_mega(problem, config, state, tau)
    np.testing.assert_allclose(ms.lam, rs.lam, atol=PARITY_TOL)
    np.testing.assert_allclose(ms.phi.rows, rs.phi.rows, atol=PARITY_TOL)
    np.testing.assert_allclose(ms.phi.src, rs.phi.src, atol=PARITY_TOL)
    np.testing.assert_allclose(mi.grad, ri.grad, atol=PARITY_TOL)
    np.testing.assert_allclose(float(mi.cost), float(ri.cost),
                               rtol=PARITY_TOL, atol=PARITY_TOL)


@pytest.mark.parametrize("k_iters", [1, 4])
def test_parity_across_oracle_depths(k_iters):
    """K=1 is OMAD (Alg. 3); deeper K exercises the k-loop grid axis."""
    problem, config, state, tau = _setup(k_iters=k_iters)
    (rs, _), (ms, _) = _ref_and_mega(problem, config, state, tau)
    np.testing.assert_allclose(ms.lam, rs.lam, atol=PARITY_TOL)
    np.testing.assert_allclose(ms.phi, rs.phi, atol=PARITY_TOL)


def test_multi_step_trajectory_parity():
    """Three threaded steps stay in lockstep — VMEM state re-seeds
    correctly between kernel invocations (no stale-scratch carryover)."""
    problem, config, state, tau = _setup()
    ref_st, mega_st = state, state
    for k in range(3):
        ref_st, _ = S.step(problem, config, ref_st, tau)
        with dispatch.megakernel_dispatch(1):
            mega_st, _ = S.step(problem, config, mega_st, tau)
        np.testing.assert_allclose(mega_st.lam, ref_st.lam, atol=PARITY_TOL)
        np.testing.assert_allclose(mega_st.phi, ref_st.phi, atol=PARITY_TOL)
        assert int(mega_st.t) == k + 1


# ---------------------------------------------------------------------------
# bf16 storage mode (DESIGN.md §17.3) — golden-trace bounds
# ---------------------------------------------------------------------------

def test_bf16_storage_tracks_golden_trace(monkeypatch):
    """bf16 φ-storage (f32 accumulate) on the committed Fig. 7 golden
    config: 20 outer iterations stay within the documented §17.3 drift
    bounds (utility rtol ≲1e-3 of a |U|~80 trajectory, λ within 0.2 of
    λ_total=60).  Measured drift is ~2.3e-4 rel / 0.083 abs — the bounds
    carry ~2.5× headroom, so a storage-path regression fails loudly."""
    from scripts.make_golden_trace import solve

    golden = np.load(pathlib.Path(__file__).parent / "golden"
                     / "fig7_gs_oma_traj.npz")
    monkeypatch.setenv("REPRO_MEGAKERNEL_PHI_DTYPE", "bfloat16")
    with dispatch.megakernel_dispatch(1):
        res = solve()
    np.testing.assert_allclose(np.asarray(res.utility_traj, np.float64),
                               golden["utility_traj"], rtol=1e-3, atol=0.05)
    np.testing.assert_allclose(np.asarray(res.lam, np.float64),
                               golden["lam"], atol=0.2)


def test_f32_megakernel_matches_golden_trace():
    """The f32 megakernel reproduces the golden trajectory within the
    *golden* tolerance itself (measured ≤4e-5) — the fused path is a
    drop-in for the pinned control-step semantics, not a variant."""
    from scripts.make_golden_trace import solve

    golden = np.load(pathlib.Path(__file__).parent / "golden"
                     / "fig7_gs_oma_traj.npz")
    with dispatch.megakernel_dispatch(1):
        res = solve()
    np.testing.assert_allclose(np.asarray(res.utility_traj, np.float64),
                               golden["utility_traj"], rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(np.asarray(res.lam, np.float64),
                               golden["lam"], rtol=2e-4, atol=2e-3)


def test_bf16_phi_dtype_knob_validated(monkeypatch):
    monkeypatch.setenv("REPRO_MEGAKERNEL_PHI_DTYPE", "float16")
    with pytest.raises(ValueError, match="float32.*bfloat16"):
        dispatch.megakernel_phi_dtype()


# ---------------------------------------------------------------------------
# jit / vmap / shard_map compat
# ---------------------------------------------------------------------------

def test_jit_parity():
    problem, config, state, tau = _setup()
    ref, _ = S.step(problem, config, state, tau)
    with dispatch.megakernel_dispatch(1):
        jitted = jax.jit(lambda s, u: S.step(problem, config, s, u))
        got, _ = jitted(state, tau)
    np.testing.assert_allclose(got.lam, ref.lam, atol=PARITY_TOL)
    np.testing.assert_allclose(got.phi, ref.phi, atol=PARITY_TOL)


def test_vmap_over_observations():
    """vmap over the [2W] task-utility axis (a RouterFleet batching
    tenant observations) matches per-row fused steps."""
    problem, config, state, tau = _setup()
    taus = jnp.stack([tau, tau * 1.5, tau * 0.25])
    with dispatch.megakernel_dispatch(1):
        batched = jax.vmap(lambda u: S.step(problem, config, state, u))
        states, infos = batched(taus)
        for b in range(taus.shape[0]):
            one_s, one_i = S.step(problem, config, state, taus[b])
            np.testing.assert_allclose(states.lam[b], one_s.lam,
                                       atol=PARITY_TOL)
            np.testing.assert_allclose(states.phi[b], one_s.phi,
                                       atol=PARITY_TOL)
            np.testing.assert_allclose(infos.grad[b], one_i.grad,
                                       atol=PARITY_TOL)


def test_batched_solve_matches_jnp_path():
    """solve_jowr_batch (fused_step_batch's vmap-of-steps) under
    megakernel dispatch reproduces the jnp-path trajectories."""
    from repro.core import CECGraphBatch, make_bank, solve_jowr_batch

    graphs = [build_random_cec(connected_er(12, 0.35, seed=10 + s), 3,
                               8.0, seed=s) for s in range(2)]
    banks = [make_bank("log", 3, seed=s, lam_total=LAM_TOTAL)
             for s in range(2)]
    batch = CECGraphBatch.from_graphs(graphs)
    kw = dict(method="nested", eta_outer=0.05, eta_inner=3.0,
              outer_iters=4, inner_iters=2)
    ref = solve_jowr_batch(batch, banks, LAM_TOTAL, **kw)
    with dispatch.megakernel_dispatch(1):
        got = solve_jowr_batch(batch, banks, LAM_TOTAL, **kw)
    np.testing.assert_allclose(got.lam, ref.lam, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got.utility_traj),
                               np.asarray(ref.utility_traj), atol=1e-3)


def test_sharded_fleet_inherits_megakernel():
    """run_batch_sharded (shard_map over the fleet axis) composes with
    the megakernel and matches the unsharded vmap path."""
    from repro.core import CECGraphBatch, make_bank, run_batch
    from repro.core.batch import run_batch_sharded
    from repro.launch.mesh import fleet_mesh

    graphs = [build_random_cec(connected_er(12, 0.35, seed=20 + s), 3,
                               8.0, seed=s)
              for s in range(jax.device_count())]
    banks = [make_bank("log", 3, seed=s, lam_total=LAM_TOTAL)
             for s in range(len(graphs))]
    batch = CECGraphBatch.from_graphs(graphs)
    config = S.SolverConfig(method="nested", delta=0.5, eta_outer=0.05,
                            eta_inner=3.0, inner_iters=2,
                            grad_mode="sampled")
    with dispatch.megakernel_dispatch(1):
        ref = run_batch(batch, banks, LAM_TOTAL, config, iters=3)
        got = run_batch_sharded(batch, banks, LAM_TOTAL, config, iters=3,
                                mesh=fleet_mesh())
    np.testing.assert_allclose(got.lam, ref.lam, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got.utility_traj),
                               np.asarray(ref.utility_traj), atol=1e-4)


# ---------------------------------------------------------------------------
# dispatch policy (DESIGN.md §17.2/§17.4)
# ---------------------------------------------------------------------------

def test_policy_off_by_default_on_cpu():
    assert not dispatch.use_megakernel(10_000, 8)


def test_policy_engages_under_override_and_respects_vmem():
    with dispatch.megakernel_dispatch(1):
        assert dispatch.use_megakernel(16, 3)
        # a fleet-scale graph whose resident φ exceeds the VMEM budget
        # must fall back to the stitched path even when forced
        assert not dispatch.use_megakernel(8192, 64)
    assert not dispatch.use_megakernel(16, 3)


def test_sparse_megakernel_never_picked_on_tpu(monkeypatch):
    """The sparse kernel's gathers do not lower for a TPU: there the
    default dispatch leaves a sparse graph to the stitched kernels, and
    forcing the megakernel on it is an error, not a quiet fallback."""
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    assert dispatch.use_megakernel(300, 3)
    assert not dispatch.use_megakernel(300, 3, sparse=True)
    with dispatch.megakernel_dispatch(1):
        with pytest.raises(NotImplementedError, match="sparse megakernel"):
            dispatch.use_megakernel(300, 3, sparse=True)


def test_bf16_doubles_admissible_size():
    """§17.3: halving the φ itemsize roughly doubles what fits."""
    n = 1024
    w = 16
    assert not dispatch.megakernel_fits(w, n, itemsize=4)
    assert dispatch.megakernel_fits(w, n, itemsize=2)


def test_env_knobs_reread_after_import(monkeypatch):
    """§17.4 regression: dispatch knobs used to be bound at import, so a
    late os.environ mutation was a silent no-op.  Now every policy query
    and ``state_key()`` re-reads the environment."""
    key0 = dispatch.state_key()
    monkeypatch.setenv("REPRO_MEGAKERNEL_NBAR_THRESHOLD", "7")
    assert dispatch.megakernel_threshold() == 7
    assert dispatch.state_key() != key0
    # the env knob is an explicit opt-in: the policy engages off-TPU
    assert dispatch.use_megakernel(8, 2)
    monkeypatch.setenv("REPRO_MEGAKERNEL_PHI_DTYPE", "bfloat16")
    assert dispatch.megakernel_phi_dtype() == "bfloat16"
    assert "bfloat16" in dispatch.state_key()
    monkeypatch.delenv("REPRO_MEGAKERNEL_NBAR_THRESHOLD")
    monkeypatch.delenv("REPRO_MEGAKERNEL_PHI_DTYPE")
    assert dispatch.state_key() == key0
