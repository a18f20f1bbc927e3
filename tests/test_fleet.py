"""RouterFleet contracts (ISSUE 7, DESIGN.md §15): K-tenant parity with
independent CECRouters (steady + churn), double-buffer discipline,
buffer donation, no-retrace churn, and the microbatched callback."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import solver as _solver
from repro.core.batch import fused_step_batch
from repro.core.scenario import (Scenario, event_schedule, initial_state,
                                 named_scenarios)
from repro.serve import CECRouter, RouterFleet

PARITY_ATOL = 1e-5     # the ISSUE acceptance bar; in practice bit-identical


def _make_tenants(n_tenants, *, scenario="steady", horizon=20, n=10, p=0.4,
                  topology="connected_er"):
    if topology == "connected_er":
        sc = named_scenarios(horizon=horizon, n=n, p=p)[scenario]
    else:                   # a paper topology, its Table II capacity
        sc = Scenario(scenario, horizon=horizon, topology=topology)
    states = [initial_state(sc, seed=s) for s in range(n_tenants)]
    graphs = [st.graph() for st in states]
    fns = [
        (lambda lams, b=st.bank:
         np.asarray(jax.vmap(b.total)(jnp.asarray(lams))))
        for st in states]
    return sc, states, graphs, fns


def _donation_supported():
    x = jnp.ones(4)
    jax.jit(lambda v: v + 1.0, donate_argnums=0)(x)
    return x.is_deleted()


@pytest.mark.parametrize("topology",
                         ["connected_er", "abilene", "geant", "fog"])
def test_fleet_parity_with_independent_routers(topology):
    """K stacked tenants advance exactly like K CECRouters: same Λ, same
    net utility, same replica weights, interval for interval."""
    sc, _, graphs, fns = _make_tenants(3, topology=topology)
    lam_totals = [60.0, 45.0, 75.0]
    routers = [CECRouter(g, lam_total=lt)
               for g, lt in zip(graphs, lam_totals)]
    fleet = RouterFleet(graphs, lam_totals)
    for _ in range(6):
        recs = [r.control_step(fn) for r, fn in zip(routers, fns)]
        frec = fleet.control_step(fns)
        for k, r in enumerate(routers):
            np.testing.assert_allclose(frec["lam"][k], recs[k]["lam"],
                                       atol=PARITY_ATOL)
            np.testing.assert_allclose(frec["utility"][k],
                                       recs[k]["utility"], atol=PARITY_ATOL)
    for k, r in enumerate(routers):
        np.testing.assert_allclose(
            fleet.view.replica_weights()[k][:, : r.graph.n_phys],
            r.replica_weights(), atol=PARITY_ATOL)


def test_fleet_parity_under_churn_timeline():
    """The acceptance bar's hard half: parity holds through a scenario
    timeline (node failures + demand surge) consumed per tenant."""
    sc, scn_states, graphs, fns = _make_tenants(
        2, scenario="flash_crowd", horizon=24)
    routers = [CECRouter(g, lam_total=sc.lam_total) for g in graphs]
    fleet = RouterFleet(graphs, [sc.lam_total] * 2)
    schedule = {at: evs for at, evs in event_schedule(sc) if evs}
    r_states = list(scn_states)
    f_states = list(scn_states)
    for t in range(sc.horizon):
        for ev in schedule.get(t, ()):
            for k in range(2):
                r_states[k] = routers[k].apply_scenario_event(r_states[k], ev)
                f_states[k] = fleet.apply_scenario_event(k, f_states[k], ev)
        recs = [r.control_step(fn) for r, fn in zip(routers, fns)]
        frec = fleet.control_step(fns)
        for k in range(2):
            np.testing.assert_allclose(frec["lam"][k], recs[k]["lam"],
                                       atol=PARITY_ATOL)
    # demand surge actually landed: fleet totals follow the events
    np.testing.assert_allclose(fleet.lam_totals,
                               [r_states[0].lam_total] * 2)


def test_published_view_survives_donated_steps():
    """Double-buffer discipline (DESIGN.md §15.2): a FleetView taken
    before N further control steps still reads cleanly afterwards —
    its buffers are computed copies, never aliases of donated state."""
    _, _, graphs, fns = _make_tenants(2)
    fleet = RouterFleet(graphs, [60.0, 60.0])
    fleet.control_step(fns)
    view = fleet.view
    lam_snapshot = np.asarray(view.lam).copy()
    for _ in range(3):
        fleet.control_step(fns)
    # old front still alive and unchanged; new front has moved on
    assert not view.lam.is_deleted()
    np.testing.assert_array_equal(np.asarray(view.lam), lam_snapshot)
    assert (np.asarray(fleet.view.lam) != lam_snapshot).any()
    # serving-plane reads: split is a distribution, weights rows sum to 1
    split = fleet.view.admission_split()
    np.testing.assert_allclose(split.sum(-1), 1.0, atol=1e-5)
    w = fleet.view.replica_weights()
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-4)


def test_steady_state_step_donates_buffers():
    """Donation invariant (DESIGN.md §15.3): after a control step the
    previous stacked state is dead — XLA reused its buffers."""
    if not _donation_supported():
        pytest.skip("backend ignores donate_argnums (documented deviation, "
                    "DESIGN.md §15.3)")
    _, _, graphs, fns = _make_tenants(2)
    fleet = RouterFleet(graphs, [60.0, 60.0])
    old = fleet.state
    fleet.control_step(fns)
    assert old.lam.is_deleted()
    # opting out keeps the old state readable
    fleet_nd = RouterFleet(graphs, [60.0, 60.0], donate=False)
    old = fleet_nd.state
    fleet_nd.control_step(fns)
    assert not old.lam.is_deleted()
    np.testing.assert_allclose(np.asarray(fleet.view.lam),
                               np.asarray(fleet_nd.view.lam),
                               atol=PARITY_ATOL)


def test_demand_and_same_shape_churn_never_retrace():
    """Demand is a traced leaf and churn is same-shape by construction:
    the fleet's compiled step count stays at one executable."""
    _, scn_states, graphs, fns = _make_tenants(2)
    # depth headroom so the rewired graph below still fits the layout
    fleet = RouterFleet(graphs, [60.0, 60.0],
                        depth_max=max(g.depth_max for g in graphs) + 4)
    step = fused_step_batch(fleet.config, cost=fleet.cost_name,
                            donate=fleet.donate)
    if not hasattr(step, "_cache_size"):
        pytest.skip("jax version without jit cache introspection")
    fleet.control_step(fns)
    n0 = step._cache_size()
    fleet.set_demand([80.0, 55.0])
    fleet.control_step(fns)
    from repro.core.scenario import Rewire, apply_event
    new_scn = apply_event(scn_states[0], Rewire(at=1, frac=0.3, seed=3))
    fleet.update_tenant_graph(0, new_scn.graph())
    fleet.control_step(fns)
    assert step._cache_size() == n0


def test_set_demand_projects_onto_each_tenants_box():
    _, _, graphs, _ = _make_tenants(3)
    fleet = RouterFleet(graphs, [60.0, 60.0, 60.0])
    fleet.set_demand([90.0, 30.0, 60.0])
    lam = np.asarray(fleet.state.lam)
    np.testing.assert_allclose(lam.sum(-1), [90.0, 30.0, 60.0], rtol=1e-5)
    delta = fleet.config.delta
    for k, tot in enumerate([90.0, 30.0, 60.0]):
        assert (lam[k] >= delta - 1e-5).all()
        assert (lam[k] <= tot - delta + 1e-5).all()
    with pytest.raises(ValueError):
        fleet.set_demand([1.0, 2.0])        # wrong tenant count


def test_microbatched_callback_contract():
    """One fleet-batched call covers every tenant's perturbation sweep;
    per-tenant callables are called once per measurement each; a
    wrong-shaped batched callback is an error, not a fallback."""
    _, _, graphs, _ = _make_tenants(2)
    fleet = RouterFleet(graphs, [60.0, 60.0])
    K, W = fleet.n_tenants, fleet.n_sessions
    calls = []

    def fleet_batched(lams):
        calls.append(np.asarray(lams).shape)
        return np.ones(np.asarray(lams).shape[:2], np.float32)

    fleet.control_step(fleet_batched)
    # exactly two microbatches: the [K, 2W, W] sweep + the committed [K, 1, W]
    assert calls == [(K, 2 * W, W), (K, 1, W)]

    with pytest.raises(TypeError):
        fleet.control_step(lambda lams: np.ones(3, np.float32))


def test_fleet_construction_validates():
    _, _, graphs, _ = _make_tenants(2)
    with pytest.raises(ValueError):
        RouterFleet(graphs, [60.0])          # one demand per tenant
    fleet = RouterFleet(graphs, [60.0, 60.0])
    big = dataclasses.replace(graphs[0])
    with pytest.raises(ValueError):
        # a tenant outgrowing the fleet layout must raise, not retrace
        from repro.core.batch import pad_graph
        fleet.update_tenant_graph(0, pad_graph(big, fleet.batch.n_phys + 2))


def test_fleet_live_migration_to_learned_gradients():
    """Under a learned ``grad_policy`` the fleet samples until *every*
    tenant's fitter clears its holdout bar, then migrates live: one
    measured admission per tenant per interval instead of 2W+1, with net
    utility within 1% of an all-sampled twin (DESIGN.md §16.4)."""
    sc, _, graphs, fns = _make_tenants(2)
    fleet = RouterFleet(graphs, [60.0, 60.0], grad_policy="auto",
                        util_family="log")
    twin = RouterFleet(graphs, [60.0, 60.0])
    for f in fleet.fitters:
        f.min_samples, f.refit_every, f.fit_steps = 20, 8, 1500
        f.threshold = 0.02          # earn the switch with a tight surrogate
    # long enough that post-switch refits sharpen the surrogate at the
    # operating point (the learned steady state converges onto sampled's)
    for _ in range(70):
        rec = fleet.control_step(fns)
        ref = twin.control_step(fns)
    assert rec["mode"] == "learned"
    modes = [h["mode"] for h in fleet.history if "mode" in h]
    assert modes[0] == "sampled" and "learned" in modes
    # the whole point: an interval costs 1 oracle call instead of 2W+1
    assert rec["oracle_calls"] == 1
    assert ref["oracle_calls"] == 2 * fleet.n_sessions + 1
    assert float(rec["utility"].sum()) >= 0.99 * float(ref["utility"].sum())


def test_fleet_learned_interval_skips_perturbation_measurements():
    """In learned mode the measured-utility callback sees exactly one
    admission per tenant (the committed Λ) — no perturbation sweep."""
    sc, _, graphs, fns = _make_tenants(2)
    fleet = RouterFleet(graphs, [60.0, 60.0], grad_policy="learned",
                        util_family="log")
    for f in fleet.fitters:
        f.min_samples, f.refit_every, f.fit_steps = 20, 8, 800
    seen = []

    def counting(k):
        def fn(lams):
            seen.append(lams.shape[0])
            return fns[k](lams)
        return fn

    wrapped = [counting(k) for k in range(2)]
    while fleet._grad_mode_now() != "learned":
        fleet.control_step(wrapped)
    seen.clear()
    fleet.control_step(wrapped)
    assert seen == [1, 1], seen   # one committed admission per tenant
