"""Serving plane: the CEC router."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_random_cec, get_cost, total_cost
from repro.core.routing import solve_routing
from repro.serve import CECRouter
from repro.topo import connected_er


def test_cec_router_dispatch_consistency():
    g = build_random_cec(connected_er(10, 0.35, seed=2), 3, 20.0, seed=0)
    router = CECRouter(g, lam_total=12.0)
    split = router.admission_split()
    np.testing.assert_allclose(split.sum(), 1.0, atol=1e-6)
    w = router.replica_weights()
    dep = np.asarray(g.deploy)
    # weights live only on deploying replicas and sum to 1 per version
    assert (w[~dep.astype(bool)] == 0).all()
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-5)

    # a few control steps with a synthetic measured utility improve Λ
    quality = np.array([1.0, 1.5, 2.0])
    for _ in range(5):
        router.control_step(lambda lam: float((quality * lam).sum()) * 0.5)
    lam = np.asarray(router.lam)
    np.testing.assert_allclose(lam.sum(), 12.0, rtol=1e-4)
    assert lam[2] > lam[0]        # shifted toward the higher-quality version


def test_router_topology_change_keeps_feasibility():
    g1 = build_random_cec(connected_er(10, 0.35, seed=2), 3, 20.0, seed=0)
    router = CECRouter(g1, lam_total=12.0)
    router.control_step(lambda lam: float(np.sum(lam)))
    g2 = build_random_cec(connected_er(10, 0.35, seed=7), 3, 20.0, seed=0)
    router.on_topology_change(g2)
    phi = np.asarray(router.phi)
    mask = np.asarray(g2.out_mask)
    assert (phi[mask == 0] == 0).all()
    rows = phi.sum(-1)
    np.testing.assert_allclose(rows[mask.sum(-1) > 0], 1.0, atol=1e-5)
    # churn warm-start: every allowed edge keeps exploration mass — the
    # multiplicative update can only revive what this mix seeds
    assert (phi[mask > 0] > 0).all()


def test_router_consumes_scenario_event_stream():
    """The serving control plane consumes the same declarative events the
    scenario engine sweeps offline (DESIGN.md §10)."""
    from repro.core import DemandShift, NodeFail, Scenario, initial_state

    sc = Scenario("fleet", horizon=10, topo_kwargs={"n": 12, "p": 0.35},
                  mean_capacity=20.0, lam_total=12.0)
    state = initial_state(sc, seed=0)
    router = CECRouter(state.graph(), lam_total=12.0)
    router.control_step(lambda lam: float(np.sum(lam)))

    state = router.apply_scenario_event(state, NodeFail(at=1, count=2,
                                                        seed=4))
    assert state.alive.sum() == 10
    mask = np.asarray(router.graph.out_mask)
    phi = np.asarray(router.phi)
    dead = np.nonzero(~state.alive)[0]
    assert (mask[:, dead, :] == 0).all()          # failed nodes have no edges
    assert (phi[mask == 0] == 0).all()
    assert (phi[mask > 0] > 0).all()              # warm-start exploration
    np.testing.assert_allclose(phi.sum(-1)[mask.sum(-1) > 0], 1.0, atol=1e-5)

    state = router.apply_scenario_event(state, DemandShift(at=2,
                                                           lam_total=18.0))
    assert router.lam_total == 18.0
    np.testing.assert_allclose(np.asarray(router.lam).sum(), 18.0, rtol=1e-4)

    # the router keeps serving after the event stream
    rec = router.control_step(lambda lam: float(np.sum(lam)))
    np.testing.assert_allclose(rec["lam"].sum(), 18.0, rtol=1e-4)
    # dispatch weights stay consistent on the post-churn fleet
    w = router.replica_weights()
    alive_dep = np.asarray(router.graph.deploy)
    assert (w[~alive_dep.astype(bool)] == 0).all()
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-5)


def test_control_step_parity_with_reference_loop():
    """The fused device-resident step reproduces the per-observation host
    loop (the pre-PR-3 router semantics, preserved verbatim as
    ``benchmarks.bench_router._legacy_control_step`` — one reference, the
    bench's speedup baseline and this parity oracle) within 1e-5."""
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    from benchmarks.bench_router import _legacy_control_step

    g = build_random_cec(connected_er(12, 0.35, seed=4), 3, 15.0, seed=1)
    cost = get_cost("exp")
    quality = np.array([1.0, 1.4, 1.8])
    scalar_fn = lambda lam: float((quality * np.asarray(lam)).sum())

    router = CECRouter(g, lam_total=12.0)
    want_lam, phi = _legacy_control_step(
        g, cost, jnp.asarray(router.lam), g.uniform_phi(), 12.0, scalar_fn,
        delta=router.delta, eta_outer=router.eta_outer,
        eta_inner=router.eta_inner)
    # ... plus the committed observation the fused step appends
    want_phi, _ = solve_routing(g, cost, want_lam, phi, router.eta_inner, 1)
    want_cost = float(total_cost(g, cost, want_phi, want_lam))

    rec = router.control_step(scalar_fn)
    np.testing.assert_allclose(rec["lam"], np.asarray(want_lam),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(router.phi), np.asarray(want_phi),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(rec["cost"], want_cost, rtol=1e-5, atol=1e-5)


def test_router_under_churn_recovers_utility():
    """Live mirror of ``test_link_churn_recovers_pre_event_utility``: the
    router consumes the named link_churn timeline mid-serving and the
    measured network utility re-crosses 95% of its pre-event level within
    the post-event budget (DESIGN.md §11)."""
    from repro.core import event_schedule, initial_state, named_scenarios

    sc = named_scenarios(horizon=40, n=12, p=0.35)["link_churn"]
    state = initial_state(sc, seed=0)
    bank = state.bank

    def measured(lams):                       # batched bank observation
        return np.asarray(jax.vmap(bank.total)(jnp.asarray(lams)))

    router = CECRouter(state.graph(), lam_total=sc.lam_total)
    schedule = {at: evs for at, evs in event_schedule(sc) if evs}
    utilities = []
    for t in range(sc.horizon):
        for ev in schedule.get(t, ()):
            state = router.apply_scenario_event(state, ev)
        utilities.append(router.control_step(measured)["utility"])
    u = np.asarray(utilities)
    (t0,) = sc.event_times                    # the rewire boundary
    pre = u[t0 - 5:t0].mean()
    post = u[t0:]
    recovered = post >= 0.95 * pre
    assert recovered.any() and int(np.argmax(recovered)) <= 30
    assert post[-1] >= 0.95 * pre             # and it holds at the end


def test_router_migrates_to_learned_and_drift_demotes():
    """grad_policy="auto": the router samples until the fitter's holdout
    clears, migrates to learned gradients (1 measured admission per
    interval instead of 2W+1), and demotes itself when the measured
    environment moves from under the surrogate (DESIGN.md §16.4)."""
    from repro.core import make_bank

    g = build_random_cec(connected_er(10, 0.35, seed=2), 3, 20.0, seed=0)
    W = g.n_sessions
    bank = make_bank("log", W, seed=0)
    scale = [1.0]

    def util(lams):
        lams = np.atleast_2d(np.asarray(lams))
        return scale[0] * np.asarray(
            jax.vmap(bank.total)(jnp.asarray(lams)))

    router = CECRouter(g, lam_total=12.0, grad_policy="auto",
                       util_family="log")
    router.fitter.min_samples, router.fitter.refit_every = 20, 8
    router.fitter.fit_steps = 800
    for _ in range(12):
        rec = router.control_step(util)
    assert rec["mode"] == "learned"
    assert rec["oracle_calls"] == 1
    modes = [h["mode"] for h in router.history if "mode" in h]
    assert modes[0] == "sampled"
    assert {h["oracle_calls"] for h in router.history
            if h.get("mode") == "sampled"} == {2 * W + 1}
    # the environment moves hard: measured utilities scale 2.5× — the
    # drift EMA crosses its threshold and the router falls back
    scale[0] = 2.5
    demoted = False
    for _ in range(6):
        rec = router.control_step(util)
        demoted = demoted or rec["mode"] == "sampled"
    assert demoted


def test_router_learned_pinned_policy_stays_learned():
    """grad_policy="learned" is the pinned variant: drift is tracked but
    never demotes."""
    from repro.core import make_bank

    g = build_random_cec(connected_er(10, 0.35, seed=2), 3, 20.0, seed=0)
    bank = make_bank("log", g.n_sessions, seed=0)
    scale = [1.0]

    def util(lams):
        lams = np.atleast_2d(np.asarray(lams))
        return scale[0] * np.asarray(
            jax.vmap(bank.total)(jnp.asarray(lams)))

    router = CECRouter(g, lam_total=12.0, grad_policy="learned",
                       util_family="log")
    router.fitter.min_samples, router.fitter.refit_every = 20, 8
    router.fitter.fit_steps = 800
    for _ in range(10):
        rec = router.control_step(util)
    assert rec["mode"] == "learned"
    scale[0] = 2.5
    for _ in range(4):
        rec = router.control_step(util)
        assert rec["mode"] == "learned"


def test_router_rejects_unknown_grad_policy():
    g = build_random_cec(connected_er(10, 0.35, seed=2), 3, 20.0, seed=0)
    with pytest.raises(ValueError, match="grad_policy"):
        CECRouter(g, lam_total=12.0, grad_policy="leraned")


@pytest.mark.parametrize("event", ["NodeFail", "DemandShift"])
@pytest.mark.parametrize("layout", ["dense", "edges"])
def test_router_in_the_loop(layout, event):
    """A scenario drives the live router on either graph layout, against a
    synthetic measured utility: the event fires at its interval, Λ stays
    on its box-simplex every interval, and utility climbs again after the
    event to at least 95% of its level before it."""
    import contextlib

    from repro.core import (CECGraphSparse, DemandShift, NodeFail, Scenario,
                            dispatch, event_schedule, initial_state,
                            make_bank)

    at = 8
    ev = {"NodeFail": NodeFail(at=at, count=1, seed=4),
          "DemandShift": DemandShift(at=at, lam_total=18.0)}[event]
    sc = Scenario("fleet", horizon=24, topo_kwargs={"n": 12, "p": 0.35},
                  mean_capacity=20.0, lam_total=12.0, events=(ev,))
    state = initial_state(sc, seed=0)
    bank = make_bank("log", 3, seed=0)

    def measured(lams):                       # batched measured utility
        lams = jnp.atleast_2d(jnp.asarray(lams))
        return np.asarray(jax.vmap(bank.total)(lams))

    layout_ctx = (dispatch.sparse_dispatch(1) if layout == "edges"
                  else contextlib.nullcontext())
    with layout_ctx:
        router = CECRouter(state.graph(), lam_total=sc.lam_total)
        schedule = {t: evs for t, evs in event_schedule(sc) if evs}
        fired, utilities = [], []
        for t in range(sc.horizon):
            for e in schedule.get(t, ()):
                state = router.apply_scenario_event(state, e)
                fired.append((t, e.kind))
            assert isinstance(router.graph, CECGraphSparse) == \
                (layout == "edges")
            rec = router.control_step(measured)
            lam, total = np.asarray(rec["lam"]), router.lam_total
            np.testing.assert_allclose(lam.sum(), total, rtol=1e-5)
            assert (lam >= router.delta - 1e-4).all()
            assert (lam <= total - router.delta + 1e-4).all()
            utilities.append(rec["utility"])
    assert fired == [(at, event)]
    assert router.history[at] == {"event": event, "at": at}
    assert router.lam_total == state.lam_total
    u = np.asarray(utilities)
    pre, post = u[at - 3:at].mean(), u[at:]
    assert post[-1] > post[0]                 # re-converges after it
    assert post[-1] >= 0.95 * pre             # and ends near the pre level
