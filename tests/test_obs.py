"""Observability subsystem contracts (ISSUE 10, DESIGN.md §18).

Four tiers, mirroring the subsystem's layering:

* **ring semantics** — wrap-around, chronological ``order``, annotate-
  latest, NaN seeding (``repro.obs.telemetry`` in isolation);
* **zero-cost recording** — ``fused_step`` / ``fused_step_batch`` with
  telemetry enabled donate the ring alongside the state (steady state
  allocates nothing) and never retrace across ≥10 intervals;
* **monitor fidelity** — no false trips on event-free runs of every
  named scenario, the regret monitor's accounting agrees with the
  ``segment_optima`` genie ≤1e-6, verdicts are bit-identical between
  the fleet vmap and per-lane evaluation (this module also runs in the
  CI ``sharded-multidevice`` job under 8 forced CPU devices), and the
  golden Fig. 7 trajectory never trips the descent monitor;
* **export formats** — Chrome trace-event JSON and metrics JSONL are
  valid and carry the spans/records the wiring promises.
"""
import dataclasses
import json
import pathlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Problem, SolverConfig, resolve_cost
from repro.core import solver as _solver
from repro.core.batch import fused_step_batch
from repro.core.graph import build_random_cec
from repro.core.scenario import (initial_state, named_scenarios,
                                 run_scenario, scenario_metrics,
                                 segment_optima)
from repro.core.utility import make_bank
from repro.obs import telemetry as obs_tel
from repro.obs import trace as obs_trace
from repro.obs.export import (export_ring, metrics_rows, write_chrome_trace,
                              write_metrics_jsonl)
from repro.obs.monitors import (check_state, dynamic_regret,
                                monotone_descent)
from repro.serve import CECRouter, RouterFleet
from repro.topo import connected_er

GOLDEN = pathlib.Path(__file__).parent / "golden" / "fig7_gs_oma_traj.npz"

CONFIG = SolverConfig(method="single", delta=0.5, eta_outer=0.05,
                      eta_inner=3.0, inner_iters=1)


def _instance(seed=0, n=10, p=0.4, n_sessions=2, lam_total=60.0):
    graph = build_random_cec(connected_er(n, p, seed=seed), n_sessions,
                             10.0, seed=seed)
    bank = make_bank("log", n_sessions, seed=seed, lam_total=lam_total)
    problem = Problem(graph=graph, bank=bank,
                      lam_total=jnp.float32(lam_total),
                      cost=resolve_cost("exp")).canonical().validate()
    return problem, bank


def _donation_supported():
    x = jnp.ones(4)
    jax.jit(lambda v: v + 1.0, donate_argnums=0)(x)
    return x.is_deleted()


def _ring_from_utilities(u, capacity=None):
    """A Telemetry carrying just a utility trajectory (monitor-input
    fixture — dtype follows ``u`` so x64 tests keep f64 accounting)."""
    u = jnp.asarray(u)
    c = int(u.shape[0]) if capacity is None else int(capacity)
    tel = obs_tel.init_ring(c, 1)
    zeros = jnp.zeros((c,), u.dtype)
    return dataclasses.replace(
        tel, utility=u.astype(u.dtype), cost=zeros, grad_norm=zeros,
        proj_residual=zeros, wall_clock_us=zeros,
        head=jnp.int32(u.shape[0]), count=jnp.int32(u.shape[0]))


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

class _St(NamedTuple):
    lam: jnp.ndarray


class _In(NamedTuple):
    grad: jnp.ndarray
    cost: jnp.ndarray


def _record_n(tel, n, w=2):
    for i in range(n):
        st = _St(lam=jnp.full((w,), 1.0 + i))
        info = _In(grad=jnp.ones((w,)), cost=jnp.float32(10.0 + i))
        tel = obs_tel.record(tel, st, info, lam_total=2.0 * (1.0 + i),
                             delta=0.0, oracle_calls=5)
        tel = obs_tel.annotate(tel, utility=jnp.float32(100.0 + i))
    return tel


def test_ring_wraparound_and_order():
    tel = obs_tel.init_ring(4, 2)
    tel = _record_n(tel, 6)
    assert int(tel.head) == 6
    assert int(tel.count) == 4            # saturated at capacity
    idx, valid = obs_tel.order(tel)
    assert bool(valid.all())
    cols = export_ring(tel)
    # oldest surviving row is interval 2; newest is interval 5
    np.testing.assert_allclose(cols["utility"], [102.0, 103, 104, 105])
    np.testing.assert_allclose(cols["cost"], [12.0, 13, 14, 15])
    np.testing.assert_allclose(cols["lam"][:, 0], [3.0, 4, 5, 6])
    assert (cols["oracle_calls"] == 5).all()
    # the exact-projection residual of a feasible Λ is ~0
    assert cols["proj_residual"].max() < 1e-5
    # wall-clock was never annotated: NaN survives to export
    assert np.isnan(cols["wall_clock_us"]).all()


def test_partial_ring_masks_unwritten_slots():
    tel = _record_n(obs_tel.init_ring(8, 2), 3)
    assert int(tel.count) == 3
    _, valid = obs_tel.order(tel)
    assert int(np.asarray(valid).sum()) == 3
    cols = export_ring(tel)
    assert cols["utility"].shape == (3,)
    np.testing.assert_allclose(cols["utility"], [100.0, 101, 102])


def test_annotate_patches_only_latest_row():
    tel = _record_n(obs_tel.init_ring(4, 2), 2)
    tel = obs_tel.annotate(tel, wall_clock_us=jnp.float32(42.0))
    cols = export_ring(tel)
    assert np.isnan(cols["wall_clock_us"][0])
    assert cols["wall_clock_us"][1] == 42.0


# ---------------------------------------------------------------------------
# zero-cost recording: donation + no retrace
# ---------------------------------------------------------------------------

def test_fused_step_telemetry_donates_and_never_retraces():
    """≥10 intervals through the telemetry-enabled fused step: the ring
    and state are donated every interval (steady state allocates
    nothing) and the executable never retraces (ISSUE 10 acceptance)."""
    problem, bank = _instance(seed=1)
    config = CONFIG.replace(telemetry=5)
    fn = _solver.fused_step(config, donate=True)
    state = _solver.init(problem, config)
    tel = obs_tel.init_ring(config.telemetry, problem.graph.n_sessions)
    check_donation = _donation_supported()
    sizes = []
    for t in range(12):
        task_u = jax.vmap(bank.total)(
            _solver.perturbed_allocations(state.lam, config.delta))
        old_state, old_tel = state, tel
        state, info, tel = fn(problem, state, task_u, tel)
        if check_donation:
            assert old_state.lam.is_deleted(), t
            if t > 0:          # the initial ring may alias init constants
                assert old_tel.utility.is_deleted(), t
        if hasattr(fn, "_cache_size"):
            sizes.append(fn._cache_size())
    if sizes:
        assert sizes == [sizes[0]] * len(sizes), "fused step retraced"
    assert int(tel.head) == 12
    assert int(tel.count) == 5
    cols = export_ring(tel)
    assert np.isfinite(cols["cost"]).all()
    assert (cols["oracle_calls"]
            == 2 * problem.graph.n_sessions + 1).all()


def test_fused_step_batch_telemetry_donates_and_never_retraces():
    """The fleet analogue: a [K]-stacked ring donated through ≥10
    ``fused_step_batch`` intervals with a stable jit cache."""
    graphs = [build_random_cec(connected_er(10, 0.4, seed=s), 2, 10.0,
                               seed=s) for s in range(2)]
    fleet = RouterFleet(graphs, [60.0, 55.0], telemetry=6)
    assert fleet.config.telemetry == 6
    fns = [(lambda lams, b=make_bank("log", 2, seed=s):
            np.asarray(jax.vmap(b.total)(jnp.asarray(lams))))
           for s in range(2)]
    step = fused_step_batch(fleet.config, cost=fleet.cost_name,
                            donate=fleet.donate)
    check_donation = _donation_supported() and fleet.donate
    sizes = []
    for t in range(11):
        old_tel = fleet.tel
        fleet.control_step(fns)
        if check_donation:
            assert old_tel.utility.is_deleted(), t
        if hasattr(step, "_cache_size"):
            sizes.append(step._cache_size())
    if sizes:
        assert sizes == [sizes[0]] * len(sizes), "fleet step retraced"
    assert [int(h) for h in np.asarray(fleet.tel.head)] == [11, 11]
    # the published view carries per-lane verdicts and survives the
    # donated steps (double-buffer discipline extends to §18 outputs)
    assert fleet.view.verdicts is not None
    v = fleet.view.verdicts["kkt_gap"]
    assert np.asarray(v.value).shape == (2,)
    cols = export_ring(fleet.tel)
    assert cols["utility"].shape[0] == 2
    assert np.isfinite(cols["utility"]).all()


# ---------------------------------------------------------------------------
# monitor fidelity
# ---------------------------------------------------------------------------

def test_monitors_no_false_positives_on_event_free_scenarios():
    """Event-free runs of every named scenario, default thresholds:
    nothing trips, and the exact projection keeps budget feasibility
    below even its warn level (the ISSUE's no-false-positive bar)."""
    scenarios = named_scenarios(horizon=18, n=10, p=0.4)
    for i, (sname, sc) in enumerate(sorted(scenarios.items())):
        sc = dataclasses.replace(sc, events=())
        st = initial_state(sc, seed=i)
        problem = Problem(graph=st.graph(), bank=st.bank,
                          lam_total=jnp.float32(st.lam_total),
                          cost=resolve_cost("exp")).canonical().validate()
        config = CONFIG.replace(telemetry=sc.horizon)
        res = _solver.run(problem, config, iters=sc.horizon)
        verdicts = check_state(problem, res.state, res.telemetry)
        for mname, v in verdicts.items():
            assert not bool(np.asarray(v.trip).any()), \
                f"{mname} tripped on event-free {sname}: {float(v.value)}"
        assert not bool(verdicts["budget_feasibility"].warn), sname


def test_regret_monitor_agrees_with_genie_accounting():
    """``dynamic_regret`` on a per-interval genie comparator reproduces
    ``scenario_metrics``'s Σ_seg Σ_t (U*_seg − U_t) to ≤1e-6 (f64)."""
    sc = named_scenarios(horizon=16, n=10, p=0.4)["demand_surge"]
    res = run_scenario(sc, seeds=(0,), config=CONFIG)
    genie = segment_optima(sc, (0,), outer_iters=60, inner_iters=40)
    expected = scenario_metrics(res, opt_utilities=genie)["dynamic_regret"]
    traj = np.asarray(res.utility_traj[0], np.float64)
    comp = np.zeros_like(traj)
    for j, seg in enumerate(res.segments):
        comp[seg.start:seg.start + seg.n_iters] = genie[0, j]
    with jax.enable_x64(True):
        tel = _ring_from_utilities(jnp.asarray(traj, jnp.float64))
        got = float(dynamic_regret(tel, jnp.asarray(comp)).value)
    assert abs(got - expected) <= 1e-6 * max(1.0, abs(expected))


def test_fleet_verdicts_bitwise_match_per_lane():
    """Lane k of the vmapped ``fleet_verdicts`` equals the scalar
    monitors on tenant k alone — bit-identical, on 1 device and on the
    CI job's 8 forced CPU devices alike."""
    graphs = [build_random_cec(connected_er(10, 0.4, seed=s), 2, 10.0,
                               seed=s) for s in range(3)]
    lam_totals = [60.0, 45.0, 75.0]
    fleet = RouterFleet(graphs, lam_totals, telemetry=4)
    fns = [(lambda lams, b=make_bank("log", 2, seed=s):
            np.asarray(jax.vmap(b.total)(jnp.asarray(lams))))
           for s in range(3)]
    for _ in range(3):
        fleet.control_step(fns)
    stacked = fleet.view.verdicts
    graph = fleet.batch.stacked_graph()
    lane = lambda tree, k: jax.tree_util.tree_map(lambda x: x[k], tree)
    for k in range(3):
        problem = Problem(graph=lane(graph, k), bank=None,
                          lam_total=jnp.float32(lam_totals[k]),
                          cost=resolve_cost(fleet.cost_name))
        solo = check_state(problem, lane(fleet.state, k),
                           lane(fleet.tel, k))
        assert set(solo) == set(stacked)
        for mname, v in solo.items():
            sv = stacked[mname]
            np.testing.assert_array_equal(
                np.asarray(sv.value)[k], np.asarray(v.value),
                err_msg=f"{mname} lane {k} value drifted under vmap")
            assert bool(np.asarray(sv.warn)[k]) == bool(v.warn), mname
            assert bool(np.asarray(sv.trip)[k]) == bool(v.trip), mname


def test_state_monitors_cover_sparse_representation():
    """The flow/capacity monitors evaluate the sparse graph through the
    same recursion the sparse engine runs — no dense fallback, verdicts
    stay healthy on a converged sparse solve."""
    from repro.core.graph import sparsify

    graph = sparsify(build_random_cec(connected_er(12, 0.35, seed=4), 2,
                                      10.0, seed=4))
    bank = make_bank("log", 2, seed=4)
    problem = Problem(graph=graph, bank=bank, lam_total=jnp.float32(60.0),
                      cost=resolve_cost("exp")).canonical().validate()
    res = _solver.run(problem, CONFIG.replace(telemetry=8), iters=12)
    verdicts = check_state(problem, res.state, res.telemetry)
    for mname, v in verdicts.items():
        assert not bool(np.asarray(v.trip).any()), mname


def test_write_chrome_trace_requires_a_tracer(tmp_path):
    assert obs_trace.current_tracer() is None
    with pytest.raises(ValueError, match="install_tracer"):
        write_chrome_trace(tmp_path / "t.json")


def test_golden_trajectory_never_trips_descent_monitor():
    """The committed Fig. 7 gs_oma trajectory ascends monotonically —
    the Theorem-4 descent monitor stays strictly below its warn level
    (ISSUE 10 acceptance pin on the golden fixture)."""
    ref = np.load(GOLDEN)
    tel = _ring_from_utilities(
        jnp.asarray(ref["utility_traj"], jnp.float32))
    v = monotone_descent(tel)
    assert float(v.value) <= 0.0          # no one-interval drop at all
    assert not bool(v.warn) and not bool(v.trip)
    # regret against the trajectory's own best is non-negative and the
    # final-interval term is 0 — the accounting is anchored correctly
    best = float(ref["utility_traj"].max())
    r = dynamic_regret(tel, jnp.float32(best))
    assert float(r.value) >= -1e-4


# ---------------------------------------------------------------------------
# export formats: Chrome trace + metrics JSONL
# ---------------------------------------------------------------------------

def _router_with_history(capacity=4, steps=3, seed=2):
    graph = build_random_cec(connected_er(10, 0.4, seed=seed), 2, 10.0,
                             seed=seed)
    bank = make_bank("log", 2, seed=seed)
    router = CECRouter(graph, lam_total=60.0, telemetry=capacity)
    fn = lambda lams: np.asarray(jax.vmap(bank.total)(jnp.asarray(lams)))
    for _ in range(steps):
        router.control_step(fn)
    return router


def test_chrome_trace_export_is_valid(tmp_path):
    tracer = obs_trace.Tracer()
    obs_trace.install_tracer(tracer)
    try:
        router = _router_with_history()
        sc = named_scenarios(horizon=8, n=10, p=0.4)["link_churn"]
        run_scenario(sc, seeds=(0,), config=CONFIG)
        path = write_chrome_trace(tmp_path / "trace.json")
    finally:
        obs_trace.uninstall_tracer()
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert events and doc["displayTimeUnit"] == "ms"
    for ev in events:
        assert set(ev) >= {"name", "cat", "ph", "ts", "pid", "tid"}
        assert ev["ph"] in ("X", "i")
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    names = [ev["name"] for ev in events]
    assert names.count("router.interval") == 3          # one span per step
    assert any(n.startswith("solver.dispatch:") for n in names)
    assert "scenario.segment" in names                  # run_scenario spans
    assert any(n.startswith("event:") for n in names)   # churn instants
    # timestamps are monotone within the sort the writer promises
    ts = [ev["ts"] for ev in events]
    assert ts == sorted(ts)
    del router


def test_metrics_jsonl_export_is_valid(tmp_path):
    router = _router_with_history(capacity=4, steps=5)
    verdicts = router.verdicts()
    path = write_metrics_jsonl(tmp_path / "metrics.jsonl", router.tel,
                               verdicts=verdicts, name="router")
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(rows) == 5                 # 4 interval rows + verdict row
    for i, row in enumerate(rows[:-1]):
        assert row["name"] == "router"
        assert row["t"] == 1 + i          # ring kept the last 4 of 5
        assert isinstance(row["utility"], float)
        assert isinstance(row["lam"], list) and len(row["lam"]) == 2
        assert row["oracle_calls"] == 5   # 2W+1 sampled admissions
        assert row["wall_clock_us"] > 0.0
    tail = rows[-1]
    assert tail["name"] == "router.verdicts"
    for mname in ("flow_conservation", "capacity_slack", "kkt_gap",
                  "monotone_descent", "budget_feasibility"):
        assert set(tail[mname]) == {"value", "warn", "trip"}
        assert tail[mname]["trip"] is False


def test_metrics_rows_rejects_fleet_stacked_ring():
    graphs = [build_random_cec(connected_er(8, 0.5, seed=s), 2, 10.0,
                               seed=s) for s in range(2)]
    fleet = RouterFleet(graphs, [60.0, 60.0], telemetry=3)
    with pytest.raises(ValueError, match="lane"):
        metrics_rows(fleet.tel)


def test_solver_run_threads_telemetry_through_scan():
    """``Result.telemetry`` holds the scan's ring: count saturates at
    capacity and the annotated utilities are exactly the trajectory
    tail (written device-side inside the same scan iteration)."""
    problem, _ = _instance(seed=3)
    res = _solver.run(problem, CONFIG.replace(telemetry=6), iters=10)
    tel = res.telemetry
    assert tel is not None and int(tel.count) == 6 and int(tel.head) == 10
    cols = export_ring(tel)
    np.testing.assert_array_equal(cols["utility"],
                                  np.asarray(res.utility_traj[-6:]))
    np.testing.assert_array_equal(cols["lam"],
                                  np.asarray(res.lam_traj[-6:]))
    # telemetry off → no ring on the result, and no ring work in the scan
    assert _solver.run(problem, CONFIG, iters=3).telemetry is None


def test_trajectory_reader_tolerates_old_schemas(tmp_path):
    """Schema-3 rows carry ``dirty``/``jax_version`` first-class; the
    reader back-fills both on historical rows instead of KeyError-ing
    (satellite: old-row tolerance rides the schema bump)."""
    from benchmarks.run import TRAJECTORY_SCHEMA, read_trajectory

    assert TRAJECTORY_SCHEMA >= 3
    (tmp_path / "BENCH_old1.json").write_text(json.dumps(
        {"schema": 1, "commit": "old1", "date": "2026-01-01T00:00:00+00:00",
         "smoke": True, "jax": "0.4.30", "benches": {"fig7": {}}}))
    (tmp_path / "BENCH_new1.json").write_text(json.dumps(
        {"schema": 3, "commit": "new1", "date": "2026-02-01T00:00:00+00:00",
         "smoke": True, "dirty": False, "jax": "0.4.37",
         "jax_version": "0.4.37", "benches": {}}))
    old, new = read_trajectory(tmp_path)
    assert old["commit"] == "old1" and new["commit"] == "new1"
    assert old["jax_version"] == "0.4.30"     # back-filled from legacy key
    assert old["dirty"] is True               # conservative default
    assert new["jax_version"] == "0.4.37" and new["dirty"] is False
    # the committed trajectory itself must load through the same reader
    real = read_trajectory()
    assert real and all("jax_version" in e and "dirty" in e for e in real)
