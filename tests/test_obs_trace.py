"""The control-plane tracer (DESIGN.md §18.3): spans on the profiler's
clock, per-phase aggregates instead of per-interval events, the
``host_syncs`` counter, traces counted under the phase that made them,
and the ``wall_clock_us`` telemetry column that stops once the step's
result is on the host."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import build_random_cec
from repro.obs import trace as obs_trace
from repro.obs.export import export_ring, write_chrome_trace
from repro.serve import CECRouter, RouterFleet
from repro.topo import connected_er

W = 2


def _graph(seed):
    return build_random_cec(connected_er(10, 0.4, seed=seed), W, 10.0,
                            seed=seed)


def _utility(lams):
    """Σ_w a_w·log(1 + b_w·λ_w) of a [..., W] stack, on the host."""
    lams = np.asarray(lams, np.float64)
    return (np.array([20.0, 30.0]) * np.log1p(0.3 * lams)).sum(-1)


def _entry(kind, policy="sampled", telemetry=0):
    if kind == "fleet":
        return RouterFleet([_graph(0), _graph(1)], [60.0, 60.0],
                           grad_policy=policy, telemetry=telemetry)
    return CECRouter(_graph(0), lam_total=60.0, grad_policy=policy,
                     telemetry=telemetry)


@pytest.fixture
def tracer():
    tr = obs_trace.install_tracer()
    yield tr
    obs_trace.uninstall_tracer()


def test_no_tracer_never_touches_the_profiler(monkeypatch):
    """Without a tracer a span is the one shared null context and
    ``to_host`` a plain copy: a control step never reaches jax.profiler."""
    class Refused:
        def __init__(self, *a, **k):
            raise AssertionError("jax.profiler touched with no tracer")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Refused)
    assert obs_trace.current_tracer() is None
    assert obs_trace.span("a") is obs_trace.phase("control.sync")
    with obs_trace.span("fleet.interval", args={"t": 0}):
        with obs_trace.phase("control.perturb"):
            x = obs_trace.to_host(jnp.arange(3.0))
    x[0] = 7.0                      # a fresh, writable host copy
    np.testing.assert_array_equal(x, [7.0, 1.0, 2.0])
    assert obs_trace.to_host(jnp.ones(2), np.float64).dtype == np.float64
    for kind in ("fleet", "router"):
        entry = _entry(kind)
        for _ in range(2):
            entry.control_step(_utility)
        assert len(entry.history) == 2


# phase counts and device-to-host reads per sampled interval, as the
# control steps' code makes them (PERF.md §3, DESIGN.md §18.3)
PER_INTERVAL = {
    ("fleet", "sampled"): ({"control.perturb": 1, "control.measure": 2,
                            "control.dispatch": 1, "control.sync": 5,
                            "control.publish": 1, "control.record": 1}, 5),
    ("fleet", "auto"): ({"control.perturb": 1, "control.measure": 2,
                         "control.dispatch": 1, "control.sync": 6,
                         "control.fit": 2, "control.publish": 1,
                         "control.record": 1}, 6),
    ("router", "sampled"): ({"control.perturb": 1, "control.measure": 2,
                             "control.dispatch": 1, "control.sync": 4,
                             "control.record": 1}, 4),
    ("router", "auto"): ({"control.perturb": 1, "control.measure": 2,
                          "control.dispatch": 1, "control.sync": 4,
                          "control.fit": 2, "control.record": 1}, 4),
}


@pytest.mark.parametrize("kind,policy", sorted(PER_INTERVAL))
def test_phase_counts_and_host_syncs_per_interval(tracer, kind, policy):
    counts, syncs = PER_INTERVAL[kind, policy]
    jax.clear_caches()              # the first interval compiles the step
    entry = _entry(kind, policy)
    before = tracer.snapshot(restart_longest=True)
    entry.control_step(_utility)
    warm = tracer.snapshot()
    for _ in range(2):
        entry.control_step(_utility)
    assert [r["mode"] for r in entry.history] == ["sampled"] * 3
    got = obs_trace.delta(before, tracer.snapshot())
    # once the first interval has compiled, the sweep is built on the
    # host from one read of Λ: nothing traces under control.perturb
    steady = obs_trace.delta(warm, tracer.snapshot())
    assert steady["phases"]["control.perturb"]["count"] == 2
    assert steady["phases"]["control.perturb"]["traces"] == 0
    assert got["host_syncs"] == 3 * syncs
    assert {n: p["count"] for n, p in got["phases"].items()} == \
        {n: 3 * c for n, c in counts.items()}
    assert set(got["phases"]) <= set(obs_trace.PHASES)
    for p in got["phases"].values():
        assert 0.0 <= p["self_seconds"] <= p["seconds"]
        assert 0.0 < p["longest_s"] <= p["seconds"]
    assert got["phases"]["control.dispatch"]["traces"] >= 1
    assert got["traces"] >= sum(p["traces"]
                                for p in got["phases"].values())
    # a sync nests in the phase that reads: the perturbation's own read
    # is part of its time, not of its self time
    perturb = got["phases"]["control.perturb"]
    assert perturb["self_seconds"] < perturb["seconds"]


def test_traces_count_under_the_innermost_phase(tracer):
    """A fresh jitted function traced and compiled inside a phase counts
    under that phase; outside any phase only in the totals; after
    ``uninstall_tracer`` the listener is gone."""
    x = jnp.ones(3)
    before = tracer.snapshot()
    with obs_trace.span("fleet.interval", args={"t": 0}):
        with obs_trace.phase("control.dispatch"):
            jax.jit(lambda x: x * 3.0 + 1.0)(x).block_until_ready()
        jax.jit(lambda x: x * 5.0 - 2.0)(x).block_until_ready()
    snap = tracer.snapshot()
    dispatch = snap["phases"]["control.dispatch"]
    assert dispatch["traces"] >= 1 and dispatch["compiles"] >= 1
    # the second function's trace and compile count in the totals only
    assert snap["traces"] - before["traces"] == 2 * dispatch["traces"]
    assert snap["compiles"] - before["compiles"] == 2 * dispatch["compiles"]
    assert obs_trace.uninstall_tracer() is tracer
    jax.jit(lambda x: x * 7.0 + 4.0)(x).block_until_ready()
    assert tracer.counters == {k: snap[k] for k in tracer.counters}
    obs_trace.install_tracer(tracer)     # the fixture uninstalls it again


def test_spans_enter_the_profiler_annotations(monkeypatch):
    """Interval spans enter a StepTraceAnnotation numbered by the
    interval; every phase of that interval a TraceAnnotation with the
    same ``t``."""
    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            seen.append((type(self).__name__, name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    class Step(Annotation):
        pass

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Step)
    router = _entry("router")
    obs_trace.install_tracer()
    try:
        for _ in range(2):
            router.control_step(_utility)
    finally:
        obs_trace.uninstall_tracer()
    steps = [(name, kw) for cls, name, kw in seen if cls == "Step"]
    assert steps == [("router.interval", {"step_num": 0}),
                     ("router.interval", {"step_num": 1})]
    phases = [(name, kw["t"]) for cls, name, kw in seen
              if cls == "Annotation"]
    assert {name for name, _ in phases} == {
        "control.perturb", "control.measure", "control.dispatch",
        "control.sync", "control.record"}
    assert [t for _, t in phases] == sorted(t for _, t in phases)
    assert {t for _, t in phases} == {0, 1}


def test_chrome_export_has_intervals_and_no_phase_events(tmp_path, tracer):
    router = _entry("router")
    for _ in range(3):
        router.control_step(_utility)
    doc = json.loads(write_chrome_trace(tmp_path / "t.json").read_text())
    names = [ev["name"] for ev in doc["traceEvents"]]
    assert names.count("router.interval") == 3
    assert not [n for n in names if n.startswith("control.")]
    other = doc["otherData"]
    assert other["host_syncs"] == 3 * 4
    assert other["phases"]["control.sync"]["count"] == 3 * 4


def test_snapshot_delta_and_window_longest(tracer):
    with obs_trace.phase("control.fit"):
        pass
    before = tracer.snapshot(restart_longest=True)
    assert tracer.phases["control.fit"]["longest_s"] == 0.0
    assert before["phases"]["control.fit"]["longest_s"] > 0.0
    for _ in range(2):
        with obs_trace.phase("control.fit"):
            obs_trace.to_host(jnp.ones(2))
    got = obs_trace.delta(before, tracer.snapshot())
    fit, sync = got["phases"]["control.fit"], got["phases"]["control.sync"]
    assert fit["count"] == 2 and sync["count"] == 2
    assert got["host_syncs"] == 2
    assert fit["self_seconds"] == pytest.approx(
        fit["seconds"] - sync["seconds"])
    assert fit["longest_s"] <= fit["seconds"]


@pytest.mark.parametrize("kind", ["fleet", "router"])
def test_wall_clock_us_holds_the_step(monkeypatch, tracer, kind):
    """``wall_clock_us`` runs from the interval's start to the step's new
    Λ on the host: at least the perturbation, the dispatch and the first
    read after it (the one that waits for the step), at most the
    interval."""
    log = []
    exit_ = obs_trace._Span.__exit__

    def logged_exit(span, *exc):
        if span.cat != obs_trace.PHASE:
            exit_(span, *exc)
            log.append((span.name, span.tracer.events[-1]["dur"] * 1e-6))
            return
        p = span.tracer.phases.get(span.name, {"seconds": 0.0})["seconds"]
        exit_(span, *exc)
        log.append((span.name, span.tracer.phases[span.name]["seconds"] - p))

    monkeypatch.setattr(obs_trace._Span, "__exit__", logged_exit)
    entry = _entry(kind, telemetry=4)
    log.clear()
    for _ in range(3):
        entry.control_step(_utility)
    wall = np.asarray(export_ring(entry.tel)["wall_clock_us"])
    wall = wall[0] if kind == "fleet" else wall     # lanes share it
    intervals, cur = [], []
    for name, dur in log:
        if name.endswith(".interval"):
            intervals.append((cur, dur))
            cur = []
        else:
            cur.append((name, dur))
    assert len(intervals) == 3
    for (phases, total), w in zip(intervals, wall):
        names = [n for n, _ in phases]
        after = names.index("control.dispatch")
        first_sync = names.index("control.sync", after)
        need = (dict(phases)["control.perturb"] + phases[after][1]
                + phases[first_sync][1])
        assert need * 1e6 <= w <= total * 1e6


@pytest.mark.parametrize("layout", ["dense", "edges"])
def test_marginal_split_instant(tracer, layout):
    """An edge-list router's step, when it compiles, emits beside its
    ``solver.dispatch:*`` instant the out-slots one marginal-recursion
    step gathers: on a Barabási–Albert fleet with hubs fewer than the full
    width.  A dense graph has no slots and emits none."""
    from repro.core import build_augmented, build_augmented_sparse
    from repro.core.graph import draw_instance
    from repro.topo import power_law

    inst = draw_instance(power_law(300, 2), 3, 10.0, 0)
    build = build_augmented_sparse if layout == "edges" else build_augmented
    graph = build(power_law(300, 2), inst.deploy, inst.link_capacity,
                  inst.compute_capacity)
    router = CECRouter(graph, lam_total=60.0)
    router.control_step(
        lambda lams: (25.0 * np.log1p(0.3 * np.asarray(lams))).sum(-1))
    names = [e["name"] for e in tracer.events]
    assert "solver.dispatch:sampled" in names
    split = [e["args"] for e in tracer.events
             if e["name"] == "solver.marginal_split"]
    if layout == "dense":
        assert split == []
        return
    (args,) = split
    assert args == {
        "marginal_slots": graph.n_bar * graph.d_light
        + graph.n_heavy * graph.d_max,
        "slots": graph.n_bar * graph.d_max,
        "d_light": graph.d_light, "n_heavy": graph.n_heavy}
    assert 0 < args["n_heavy"] and args["d_light"] < graph.d_max
    assert args["marginal_slots"] < args["slots"]
