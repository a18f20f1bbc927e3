"""Non-stationary scenario engine (DESIGN.md §10).

The paper's core claim is *online* operation: the controller tracks the
optimum under bandit feedback while the system changes underneath it.
This module makes that a first-class workload.  A :class:`Scenario` is a
declarative event timeline over a fixed node-index space — link rewiring
(device mobility), node failures/joins, capacity drift, utility-bank
swaps, demand shifts — and :func:`run_scenario` advances OMAD / GS-OMA
across the induced segments with library-grade warm-starting:

* φ re-targets through :func:`core.routing.warm_start_phi` (exploration
  mix — multiplicative OMD can never revive a zeroed edge on its own);
* Λ rescales onto the new total demand and re-projects into the box.

Every segment solves **batched over seeds** on the PR-1
``CECGraphBatch`` path; all segments are padded to one global
(``n_phys``, ``depth_max``) so consecutive segments share a single
compiled XLA program per distinct segment length (graphs differ only in
leaf *values*).  Node churn keeps indices stable by construction: a dead
node is an isolated, never-deployed index — exactly the pad-node
convention of ``core/batch.pad_graph`` — via ``build_augmented``'s
``alive`` mask, so iterates never need remapping.

:func:`scenario_metrics` reports dynamic regret and per-event recovery
times; :func:`segment_optima` computes the genie (true-gradient) per-
segment optima when an absolute comparator is wanted.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.topo import make_topology
from repro.topo.churn import rewire_links

from . import costs as _costs
from . import dispatch
from .batch import CECGraphBatch, pad_graph, stack_banks
from .graph import CECGraph, InfeasibleTopology, build_augmented, draw_instance
from .routing import warm_start_phi
from .solver import Method, SolverConfig, SolverState, project_box_simplex
from .utility import UtilityBank, make_bank

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Event:
    """Base timeline event; fires *before* outer iteration ``at``."""

    at: int
    # True when the event changes the augmented graph (masks/capacities):
    # only those boundaries re-mix φ with exploration mass.  DemandShift
    # and BankSwap leave the feasible set untouched, so the routing
    # iterate carries over as-is — the same policy the serving router
    # applies (``CECRouter.apply_scenario_event``).
    changes_graph = True

    @property
    def kind(self) -> str:
        return type(self).__name__


@dataclasses.dataclass(frozen=True)
class Rewire(Event):
    """Move a share of physical links to new endpoints (device mobility)."""

    frac: float = 0.3
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class NodeFail(Event):
    """Fail ``count`` random nodes, keeping every version deployed and
    every session admissible (draws are retried until feasible)."""

    count: int = 1
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class NodeJoin(Event):
    """Revive ``count`` failed nodes (all of them when ``count`` is None)."""

    count: int | None = None
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class CapacityScale(Event):
    """Multiply link / compute capacities (interference, thermal drift)."""

    link: float = 1.0
    compute: float = 1.0


@dataclasses.dataclass(frozen=True)
class BankSwap(Event):
    """Swap the (hidden) utility bank — the tasks themselves change."""

    bank_kind: str = "sqrt"
    seed: int = 0
    changes_graph = False


@dataclasses.dataclass(frozen=True)
class DemandShift(Event):
    """Change the total admitted demand λ (flash crowd / lull)."""

    lam_total: float = 60.0
    changes_graph = False


# ---------------------------------------------------------------------------
# scenario + per-seed mutable state
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Scenario:
    """Declarative non-stationary workload: initial draw + event timeline."""

    name: str
    horizon: int
    events: tuple[Event, ...] = ()
    topology: str = "connected_er"
    topo_kwargs: dict = dataclasses.field(default_factory=dict)
    n_sessions: int = 3
    mean_capacity: float | None = None        # None → topology default
    bank_kind: str = "log"
    lam_total: float = 60.0

    def __post_init__(self):
        object.__setattr__(self, "events",
                           tuple(sorted(self.events, key=lambda e: e.at)))
        for e in self.events:
            if not 0 < e.at < self.horizon:
                raise ValueError(f"event {e} outside (0, {self.horizon})")

    @property
    def event_times(self) -> tuple[int, ...]:
        return tuple(sorted({e.at for e in self.events}))


@dataclasses.dataclass
class ScenarioState:
    """Per-seed numpy instance state the events mutate between segments."""

    adj: np.ndarray           # [N, N] bool physical adjacency
    alive: np.ndarray         # [N] bool
    deploy: np.ndarray        # [W, N] bool (dead nodes keep their row —
                              #   masked at build, restored on rejoin)
    link_capacity: np.ndarray   # [N, N]
    compute_capacity: np.ndarray  # [N]
    bank: UtilityBank
    lam_total: float
    seed: int

    def graph(self) -> CECGraph:
        return build_augmented(self.adj, self.deploy, self.link_capacity,
                               self.compute_capacity, alive=self.alive)


def initial_state(scenario: Scenario, seed: int) -> ScenarioState:
    kw = dict(scenario.topo_kwargs)
    if scenario.topology == "connected_er":
        kw.setdefault("seed", 1 + seed)
    adj, cbar = make_topology(scenario.topology, **kw)
    mean_cap = scenario.mean_capacity or cbar
    _, deploy, link_cap, comp_cap = draw_instance(
        adj, scenario.n_sessions, mean_cap, seed)
    bank = make_bank(scenario.bank_kind, scenario.n_sessions, seed=seed,
                     lam_total=scenario.lam_total)
    return ScenarioState(adj=adj, alive=np.ones(adj.shape[0], bool),
                         deploy=deploy, link_capacity=link_cap,
                         compute_capacity=comp_cap, bank=bank,
                         lam_total=scenario.lam_total, seed=seed)


def _event_rng(event_seed: int, state_seed: int, attempt: int = 0):
    return np.random.default_rng(
        1_000_003 * event_seed + 7919 * attempt + state_seed)


def _fail_nodes(state: ScenarioState, ev: NodeFail,
                max_tries: int = 200) -> np.ndarray:
    alive_idx = np.nonzero(state.alive)[0]
    if ev.count >= len(alive_idx):
        raise InfeasibleTopology("cannot fail every alive node")
    for t in range(max_tries):
        rng = _event_rng(ev.seed, state.seed, t)
        down = rng.choice(alive_idx, size=ev.count, replace=False)
        alive = state.alive.copy()
        alive[down] = False
        if not (state.deploy[:, alive].sum(1) > 0).all():
            continue                       # a version lost its last replica
        try:
            build_augmented(state.adj, state.deploy, state.link_capacity,
                            state.compute_capacity, alive=alive)
        except InfeasibleTopology:
            continue                       # some session lost admission
        return alive
    raise InfeasibleTopology(
        f"no feasible {ev.count}-node failure found for seed {state.seed}")


def apply_event(state: ScenarioState, ev: Event) -> ScenarioState:
    """Pure event transition: returns the post-event state (numpy copies)."""
    s = dataclasses.replace(state)
    if isinstance(ev, Rewire):
        # rewire the alive-induced subgraph; links among dead nodes persist
        idx = np.nonzero(state.alive)[0]
        sub = rewire_links(state.adj[np.ix_(idx, idx)], ev.frac,
                           seed=1_000_003 * ev.seed + state.seed)
        adj = state.adj.copy()
        adj[np.ix_(idx, idx)] = sub
        s.adj = adj
    elif isinstance(ev, NodeFail):
        s.alive = _fail_nodes(state, ev)
    elif isinstance(ev, NodeJoin):
        dead = np.nonzero(~state.alive)[0]
        k = len(dead) if ev.count is None else min(ev.count, len(dead))
        if k:
            rng = _event_rng(ev.seed, state.seed)
            up = rng.choice(dead, size=k, replace=False)
            alive = state.alive.copy()
            alive[up] = True
            s.alive = alive
    elif isinstance(ev, CapacityScale):
        s.link_capacity = state.link_capacity * ev.link
        s.compute_capacity = state.compute_capacity * ev.compute
    elif isinstance(ev, BankSwap):
        s.bank = make_bank(ev.bank_kind, state.deploy.shape[0],
                           seed=1_000_003 * ev.seed + state.seed,
                           lam_total=state.lam_total)
    elif isinstance(ev, DemandShift):
        s.lam_total = float(ev.lam_total)
    else:
        raise TypeError(f"unknown event {ev!r}")
    return s


# ---------------------------------------------------------------------------
# segment compilation
# ---------------------------------------------------------------------------

def event_schedule(scenario: Scenario
                   ) -> tuple[tuple[int, tuple[Event, ...]], ...]:
    """(segment_start, events firing there) pairs covering the horizon.

    The first entry is ``(0, ())`` — the initial segment.  This is the one
    definition of "when does what fire": :func:`compile_segments` consumes
    it for the offline batched sweeps, and a serving loop replays the same
    schedule against the live router (``CECRouter.apply_scenario_event``),
    so what is benchmarked is what serves (DESIGN.md §11).
    """
    bounds = (0,) + scenario.event_times + (scenario.horizon,)
    return tuple(
        (start, tuple(e for e in scenario.events if e.at == start))
        for start in bounds[:-1])


class Segment(NamedTuple):
    start: int                  # first outer iteration of the segment
    n_iters: int
    events: tuple[Event, ...]   # events applied at `start` (empty for first)
    batch: CECGraphBatch        # [B] instances, globally padded
    banks: UtilityBank          # stacked [B, W]
    lam_total: float


def compile_segments(scenario: Scenario,
                     seeds: Sequence[int]) -> tuple[Segment, ...]:
    """Evolve per-seed states through the timeline and batch each segment.

    Every graph is padded to the global (``n_phys``, ``depth_max``) over
    all segments and seeds, so all ``CECGraphBatch``es share static
    metadata — segments of equal length reuse one compiled solver.
    """
    states = [initial_state(scenario, s) for s in seeds]
    sched = event_schedule(scenario)
    ends = tuple(start for start, _ in sched[1:]) + (scenario.horizon,)

    raw: list[tuple[int, int, tuple[Event, ...], list[CECGraph],
                    list[UtilityBank], float]] = []
    for (start, evs), end in zip(sched, ends):
        for e in evs:                      # () for the first segment
            states = [apply_event(st, e) for st in states]
        lam_totals = {st.lam_total for st in states}
        assert len(lam_totals) == 1       # events are seed-uniform in λ
        raw.append((start, end - start, evs,
                    [st.graph() for st in states],
                    [st.bank for st in states], lam_totals.pop()))

    n_phys = max(g.n_phys for _, _, _, gs, _, _ in raw for g in gs)
    depth = max(g.depth_max for _, _, _, gs, _, _ in raw for g in gs)
    return tuple(
        Segment(start=start, n_iters=n, events=evs,
                batch=CECGraphBatch.from_graphs(
                    [pad_graph(g, n_phys, depth) for g in graphs]),
                banks=stack_banks(banks), lam_total=lam_total)
        for start, n, evs, graphs, banks, lam_total in raw)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

class ScenarioResult(NamedTuple):
    utility_traj: Array         # [B, horizon]
    lam_traj: Array             # [B, horizon, W]
    lam: Array                  # [B, W] final allocation
    phi: Array                  # [B, W, Nb, Nb] final routing
    segments: tuple[Segment, ...]


@functools.lru_cache(maxsize=None)
def _segment_solver(config: SolverConfig, cost_name: str, outer_iters: int,
                    mesh=None, _dispatch_key=None):
    """One jitted batched segment solve, cached on its static knobs.

    ``lam_total`` is a traced scalar argument (not a closure constant) so
    demand shifts reuse the same executable; the carried iterates enter
    and leave as a stacked ``SolverState`` (``None`` for the cold first
    segment).  ``mesh`` switches the segment onto the sharded fleet
    driver (``run_batch_sharded``) — a ``jax.sharding.Mesh`` is hashable,
    so it participates in the cache key, and ``_dispatch_key`` (pass
    ``dispatch.state_key()``) keeps entries from aliasing across kernel/
    sparse/fleet dispatch overrides active at trace time.
    """
    from .batch import run_batch, run_batch_sharded

    def fn(batch, banks, lam_total, state):
        if mesh is not None:
            return run_batch_sharded(batch, banks, lam_total, config,
                                     iters=outer_iters, cost=cost_name,
                                     mesh=mesh, state=state)
        return run_batch(batch, banks, lam_total, config,
                         iters=outer_iters, cost=cost_name, state=state)

    return jax.jit(fn)


def run_scenario(
    scenario: Scenario,
    *,
    seeds: Sequence[int] = (0,),
    method: Method = "single",
    cost_name: str = "exp",
    delta: float = 0.5,
    eta_outer: float = 0.05,
    eta_inner: float = 3.0,
    inner_iters: int = 1,
    explore: float = 0.1,
    config: SolverConfig | None = None,
    mesh=None,
) -> ScenarioResult:
    """Advance the online solver through the scenario's segments.

    The solver core's :class:`SolverState` is threaded across segment
    boundaries (warm-started at each event: φ through
    ``routing.warm_start_phi``, Λ rescaled and re-projected), so what
    crosses an event is exactly what the engine would carry — no raw
    ``(lam, phi)`` tuple plumbing.  Pass ``config`` (a ``SolverConfig``)
    to use the first-class API; the individual keyword knobs are the
    legacy surface and are ignored when ``config`` is given.  Returns
    stacked trajectories over the full horizon, which is what the
    dynamic-regret / recovery metrics (:func:`scenario_metrics`)
    measure.  An event-free scenario is exactly one batched
    ``solve_jowr`` (the static engine) — asserted to machine precision
    in the tests.

    ``mesh`` (a 1-D fleet mesh, see ``core.mesh.fleet_mesh``) runs
    every segment on the sharded fleet driver
    (:func:`core.batch.run_batch_sharded`): the seed axis is partitioned
    across the mesh, warm-starts included — large seed ensembles scale
    across devices without touching the timeline logic.  Parity with the
    unsharded driver is part of the sharding test tier (DESIGN.md §14).
    """
    if config is None:
        config = SolverConfig(method=method, delta=float(delta),
                              eta_outer=float(eta_outer),
                              eta_inner=float(eta_inner),
                              inner_iters=int(inner_iters))
    from repro.obs import trace as _obs_trace

    segments = compile_segments(scenario, seeds)
    state: SolverState | None = None
    u_trajs, lam_trajs = [], []
    for k, seg in enumerate(segments):
        if k > 0:
            prev = segments[k - 1]
            for e in seg.events:
                _obs_trace.instant(f"event:{e.kind}", cat="scenario",
                                   args={"kind": e.kind, "segment": k,
                                         "at": seg.start})
            if any(e.changes_graph for e in seg.events):
                state = state._replace(phi=warm_start_phi(
                    state.phi, seg.batch.out_mask, explore))
            if seg.lam_total != prev.lam_total:
                lam = state.lam * (seg.lam_total / prev.lam_total)
                lam = project_box_simplex(lam, seg.lam_total, config.delta)
                state = state._replace(lam=lam)
        solve = _segment_solver(config, cost_name, seg.n_iters, mesh,
                                dispatch.state_key())
        with _obs_trace.span("scenario.segment", cat="scenario",
                             args={"segment": k, "start": seg.start,
                                   "iters": seg.n_iters,
                                   "lam_total": float(seg.lam_total)}):
            res = solve(seg.batch, seg.banks, jnp.float32(seg.lam_total),
                        state)
            if _obs_trace.current_tracer() is not None:
                # make the span cover the solve, not just the dispatch
                res.utility_traj.block_until_ready()
        state = res.state
        u_trajs.append(res.utility_traj)
        lam_trajs.append(res.lam_traj)
    return ScenarioResult(
        utility_traj=jnp.concatenate(u_trajs, axis=1),
        lam_traj=jnp.concatenate(lam_trajs, axis=1),
        lam=state.lam, phi=state.phi, segments=segments)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

class EventReport(NamedTuple):
    at: int
    kinds: tuple[str, ...]
    u_pre: float                # ensemble-mean utility just before the event
    u_drop: float               # ensemble-mean utility at the event iteration
    u_final: float              # ensemble-mean utility at segment end
    recovery_iters: float       # mean iterations to recovery (recovered seeds)
    recovered_frac: float       # share of seeds recovered within the segment


def scenario_metrics(
    result: ScenarioResult,
    *,
    recovery_frac: float = 0.95,
    pre_window: int = 5,
    opt_utilities: np.ndarray | None = None,
) -> dict:
    """Dynamic regret + per-event recovery from a scenario trajectory.

    Recovery for an event at t: first iteration τ ≥ t with
    U_τ ≥ ``recovery_frac`` · mean(U over the ``pre_window`` iterations
    before t), per seed, within the post-event segment.

    Dynamic regret is Σ_t (U*_seg(t) − U_t), averaged over seeds, against
    the per-segment comparator: ``opt_utilities`` ([n_segments] or
    [B, n_segments] genie optima from :func:`segment_optima`) when given,
    else the segment's own best observed utility (a lower bound on the
    true comparator — useful for trend tracking, not absolute claims).
    """
    traj = np.asarray(result.utility_traj)          # [B, T]
    B, T = traj.shape
    segs = result.segments

    if opt_utilities is None:
        comp = np.stack([traj[:, s.start:s.start + s.n_iters].max(-1)
                         for s in segs], axis=1)    # [B, n_segments]
    else:
        comp = np.asarray(opt_utilities, np.float64)
        if comp.ndim == 1:
            comp = np.broadcast_to(comp, (B, len(segs)))
    regret = 0.0
    for j, s in enumerate(segs):
        seg_traj = traj[:, s.start:s.start + s.n_iters]
        regret += (comp[:, j:j + 1] - seg_traj).sum(-1)
    dynamic_regret = float(np.mean(regret))

    reports = []
    for j, s in enumerate(segs):
        if not s.events:
            continue
        t0 = s.start
        pre = traj[:, max(0, t0 - pre_window):t0].mean(-1)      # [B]
        seg_traj = traj[:, t0:t0 + s.n_iters]
        thresh = recovery_frac * pre
        hit = seg_traj >= thresh[:, None]
        rec_iters = np.where(hit.any(-1), hit.argmax(-1), -1)    # [B]
        ok = rec_iters >= 0
        reports.append(EventReport(
            at=t0,
            kinds=tuple(e.kind for e in s.events),
            u_pre=float(pre.mean()),
            u_drop=float(seg_traj[:, 0].mean()),
            u_final=float(seg_traj[:, -1].mean()),
            recovery_iters=float(rec_iters[ok].mean()) if ok.any() else float("inf"),
            recovered_frac=float(ok.mean()),
        ))
    return {"dynamic_regret": dynamic_regret,
            "comparator": "genie" if opt_utilities is not None else "self-max",
            "horizon": T, "n_seeds": B,
            "events": reports}


def segment_optima(scenario: Scenario, seeds: Sequence[int], *,
                   cost_name: str = "exp", outer_iters: int = 150,
                   inner_iters: int = 60, eta: float = 0.05,
                   eta_inner: float = 3.0) -> np.ndarray:
    """[B, n_segments] genie (true-gradient) optimum U* per segment.

    The absolute dynamic-regret comparator: what a controller that *knew*
    the utilities could reach in each segment.  Python-loop expensive —
    meant for benchmarks and offline analysis, not the hot path.
    """
    from .opt_baseline import exact_gradient_allocation

    cost = _costs.get(cost_name)
    segments = compile_segments(scenario, seeds)
    out = np.zeros((len(seeds), len(segments)))
    for j, seg in enumerate(segments):
        for b in range(len(seeds)):
            bank = UtilityBank(a=seg.banks.a[b], b=seg.banks.b[b],
                               kind=seg.banks.kind, noise=seg.banks.noise)
            _, _, u = exact_gradient_allocation(
                seg.batch.instance(b), cost, bank, seg.lam_total,
                eta=eta, outer_iters=outer_iters, inner_iters=inner_iters,
                eta_inner=eta_inner)
            out[b, j] = u
    return out


# ---------------------------------------------------------------------------
# named catalog — the benchmark suite and any "imagine a scenario" consumer
# ---------------------------------------------------------------------------

def named_scenarios(horizon: int = 100, *, n: int = 25, p: float = 0.2,
                    n_sessions: int = 3, lam_total: float = 60.0) -> dict:
    """The standard suite over Connected-ER(n, p) (benchmarks/tests)."""
    base = dict(horizon=horizon, topology="connected_er",
                topo_kwargs={"n": n, "p": p}, n_sessions=n_sessions,
                mean_capacity=10.0, bank_kind="log", lam_total=lam_total)
    h = horizon
    scenarios = [
        Scenario("steady", **base),
        Scenario("link_churn", events=(Rewire(at=h // 2, frac=0.3, seed=5),),
                 **base),
        Scenario("node_failure",
                 events=(NodeFail(at=2 * h // 5, count=3, seed=11),
                         NodeJoin(at=4 * h // 5)), **base),
        Scenario("capacity_drift",
                 events=(CapacityScale(at=h // 4, link=0.6, compute=0.8),
                         CapacityScale(at=3 * h // 4, link=1.5,
                                       compute=1.25)), **base),
        # +25% keeps the surge inside network capacity (a 1.5× surge on the
        # paper instance saturates links into the linearized-exp regime)
        Scenario("demand_surge",
                 events=(DemandShift(at=h // 2, lam_total=1.25 * lam_total),),
                 **base),
        Scenario("utility_swap",
                 events=(BankSwap(at=h // 2, bank_kind="sqrt", seed=3),),
                 **base),
        Scenario("flash_crowd",
                 events=(NodeFail(at=h // 2, count=2, seed=17),
                         DemandShift(at=h // 2, lam_total=1.25 * lam_total)),
                 **base),
    ]
    return {s.name: s for s in scenarios}
