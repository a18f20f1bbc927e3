"""CEC router: the paper's control plane driving live serving decisions.

The router is a thin stateful holder over the solver core (DESIGN.md
§13): a :class:`~repro.core.problem.Problem` (graph + cost + demand, no
bank — utilities are *measured*), a :class:`~repro.core.solver.
SolverConfig` (``solver.serving_defaults()`` unless overridden), and a
device-resident :class:`~repro.core.solver.SolverState` (Λ, φ, t).
Every control interval is one jitted fused call —
``core.solver.fused_step``, the exact ``step`` the offline solvers scan
— covering all 2W perturbed observations, the mirror-ascent/projection
update, and the committed observation, with no per-session Python loop
and no solver math of its own.  Each interval it:

 1. admits the 2W perturbed allocations Λ ± δ·e_w and collects their
    *measured* task utilities through the utility callback (batched in one
    call where the utility source allows it — see :func:`_call_utility`);
 2. advances OMAD (Alg. 3) one outer iteration on device, the network-cost
    half of every observation priced at the routing iterate the oracle
    reached for that admission;
 3. exposes the new admission split Λ/λ (which version serves what share
    of traffic) and per-replica dispatch weights t_i(w)/λ_w (how much of
    version w's traffic each deploying device processes).

Node churn (device joins/leaves) rebuilds the graph and *warm-starts* φ
with an exploration mix (``core.routing.warm_start_phi``) — the Fig. 11
online-adaptation behaviour.  The router also consumes the scenario
engine's event stream directly (``apply_scenario_event``, DESIGN.md §10):
the same declarative events that drive offline scenario sweeps drive the
live control plane, and because the scenario engine keeps the node-index
space stable (dead node == isolated index), same-shape churn never
retraces the fused step.  Fleet-scale graphs flip to the edge-list
representation through the same ``Problem.canonical`` policy every other
entry point uses, and demand shifts only swap the traced
``Problem.lam_total`` leaf — never a retrace.

The fused step runs through ``core.flow`` / ``core.routing`` and therefore
inherits the size-based kernel dispatch (core/dispatch.py): a fleet whose
augmented graph clears the threshold serves its flow-propagation and
mirror-descent steps from the Pallas kernels on TPU backends (off-TPU the
kernels engage only under an explicit override, in interpret mode), the
dispatch state being part of the jit-cache key (DESIGN.md §11).

The router is the *single-tenant* control plane.  K tenants multiplexed
on one device are ``serve.fleet.RouterFleet`` (DESIGN.md §15) — the same
``step`` vmapped over stacked ``Problem`` pytrees with double-buffered
state and donated buffers; every semantic here (perturbation order,
``_call_utility`` contract, demand rescale, event consumption) is the
per-tenant slice of the fleet's, and ``tests/test_fleet.py`` holds the
two to ≤1e-5 parity.
"""
from __future__ import annotations

import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from repro.core import CECGraph, CECGraphSparse, SparsePhi, propagate
from repro.core import solver as _solver
from repro.core.problem import Problem, resolve_cost
from repro.core.routing import warm_start_phi
from repro.core.scenario import (DemandShift, Event, ScenarioState,
                                 apply_event)
from repro.core.solver import SolverConfig, SolverState, project_box_simplex
from repro.core.utility import OnlineFitter
from repro.obs import trace as _obs_trace

GRAD_POLICIES = ("sampled", "learned", "auto")


def _call_utility(utility_fn, lams: np.ndarray) -> np.ndarray:
    """Evaluate the measured-utility callback over a [K, W] admission stack.

    Contract (DESIGN.md §11): ``utility_fn(lams: [K, W]) -> [K]`` measured
    task utilities.  A legacy scalar callable ``fn(lam: [W]) -> float`` is
    detected (wrong output shape, or the batched call raising a shape-type
    error) and evaluated row by row — correct either way, just 2W calls
    instead of 1.  Other exception types propagate: a conforming batched
    callback failing for a real reason must not be silently retried.
    """
    lams = np.asarray(lams)
    try:
        out = np.asarray(utility_fn(lams), np.float32).reshape(-1)
        if out.shape == (lams.shape[0],):
            return out
    except (TypeError, ValueError, IndexError):
        pass
    return np.asarray([float(utility_fn(row)) for row in lams], np.float32)


@dataclasses.dataclass
class CECRouter:
    """Live control plane = ``Problem`` + ``SolverConfig`` + ``SolverState``.

    Construct with a graph and either a ``config`` (the first-class API)
    or the legacy keyword knobs, which default to
    ``solver.serving_defaults()`` — single-loop OMAD with the hot
    η_inner=3.0 oracle (see that preset's docstring for why serving
    diverges from ``paper_defaults()``).

    ``grad_policy`` picks how the outer gradient is obtained
    (DESIGN.md §16.4):

    * ``"sampled"`` (default) — every interval admits the 2W perturbed
      allocations and two-point-estimates the gradient from measured
      utilities.  Exactly the pre-§16 router.
    * ``"learned"`` — the measured (Λ, û) pairs feed an
      :class:`~repro.core.utility.OnlineFitter`; once the held-out error
      clears its threshold the router *migrates live* to
      ``grad_mode="learned"`` — one committed measurement per interval
      and an analytic gradient of the fitted surrogate through the
      implicit routing layer.  Pinned: once earned it stays learned
      (drift is tracked but does not demote).
    * ``"auto"`` — like ``"learned"``, but :meth:`OnlineFitter.drifted`
      demotes the router back to sampling until a refit re-clears the
      threshold — the safe default for non-stationary environments
      (bank swaps, goodput shifts).

    The per-interval record gains ``mode`` (which gradient ran) and
    ``oracle_calls`` (measured admissions this interval: 2W+1 sampled,
    1 learned — the quantity ``benchmarks/bench_learned.py`` tracks).
    """

    graph: CECGraph | CECGraphSparse
    lam_total: float
    delta: float = 0.5
    eta_outer: float = 0.05
    eta_inner: float = 3.0
    inner_iters: int = 1
    cost_name: str = "exp"
    config: SolverConfig | None = None
    grad_policy: str = "sampled"
    util_family: str | None = None
    telemetry: int = 0

    def __post_init__(self):
        if self.grad_policy not in GRAD_POLICIES:
            raise ValueError(f"grad_policy must be one of {GRAD_POLICIES}; "
                             f"got {self.grad_policy!r}")
        if self.config is None:
            # the legacy knobs, expressed as a config: K=1 is OMAD
            method = "single" if self.inner_iters == 1 else "nested"
            self.config = _solver.serving_defaults().replace(
                method=method, delta=float(self.delta),
                eta_outer=float(self.eta_outer),
                eta_inner=float(self.eta_inner),
                inner_iters=int(self.inner_iters),
                telemetry=int(self.telemetry))
        else:
            # keep the legacy attribute reads truthful
            if self.telemetry and self.config.telemetry != self.telemetry:
                # the router-level knob wins: sizing the ring at the
                # router is the ergonomic path (the config is often a
                # shared preset)
                self.config = self.config.replace(
                    telemetry=int(self.telemetry))
            self.delta = self.config.delta
            self.eta_outer = self.config.eta_outer
            self.eta_inner = self.config.eta_inner
            self.inner_iters = self.config.oracle_iters
        self.telemetry = self.config.telemetry
        # one Problem: representation policy + demand as a traced leaf
        # (Problem.canonical is the same conversion every entry point uses;
        # strong-float32 demand so the fused step never retraces on it)
        self.problem = Problem(
            graph=self.graph, bank=None,
            lam_total=jnp.float32(self.lam_total),
            cost=resolve_cost(self.cost_name)).canonical().validate()
        self.graph = self.problem.graph
        self.state: SolverState = _solver.init(self.problem, self.config)
        if self.telemetry > 0:
            from repro.obs import telemetry as _obs_tel

            self.tel = _obs_tel.init_ring(self.telemetry,
                                          self.graph.n_sessions)
        else:
            self.tel = None
        self.history: list[dict] = []
        self.fitter: OnlineFitter | None = None
        self._migrated = False
        if self.grad_policy != "sampled":
            if self.util_family is None:
                self.util_family = "log"
            self.fitter = OnlineFitter(self.util_family,
                                       self.graph.n_sessions)

    def _grad_mode_now(self) -> str:
        """Which gradient this interval runs (the migration decision)."""
        if self.grad_policy == "learned" and self._migrated:
            return "learned"      # pinned: the switch is one-way
        if self.fitter is None or not self.fitter.ready:
            return "sampled"
        if self.grad_policy == "auto" and self.fitter.drifted():
            return "sampled"
        return "learned"

    # -- the solver state, exposed under its historical names ---------------
    @property
    def lam(self):
        """[W] current admission allocation Λ (device-resident)."""
        return self.state.lam

    @property
    def phi(self):
        """Current routing iterate (dense tensor or ``SparsePhi``)."""
        return self.state.phi

    def control_step(self, utility_fn) -> dict:
        """One OMAD outer iteration, fused on device.

        ``utility_fn`` reports the *measured* task utility for admitted
        allocations (the engine serves the split and reports
        quality-weighted goodput).  In sampled mode it is called once
        with the [2W, W] stack of perturbed admissions and once with the
        committed allocation (see :func:`_call_utility` for the
        batched/scalar contract); in learned mode (``grad_policy`` with
        a :attr:`fitter` that is :attr:`~repro.core.utility.OnlineFitter.
        ready`) only the committed call happens — the gradient is
        analytic through the fitted surrogate and the implicit routing
        layer (DESIGN.md §16.4).  Everything else — oracle invocations,
        gradient, mirror ascent, exact projection, committed observation
        — is a single jitted ``solver.fused_step`` call; the
        ``SolverState`` never leaves the device.  Under an installed
        tracer the interval is one ``router.interval`` span over the
        ``control.*`` phases it shares with ``RouterFleet``, and its four
        device-to-host reads (three learned) go through
        ``obs.trace.to_host`` (DESIGN.md §18.3).
        """
        mode = self._grad_mode_now()
        W = self.graph.n_sessions
        phase, to_host = _obs_trace.phase, _obs_trace.to_host
        with _obs_trace.span("router.interval", cat="interval",
                             args={"t": len(self.history), "mode": mode}):
            t0 = time.perf_counter()
            if mode == "learned":
                self._migrated = True
                with phase("control.dispatch"):
                    prob = self.problem.with_utilities(self.util_family,
                                                       self.fitter.params)
                    cfg = self.config.replace(grad_mode="learned")
                    fused = _solver.fused_step(cfg)
                    zeros = jnp.zeros((2 * W,), jnp.float32)
                    if self.tel is None:
                        self.state, info = fused(prob, self.state, zeros)
                    else:
                        self.state, info, self.tel = fused(
                            prob, self.state, zeros, self.tel)
                oracle_calls = 1
            else:
                with phase("control.perturb"):
                    pert = _solver.perturbed_allocations_host(
                        to_host(self.state.lam), self.config.delta)
                with phase("control.measure"):
                    task_u = _call_utility(utility_fn, pert)
                with phase("control.dispatch"):
                    fused = _solver.fused_step(self.config)
                    if self.tel is None:
                        self.state, info = fused(self.problem, self.state,
                                                 jnp.asarray(task_u))
                    else:
                        self.state, info, self.tel = fused(
                            self.problem, self.state, jnp.asarray(task_u),
                            self.tel)
                if self.fitter is not None:
                    with phase("control.fit"):
                        self.fitter.add(pert, task_u)
                oracle_calls = 2 * W + 1
            # the step's fresh Λ on the host: the first read that waits for
            # the step, so the clock stops once the solver's result exists
            lam = to_host(self.state.lam)
            solver_us = (time.perf_counter() - t0) * 1e6
            with phase("control.measure"):
                u_task = float(_call_utility(utility_fn, lam[None])[0])
            if self.fitter is not None:
                with phase("control.fit"):
                    self.fitter.observe_live(lam, u_task)
                    self.fitter.maybe_fit()
            with phase("control.record"):
                cost = float(to_host(info.cost))
                rec = {"lam": lam,
                       "cost": cost,
                       "utility": u_task - cost,
                       "grad": to_host(info.grad),
                       "mode": mode,
                       "oracle_calls": oracle_calls}
                if self.tel is not None:
                    # patch the row the jitted step NaN-seeded: the
                    # measured net utility and the host time from the
                    # interval's start to the step's result on the host
                    from repro.obs import telemetry as _obs_tel

                    self.tel = _obs_tel.annotate_donated(
                        self.tel, utility=jnp.float32(rec["utility"]),
                        wall_clock_us=jnp.float32(solver_us))
                self.history.append(rec)
        return rec

    def verdicts(self, comparator=None) -> dict:
        """Run the paper-invariant monitors on the live iterates (and the
        telemetry ring when one is enabled): flow conservation, capacity
        slack, Theorem-3 KKT gap, plus the ring's monotone-descent and
        budget-feasibility checks — ``repro.obs.monitors.check_state``
        with default thresholds (DESIGN.md §18.2).  Host-blocking in the
        sense that the caller will read the verdict arrays; the monitors
        themselves are pure jnp."""
        from repro.obs import monitors as _monitors

        return _monitors.check_state(self.problem, self.state, self.tel,
                                     comparator=comparator)

    # -- dispatch interfaces used by the engine ------------------------------
    def admission_split(self) -> np.ndarray:
        """P(version w) for an incoming request."""
        lam = np.asarray(self.state.lam)
        return lam / lam.sum()

    def replica_weights(self) -> np.ndarray:
        """[W, n_phys] share of version-w traffic each deployed replica
        processes = t_i(w)/λ_w at the nodes deploying w."""
        t = np.asarray(propagate(self.graph, self.state.phi, self.state.lam))
        dep = np.asarray(self.graph.deploy)
        shares = t[:, : self.graph.n_phys] * dep
        tot = shares.sum(-1, keepdims=True)
        return shares / np.where(tot > 0, tot, 1.0)

    # -- fault tolerance: node churn -----------------------------------------
    def on_topology_change(self, new_graph: CECGraph | CECGraphSparse,
                           explore: float = 0.1):
        """Re-target the running iterates onto a new graph (node fail/join).

        φ restarts from an exploration mix so edges that multiplicative
        updates had zeroed can be rediscovered (DESIGN.md §5, §10).  The
        new graph goes through the same representation policy as the
        constructor (``Problem.canonical``).  On the sparse path the
        running ``SparsePhi`` is first re-expressed on the new slot
        layout by **edge identity** (``core.sparse.remap_phi`` — churn
        can repack CSR slots even at unchanged widths, so positional
        reuse would scramble edges), then warm-started part-wise through
        the same ``warm_start_phi`` row math as the dense tensor."""
        old_graph, phi = self.graph, self.state.phi
        self.problem = dataclasses.replace(
            self.problem, graph=new_graph).canonical().validate()
        new_graph = self.graph = self.problem.graph
        if isinstance(new_graph, CECGraphSparse):
            if (isinstance(phi, SparsePhi)
                    and isinstance(old_graph, CECGraphSparse)
                    and old_graph.n_bar == new_graph.n_bar):
                from repro.core.sparse import remap_phi

                phi = remap_phi(old_graph, new_graph, phi)
                phi = SparsePhi(
                    rows=warm_start_phi(phi.rows, new_graph.out_mask,
                                        explore),
                    src=warm_start_phi(phi.src, new_graph.src_out_mask,
                                       explore))
            else:
                phi = new_graph.uniform_phi()
        elif (not isinstance(phi, SparsePhi)
                and phi.shape == new_graph.out_mask.shape):
            phi = warm_start_phi(phi, new_graph.out_mask, explore)
        else:
            phi = new_graph.uniform_phi()
        self.state = self.state._replace(phi=phi)

    def on_demand_change(self, lam_total: float):
        """Re-scale the admission split onto a new total demand λ.

        Only the ``Problem.lam_total`` leaf changes — the fused step's
        compiled executable is reused as-is.
        """
        lam = self.state.lam * (lam_total / self.lam_total)
        self.lam_total = float(lam_total)
        self.problem = self.problem.with_demand(jnp.float32(lam_total))
        self.state = self.state._replace(
            lam=project_box_simplex(lam, self.lam_total, self.config.delta))

    def apply_scenario_event(self, state: ScenarioState,
                             event: Event, explore: float = 0.1
                             ) -> ScenarioState:
        """Consume one scenario-engine event against the live iterates.

        ``state`` is the fleet's physical description (the same
        ``core.scenario.ScenarioState`` the offline sweeps evolve); the
        event is applied there, the augmented graph rebuilt, and the
        running ``SolverState`` warm-started exactly as ``run_scenario``
        does.  Returns the post-event state — thread it into the next
        call.  Bank swaps change only the *measured* utility (the
        environment), so the router's iterates carry over untouched."""
        _obs_trace.instant(f"event:{event.kind}", cat="scenario",
                           args={"kind": event.kind,
                                 "at": len(self.history)})
        new_state = apply_event(state, event)
        if isinstance(event, DemandShift):
            self.on_demand_change(new_state.lam_total)
        elif event.changes_graph:
            self.on_topology_change(new_state.graph(), explore=explore)
        self.history.append({"event": event.kind, "at": len(self.history)})
        return new_state
