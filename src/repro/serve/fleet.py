"""RouterFleet: K tenant control planes multiplexed on one device.

``CECRouter`` holds one (Λ, φ) and drives one tenant.  Production shape
(ROADMAP "million-session serving") is K tenants — K independent
``Problem`` pytrees sharing one ``SolverConfig`` — stacked on a leading
tenant axis and advanced by **one** jitted ``vmap(solver.step)`` call
per control interval (``core.batch.fused_step_batch``).  The solver
core makes this nearly free: the fleet step is the single-tenant step,
vmapped, so every ``CECRouter`` semantic (perturbation order, oracle
pricing, projection, demand rescale) carries over bit-for-bit — the
parity contract ``tests/test_fleet.py`` pins at ≤ 1e-5 per tenant,
churn included (DESIGN.md §15.1).

Two disciplines distinguish the fleet from a loop over routers:

* **Double-buffered state** (DESIGN.md §15.2): the serving plane never
  reads the solver's working iterates.  Each interval publishes a
  :class:`FleetView` — the admission split and replica weights the
  dispatch path reads — and because JAX dispatch is async, the next
  control step's device work overlaps request serving against the
  previously published view.  The view's Λ is a *computed copy*
  (``lam + 0.0``), never an alias of the working buffer, which is what
  makes the second discipline safe:

* **Buffer donation** (DESIGN.md §15.3): the stacked ``SolverState`` is
  donated into the jitted step (``donate_argnums``), so XLA writes
  iteration t+1 into iteration t's buffers and the steady-state control
  loop allocates nothing per interval.  The donated input is dead after
  the call — only the fleet's own reference is ever donated, and the
  published view holds copies.

Measured utilities arrive through one microbatched callback per
interval: a fleet-batched ``fn([K, 2W, W]) -> [K, 2W]`` covering every
tenant's perturbation sweep in one call, or a sequence of K per-tenant
callables (each the ``CECRouter`` batched/scalar contract,
``cec_router._call_utility``).  Traffic traces (``serve/traffic.py``)
drive per-tenant demand between intervals via :meth:`RouterFleet.
set_demand` — only the traced ``lam_total`` leaf changes, never a
retrace (DESIGN.md §15.4).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import CECGraphSparse, propagate
from repro.core import solver as _solver
from repro.core.batch import CECGraphBatch, fused_step_batch, pad_graph
from repro.core.dispatch import state_key as _dispatch_key
from repro.core.graph import CECGraph
from repro.core.routing import warm_start_phi
from repro.core.scenario import (DemandShift, Event, ScenarioState,
                                 apply_event)
from repro.core.solver import SolverConfig, SolverState, project_box_simplex
from repro.core.utility import OnlineFitter
from repro.obs import trace as _obs_trace

from .cec_router import GRAD_POLICIES, _call_utility

__all__ = ["FleetView", "RouterFleet"]


@dataclasses.dataclass(frozen=True)
class FleetView:
    """The published serving-plane snapshot (the double buffer's front).

    Immutable by construction and backed by buffers the control plane
    never donates — valid until the *next* publish, regardless of how
    many donated steps run meanwhile (DESIGN.md §15.2).
    """

    lam: jax.Array       # [K, W] committed admission splits
    weights: jax.Array   # [K, W, n_phys] replica dispatch weights
    verdicts: dict | None = None   # {monitor: Verdict([K] leaves)} — §18.2

    @property
    def n_tenants(self) -> int:
        return self.lam.shape[0]

    def admission_split(self) -> np.ndarray:
        """[K, W] P(version w | tenant k) for incoming requests."""
        lam = np.asarray(self.lam)
        return lam / lam.sum(-1, keepdims=True)

    def replica_weights(self) -> np.ndarray:
        """[K, W, n_phys] share of tenant k / version w traffic per node."""
        return np.asarray(self.weights)


@functools.lru_cache(maxsize=None)
def _publisher(_key, cost_name: str | None = None):
    """Jitted front-buffer builder: (Λ copy, replica weights) per tenant.

    ``state.lam + 0.0`` is a real XLA computation, so the published Λ is
    a fresh buffer — bit-identical in value (Λ ≥ δ > 0, no signed-zero
    corner) but never aliased to the donated working state.  The weights
    math is ``CECRouter.replica_weights`` vmapped over tenants.

    With ``cost_name`` set (a telemetry-enabled fleet) the publish also
    runs the fleet-vmapped paper-invariant monitors
    (``repro.obs.monitors.fleet_verdicts``, DESIGN.md §18.2) in the same
    jitted call — verdicts ride the front buffer as [K]-leaf pytrees, so
    reading them costs no extra dispatch.
    """

    def weights_of(graph, state):
        def one(g, lam, phi):
            t = propagate(g, phi, lam)
            shares = t[:, : g.n_phys] * g.deploy.astype(t.dtype)
            tot = shares.sum(-1, keepdims=True)
            return shares / jnp.where(tot > 0, tot, 1.0)

        return jax.vmap(one)(graph, state.lam, state.phi)

    if cost_name is None:
        def fn(graph, state):
            return state.lam + 0.0, weights_of(graph, state)

        return jax.jit(fn)

    from repro.obs import monitors as _monitors

    def fn(graph, state, lam_totals, tel):
        verdicts = _monitors.fleet_verdicts(graph, lam_totals, state, tel,
                                            cost=cost_name)
        return state.lam + 0.0, weights_of(graph, state), verdicts

    return jax.jit(fn)


class RouterFleet:
    """K live control planes, one vmapped+donated step per interval.

    Construct from K per-tenant graphs (dense ``CECGraph``; tenants of
    different physical size are padded to a common augmented layout by
    ``CECGraphBatch`` — solve-equivalent, DESIGN.md §15.1) and [K]
    provisioned demands.  All tenants share one ``SolverConfig``
    (default ``solver.serving_defaults()``, like ``CECRouter``).

    ``donate=False`` opts out of buffer donation (e.g. to keep old
    states around for debugging); the published view and all results
    are identical either way — donation is purely an allocation
    discipline (DESIGN.md §15.3).
    """

    def __init__(self, graphs: Sequence[CECGraph], lam_totals,
                 *, cost_name: str = "exp",
                 config: SolverConfig | None = None, donate: bool = True,
                 n_phys: int | None = None, depth_max: int | None = None,
                 grad_policy: str = "sampled",
                 util_family: str | None = None, telemetry: int = 0):
        if grad_policy not in GRAD_POLICIES:
            raise ValueError(f"grad_policy must be one of {GRAD_POLICIES}; "
                             f"got {grad_policy!r}")
        graphs = list(graphs)
        if any(isinstance(g, CECGraphSparse) for g in graphs):
            raise NotImplementedError(
                "RouterFleet stacks dense tenants; fleet-scale sparse "
                "tenants go through run_batch / run_batch_sharded")
        if n_phys is not None or depth_max is not None:
            # layout headroom: churn that grows a tenant (rewires can
            # deepen the graph) must fit the fixed stacked layout, so
            # operators provision margin up front — padding is
            # solve-equivalent (core.batch.pad_graph), so headroom costs
            # memory/FLOPs, never accuracy
            graphs = [pad_graph(g,
                                max(n_phys or 0, g.n_phys),
                                max(depth_max or 0, g.depth_max))
                      for g in graphs]
        self.batch = CECGraphBatch.from_graphs(graphs)
        lam_totals = np.asarray(lam_totals, np.float32).reshape(-1)
        if lam_totals.shape != (self.batch.n_instances,):
            raise ValueError(
                f"need one lam_total per tenant: {lam_totals.shape} "
                f"vs {self.batch.n_instances} tenants")
        self.lam_totals = lam_totals
        self.cost_name = cost_name
        self.config = config if config is not None \
            else _solver.serving_defaults()
        if telemetry and self.config.telemetry != telemetry:
            # like CECRouter: the fleet-level ring knob wins over a
            # shared preset config
            self.config = self.config.replace(telemetry=int(telemetry))
        self.donate = bool(donate)
        K, W = self.batch.n_instances, self.batch.n_sessions
        # stacked iterates == vmap of solver.init over tenants
        self.state = SolverState(
            lam=jnp.asarray(np.repeat(lam_totals[:, None] / W, W, axis=1),
                            jnp.float32),
            phi=self.batch.uniform_phi(),
            t=jnp.zeros((K,), jnp.int32))
        if self.config.telemetry > 0:
            from repro.obs import telemetry as _obs_tel

            cap = self.config.telemetry
            # [K]-stacked fresh rings: vmap broadcasts one init over lanes
            self.tel = jax.vmap(
                lambda _: _obs_tel.init_ring(cap, W))(jnp.zeros((K,)))
        else:
            self.tel = None
        self.history: list[dict] = []
        # live sampled→learned migration (DESIGN.md §16.4): one fitter per
        # tenant; the switch is all-or-nothing because the fleet step is one
        # jitted call with a single static grad_mode — a half-migrated fleet
        # would split the batch.
        self.grad_policy = grad_policy
        self.util_family = util_family
        self._migrated = False
        self.fitters: list[OnlineFitter] | None = None
        if grad_policy != "sampled":
            if self.util_family is None:
                self.util_family = "log"
            self.fitters = [OnlineFitter(self.util_family, W, seed=k)
                            for k in range(K)]
        self._publish()

    def _grad_mode_now(self) -> str:
        """Which gradient this interval runs — learned only once *every*
        tenant's fitter is ready (and, under ``"auto"``, none drifted).
        ``"learned"`` is the pinned variant: the switch is one-way."""
        if self.grad_policy == "learned" and self._migrated:
            return "learned"
        if self.fitters is None or not all(f.ready for f in self.fitters):
            return "sampled"
        if self.grad_policy == "auto" \
                and any(f.drifted() for f in self.fitters):
            return "sampled"
        return "learned"

    # -- fleet shape --------------------------------------------------------
    @property
    def n_tenants(self) -> int:
        return self.batch.n_instances

    @property
    def n_sessions(self) -> int:
        return self.batch.n_sessions

    @property
    def view(self) -> FleetView:
        """The current front buffer (serving plane reads go here)."""
        return self._view

    def _publish(self):
        with _obs_trace.phase("control.publish"):
            graph = self.batch.stacked_graph()
            if self.tel is None:
                lam, weights = _publisher(_dispatch_key())(graph,
                                                           self.state)
                self._view = FleetView(lam=lam, weights=weights)
            else:
                lam, weights, verdicts = _publisher(
                    _dispatch_key(), self.cost_name)(
                        graph, self.state, jnp.asarray(self.lam_totals),
                        self.tel)
                self._view = FleetView(lam=lam, weights=weights,
                                       verdicts=verdicts)

    # -- measured utilities -------------------------------------------------
    def _measure(self, utility_fn, lams: np.ndarray) -> np.ndarray:
        """[K, m] utilities for a [K, m, W] admission stack.

        A sequence of K callables is evaluated tenant-wise through the
        ``CECRouter`` batched/scalar contract; a single callable must be
        fleet-batched — ``fn([K, m, W]) -> [K, m]`` — and a wrong output
        shape is an error, not a fallback (a per-tenant scalar function
        silently applied to every tenant would be a correctness bug).
        """
        K, m = lams.shape[0], lams.shape[1]
        if isinstance(utility_fn, (list, tuple)):
            if len(utility_fn) != K:
                raise ValueError(f"need {K} per-tenant callbacks, "
                                 f"got {len(utility_fn)}")
            return np.stack([_call_utility(fn, lams[k])
                             for k, fn in enumerate(utility_fn)])
        out = np.asarray(utility_fn(lams), np.float32)
        if out.shape != (K, m):
            raise TypeError(
                f"fleet-batched utility callback must map [K, m, W] -> "
                f"[K, m]; got {out.shape} for K={K}, m={m} (pass a "
                f"sequence of K callables for per-tenant callbacks)")
        return out

    # -- the control interval -----------------------------------------------
    def control_step(self, utility_fn) -> dict:
        """One OMAD outer iteration for every tenant, fused on device.

        The 2W perturbed admissions per tenant are generated from the
        *published* Λ (bit-identical to the working Λ, but donation-safe
        to read), measured through one microbatched callback, and the
        stacked state advances through the donated
        ``core.batch.fused_step_batch`` — after which the old state
        buffers are dead and a fresh :class:`FleetView` is published.
        Returns a record of [K]-shaped arrays (per-tenant cost, measured
        task utility at the committed Λ, net utility), appended to
        ``history`` — the ``CECRouter.control_step`` record, vectorized.

        Under a non-sampled ``grad_policy`` the sweep's measurements feed
        the per-tenant fitters, and once **every** fitter is ready the
        fleet migrates live to learned gradients — one committed
        measurement per tenant per interval, stacked [K, W, P] surrogate
        params threaded through ``fused_step_batch`` as a data leaf
        (refits never retrace; DESIGN.md §16.4).

        Under an installed tracer the interval is one ``fleet.interval``
        span, its steps are the ``control.*`` phases it shares with
        ``CECRouter``, and every device-to-host read goes through
        ``obs.trace.to_host``: five in a sampled interval without
        fitters (DESIGN.md §18.3).
        """
        mode = self._grad_mode_now()
        K, W = self.n_tenants, self.n_sessions
        phase, to_host = _obs_trace.phase, _obs_trace.to_host
        with _obs_trace.span("fleet.interval", cat="interval",
                             args={"t": len(self.history), "mode": mode,
                                   "tenants": K}):
            t0 = time.perf_counter()
            if mode == "learned":
                self._migrated = True
                with phase("control.dispatch"):
                    params = jnp.stack([f.params for f in self.fitters])
                    step = fused_step_batch(
                        self.config.replace(grad_mode="learned"),
                        cost=self.cost_name, donate=self.donate,
                        util_family=self.util_family)
                    zeros = jnp.zeros((K, 2 * W), jnp.float32)
                    if self.tel is None:
                        self.state, info = step(
                            self.batch.stacked_graph(),
                            jnp.asarray(self.lam_totals), self.state, zeros,
                            params)
                    else:
                        self.state, info, self.tel = step(
                            self.batch.stacked_graph(),
                            jnp.asarray(self.lam_totals), self.state, zeros,
                            self.tel, params)
                oracle_calls = 1
            else:
                with phase("control.perturb"):
                    pert = _solver.perturbed_allocations_host(
                        to_host(self._view.lam), self.config.delta)
                with phase("control.measure"):
                    task_u = self._measure(utility_fn, pert)
                with phase("control.dispatch"):
                    step = fused_step_batch(self.config, cost=self.cost_name,
                                            donate=self.donate)
                    if self.tel is None:
                        self.state, info = step(
                            self.batch.stacked_graph(),
                            jnp.asarray(self.lam_totals),
                            self.state, jnp.asarray(task_u))
                    else:
                        self.state, info, self.tel = step(
                            self.batch.stacked_graph(),
                            jnp.asarray(self.lam_totals),
                            self.state, jnp.asarray(task_u), self.tel)
                if self.fitters is not None:
                    with phase("control.fit"):
                        for k, f in enumerate(self.fitters):
                            f.add(pert[k], task_u[k])
                oracle_calls = 2 * W + 1
            # the step's fresh Λ on the host: the first read that waits for
            # the step, so the clock stops once the solver's result exists
            lam_new = to_host(self.state.lam)
            solver_us = (time.perf_counter() - t0) * 1e6
            # measure at the committed Λ (value-identical to the view
            # published below, which happens after the ring annotation so
            # the verdicts see this interval's U)
            with phase("control.measure"):
                u_task = self._measure(utility_fn,
                                       lam_new[:, None, :])[:, 0]
            if self.fitters is not None:
                lam = to_host(self._view.lam)
                with phase("control.fit"):
                    for k, f in enumerate(self.fitters):
                        f.observe_live(lam[k], float(u_task[k]))
                        f.maybe_fit()
            cost = to_host(info.cost, np.float32)
            if self.tel is not None:
                # per-lane net utility; one fused call serves all K
                # lanes, so they share the measured wall-clock
                from repro.obs import telemetry as _obs_tel

                with phase("control.record"):
                    self.tel = _obs_tel.annotate_donated(
                        self.tel, utility=jnp.asarray(u_task - cost),
                        wall_clock_us=jnp.full((K,), solver_us,
                                               jnp.float32))
            self._publish()
            with phase("control.record"):
                rec = {"lam": to_host(self._view.lam),
                       "cost": cost,
                       "utility": u_task - cost,
                       "grad": to_host(info.grad),
                       "mode": mode,
                       "oracle_calls": oracle_calls}
                self.history.append(rec)
        return rec

    # -- churn --------------------------------------------------------------
    def set_demand(self, lam_totals):
        """Re-scale every tenant onto new provisioned demands [K].

        ``CECRouter.on_demand_change`` vectorized: each tenant's Λ
        scales by its demand ratio and re-projects exactly onto its box
        (per-tenant totals via vmapped ``project_box_simplex``).  Demand
        is a traced leaf of the fleet step — no retrace (DESIGN.md
        §15.4)."""
        new = np.asarray(lam_totals, np.float32).reshape(-1)
        if new.shape != (self.n_tenants,):
            raise ValueError(f"need [{self.n_tenants}] demands, "
                             f"got {new.shape}")
        scale = jnp.asarray(new / self.lam_totals)
        lam = self.state.lam * scale[:, None]
        lam = jax.vmap(project_box_simplex, in_axes=(0, 0, None))(
            lam, jnp.asarray(new), self.config.delta)
        self.lam_totals = new
        self.state = self.state._replace(lam=lam)
        self._publish()

    def update_tenant_graph(self, tenant: int,
                            new_graph: CECGraph, explore: float = 0.1):
        """Re-target one tenant onto a changed topology (fail/join/rewire).

        The new graph is padded into the fleet's shared augmented layout
        (``core.batch.pad_graph`` — solve-equivalent) and spliced into
        the stacked leaves; the tenant's φ row is warm-started with an
        exploration mix exactly like ``CECRouter.on_topology_change``.
        Same-shape churn by construction: the fleet step never retraces.
        The fleet's layout is fixed at construction — a tenant outgrowing
        it (more physical nodes, deeper graph) raises rather than
        silently retracing every tenant."""
        if isinstance(new_graph, CECGraphSparse):
            raise NotImplementedError("RouterFleet tenants are dense")
        if new_graph.n_sessions != self.n_sessions:
            raise ValueError("tenant session count W is fixed")
        if (new_graph.n_phys > self.batch.n_phys
                or new_graph.depth_max > self.batch.depth_max):
            raise ValueError(
                f"tenant graph (n_phys={new_graph.n_phys}, depth_max="
                f"{new_graph.depth_max}) exceeds the fleet layout "
                f"(n_phys={self.batch.n_phys}, depth_max="
                f"{self.batch.depth_max}); rebuild the fleet")
        g = pad_graph(new_graph, self.batch.n_phys, self.batch.depth_max)
        self.batch = dataclasses.replace(
            self.batch,
            out_mask=self.batch.out_mask.at[tenant].set(g.out_mask),
            edge_mask=self.batch.edge_mask.at[tenant].set(g.edge_mask),
            capacity=self.batch.capacity.at[tenant].set(g.capacity),
            deploy=self.batch.deploy.at[tenant].set(g.deploy),
            sinks=self.batch.sinks.at[tenant].set(g.sinks))
        phi_row = warm_start_phi(self.state.phi[tenant], g.out_mask, explore)
        self.state = self.state._replace(
            phi=self.state.phi.at[tenant].set(phi_row))
        self._publish()

    def apply_scenario_event(self, tenant: int, state: ScenarioState,
                             event: Event, explore: float = 0.1
                             ) -> ScenarioState:
        """Consume one scenario-engine event against one tenant.

        The per-tenant mirror of ``CECRouter.apply_scenario_event``:
        ``state`` is that tenant's physical description, the event is
        applied there, and the stacked iterates are re-targeted (demand
        events rescale the tenant's Λ row, graph events splice +
        warm-start; bank swaps change only the measured environment).
        Returns the post-event state — thread it into the next call.
        """
        new_state = apply_event(state, event)
        if isinstance(event, DemandShift):
            totals = self.lam_totals.copy()
            totals[tenant] = new_state.lam_total
            self.set_demand(totals)
        elif event.changes_graph:
            self.update_tenant_graph(tenant, new_state.graph(),
                                     explore=explore)
        self.history.append({"event": event.kind, "tenant": tenant,
                             "at": len(self.history)})
        return new_state
