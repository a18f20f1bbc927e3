"""Paper-derived invariant monitors (DESIGN.md §18.2).

Each monitor is a pure jnp function returning a
:class:`~repro.obs.telemetry.Verdict` — a scalar residual plus
``warn``/``trip`` booleans — so it jits, vmaps over a fleet axis
(:func:`fleet_verdicts`) and rides inside ``shard_map`` bodies
unchanged.  Two input families:

* **ring monitors** (:func:`monotone_descent`, :func:`dynamic_regret`,
  :func:`budget_feasibility`) read the :class:`Telemetry` ring — history
  invariants over the committed trajectory;
* **state monitors** (:func:`flow_conservation`, :func:`capacity_slack`,
  :func:`kkt_gap`) read the *live* ``(problem, state)`` iterates — the
  paper's fixed-point/KKT conditions at one instant.

Semantics (and the theorem each one operationalizes):

``monotone_descent``
    Theorem 4 guarantees the routing oracle's OMD descends network cost
    at fixed Λ; across control intervals (Λ moving by one mirror-ascent
    step) the observable proxy is that committed net utility does not
    *fall* materially in an event-free environment.  Value: the largest
    one-interval utility drop in the ring, in units of the ring's mean
    |U| (scale-free).  The golden ``fig7_gs_oma_traj.npz`` trajectory is
    strictly increasing — this monitor never trips on it (pinned in
    ``tests/test_obs.py``).
``dynamic_regret``
    Σ_t (U*(t) − U_t) against a comparator — the ``segment_optima``
    genie per-segment optimum (§IV's absolute comparator) or any scalar
    baseline.  Agrees with ``scenario_metrics``'s accounting ≤1e-6
    (pinned).  Unbounded in the horizon, so warn/trip default off —
    callers with a regret budget pass thresholds.
``budget_feasibility``
    The box-simplex constraint {δ ≤ λ_w ≤ Λ−δ, Σλ_w = Λ}: max of the
    ring's per-interval projection residuals.  The exact projection
    (Alg. 1 line 9) makes this f32-rounding-sized; growth means someone
    bypassed the projection.
``flow_conservation``
    The session rates must satisfy the paper's flow fixed point
    t = inject + t·φ (eq. (2)–(3) recursion).  Value: max |T(t) − t| of
    one extra Jacobi application, relative to the injected demand — the
    residual the ``depth_max``-step relaxation left behind.
``capacity_slack``
    Max relative link overload (F_ij − C_ij)/C_ij over real edges (eq.
    (4) flows).  Negative = slack everywhere.  The soft exponential cost
    tolerates transient overload; sustained trips mean admission is
    outrunning the network.
``kkt_gap``
    Theorem 3 stationarity: ``routing.kkt_residual`` — at φ* the active
    marginal costs per row are equal and minimal.

Thresholds are keyword arguments with conservative defaults calibrated
on the event-free ``named_scenarios`` suite (no false trips — a property
``tests/test_obs.py`` enforces); ``warn`` is the soft heads-up, ``trip``
the invariant-violation alarm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import routing as _routing
from repro.core import sparse as _sparse
from repro.core.flow import HIGHEST, link_flows, propagate
from repro.core.graph import CECGraphSparse
from repro.core.problem import Problem, resolve_cost

from .telemetry import Telemetry, Verdict, order

Array = jnp.ndarray


def _verdict(value, warn_at, trip_at) -> Verdict:
    value = jnp.asarray(value)
    return Verdict(value=value, warn=value > warn_at, trip=value > trip_at)


# ---------------------------------------------------------------------------
# ring monitors
# ---------------------------------------------------------------------------

def monotone_descent(tel: Telemetry, *, warn: float = 0.02,
                     trip: float = 0.25) -> Verdict:
    """Largest one-interval drop of committed utility, scale-free.

    Value = max_t (U_t − U_{t+1}) / mean|U| over consecutive valid,
    annotated (non-NaN) ring rows; ≤0 on a monotone trajectory.  Rows
    never annotated with a utility are skipped, not treated as drops.
    """
    idx, valid = order(tel)
    u = tel.utility[idx]
    ok = valid & jnp.isfinite(u)
    pair = ok[:-1] & ok[1:]
    drop = jnp.where(pair, u[:-1] - u[1:], -jnp.inf)
    scale = (jnp.abs(jnp.where(ok, u, 0.0)).sum()
             / jnp.maximum(ok.sum(), 1)) + 1e-9
    worst = jnp.where(pair.any(), drop.max() / scale, 0.0)
    return _verdict(worst, warn, trip)


def dynamic_regret(tel: Telemetry, comparator, *, warn: float = jnp.inf,
                   trip: float = jnp.inf) -> Verdict:
    """Σ over valid annotated rows of (comparator − U_t).

    ``comparator`` is a scalar U* or a ``[capacity]`` per-row (chrono-
    logically ordered) comparator — :func:`repro.core.scenario.
    segment_optima` values broadcast per segment.  Defaults never warn:
    regret grows with the horizon by construction; callers with a budget
    (e.g. the sublinearity trend) supply thresholds.
    """
    idx, valid = order(tel)
    u = tel.utility[idx]
    comp = jnp.asarray(comparator)
    comp = jnp.broadcast_to(comp, u.shape) if comp.ndim == 0 else comp
    ok = valid & jnp.isfinite(u)
    regret = jnp.where(ok, comp - u, 0.0).sum()
    return _verdict(regret, warn, trip)


def budget_feasibility(tel: Telemetry, *, warn: float = 1e-3,
                       trip: float = 1e-1) -> Verdict:
    """Max recorded box-simplex projection residual (absolute, in demand
    units) — |ΣΛ − λ_total| + box violations, per ``telemetry.record``."""
    idx, valid = order(tel)
    r = tel.proj_residual[idx]
    ok = valid & jnp.isfinite(r)
    worst = jnp.where(ok.any(), jnp.where(ok, r, -jnp.inf).max(), 0.0)
    return _verdict(worst, warn, trip)


# ---------------------------------------------------------------------------
# state monitors
# ---------------------------------------------------------------------------

def _one_jacobi(graph, phi, lam, t):
    """One application of the flow recursion T(t) — both representations."""
    if isinstance(graph, CECGraphSparse):
        base = _sparse.source_inflow(graph, phi, lam)
        t_new = base + _sparse._relay_inflow(graph, phi.rows, t)
        wi = jnp.arange(graph.n_sessions)
        return t_new.at[wi, graph.sinks].set(
            _sparse._sink_inflow(graph, phi.rows, t))
    return graph.injection(lam) + jnp.einsum("wi,wij->wj", t, phi,
                                             precision=HIGHEST)


def flow_conservation(problem: Problem, state, *, warn: float = 1e-3,
                      trip: float = 1e-1) -> Verdict:
    """Fixed-point residual max|T(t) − t| / λ_total of the session-rate
    recursion at the solver's routing iterate (eq. (2)–(3))."""
    graph = problem.graph
    t = propagate(graph, state.phi, state.lam)
    resid = jnp.abs(_one_jacobi(graph, state.phi, state.lam, t) - t).max()
    return _verdict(resid / (problem.lam_total + 1e-9), warn, trip)


def capacity_slack(problem: Problem, state, *, warn: float = 0.0,
                   trip: float = 2.0) -> Verdict:
    """Max relative link overload (F − C)/C over real edges; negative
    everywhere means every link has slack."""
    graph = problem.graph
    t = propagate(graph, state.phi, state.lam)
    F = link_flows(graph, state.phi, t)
    if isinstance(graph, CECGraphSparse):
        over_rows = jnp.where(
            graph.edge_mask > 0, (F.rows - graph.capacity) / graph.capacity,
            -jnp.inf)
        over_src = jnp.where(
            graph.src_edge_mask > 0,
            (F.src - graph.src_capacity) / graph.src_capacity, -jnp.inf)
        worst = jnp.maximum(over_rows.max(), over_src.max())
    else:
        worst = jnp.where(graph.edge_mask > 0,
                          (F - graph.capacity) / graph.capacity,
                          -jnp.inf).max()
    return _verdict(worst, warn, trip)


def kkt_gap(problem: Problem, state, *, warn: float = 1.0,
            trip: float = 100.0) -> Verdict:
    """Theorem 3 stationarity residual of the routing iterate
    (``routing.kkt_residual`` — max over rows of support-max minus
    allowed-min marginal cost).  Mid-flight OMAD iterates sit at O(0.1);
    the trip level flags divergence, not mere non-convergence."""
    r = _routing.kkt_residual(problem.graph, problem.cost, state.phi,
                              state.lam)
    return _verdict(r, warn, trip)


# ---------------------------------------------------------------------------
# bundles
# ---------------------------------------------------------------------------

def check_state(problem: Problem, state, tel: Telemetry | None = None, *,
                comparator=None) -> dict[str, Verdict]:
    """Every applicable monitor at once, default thresholds.

    State monitors always run; ring monitors when ``tel`` is given;
    regret when a ``comparator`` is.  Pure — jit/vmap/shard_map it.
    """
    out = {
        "flow_conservation": flow_conservation(problem, state),
        "capacity_slack": capacity_slack(problem, state),
        "kkt_gap": kkt_gap(problem, state),
    }
    if tel is not None:
        out["monotone_descent"] = monotone_descent(tel)
        out["budget_feasibility"] = budget_feasibility(tel)
        if comparator is not None:
            out["dynamic_regret"] = dynamic_regret(tel, comparator)
    return out


def fleet_verdicts(graph, lam_total, state, tel: Telemetry | None = None, *,
                   cost="exp", comparator=None) -> dict[str, Verdict]:
    """:func:`check_state` vmapped over a fleet/tenant axis.

    ``graph`` is a stacked view (``CECGraphBatch.stacked_graph()`` or
    per-leaf-stacked tenants as the ``RouterFleet`` holds them),
    ``lam_total`` is ``[K]``, ``state``/``tel`` are stacked pytrees;
    returns the same dict with ``[K]``-leaf Verdicts.  Lane k's verdicts
    are bit-identical to running the scalar monitors on tenant k alone —
    the vmap axis never mixes lanes (pinned in ``tests/test_obs.py``).
    """
    costfn = resolve_cost(cost)

    def one(g, lt, s, t_r, comp):
        problem = Problem(graph=g, bank=None, lam_total=lt, cost=costfn)
        return check_state(problem, s, t_r, comparator=comp)

    in_axes = (0, 0, 0, None if tel is None else 0,
               None if comparator is None else 0)
    return jax.vmap(one, in_axes=in_axes)(graph, lam_total, state, tel,
                                          comparator)
