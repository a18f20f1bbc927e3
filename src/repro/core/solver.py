"""The solver half of the core: one functional engine (DESIGN.md §13).

Optax-style API over :class:`~repro.core.problem.Problem`:

    state = init(problem, config)                     # SolverState (Λ, φ, t)
    state, info = step(problem, config, state, u)     # one outer iteration
    result = run(problem, config, iters=T)            # scanned, jit-friendly

``step`` is the paper's fused control iteration (GS-OMA Alg. 1; with
``method="single"`` the oracle runs K=1 and the same code *is* OMAD,
Alg. 3): a ``lax.scan`` over the 2W perturbed observations (each one
oracle invocation, ``routing.oracle_observe``), the two-point gradient
estimate, online mirror ascent on the scaled simplex (eq. (10)), the
exact box-simplex projection, and a final observation at the committed
allocation.  This is the **only** implementation of that update in the
repo: ``gs_oma``/``omad``/``solve_jowr`` delegate to :func:`run`, the
batched ensemble solvers ``jax.vmap`` it, ``run_scenario`` threads
:class:`SolverState` across its segments, and the serving ``CECRouter``
holds a ``SolverState`` and calls the jitted :func:`fused_step`.

Task utilities enter ``step`` as a precomputed [2W] vector in the row
order of :func:`perturbed_allocations` — a closed-form bank evaluates
them under vmap inside the jit (what :func:`run` does), a serving fleet
measures them out-of-band and injects the observations (what the router
does); the solver cannot tell the difference.

:class:`SolverConfig` carries every hyperparameter that used to be
re-declared as keyword soup by each entry point.  The two named presets
document a divergence that previously lived as silently drifted
defaults: :func:`paper_defaults` (the offline evaluation setup,
``eta_inner=0.05``) vs :func:`serving_defaults` (the live router,
``eta_inner=3.0`` with K=1 — the aggressive single-step oracle the
serving plane has always run).  ``configs/cec_paper.py`` exposes the
paper §IV scenario as a third preset.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Literal, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dispatch
from .graph import CECGraphSparse, SparsePhi
from .problem import Problem
from .routing import oracle_observe

Array = jnp.ndarray

Method = Literal["nested", "single"]
METHODS = ("nested", "single")

GradMode = Literal["sampled", "learned"]
GRAD_MODES = ("sampled", "learned")


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Hyperparameters of the GS-OMA/OMAD engine (hashable, jit-static).

    ``method="single"`` is OMAD: the oracle advances φ exactly one
    mirror-descent step per observation regardless of ``inner_iters``
    (:attr:`oracle_iters` is the resolved count).  ``eta_inner`` must be
    a Python float — it is a static parameter of the Pallas kernel path
    (DESIGN.md §9.2).

    ``grad_mode`` selects the outer gradient estimator (DESIGN.md §16.2):
    ``"sampled"`` is the paper's 2W two-point perturbation sweep (2W+1
    oracle observations per iteration); ``"learned"`` differentiates a
    fitted utility surrogate (``Problem.util_family``/``util_params``, or
    a closed-form ``bank``) through the implicit routing fixed point —
    one analytic gradient evaluation + the committed observation, 2
    oracle calls per iteration.

    ``telemetry`` is the observability ring capacity (DESIGN.md §18):
    0 (default) records nothing; N > 0 makes :func:`step` accept/return a
    ``repro.obs.Telemetry`` ring of N rows and :func:`run`/
    :func:`fused_step` thread it — static, so each capacity compiles its
    own executable (rings never resize in-flight).
    """

    method: Method = "single"
    delta: float = 0.5            # two-point perturbation radius (Alg. 1)
    eta_outer: float = 0.05       # mirror-ascent step on Λ (eq. (10))
    eta_inner: float = 0.05       # OMD-RT step on φ (eq. (22))
    inner_iters: int = 50         # oracle steps per observation (nested)
    grad_mode: GradMode = "sampled"  # outer gradient estimator (§16.2)
    telemetry: int = 0            # obs ring capacity; 0 = recording off (§18)

    def __post_init__(self):
        if self.method not in METHODS:
            raise ValueError(
                f"unknown method {self.method!r}: valid methods are "
                f"{METHODS}")
        if self.grad_mode not in GRAD_MODES:
            raise ValueError(
                f"unknown grad_mode {self.grad_mode!r}: valid modes are "
                f"{GRAD_MODES}")
        if not self.delta > 0:
            raise ValueError(f"delta must be positive, got {self.delta}")
        if self.inner_iters < 1:
            raise ValueError(
                f"inner_iters must be >= 1, got {self.inner_iters}")
        if self.telemetry < 0:
            raise ValueError(
                f"telemetry (ring capacity) must be >= 0, got "
                f"{self.telemetry}")

    @property
    def oracle_iters(self) -> int:
        """Routing steps per observation: 1 for OMAD, else ``inner_iters``."""
        return 1 if self.method == "single" else self.inner_iters

    def replace(self, **kw) -> "SolverConfig":
        return dataclasses.replace(self, **kw)

    @classmethod
    def from_legacy(cls, *, method: str = "nested", delta: float,
                    eta_outer: float, eta_inner: float,
                    inner_iters: int) -> "SolverConfig":
        """A config from the pre-§13 keyword soup (the shims' adapter)."""
        return cls(method=method, delta=float(delta),
                   eta_outer=float(eta_outer), eta_inner=float(eta_inner),
                   inner_iters=int(inner_iters))


def paper_defaults() -> SolverConfig:
    """The published offline defaults (`solve_jowr`/`gs_oma` signatures):
    nested loop, gentle inner step η_inner=0.05, K=50 oracle steps,
    sampled (two-point) gradients — the paper's information structure."""
    return SolverConfig(method="nested", delta=0.5, eta_outer=0.05,
                        eta_inner=0.05, inner_iters=50,
                        grad_mode="sampled")


def serving_defaults() -> SolverConfig:
    """The live control plane's defaults (`CECRouter`): single-loop OMAD
    with the η_inner=3.0 single-step oracle, sampled gradients (a fresh
    router has no fitted surrogate — it migrates to ``grad_mode=
    "learned"`` live once its ``OnlineFitter`` is ready, DESIGN.md
    §16.4).

    The η_inner gap from :func:`paper_defaults` (3.0 vs 0.05) is no
    longer hand-maintained lore: ``core.hypergrad.tune_etas`` meta-tunes
    both step sizes by hypergradient through the implicit routing layer
    (DESIGN.md §16.3) and lands in this regime — a K=1 oracle needs a
    hot inner step to track churn, a nested K=50 oracle wants many small
    steps.  These literals record that operating point; re-derive them
    for a new topology with ``tune_etas(problem, serving_defaults())``.
    """
    return SolverConfig(method="single", delta=0.5, eta_outer=0.05,
                        eta_inner=3.0, inner_iters=1, grad_mode="sampled")


# ---------------------------------------------------------------------------
# state / results
# ---------------------------------------------------------------------------

class SolverState(NamedTuple):
    """The engine's carried iterates — a pytree; stack it to batch."""

    lam: Array                    # [W] allocation Λ^t
    phi: Any                      # [W, Nb, Nb] dense, or a SparsePhi
    t: Array                      # scalar int32 outer-iteration counter


class StepInfo(NamedTuple):
    """Diagnostics of one outer iteration."""

    grad: Array                   # [W] two-point gradient estimate ĝ^t
    cost: Array                   # scalar D(Λ^{t+1}, φ^{t+1})


class Result(NamedTuple):
    """Unified solve record (supersedes ``JOWRResult``/``ControlStep``/
    the router's ad-hoc history dicts; the legacy shims project it back
    onto those shapes)."""

    lam: Array                    # [W] final allocation
    phi: Any                      # [W, Nb, Nb] (or SparsePhi) final routing
    utility_traj: Array           # [T] observed U(Λ^t, φ^t)
    lam_traj: Array               # [T, W]
    cost_traj: Array              # [T] network cost at the committed iterates
    grad_traj: Array              # [T, W] gradient estimates
    state: SolverState            # final state — thread into the next run
    telemetry: Any = None         # obs ring when config.telemetry > 0 (§18)


# ---------------------------------------------------------------------------
# the exact box-simplex projection (Alg. 1 line 9)
# ---------------------------------------------------------------------------

def project_box_simplex(lam: Array, lam_total, delta: float) -> Array:
    """Exact projection onto {δ ≤ λ_w ≤ λ−δ, Σλ_w = λ}.

    Euclidean projection in closed form: x = clip(y − τ*, δ, λ−δ) where τ*
    solves Σ_w x_w(τ) = λ.  The sum is piecewise linear and non-increasing
    in τ with breakpoints {y_w − δ, y_w − (λ−δ)}; sorting the 2W
    breakpoints and interpolating on the bracketing segment gives the exact
    τ* (water-filling on the dual), no iterative tolerance involved.  For
    infeasible targets (λ outside [Wδ, W(λ−δ)]) the clip saturates at the
    nearest box vertex.

    Last-axis semantics so stacked ``[B, W]`` iterates (the scenario
    engine's per-instance rows) project exactly like a single ``[W]``.
    """
    lo, hi = delta, lam_total - delta
    y = jnp.asarray(lam)
    bp = jnp.sort(jnp.concatenate([y - lo, y - hi], axis=-1), -1)  # [..., 2W]
    # Σ clip(y − τ) evaluated at every breakpoint: non-increasing in τ,
    # from W·(λ−δ) at bp[0] down to W·δ at bp[-1].
    s = jnp.clip(y[..., None, :] - bp[..., :, None], lo, hi).sum(-1)
    # bracketing segment: largest k with s_k ≥ λ (linear on [bp_k, bp_k+1])
    k = jnp.clip((s >= lam_total).sum(-1, keepdims=True) - 1,
                 0, bp.shape[-1] - 2)
    t0 = jnp.take_along_axis(bp, k, -1)
    t1 = jnp.take_along_axis(bp, k + 1, -1)
    s0 = jnp.take_along_axis(s, k, -1)
    s1 = jnp.take_along_axis(s, k + 1, -1)
    drop = jnp.where(s0 > s1, s0 - s1, 1.0)
    frac = jnp.where(s0 > s1, (s0 - lam_total) / drop, 0.0)
    tau = t0 + frac * (t1 - t0)
    return jnp.clip(y - tau, lo, hi)


# ---------------------------------------------------------------------------
# perturbation basis — THE observation order
# ---------------------------------------------------------------------------

def _perturbation_basis(W: int) -> tuple[Array, Array]:
    """([2W] signs, [2W, W] directions) shared by
    :func:`perturbed_allocations` (which callers use to evaluate task
    utilities up front) and :func:`step`'s observation scan (which pairs
    those utilities positionally): rows (2w, 2w+1) are (+e_w, −e_w)."""
    signs = jnp.tile(jnp.asarray([1.0, -1.0], jnp.float32), W)
    dirs = jnp.repeat(jnp.eye(W, dtype=jnp.float32), 2, axis=0)
    return signs, dirs


def perturbed_allocations(lam: Array, delta: float) -> Array:
    """[2W, W] admissions of one outer iteration: rows (2w, 2w+1) = Λ ± δ·e_w.

    The row order is the observation order of :func:`step`'s scan (see
    :func:`_perturbation_basis`).  Callers evaluate task utilities over
    these rows up front — under vmap for a closed-form bank, or batched
    through a measured-utility callback for a live fleet (the 2W
    admissions depend only on Λ^t, never on φ).
    """
    signs, dirs = _perturbation_basis(lam.shape[-1])
    return lam + signs[:, None] * delta * dirs


@functools.lru_cache(maxsize=64)
def _perturbation_offsets(W: int, delta: float) -> np.ndarray:
    """[2W, W] float32 offsets sign·δ·e_w in :func:`_perturbation_basis`
    order, computed as :func:`perturbed_allocations` computes them
    (read-only; cached per ``(W, δ)``)."""
    signs = np.tile(np.asarray([1.0, -1.0], np.float32), W)
    dirs = np.repeat(np.eye(W, dtype=np.float32), 2, axis=0)
    offsets = signs[:, None] * np.float32(delta) * dirs
    offsets.flags.writeable = False
    return offsets


def perturbed_allocations_host(lam: np.ndarray, delta: float) -> np.ndarray:
    """:func:`perturbed_allocations` with numpy, on a host copy of Λ:
    [..., W] -> [..., 2W, W] float32, the same rows bit for bit.

    For a serving control interval, which holds Λ on the host anyway:
    one float32 add per entry, no trace and no device dispatch (the
    offsets are exact in float32, so the device path also rounds once).
    """
    lam = np.asarray(lam, np.float32)
    return lam[..., None, :] + _perturbation_offsets(lam.shape[-1],
                                                     float(delta))


# ---------------------------------------------------------------------------
# init / step / run
# ---------------------------------------------------------------------------

def init(problem: Problem, config: SolverConfig, *,
         phi0=None, lam0: Array | None = None) -> SolverState:
    """Fresh iterates: uniform allocation, uniform routing, t=0.

    ``phi0``/``lam0`` override the warm start.  A dense ``phi0`` handed
    to a sparse-graph problem is re-laid-out onto the edge slots here —
    the one conversion point (callers never juggle representations).
    Λ is seeded strong-float32 so device-resident consumers (the serving
    router) never retrace when the first update replaces a weak-typed
    seed.
    """
    graph = problem.graph
    W = graph.n_sessions
    if lam0 is None:
        lam = jnp.full((W,), problem.lam_total / W, jnp.float32)
    else:
        lam = jnp.asarray(lam0, jnp.float32)
    if phi0 is None:
        phi = graph.uniform_phi()
    elif isinstance(graph, CECGraphSparse) and not isinstance(phi0, SparsePhi):
        from . import sparse as _sparse

        phi = _sparse.phi_to_sparse(graph, phi0)
    else:
        phi = phi0
    return SolverState(lam=lam, phi=phi, t=jnp.int32(0))


def _mirror_ascent(lam: Array, g: Array, lam_total, eta_outer,
                   delta: float) -> Array:
    """Online mirror ascent on the scaled simplex (eq. (10)) + the exact
    box-simplex projection — the one update site both gradient modes and
    the hypergradient rollout share."""
    z = eta_outer * g
    z = z - z.max()
    w = lam * jnp.exp(z)
    lam_new = lam_total * w / w.sum()
    return project_box_simplex(lam_new, lam_total, delta)


def _sampled_step(problem: Problem, config: SolverConfig, state: SolverState,
                  task_utilities: Array, eta_outer,
                  eta_inner) -> tuple[SolverState, StepInfo]:
    """The two-point estimator body (Alg. 1/3): 2W perturbed observations
    scanned with φ carried through, then commit.  η's are explicit so
    :func:`step_with_etas` can trace them (hypergradient rollouts) while
    :func:`step` passes the config's static floats."""
    graph, cost = problem.graph, problem.cost
    lam, phi = state.lam, state.phi
    lam_total = problem.lam_total
    delta = config.delta
    K = config.oracle_iters
    W = graph.n_sessions
    signs, dirs = _perturbation_basis(W)

    def observe(carry, inp):
        g, phi = carry
        sign, ew, task_u = inp
        lam_p = lam + sign * delta * ew
        phi, D = oracle_observe(graph, cost, lam_p, phi, eta_inner, K)
        g = g + sign * ((task_u - D) / (2.0 * delta)) * ew  # Alg. 1 line 6
        return (g, phi), None

    (g, phi), _ = jax.lax.scan(observe, (jnp.zeros(W), phi),
                               (signs, dirs, task_utilities))
    lam_new = _mirror_ascent(lam, g, lam_total, eta_outer, delta)
    phi, D = oracle_observe(graph, cost, lam_new, phi, eta_inner, K)
    return (SolverState(lam=lam_new, phi=phi, t=state.t + 1),
            StepInfo(grad=g, cost=D))


def _megakernel_step(problem: Problem, config: SolverConfig,
                     state: SolverState,
                     task_utilities: Array) -> tuple[SolverState, StepInfo]:
    """The one-kernel fused control step (DESIGN.md §17).

    Semantically :func:`_sampled_step` — same observation order, same
    oracle, same commit — executed as a single Pallas kernel whose
    iterates stay VMEM-resident across all 2W+1 observations
    (``kernels/control_megakernel.py``).  η's and δ are baked as static
    kernel parameters (the config's Python floats), so this path is only
    reachable from :func:`step`, never :func:`step_with_etas`.  The
    ``REPRO_MEGAKERNEL_PHI_DTYPE=bfloat16`` knob narrows the φ *storage*
    to bf16 (accumulation stays f32 — §17.3 bounds the drift).
    """
    from repro.kernels import ops as kops

    graph, cost = problem.graph, problem.cost
    interpret = dispatch.kernel_interpret()
    phi_dtype = dispatch.megakernel_phi_dtype()
    if isinstance(graph, CECGraphSparse):
        lam, rows, src_phi, g, D = kops.control_step_sparse_op(
            state.lam, state.phi.rows, state.phi.src, task_utilities,
            problem.lam_total, graph, config.oracle_iters, config.delta,
            config.eta_outer, config.eta_inner, cost, phi_dtype=phi_dtype,
            interpret=interpret)
        phi = SparsePhi(rows=rows, src=src_phi)
    else:
        lam, phi, g, D = kops.control_step_op(
            state.lam, state.phi, task_utilities, problem.lam_total, graph,
            config.oracle_iters, config.delta, config.eta_outer,
            config.eta_inner, cost, phi_dtype=phi_dtype,
            interpret=interpret)
    return (SolverState(lam=lam, phi=phi, t=state.t + 1),
            StepInfo(grad=g, cost=D))


def _task_value_fn(problem: Problem):
    """λ ↦ Σ_w u_w(λ_w) for the learned gradient: the fitted surrogate
    when one is attached, else the closed-form bank (genie-gradient
    operation — tests/benchmarks), else a loud error."""
    if problem.util_family is not None and problem.util_params is not None:
        from .utility import get_family

        family = get_family(problem.util_family)
        params = problem.util_params
        return lambda lam: family.total(params, lam)
    if problem.bank is not None:
        return lambda lam: problem.bank.per_session(lam).sum()
    raise ValueError(
        "grad_mode='learned' needs task utilities it can differentiate: "
        "attach a fitted surrogate (Problem.with_utilities / "
        "utility.fit_utilities) or a closed-form bank — a measured-utility "
        "problem with neither must run grad_mode='sampled'")


def _learned_step(problem: Problem, config: SolverConfig, state: SolverState,
                  task_utilities: Array) -> tuple[SolverState, StepInfo]:
    """The analytic-gradient body (DESIGN.md §16.2): one ``jax.grad`` of
    U(Λ) = Σ u_w(λ_w) − D(Λ, φ*(Λ)) through the implicit routing fixed
    point (``core.implicit``), then the same mirror-ascent/projection/
    commit as the sampled path.  2 oracle invocations per iteration — the
    gradient's fixed-point solve and the committed observation — versus
    the sampled path's 2W+1.  ``task_utilities`` is unused (the surrogate
    replaces the perturbation sweep); callers pass zeros.
    """
    del task_utilities
    graph, cost = problem.graph, problem.cost
    task_value = _task_value_fn(problem)
    lam, phi = state.lam, state.phi
    eta_inner = config.eta_inner
    K = config.oracle_iters

    # envelope form of the paper's Theorem-1 gradient: at the oracle's
    # fixed point ∂U/∂λ_w = u'_w(λ_w) − ∂D/∂λ_w |_{φ*}; away from it the
    # implicit VJP's linearization at the returned iterate is the K-step
    # approximation (core/implicit.py caveats)
    def net_utility(lam_in):
        phi1, D = oracle_observe(graph, cost, lam_in, phi, eta_inner, K)
        return task_value(lam_in) - D, phi1

    g, phi = jax.grad(net_utility, has_aux=True)(lam)
    lam_new = _mirror_ascent(lam, g, problem.lam_total, config.eta_outer,
                             config.delta)
    phi, D = oracle_observe(graph, cost, lam_new, phi, eta_inner, K)
    return (SolverState(lam=lam_new, phi=phi, t=state.t + 1),
            StepInfo(grad=g, cost=D))


def step(problem: Problem, config: SolverConfig, state: SolverState,
         task_utilities: Array, telemetry=None
         ) -> tuple[SolverState, StepInfo] | tuple:
    """One fused outer iteration of GS-OMA/OMAD on the current iterates.

    ``task_utilities`` is the [2W] vector of *task* utilities Σ_w u_w(λ_w)
    observed for the perturbed admissions of :func:`perturbed_allocations`
    (same row order); the network-cost half of each observation is computed
    here, at the routing iterate the oracle reached for that admission.
    The scan carries φ through all 2W observations (one oracle invocation
    each), takes the mirror-ascent step, projects exactly onto the
    box-simplex, then observes once more at the committed allocation so
    the returned (Λ, φ, cost) are mutually consistent — the paper's
    U(Λ^t, φ^t).  Pure traceable JAX: :func:`run` scans it, the batch
    engine vmaps it, :func:`fused_step` jits it for the serving router.

    With ``config.grad_mode="learned"`` the perturbation sweep is replaced
    by one analytic gradient through the implicit routing layer
    (``task_utilities`` is ignored — pass zeros); the dispatch is static,
    so each mode compiles its own lean program.

    With ``telemetry`` (a ``repro.obs.Telemetry`` ring — only meaningful
    when ``config.telemetry > 0`` sized it) the committed iterates are
    recorded into the ring *inside* the step (pure, donation-friendly,
    DESIGN.md §18.1) and a third return value carries the updated ring.
    """
    graph = problem.graph
    if config.grad_mode == "learned":
        mode, oracle_calls = "learned", 2
        out = _learned_step(problem, config, state, task_utilities)
    else:
        itemsize = 2 if dispatch.megakernel_phi_dtype() == "bfloat16" else 4
        if dispatch.use_megakernel(graph.n_bar, graph.n_sessions, itemsize,
                                   sparse=isinstance(graph, CECGraphSparse)):
            mode = "megakernel"
            out = _megakernel_step(problem, config, state, task_utilities)
        else:
            mode = "sampled"
            out = _sampled_step(problem, config, state, task_utilities,
                                config.eta_outer, config.eta_inner)
        oracle_calls = 2 * graph.n_sessions + 1
    _trace_dispatch(mode, graph)
    if telemetry is None:
        return out
    from repro.obs import telemetry as _tel

    st, info = out
    tel = _tel.record(telemetry, st, info, lam_total=problem.lam_total,
                      delta=config.delta, oracle_calls=oracle_calls)
    return st, info, tel


def _trace_dispatch(mode: str, graph) -> None:
    """Emit the dispatch decision on the installed obs tracer (no-op
    without one).  Runs at *trace* time — once per compilation, which is
    exactly when the decision is made; jitted steady-state intervals
    never reach here (DESIGN.md §18.3).  An edge-list graph also emits
    ``solver.marginal_split``: the out-slots one marginal-recursion step
    gathers per session (``marginal_slots``) against the full width
    (``slots``), and the split that sets them (``graph.marginal_split``)."""
    from repro.obs import trace as _trace

    if _trace.current_tracer() is None:
        return
    _trace.instant(
        f"solver.dispatch:{mode}", cat="dispatch",
        args={"mode": mode, "n_bar": int(graph.n_bar),
              "n_sessions": int(graph.n_sessions),
              "sparse": isinstance(graph, CECGraphSparse),
              # whether the per-phase Pallas kernels serve the jnp
              # body's propagation and EG update (mode "sampled"/
              # "learned"): "stitched" rather than plain jnp
              "kernels": dispatch.use_kernels(graph.n_bar)})
    if isinstance(graph, CECGraphSparse):
        _trace.instant(
            "solver.marginal_split", cat="dispatch",
            args={"marginal_slots": graph.n_bar * graph.d_light
                  + graph.n_heavy * graph.d_max,
                  "slots": graph.n_bar * graph.d_max,
                  "d_light": graph.d_light, "n_heavy": graph.n_heavy})


def step_with_etas(problem: Problem, config: SolverConfig,
                   state: SolverState, task_utilities: Array, eta_outer,
                   eta_inner) -> tuple[SolverState, StepInfo]:
    """:func:`step` with *traced* step sizes — the hypergradient surface.

    ``core.hypergrad`` differentiates rollouts of this function w.r.t.
    (η_outer, η_inner); the config's own η fields are ignored.  jnp path
    only: the Pallas kernel path bakes η as a static kernel parameter
    (``float(eta)``), so meta-tuning under kernel dispatch is refused
    loudly rather than failing inside a trace (DESIGN.md §16.3).
    """
    graph = problem.graph
    if (dispatch.use_kernels(graph.n_bar)
            or dispatch.use_megakernel(
                graph.n_bar, graph.n_sessions,
                sparse=isinstance(graph, CECGraphSparse))):
        raise NotImplementedError(
            "step_with_etas traces η through the OMD update, but the "
            "Pallas kernel paths (per-phase and megakernel alike) need a "
            "static Python-float η — run hypergradient tuning with kernel "
            "dispatch off (jnp path)")
    return _sampled_step(problem, config, state, task_utilities,
                         eta_outer, eta_inner)


def run(problem: Problem, config: SolverConfig, *, iters: int,
        state: SolverState | None = None,
        phi0=None, lam0: Array | None = None) -> Result:
    """Scan :func:`step` for ``iters`` outer iterations.

    Requires ``problem.bank`` (closed-form task utilities evaluated under
    vmap inside the scan — measured-utility consumers drive :func:`step`
    directly).  With ``state=None`` the representation policy runs once
    (``Problem.canonical``) and iterates come from :func:`init`; a passed
    ``state`` continues exactly where a previous ``run`` stopped
    (``Result.state``), which is how the scenario engine crosses segment
    boundaries.  A dense problem that auto-sparsifies still returns dense
    ``phi``/``state`` — the representation never leaks to the caller.

    With ``config.telemetry > 0`` a fresh obs ring of that capacity is
    threaded through the scan — recorded by ``step``, utility-annotated
    device-side at the committed Λ — and returned on
    ``Result.telemetry`` (DESIGN.md §18.1).
    """
    bank = problem.bank
    has_surrogate = (problem.util_family is not None
                     and problem.util_params is not None)
    if bank is None and not (config.grad_mode == "learned" and has_surrogate):
        raise ValueError(
            "solver.run needs problem.bank for task utilities; "
            "measured-utility consumers (no bank) drive solver.step with "
            "observed [2W] vectors instead (or attach a fitted surrogate "
            "via Problem.with_utilities and run grad_mode='learned')")
    if state is not None and (phi0 is not None or lam0 is not None):
        raise ValueError(
            "pass either state= (continue a previous run) or phi0=/lam0= "
            "(fresh warm-started iterates), not both — to override part of "
            "a carried state, edit it: state._replace(phi=...)")
    dense_in = problem.graph
    if state is None:
        prob = problem.canonical(phi0, lam0).validate()
        st = init(prob, config, phi0=phi0, lam0=lam0)
    else:
        # continuations re-run the representation policy too — a carried
        # dense state must not silently pin a fleet-scale solve to the
        # O(N²) path (the carried φ is re-laid-out onto the edge slots,
        # exactly like a phi0 warm start)
        prob = problem.canonical(state.lam,
                                 *jax.tree_util.tree_leaves(state.phi))
        prob, st = prob.validate(), state
        if (isinstance(prob.graph, CECGraphSparse)
                and not isinstance(st.phi, SparsePhi)):
            from . import sparse as _sparse

            st = st._replace(phi=_sparse.phi_to_sparse(prob.graph, st.phi))
    converted = prob.graph is not dense_in

    W = prob.graph.n_sessions
    # the recorded U_t prices the *true* environment when one is visible
    # (a bank), else the surrogate — both evaluate at the committed Λ
    record_value = (bank.total if bank is not None
                    else _task_value_fn(prob))

    if config.telemetry > 0:
        from repro.obs import telemetry as _obs_tel

        tel0 = _obs_tel.init_ring(config.telemetry, W)
    else:
        _obs_tel, tel0 = None, None

    def outer(carry, _):
        st, tel = carry
        if config.grad_mode == "learned":
            # the surrogate replaces the perturbation sweep — no bank
            # evaluations, and step ignores the zeros
            task_u = jnp.zeros((2 * W,), jnp.float32)
        else:
            task_u = jax.vmap(bank.total)(
                perturbed_allocations(st.lam, config.delta))
        if tel is None:
            st, info = step(prob, config, st, task_u)
        else:
            st, info, tel = step(prob, config, st, task_u, tel)
        # the recorded U_t is the paper's U(Λ^t, φ^t): task utility and
        # network cost both evaluated at the *committed* iterates, not at
        # the last perturbed observation
        U_t = record_value(st.lam) - info.cost
        if tel is not None:
            # the ring's utility column is NaN-seeded by record (a jitted
            # step cannot know the task side); here the bank is visible,
            # so annotate device-side within the same scan iteration
            tel = _obs_tel.annotate(tel, utility=U_t)
        return (st, tel), (U_t, st.lam, info.cost, info.grad)

    (st, tel), (u_traj, lam_traj, cost_traj, grad_traj) = jax.lax.scan(
        outer, (st, tel0), None, length=iters)
    if converted:
        from . import sparse as _sparse

        st = st._replace(phi=_sparse.phi_to_dense(prob.graph, st.phi))
    return Result(lam=st.lam, phi=st.phi, utility_traj=u_traj,
                  lam_traj=lam_traj, cost_traj=cost_traj,
                  grad_traj=grad_traj, state=st, telemetry=tel)


# ---------------------------------------------------------------------------
# the jitted step for device-resident consumers (the serving router)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _fused_step(config: SolverConfig, donate: bool, _dispatch_key):
    if config.telemetry > 0:
        def fn(problem: Problem, state: SolverState, task_utilities: Array,
               telemetry):
            return step(problem, config, state, task_utilities, telemetry)

        # donate the iterates AND the ring: both are replaced wholesale
        # every interval, so XLA reuses their buffers in place and the
        # recording steady state allocates nothing (DESIGN.md §18.1)
        return jax.jit(fn, donate_argnums=(1, 3) if donate else ())

    def fn(problem: Problem, state: SolverState, task_utilities: Array):
        return step(problem, config, state, task_utilities)

    return jax.jit(fn, donate_argnums=(1,) if donate else ())


def fused_step(config: SolverConfig, *, donate: bool = False):
    """``jit(step)`` with ``config`` static, cached on its knobs.

    Returns ``fn(problem, state, task_utilities) -> (SolverState,
    StepInfo)`` — or, with ``config.telemetry > 0``, ``fn(problem,
    state, task_utilities, telemetry) -> (SolverState, StepInfo,
    Telemetry)``: the obs ring rides the jit as a fourth pytree argument
    and is donated alongside the state (DESIGN.md §18.1).
    ``problem`` and ``state`` are pytree arguments, so
    same-shape topology changes (the scenario engine's stable-index
    churn) reuse the compiled executable and demand shifts
    (``problem.lam_total`` — a traced leaf) never retrace.  The cache is
    additionally keyed on ``dispatch.state_key()`` so tracing inside
    ``dispatch.kernel_dispatch``/``sparse_dispatch`` gets a fresh trace
    instead of a stale one (DESIGN.md §11).

    ``donate=True`` donates the ``state`` argument (and only it — the
    problem's graph leaves are shared, the utilities are the caller's) so
    XLA writes the new iterates into the old iterates' buffers: the
    steady-state control loop allocates nothing per interval.  The caller
    gives up the passed state — any view that must survive the step (the
    serving plane's published front buffer, DESIGN.md §15.2) has to be a
    *copy*, never an alias, and backends that decline donation simply
    fall back to allocate-and-swap (detectable via
    ``state.lam.is_deleted()`` — see ``tests/test_fleet.py``).
    """
    return _fused_step(config, bool(donate), dispatch.state_key())
