"""Device-resident telemetry ring (DESIGN.md §18.1).

A :class:`Telemetry` is a frozen-pytree ring buffer of per-interval
control-plane signals — utility, per-class Λ, network cost, gradient
norm, box-simplex projection residual, oracle-call count, and solver
wall-clock — updated *inside* the jitted control step by the pure
:func:`record`.  The contract that keeps steady-state recording free:

* **pytree, fixed shapes** — every leaf's shape depends only on the
  static ``capacity`` and the session count W, so a ring threads through
  ``jax.jit`` / ``lax.scan`` / ``vmap`` (the RouterFleet's ``[K]``
  stacking) / ``shard_map`` (the fleet mesh) like any other carry.
* **donation-compatible** — :func:`record` and :func:`annotate` return a
  ring of identical structure, so the fused step can donate the incoming
  ring and XLA writes the new row into the old buffers in place.
* **host sync is explicit** — nothing here calls back to Python; reading
  the ring is :func:`repro.obs.export.export_ring`'s job, and until then
  all values stay device-resident.

Columns a jitted step cannot know (the *measured* task utility U_t, the
host wall-clock) are written as NaN by :func:`record` and patched by the
caller via :func:`annotate` — the router annotates both, ``solver.run``
annotates U_t device-side inside its scan.

This module imports only jax/numpy (never ``repro.core``) so the solver
core can import it without a cycle; the paper-invariant checks that *do*
need the core live in :mod:`repro.obs.monitors`.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray


class Verdict(NamedTuple):
    """One monitor's output: a scalar residual plus threshold booleans.

    ``value`` is the monitored quantity (units documented per monitor in
    :mod:`repro.obs.monitors`), ``warn``/``trip`` its comparisons against
    the monitor's thresholds.  A pytree of arrays, so fleet-vmapped
    monitors return Verdicts with ``[K]`` leaves.
    """

    value: Array                  # scalar (or [K] under vmap)
    warn: Array                   # bool — soft threshold crossed
    trip: Array                   # bool — hard invariant violated


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Telemetry:
    """The ring.  ``capacity`` is static metadata (part of the treedef,
    hashable, jit-static); every other field is a fixed-shape leaf.

    Row columns (slot axis first):

    ``utility [C]``
        Net utility U(Λ^t, φ^t) at the committed iterates.  NaN until
        annotated — the jitted step sees network cost but not the
        measured task utility.
    ``lam [C, W]``
        The committed per-class allocation Λ^{t+1}.
    ``cost [C]``
        Network cost D(Λ^{t+1}, φ^{t+1}) at the committed observation.
    ``grad_norm [C]``
        ‖ĝ^t‖₂ of the outer gradient estimate.
    ``proj_residual [C]``
        Feasibility residual of the committed Λ against the box-simplex:
        |ΣΛ − λ_total| + max(0, δ − min Λ) + max(0, max Λ − (λ_total−δ)).
        Zero (to f32 rounding) whenever the exact projection ran last.
    ``oracle_calls [C]``
        Oracle invocations this interval (2W+1 sampled/megakernel, 2
        learned).
    ``wall_clock_us [C]``
        Host time in µs from the interval's start to the step's result
        on the host: the clock stops after the first read of the new Λ,
        which waits for the step, so it holds the perturbation sweep,
        its measurement, the dispatch and the step's device time.  NaN
        until annotated.

    ``head`` is the *next* write slot (monotone int32, slot = head mod C);
    ``count`` saturates at C — together they define the valid window and
    its chronological order (:func:`order`).
    """

    utility: Array
    lam: Array
    cost: Array
    grad_norm: Array
    proj_residual: Array
    oracle_calls: Array
    wall_clock_us: Array
    head: Array                   # scalar int32 — next write slot
    count: Array                  # scalar int32 — valid rows, ≤ capacity
    capacity: int = dataclasses.field(metadata=dict(static=True))


def init_ring(capacity: int, n_sessions: int) -> Telemetry:
    """A fresh ring: NaN value columns, zero counters.

    ``capacity`` rows of ``n_sessions``-wide Λ; both are static — a ring
    never resizes (resize = new ring), which is what lets the fused step
    cache one executable per (config, dispatch) key regardless of how
    long the control loop runs.
    """
    capacity = int(capacity)
    if capacity < 1:
        raise ValueError(f"ring capacity must be >= 1, got {capacity}")
    # one buffer per column: donating a fresh ring must never hand XLA
    # the same buffer twice (`f(donate(a), donate(a))` is rejected)
    nan = lambda: jnp.full((capacity,), jnp.nan, jnp.float32)
    return Telemetry(
        utility=nan(),
        lam=jnp.full((capacity, int(n_sessions)), jnp.nan, jnp.float32),
        cost=nan(),
        grad_norm=nan(),
        proj_residual=nan(),
        oracle_calls=jnp.zeros((capacity,), jnp.int32),
        wall_clock_us=nan(),
        head=jnp.int32(0),
        count=jnp.int32(0),
        capacity=capacity,
    )


def _put(col: Array, slot: Array, value) -> Array:
    return jax.lax.dynamic_update_index_in_dim(
        col, jnp.asarray(value, col.dtype), slot, 0)


def record(tel: Telemetry, state, info, *, lam_total, delta,
           oracle_calls) -> Telemetry:
    """Append one interval's row — pure, traceable, donation-friendly.

    ``state``/``info`` are the solver's post-step ``(SolverState,
    StepInfo)`` (duck-typed on ``.lam``/``.grad``/``.cost`` so this
    module stays core-free); ``lam_total``/``delta`` parameterize the
    feasibility residual; ``oracle_calls`` is the static per-mode count.
    The utility and wall-clock columns are seeded NaN for the caller's
    :func:`annotate`.
    """
    slot = jnp.mod(tel.head, tel.capacity)
    lam = jnp.asarray(state.lam, jnp.float32)
    lo, hi = delta, lam_total - delta
    residual = (jnp.abs(lam.sum() - lam_total)
                + jnp.maximum(lo - lam.min(), 0.0)
                + jnp.maximum(lam.max() - hi, 0.0))
    return dataclasses.replace(
        tel,
        utility=_put(tel.utility, slot, jnp.nan),
        lam=jax.lax.dynamic_update_index_in_dim(
            tel.lam, lam[None, :], slot, 0),
        cost=_put(tel.cost, slot, info.cost),
        grad_norm=_put(tel.grad_norm, slot,
                       jnp.linalg.norm(jnp.asarray(info.grad, jnp.float32))),
        proj_residual=_put(tel.proj_residual, slot, residual),
        oracle_calls=_put(tel.oracle_calls, slot, oracle_calls),
        wall_clock_us=_put(tel.wall_clock_us, slot, jnp.nan),
        head=tel.head + 1,
        count=jnp.minimum(tel.count + 1, tel.capacity),
    )


def annotate(tel: Telemetry, *, utility=None,
             wall_clock_us=None) -> Telemetry:
    """Patch the *most recent* row with values the jitted step could not
    know: the measured task-side utility and/or host wall-clock.  Pure —
    the router wraps it in a cached donated jit (one executable per ring
    shape), ``solver.run`` traces it inline inside its scan.
    """
    slot = jnp.mod(tel.head - 1, tel.capacity)
    kw = {}
    if utility is not None:
        kw["utility"] = _put(tel.utility, slot, utility)
    if wall_clock_us is not None:
        kw["wall_clock_us"] = _put(tel.wall_clock_us, slot, wall_clock_us)
    return dataclasses.replace(tel, **kw) if kw else tel


def order(tel: Telemetry) -> tuple[Array, Array]:
    """(``idx [C]``, ``valid [C]``): slot indices in chronological order
    plus the validity mask — the one place ring arithmetic lives, so
    monitors and the exporter cannot disagree on what "oldest" means.
    ``col[idx]`` reads oldest→newest; the first ``count`` positions are
    the valid window, the tail is unwritten slots masked out by
    ``valid``.
    """
    c = tel.capacity
    start = jnp.mod(tel.head - tel.count, c)
    idx = jnp.mod(start + jnp.arange(c, dtype=jnp.int32), c)
    valid = jnp.arange(c, dtype=jnp.int32) < tel.count
    return idx, valid


_annotate_jit = None
_annotate_fleet_jit = None


def annotate_donated(tel: Telemetry, *, utility, wall_clock_us) -> Telemetry:
    """Jitted :func:`annotate` with the ring donated — the router's
    steady-state path (zero allocation per annotate).  A fleet-stacked
    ring (``head`` of shape [K]) annotates per lane with [K] values.
    Cached executables; further specialization is by ring shape, which
    jit handles.
    """
    global _annotate_jit, _annotate_fleet_jit
    if tel.head.ndim == 0:
        if _annotate_jit is None:
            _annotate_jit = jax.jit(
                lambda t, u, w: annotate(t, utility=u, wall_clock_us=w),
                donate_argnums=(0,))
        return _annotate_jit(tel, utility, wall_clock_us)
    if _annotate_fleet_jit is None:
        _annotate_fleet_jit = jax.jit(
            jax.vmap(lambda t, u, w: annotate(t, utility=u,
                                              wall_clock_us=w)),
            donate_argnums=(0,))
    return _annotate_fleet_jit(tel, utility, wall_clock_us)
