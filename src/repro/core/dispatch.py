"""Size-based kernel dispatch for the control-plane hot path (DESIGN.md §9.2).

The jnp einsum implementations in ``core/flow.py`` / ``core/routing.py`` are
the right tool for paper-scale graphs (n̄ ≲ a few hundred): XLA fuses them
and padding to the TPU's 128-lane blocks would only waste work.  At fleet
scale (n̄ = 10³–10⁵) the same steps are served by the Pallas kernels in
``kernels/``: when :func:`use_kernels` says so, ``flow.propagate`` routes
each relaxation step through ``kernels.flow_step`` and ``routing.omd_step``
routes the exponentiated-gradient update through ``kernels.omd_update``.
Operand padding to the 128-block constraint (and slicing back) is handled
by ``kernels/ops.py``.

Dispatch policy: the graph must clear the node-count threshold
(:func:`kernel_threshold`, default 256), **and** the backend must be a real
TPU — *or* the threshold must have been set explicitly (the
``REPRO_KERNEL_NBAR_THRESHOLD`` environment variable,
:func:`set_kernel_threshold`, or the :func:`kernel_dispatch` context
manager).  Off-TPU the kernels run in Pallas ``interpret`` mode, which is
orders of magnitude slower than the fused einsums — correct for validating
the kernel path everywhere (tests and benchmarks opt in via
``kernel_dispatch``), wrong as a silent default for a large graph on CPU.

Environment knobs are **re-read on every policy query** (they used to be
bound once at import, which made late ``os.environ`` mutation a silent
no-op — DESIGN.md §17.4).  Programmatic overrides (setters / context
managers) take precedence over the environment while active; clearing an
override (``set_kernel_threshold(None)``, context exit) falls back to the
live environment, not to a stale import-time snapshot.

The dispatch decision is made at **trace time** against the *static*
``CECGraph.n_bar`` metadata, so both branches stay jit/vmap compatible and
no control flow enters the compiled program.  The flip side: a function
that was already jit-compiled keeps the branch it was traced with —
``kernel_dispatch`` / ``set_kernel_threshold`` only affect functions traced
while the override is active, and are silent no-ops for cached traces.
Trace (or re-jit) inside the override when you need the kernel path.
Every lru-cached jitted entry point keys on :func:`state_key`, which also
re-reads the environment — so a late env mutation *does* reach cached
consumers (a fresh key forces a fresh trace).

A second, orthogonal axis is the **representation** (DESIGN.md §12): past
:func:`use_sparse`'s (N, density) policy, :func:`maybe_sparsify` converts
a dense ``CECGraph`` to the O(E) ``CECGraphSparse`` edge-list layout at
the solver core's single conversion point (``Problem.canonical``,
core/problem.py — every entry point routes through it) and at the raw
routing oracle ``solve_routing``.  Conversion is Python-level only — tracer inputs pass
through untouched — and :func:`state_key` covers both axes so cached
jitted control steps retrace under either override.

A third axis is the **fused control megakernel** (DESIGN.md §17): past
:func:`use_megakernel`'s policy, ``solver.step`` replaces the whole
``lax.scan``-of-observations control iteration with the single Pallas
kernel in ``kernels/control_megakernel.py``.  Its extra condition is the
VMEM residency contract — the per-session routing variables (W·n̄² plus
the flow/marginal scratch) must fit the per-core VMEM budget, checked by
:func:`megakernel_fits` at trace time.
"""
from __future__ import annotations

import contextlib
import os

import jax
import numpy as np

# Defaults when the env knob is absent and no override is active.
DEFAULT_THRESHOLD = 256
SPARSE_DEFAULT_THRESHOLD = 512
SPARSE_DEFAULT_DENSITY = 0.15
MEGAKERNEL_DEFAULT_THRESHOLD = 256

# Programmatic overrides (setter / context manager).  ``None`` means "no
# override: follow the live environment".  The env vars themselves are
# re-read at query time — never cached at import.
_threshold: int | None = None
_explicit: bool | None = None
_sparse_threshold: int | None = None
_sparse_density: float | None = None
_mega_threshold: int | None = None
_mega_explicit: bool | None = None


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def _env_float(name: str, default: float) -> float:
    return float(os.environ.get(name, default))


def kernel_threshold() -> int:
    """Augmented node count n̄ at which the Pallas path takes over."""
    if _threshold is not None:
        return _threshold
    return _env_int("REPRO_KERNEL_NBAR_THRESHOLD", DEFAULT_THRESHOLD)


def _kernel_explicit() -> bool:
    """Whether the kernel threshold was explicitly configured (env/setter)."""
    if _explicit is not None:
        return _explicit
    return "REPRO_KERNEL_NBAR_THRESHOLD" in os.environ


def set_kernel_threshold(n: int | None) -> None:
    """Set the dispatch threshold explicitly; ``None`` restores defaults.

    An explicit threshold also enables the kernel path off-TPU (interpret
    mode).  Only affects functions traced after the call (see module
    docstring).  ``None`` falls back to the *live* environment — it does
    not pin an import-time snapshot.
    """
    global _threshold, _explicit
    if n is None:
        _threshold = _explicit = None
    else:
        _threshold = int(n)
        _explicit = True


@contextlib.contextmanager
def kernel_dispatch(threshold: int):
    """Temporarily force the dispatch threshold (tests/benchmarks).

    ``with kernel_dispatch(1): ...`` sends every flow/OMD step traced
    inside the block through the Pallas kernels regardless of graph size
    or backend (interpret mode off-TPU).  Functions jit-compiled *before*
    entering the block keep their cached jnp-path trace.
    """
    global _threshold, _explicit
    prev = (_threshold, _explicit)
    _threshold, _explicit = int(threshold), True
    try:
        yield
    finally:
        _threshold, _explicit = prev


def sparse_threshold() -> int:
    """Augmented node count n̄ at which sparsification is considered."""
    if _sparse_threshold is not None:
        return _sparse_threshold
    return _env_int("REPRO_SPARSE_NBAR_THRESHOLD", SPARSE_DEFAULT_THRESHOLD)


def sparse_density_max() -> float:
    """Union edge density |Ē|/n̄² at or below which sparsification engages."""
    if _sparse_density is not None:
        return _sparse_density
    return _env_float("REPRO_SPARSE_DENSITY_MAX", SPARSE_DEFAULT_DENSITY)


def set_sparse_threshold(n: int | None, density_max: float | None = None):
    """Set the sparse-representation policy; ``None`` n restores defaults."""
    global _sparse_threshold, _sparse_density
    if n is None:
        _sparse_threshold = _sparse_density = None
    else:
        _sparse_threshold = int(n)
        if density_max is not None:
            _sparse_density = float(density_max)


@contextlib.contextmanager
def sparse_dispatch(threshold: int, density_max: float = 1.0):
    """Temporarily force the sparse policy (tests/benchmarks).

    ``with sparse_dispatch(1): ...`` sparsifies every dense graph reaching
    :func:`maybe_sparsify` inside the block regardless of size or density.
    Like ``kernel_dispatch`` this only affects *conversion points* entered
    inside the block; already-converted or already-traced state keeps its
    representation.
    """
    global _sparse_threshold, _sparse_density
    prev = (_sparse_threshold, _sparse_density)
    _sparse_threshold, _sparse_density = int(threshold), float(density_max)
    try:
        yield
    finally:
        _sparse_threshold, _sparse_density = prev


def use_sparse(n_bar: int, density: float) -> bool:
    """True when a graph of ``n_bar`` nodes / ``density`` should go sparse."""
    return n_bar >= sparse_threshold() and density <= sparse_density_max()


def maybe_sparsify(graph, *companions):
    """Convert a dense ``CECGraph`` to ``CECGraphSparse`` past the policy.

    The conversion builds numpy edge lists, so it only happens at the
    Python level: if the graph's leaves or any ``companion`` array (e.g. a
    caller's φ⁰ that would need re-layout) is a tracer, the graph is
    returned unchanged — inside jit/vmap/scan the representation is
    whatever the caller traced with.  Sparse graphs and sub-threshold
    dense graphs pass through untouched.
    """
    from .graph import CECGraph, sparsify

    if not isinstance(graph, CECGraph):
        return graph
    if graph.n_bar < sparse_threshold():     # cheap static reject first —
        return graph                         # no device→host mask transfer
    if any(isinstance(x, jax.core.Tracer)
           for x in (graph.edge_mask, *companions) if x is not None):
        return graph
    density = float(np.asarray(graph.edge_mask).sum()) / graph.n_bar ** 2
    if not use_sparse(graph.n_bar, density):
        return graph
    return sparsify(graph)


# --------------------------------------------------------------------------
# megakernel axis (DESIGN.md §17): whether ``solver.step`` should run the
# whole control iteration (perturbation sweep + oracle + mirror ascent +
# projection) as the single fused Pallas kernel instead of the stitched
# lax.scan over per-phase kernels.
# --------------------------------------------------------------------------

# Per-core VMEM of a TPU v5e, as its compiler states it: a kernel that asks
# for more is refused ("would exceed memory (size=134217728)").  This is the
# one VMEM figure of the repo.  Every control-plane kernel in ``kernels/``
# is compiled with it as its ``vmem_limit_bytes``, and
# :func:`megakernel_fits` admits a shape only if its modelled footprint fits.
VMEM_BYTES = 128 * 1024 * 1024

# f32 [N̄p, N̄p] planes the dense megakernel holds besides φ: the F and D′
# scratch (2), double-buffered pipeline blocks for φ⁰, the session mask,
# the edge mask, the capacity and φ out (10), and the temporaries of the
# phase bodies.  Compiling for a v5e at W from 1 to 64, the largest shapes
# that compile imply 14-16 planes in all; 20 keeps a margin, and
# ``tests/test_tpu_compile.py`` compiles the largest shape admitted.
MEGAKERNEL_F32_PLANES = 20


def megakernel_threshold() -> int:
    """Augmented node count n̄ at which the fused control step engages."""
    if _mega_threshold is not None:
        return _mega_threshold
    return _env_int("REPRO_MEGAKERNEL_NBAR_THRESHOLD",
                    MEGAKERNEL_DEFAULT_THRESHOLD)


def _megakernel_explicit() -> bool:
    if _mega_explicit is not None:
        return _mega_explicit
    return "REPRO_MEGAKERNEL_NBAR_THRESHOLD" in os.environ


def set_megakernel_threshold(n: int | None) -> None:
    """Set the megakernel threshold; ``None`` restores env-following."""
    global _mega_threshold, _mega_explicit
    if n is None:
        _mega_threshold = _mega_explicit = None
    else:
        _mega_threshold = int(n)
        _mega_explicit = True


@contextlib.contextmanager
def megakernel_dispatch(threshold: int):
    """Temporarily force the fused control step (tests/benchmarks).

    ``with megakernel_dispatch(1): ...`` sends every ``solver.step``
    traced inside the block through ``kernels.control_megakernel``
    regardless of graph size or backend (interpret mode off-TPU), VMEM
    fit permitting.  Same trace-time caveat as :func:`kernel_dispatch`.
    """
    global _mega_threshold, _mega_explicit
    prev = (_mega_threshold, _mega_explicit)
    _mega_threshold, _mega_explicit = int(threshold), True
    try:
        yield
    finally:
        _mega_threshold, _mega_explicit = prev


def megakernel_phi_dtype() -> str:
    """Storage dtype for the kernel's VMEM-resident φ (DESIGN.md §17.3).

    ``REPRO_MEGAKERNEL_PHI_DTYPE=bfloat16`` halves the resident footprint
    (doubling the graph size :func:`megakernel_fits` admits); accumulation
    stays f32 regardless.  Re-read per call like every other knob.
    """
    val = os.environ.get("REPRO_MEGAKERNEL_PHI_DTYPE", "float32")
    if val not in ("float32", "bfloat16"):
        raise ValueError(
            f"REPRO_MEGAKERNEL_PHI_DTYPE must be 'float32' or 'bfloat16', "
            f"got {val!r}")
    return val


def _round_up(n: int, mult: int = 128) -> int:
    return ((n + mult - 1) // mult) * mult


def megakernel_fits(n_sessions: int, n_bar: int, itemsize: int = 4) -> bool:
    """VMEM residency check for the fused control step (DESIGN.md §17.2).

    The kernel keeps φ [W, N̄p, N̄p] resident at ``itemsize`` bytes (4 for
    f32 storage, 2 for bf16) plus :data:`MEGAKERNEL_F32_PLANES` f32
    [N̄p, N̄p] planes of working state and pipeline buffers.  All sizes
    use the 128-padded node count the kernel actually allocates, and the
    total must fit :data:`VMEM_BYTES`, the limit the kernel compiles with.
    """
    n_pad = _round_up(max(int(n_bar), 1))
    w = max(int(n_sessions), 1)
    phi_bytes = w * n_pad * n_pad * itemsize
    work_bytes = MEGAKERNEL_F32_PLANES * n_pad * n_pad * 4
    return phi_bytes + work_bytes <= VMEM_BYTES


def use_megakernel(n_bar: int, n_sessions: int, itemsize: int = 4,
                   sparse: bool = False) -> bool:
    """True when the fused control step should replace the stitched sweep.

    Conditions: n̄ clears :func:`megakernel_threshold`; the resident state
    passes :func:`megakernel_fits`; and either a real TPU backend or an
    explicit opt-in (env var / setter / ``megakernel_dispatch``) — same
    interpret-mode policy as :func:`use_kernels`.

    A sparse graph never takes the megakernel on a TPU: its edge-list
    gathers index whole node vectors, and Mosaic lowers a gather only
    within one vreg (128 lanes), so the kernel does not compile there.
    Such graphs run the stitched sparse kernels instead.  Forcing the
    megakernel for a sparse graph on a TPU is an error, not a fallback.
    """
    if n_bar < megakernel_threshold():
        return False
    if not megakernel_fits(n_sessions, n_bar, itemsize):
        return False
    on_tpu = jax.default_backend() == "tpu"
    if sparse and on_tpu:
        if _megakernel_explicit():
            raise NotImplementedError(
                "the sparse megakernel does not compile for a TPU (its "
                "gathers span more than one vreg); leave the megakernel "
                "off for sparse graphs and the stitched sparse kernels run")
        return False
    return _megakernel_explicit() or on_tpu


# --------------------------------------------------------------------------
# fleet-mesh axis (DESIGN.md §14): which device mesh, if any, the caller is
# tracing sharded fleet solves against.  Part of :func:`state_key` so cached
# jitted consumers never alias executables across mesh shapes (an 8-way
# shard_map program is a different executable from the 1-device one even
# when every pytree shape matches).
# --------------------------------------------------------------------------

_fleet_key: tuple | None = None


def mesh_fingerprint(mesh) -> tuple:
    """Hashable identity of a mesh: axis names, shape, and device ids."""
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def fleet_key() -> tuple | None:
    """The active fleet-mesh fingerprint (``None`` = unsharded vmap path)."""
    return _fleet_key


@contextlib.contextmanager
def fleet_dispatch(mesh):
    """Mark ``mesh`` as the active fleet mesh while tracing.

    ``run_batch_sharded`` / ``run_scenario(mesh=...)`` enter this around
    their shard_map construction so every cache keyed on
    :func:`state_key` (``fused_step``, the scenario segment solver)
    distinguishes mesh shapes instead of replaying a stale trace.
    """
    global _fleet_key
    prev = _fleet_key
    _fleet_key = None if mesh is None else mesh_fingerprint(mesh)
    try:
        yield
    finally:
        _fleet_key = prev


def state_key() -> tuple:
    """Hashable snapshot of the dispatch configuration.

    Callers that *cache jitted functions* (e.g. ``allocation.
    fused_control_step``) must key their cache on this so that tracing
    under ``kernel_dispatch``/``set_kernel_threshold`` gets a fresh trace
    instead of silently reusing a cached jnp-path executable (see the
    module docstring's trace-time caveat).  Includes the sparse policy
    (a router tracing under ``sparse_dispatch`` must not reuse a dense
    trace), the megakernel policy, and the fleet mesh (an executable
    traced for an 8-way ``shard_map`` must not alias the 1-device or vmap
    one).  Every component re-reads its env knob, so mutating
    ``os.environ`` after import changes the key — and with it every
    downstream lru cache entry — on the next call.
    """
    return (kernel_threshold(), _kernel_explicit(),
            sparse_threshold(), sparse_density_max(),
            megakernel_threshold(), _megakernel_explicit(),
            megakernel_phi_dtype(), _fleet_key)


def use_kernels(n_bar: int) -> bool:
    """True when a graph of ``n_bar`` augmented nodes should use kernels.

    Requires clearing the threshold and either a real TPU backend or an
    explicit threshold override (interpret mode is a validation tool, not
    a production fallback — it is far slower than the jnp path).
    """
    if n_bar < kernel_threshold():
        return False
    return _kernel_explicit() or jax.default_backend() == "tpu"


def kernel_interpret() -> bool:
    """Pallas ``interpret`` mode everywhere except real TPU backends."""
    return jax.default_backend() != "tpu"
