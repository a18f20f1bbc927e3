"""Batched multi-instance JOWR: solve an ensemble in one XLA program.

The paper's evaluation (§IV, Figs. 7–11, Table II) reports every curve as an
average over many random instance draws.  Solving those draws one at a time
from Python wastes the fact that ``gs_oma``/``omad`` are pure scanned JAX:
``CECGraphBatch`` stacks B augmented graphs into one pytree (padding draws
of different physical size to a common augmented size, DESIGN.md §9.1) and
``solve_jowr_batch`` / ``solve_routing_batch`` ``jax.vmap`` the existing
scan over the instance axis, returning stacked results.

Padding is exact, not approximate: pad nodes get no edges (all-zero masks),
unit capacity on masked-out links (the ``CECGraph`` convention for unused
entries), and the shared ``depth_max`` is the batch maximum — extra Jacobi
relaxation steps past an instance's own longest path are no-ops at the flow
fixed point, so a padded instance reproduces its standalone trajectory.
Virtual nodes are re-indexed so that ``src``/``sinks`` land at the same
(static) positions for every instance; all instances must share the session
count W.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import costs as _costs
from . import dispatch
from . import solver as _solver
from .allocation import JOWRResult
from .graph import CECGraph, CECGraphSparse
from .mesh import (fleet_axis, fleet_mesh, fleet_padded_size, fleet_specs,
                   pad_fleet, shard_map_compat, unpad_fleet)
from .problem import Problem, resolve_cost
from .routing import solve_routing, solve_routing_sgp
from .solver import Method, SolverConfig, SolverState
from .utility import UtilityBank

Array = jnp.ndarray


def pad_graph(graph: CECGraph, n_phys: int,
              depth_max: int | None = None) -> CECGraph:
    """Embed ``graph`` into an augmented graph with ``n_phys`` physical nodes.

    Physical nodes keep their indices; pad nodes ``[graph.n_phys, n_phys)``
    are isolated (no allowed out-edges, never deployed); the virtual source
    and sinks are relocated to the tail positions ``n_phys`` and
    ``n_phys + 1 + w``.  The padded instance is solve-equivalent to the
    original (see module docstring).
    """
    if n_phys < graph.n_phys:
        raise ValueError(f"cannot shrink graph: {graph.n_phys} -> {n_phys}")
    depth_max = graph.depth_max if depth_max is None else depth_max
    if depth_max < graph.depth_max:
        raise ValueError("depth_max must not decrease")
    if n_phys == graph.n_phys and depth_max == graph.depth_max:
        return graph

    W = graph.n_sessions
    n_bar = n_phys + 1 + W
    # old augmented index -> new augmented index
    idx = np.concatenate([np.arange(graph.n_phys), [n_phys],
                          n_phys + 1 + np.arange(W)])

    out_mask = np.zeros((W, n_bar, n_bar), np.float32)
    edge_mask = np.zeros((n_bar, n_bar), np.float32)
    capacity = np.ones((n_bar, n_bar), np.float32)
    for w in range(W):
        out_mask[w][np.ix_(idx, idx)] = np.asarray(graph.out_mask[w])
    edge_mask[np.ix_(idx, idx)] = np.asarray(graph.edge_mask)
    capacity[np.ix_(idx, idx)] = np.asarray(graph.capacity)

    deploy = np.zeros((W, n_phys), bool)
    deploy[:, : graph.n_phys] = np.asarray(graph.deploy)

    return CECGraph(
        out_mask=jnp.asarray(out_mask),
        edge_mask=jnp.asarray(edge_mask),
        capacity=jnp.asarray(capacity),
        deploy=jnp.asarray(deploy),
        sinks=jnp.asarray(n_phys + 1 + np.arange(W)),
        n_phys=n_phys,
        n_sessions=W,
        n_bar=n_bar,
        depth_max=depth_max,
        src=n_phys,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CECGraphBatch:
    """B CEC instances stacked on a leading axis, sharing static metadata.

    Built with :meth:`from_graphs`; consumed by ``solve_jowr_batch`` and
    ``solve_routing_batch`` which vmap the per-instance solvers over axis 0.
    """

    # --- data (pytree leaves, leading axis = instance) ---
    out_mask: jax.Array      # [B, W, Nb, Nb]
    edge_mask: jax.Array     # [B, Nb, Nb]
    capacity: jax.Array      # [B, Nb, Nb]
    deploy: jax.Array        # [B, W, N]
    sinks: jax.Array         # [B, W]
    # --- static metadata (shared across instances) ---
    n_instances: int = dataclasses.field(metadata=dict(static=True))
    n_phys: int = dataclasses.field(metadata=dict(static=True))
    n_sessions: int = dataclasses.field(metadata=dict(static=True))
    n_bar: int = dataclasses.field(metadata=dict(static=True))
    depth_max: int = dataclasses.field(metadata=dict(static=True))
    src: int = dataclasses.field(metadata=dict(static=True))

    @classmethod
    def from_graphs(cls, graphs: Sequence[CECGraph]) -> "CECGraphBatch":
        """Stack instances, padding to the common augmented size."""
        if not graphs:
            raise ValueError("need at least one graph")
        W = graphs[0].n_sessions
        if any(g.n_sessions != W for g in graphs):
            raise ValueError("all instances must share the session count W")
        n_phys = max(g.n_phys for g in graphs)
        depth_max = max(g.depth_max for g in graphs)
        padded = [pad_graph(g, n_phys, depth_max) for g in graphs]
        stack = lambda name: jnp.stack([getattr(g, name) for g in padded])
        return cls(
            out_mask=stack("out_mask"),
            edge_mask=stack("edge_mask"),
            capacity=stack("capacity"),
            deploy=stack("deploy"),
            sinks=stack("sinks"),
            n_instances=len(padded),
            n_phys=n_phys,
            n_sessions=W,
            n_bar=padded[0].n_bar,
            depth_max=depth_max,
            src=padded[0].src,
        )

    def _graph(self, leaves) -> CECGraph:
        return CECGraph(*leaves, n_phys=self.n_phys,
                        n_sessions=self.n_sessions, n_bar=self.n_bar,
                        depth_max=self.depth_max, src=self.src)

    def stacked_graph(self) -> CECGraph:
        """A ``CECGraph`` view whose leaves carry the instance axis.

        Static metadata is shared, so ``jax.vmap(fn)(batch.stacked_graph())``
        maps ``fn`` over instances with zero data movement.
        """
        return self._graph((self.out_mask, self.edge_mask, self.capacity,
                            self.deploy, self.sinks))

    def instance(self, b: int) -> CECGraph:
        """Materialize instance ``b`` as a standalone ``CECGraph``."""
        return self._graph((self.out_mask[b], self.edge_mask[b],
                            self.capacity[b], self.deploy[b], self.sinks[b]))

    def uniform_phi(self) -> jax.Array:
        """[B, W, Nb, Nb] uniform routing per instance."""
        return self.stacked_graph().uniform_phi()


def pad_sparse_graph(graph: CECGraphSparse, n_phys: int,
                     depth_max: int | None = None, d_max: int | None = None,
                     d_src: int | None = None, d_in_max: int | None = None,
                     d_light: int | None = None,
                     n_heavy: int | None = None) -> CECGraphSparse:
    """Embed a sparse graph into a larger index/slot space.

    The edge-list counterpart of :func:`pad_graph`: physical nodes keep
    their indices, pad nodes are isolated, the virtual source and sinks
    relocate to the tail positions, and every slot axis grows to the
    requested width (slots keep their positions — crucial for ``in_slot``
    validity).  Node indices stored in ``nbr``/``src_nbr``/``heavy`` are
    remapped through the same relocation.  Solve-equivalent by the same
    argument as the dense pad (extra rows/slots carry zero mask).  The
    marginal split only widens: a wider light block still holds every row
    not listed as heavy, and the heavy list grows by copies of row 0 (a row
    summed at full width is right whatever its degree).
    """
    if n_phys < graph.n_phys:
        raise ValueError(f"cannot shrink graph: {graph.n_phys} -> {n_phys}")
    depth_max = max(graph.depth_max, depth_max or 0)
    d_max = max(graph.d_max, d_max or 0)
    d_src = max(graph.d_src, d_src or 0)
    d_in_max = max(graph.d_in_max, d_in_max or 0)
    d_light = max(graph.d_light, d_light or 0)
    n_heavy = max(graph.n_heavy, n_heavy or 0)
    if (n_phys, depth_max, d_max, d_src, d_in_max, d_light, n_heavy) == (
            graph.n_phys, graph.depth_max, graph.d_max, graph.d_src,
            graph.d_in_max, graph.d_light, graph.n_heavy):
        return graph

    W = graph.n_sessions
    n_bar = n_phys + 1 + W
    shift = n_phys - graph.n_phys
    idx = np.concatenate([np.arange(graph.n_phys), [n_phys],
                          n_phys + 1 + np.arange(W)])

    def remap(v):
        v = np.asarray(v)
        return np.where(v >= graph.src, v + shift, v).astype(np.int32)

    nbr = np.tile(np.arange(n_bar, dtype=np.int32)[:, None], (1, d_max))
    nbr[idx, : graph.d_max] = remap(graph.nbr)
    out_mask = np.zeros((W, n_bar, d_max), np.float32)
    out_mask[:, idx, : graph.d_max] = np.asarray(graph.out_mask)
    edge_mask = np.zeros((n_bar, d_max), np.float32)
    edge_mask[idx, : graph.d_max] = np.asarray(graph.edge_mask)
    capacity = np.ones((n_bar, d_max), np.float32)
    capacity[idx, : graph.d_max] = np.asarray(graph.capacity)
    sink_slot = np.zeros(n_phys, np.int32)
    sink_slot[: graph.n_phys] = np.asarray(graph.sink_slot)

    src_nbr = np.full(d_src, n_phys, np.int32)
    src_nbr[: graph.d_src] = remap(graph.src_nbr)
    src_out_mask = np.zeros((W, d_src), np.float32)
    src_out_mask[:, : graph.d_src] = np.asarray(graph.src_out_mask)
    src_edge_mask = np.zeros(d_src, np.float32)
    src_edge_mask[: graph.d_src] = np.asarray(graph.src_edge_mask)
    src_capacity = np.ones(d_src, np.float32)
    src_capacity[: graph.d_src] = np.asarray(graph.src_capacity)

    in_src = np.zeros((n_bar, d_in_max), np.int32)
    in_src[idx, : graph.d_in_max] = np.asarray(graph.in_src)
    in_slot = np.zeros((n_bar, d_in_max), np.int32)
    in_slot[idx, : graph.d_in_max] = np.asarray(graph.in_slot)
    in_mask = np.zeros((n_bar, d_in_max), np.float32)
    in_mask[idx, : graph.d_in_max] = np.asarray(graph.in_mask)

    heavy = np.zeros(n_heavy, np.int32)
    heavy[: graph.n_heavy] = remap(graph.heavy)

    deploy = np.zeros((W, n_phys), bool)
    deploy[:, : graph.n_phys] = np.asarray(graph.deploy)

    return CECGraphSparse(
        nbr=jnp.asarray(nbr), out_mask=jnp.asarray(out_mask),
        edge_mask=jnp.asarray(edge_mask), capacity=jnp.asarray(capacity),
        sink_slot=jnp.asarray(sink_slot),
        src_nbr=jnp.asarray(src_nbr), src_out_mask=jnp.asarray(src_out_mask),
        src_edge_mask=jnp.asarray(src_edge_mask),
        src_capacity=jnp.asarray(src_capacity),
        in_src=jnp.asarray(in_src), in_slot=jnp.asarray(in_slot),
        in_mask=jnp.asarray(in_mask), heavy=jnp.asarray(heavy),
        deploy=jnp.asarray(deploy),
        sinks=jnp.asarray(n_phys + 1 + np.arange(W)),
        n_phys=n_phys, n_sessions=W, n_bar=n_bar, depth_max=depth_max,
        src=n_phys, d_max=d_max, d_src=d_src, d_in_max=d_in_max,
        n_edges=graph.n_edges, d_light=d_light, n_heavy=n_heavy)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CECGraphSparseBatch:
    """B sparse CEC instances stacked on a leading axis (cf.
    :class:`CECGraphBatch`).

    Instances are padded to common (``n_phys``, ``depth_max``, slot
    widths) via :func:`pad_sparse_graph` and stacked leaf-wise;
    ``solve_jowr_batch`` / ``solve_routing_batch`` accept either batch
    flavor — the vmapped per-instance solver dispatches on the graph type.
    """

    # --- data (pytree leaves, leading axis = instance) ---
    nbr: jax.Array
    out_mask: jax.Array
    edge_mask: jax.Array
    capacity: jax.Array
    sink_slot: jax.Array
    src_nbr: jax.Array
    src_out_mask: jax.Array
    src_edge_mask: jax.Array
    src_capacity: jax.Array
    in_src: jax.Array
    in_slot: jax.Array
    in_mask: jax.Array
    heavy: jax.Array
    deploy: jax.Array
    sinks: jax.Array
    # --- static metadata (shared across instances) ---
    n_instances: int = dataclasses.field(metadata=dict(static=True))
    n_phys: int = dataclasses.field(metadata=dict(static=True))
    n_sessions: int = dataclasses.field(metadata=dict(static=True))
    n_bar: int = dataclasses.field(metadata=dict(static=True))
    depth_max: int = dataclasses.field(metadata=dict(static=True))
    src: int = dataclasses.field(metadata=dict(static=True))
    d_max: int = dataclasses.field(metadata=dict(static=True))
    d_src: int = dataclasses.field(metadata=dict(static=True))
    d_in_max: int = dataclasses.field(metadata=dict(static=True))
    n_edges: int = dataclasses.field(metadata=dict(static=True))
    d_light: int = dataclasses.field(metadata=dict(static=True))
    n_heavy: int = dataclasses.field(metadata=dict(static=True))

    _LEAVES = ("nbr", "out_mask", "edge_mask", "capacity", "sink_slot",
               "src_nbr", "src_out_mask", "src_edge_mask", "src_capacity",
               "in_src", "in_slot", "in_mask", "heavy", "deploy", "sinks")

    @classmethod
    def from_graphs(cls,
                    graphs: Sequence[CECGraphSparse]) -> "CECGraphSparseBatch":
        """Stack sparse instances, padding to the common slot widths."""
        if not graphs:
            raise ValueError("need at least one graph")
        W = graphs[0].n_sessions
        if any(g.n_sessions != W for g in graphs):
            raise ValueError("all instances must share the session count W")
        kw = dict(
            n_phys=max(g.n_phys for g in graphs),
            depth_max=max(g.depth_max for g in graphs),
            d_max=max(g.d_max for g in graphs),
            d_src=max(g.d_src for g in graphs),
            d_in_max=max(g.d_in_max for g in graphs),
            d_light=max(g.d_light for g in graphs),
            n_heavy=max(g.n_heavy for g in graphs))
        padded = [pad_sparse_graph(g, **kw) for g in graphs]
        leaves = {name: jnp.stack([getattr(g, name) for g in padded])
                  for name in cls._LEAVES}
        return cls(**leaves, n_instances=len(padded), n_sessions=W,
                   n_bar=padded[0].n_bar, src=padded[0].src,
                   n_edges=max(g.n_edges for g in graphs), **kw)

    def _graph(self, leaves, n_edges: int | None = None) -> CECGraphSparse:
        return CECGraphSparse(
            **dict(zip(self._LEAVES, leaves)),
            n_phys=self.n_phys, n_sessions=self.n_sessions, n_bar=self.n_bar,
            depth_max=self.depth_max, src=self.src, d_max=self.d_max,
            d_src=self.d_src, d_in_max=self.d_in_max,
            n_edges=self.n_edges if n_edges is None else n_edges,
            d_light=self.d_light, n_heavy=self.n_heavy)

    def stacked_graph(self) -> CECGraphSparse:
        """A ``CECGraphSparse`` view whose leaves carry the instance axis.

        The shared ``n_edges`` metadata is the batch maximum (instances
        differ; padding gives them one layout) — an upper bound, fine for
        the solvers, which never read it.
        """
        return self._graph([getattr(self, name) for name in self._LEAVES])

    def instance(self, b: int) -> CECGraphSparse:
        """Materialize instance ``b`` as a standalone ``CECGraphSparse``
        (with its *own* edge count recomputed from the masks, not the
        batch-level upper bound — ``density`` stays truthful)."""
        leaves = [getattr(self, name)[b] for name in self._LEAVES]
        n_edges = int(np.asarray(self.edge_mask[b]).sum()
                      + np.asarray(self.src_edge_mask[b]).sum())
        return self._graph(leaves, n_edges=n_edges)

    def uniform_phi(self):
        """Stacked ``SparsePhi`` — uniform routing per instance."""
        return self.stacked_graph().uniform_phi()


def stack_banks(banks: Sequence[UtilityBank]) -> UtilityBank:
    """Stack per-instance utility banks (same family/noise) along axis 0."""
    kind, noise = banks[0].kind, banks[0].noise
    if any(b.kind != kind or b.noise != noise for b in banks):
        raise ValueError("all banks must share kind and noise level")
    return UtilityBank(a=jnp.stack([b.a for b in banks]),
                       b=jnp.stack([b.b for b in banks]),
                       kind=kind, noise=noise)


def _bank_axis(bank: UtilityBank):
    """0 when the bank carries an instance axis, None to broadcast one."""
    return 0 if bank.a.ndim == 2 else None


def _vmapped_run(batch, banks, lam_total, config, *, iters, costfn,
                 state, phi0, lam0) -> _solver.Result:
    """The vmapped engine both fleet drivers share: each lane builds a
    ``Problem`` from its slice of the stacked graph/banks and scans
    ``solver.step``.  Pure traceable JAX — ``run_batch`` calls it
    directly, ``run_batch_sharded`` wraps it in a ``shard_map`` body
    (so it must not touch the host: no callbacks, no concrete reads)."""

    def one(graph, bank, state, phi0, lam0):
        problem = Problem(graph=graph, bank=bank, lam_total=lam_total,
                          cost=costfn)
        return _solver.run(problem, config, iters=iters, state=state,
                           phi0=phi0, lam0=lam0)

    in_axes = (0, _bank_axis(banks),
               None if state is None else 0,
               None if phi0 is None else 0,
               None if lam0 is None else 0)
    return jax.vmap(one, in_axes=in_axes)(
        batch.stacked_graph(), banks, state, phi0, lam0)


@functools.lru_cache(maxsize=None)
def _fused_step_batch(config: SolverConfig, costfn, donate: bool,
                      util_family: str | None, _dispatch_key):
    def one(g, lt, s, u, p, tel):
        problem = Problem(graph=g, bank=None, lam_total=lt, cost=costfn,
                          util_params=p, util_family=util_family)
        return _solver.step(problem, config, s, u, tel)

    if config.telemetry > 0:
        # telemetry rides as a stacked [K]-ring pytree right after the
        # state so (state, telemetry) donate as a pair — the recording
        # fleet steady state allocates nothing per interval (§18.1)
        def fn(graph, lam_total, state, task_utilities, telemetry,
               util_params=None):
            params_axis = None if util_params is None else 0
            return jax.vmap(one, in_axes=(0, 0, 0, 0, params_axis, 0))(
                graph, lam_total, state, task_utilities, util_params,
                telemetry)

        return jax.jit(fn, donate_argnums=(2, 4) if donate else ())

    def fn(graph, lam_total, state, task_utilities, util_params=None):
        params_axis = None if util_params is None else 0
        return jax.vmap(one, in_axes=(0, 0, 0, 0, params_axis, None))(
            graph, lam_total, state, task_utilities, util_params, None)

    return jax.jit(fn, donate_argnums=(2,) if donate else ())


def fused_step_batch(config: SolverConfig, *, cost="exp",
                     donate: bool = False, util_family: str | None = None):
    """``jit(vmap(step))`` over a tenant/instance axis, measured-utility mode.

    Returns ``fn(graph, lam_total, state, task_utilities) ->
    (SolverState, StepInfo)`` where every argument carries a leading
    instance axis: ``graph`` is a stacked view
    (``CECGraphBatch.stacked_graph()``), ``lam_total`` is [K] per-tenant
    demand (a traced leaf — demand shifts never retrace),
    ``state`` is a stacked ``SolverState`` (``lam`` [K, W]) and
    ``task_utilities`` is [K, 2W] measured utilities in
    ``perturbed_allocations`` row order.  Each lane builds a bank-less
    ``Problem`` from its slice, exactly like ``_vmapped_run`` — the fleet
    step *is* the single-tenant step.

    With ``util_family`` set (and ``config.grad_mode="learned"``) the
    returned fn accepts a trailing argument: stacked [K, W, P] fitted
    ``util_params`` — a data leaf, so per-tenant refits never retrace
    (DESIGN.md §16.4); ``task_utilities`` is then ignored (pass zeros).

    With ``config.telemetry > 0`` the returned fn takes a stacked
    ``[K]``-lane obs ring as its fifth positional argument —
    ``fn(graph, lam_total, state, task_utilities, telemetry,
    util_params=None)`` — records every lane inside the jit, returns the
    updated ring third, and donates it together with the state
    (DESIGN.md §18.1).

    ``donate=True`` donates the stacked ``state`` so the K control
    iterations update in place (the ``RouterFleet`` steady state,
    DESIGN.md §15.3).  Cached on ``(config, cost, donate, util_family,
    dispatch.state_key())`` — ``cost`` must be a registry name or a
    hashable ``CostFn``.
    """
    return _fused_step_batch(config, resolve_cost(cost), bool(donate),
                             util_family, dispatch.state_key())


def run_batch(
    batch: CECGraphBatch | CECGraphSparseBatch,
    banks: UtilityBank | Sequence[UtilityBank],
    lam_total,
    config: SolverConfig,
    *,
    iters: int,
    cost="exp",
    state: SolverState | None = None,
    phi0: Array | None = None,
    lam0: Array | None = None,
) -> _solver.Result:
    """``jax.vmap`` of ``solver.run`` over the instance axis.

    The batched engine *is* the single-instance engine: each vmapped lane
    builds a ``Problem`` from its slice of the stacked graph/banks and
    scans ``solver.step``.  ``banks`` is either a list of per-instance
    banks (stacked internally), a pre-stacked bank with ``a``/``b`` of
    shape [B, W], or a single bank (shape [W]) broadcast to every
    instance.  ``state`` (a stacked ``SolverState`` — e.g. a previous
    ``Result.state``) or ``phi0``/``lam0`` must carry a leading instance
    axis.  Returns a ``solver.Result`` whose fields are stacked over
    instances: ``lam`` [B, W], ``utility_traj`` [B, T], ….

    Fleets larger than one device's memory go through
    :func:`run_batch_sharded` — same engine, instance axis sharded over
    a device mesh.
    """
    if not isinstance(banks, UtilityBank):
        banks = stack_banks(list(banks))
    return _vmapped_run(batch, banks, lam_total, config, iters=iters,
                        costfn=resolve_cost(cost), state=state, phi0=phi0,
                        lam0=lam0)


def run_batch_sharded(
    batch: CECGraphBatch | CECGraphSparseBatch,
    banks: UtilityBank | Sequence[UtilityBank],
    lam_total,
    config: SolverConfig,
    *,
    iters: int,
    cost="exp",
    mesh=None,
    state: SolverState | None = None,
    phi0: Array | None = None,
    lam0: Array | None = None,
) -> _solver.Result:
    """:func:`run_batch` with the instance axis sharded over a device mesh.

    The fleet axis of every stacked pytree — the batch's graph leaves,
    per-instance banks, a carried ``SolverState``, ``phi0``/``lam0``
    overrides — is partitioned across ``mesh`` (default: the 1-D
    ``core.mesh.fleet_mesh()`` over all visible devices) with
    ``shard_map``; each device vmaps the solver core over its local
    shard.  The per-shard solves are embarrassingly parallel, so the
    mapped body contains no collectives and no host callbacks — the
    whole scan stays device-resident.

    Fleets that do not divide the mesh are padded with replicas of the
    last instance (``core.mesh.pad_fleet``) and the pad lanes
    are sliced off the result (``unpad_fleet``) — exact masking, not an
    approximation: the returned ``Result`` matches :func:`run_batch`
    lane-for-lane (bit-identical on a 1-device mesh, ≤1e-6 across
    device counts — the ``tests/test_sharded_fleet.py`` parity tier).

    ``banks`` follows the :func:`run_batch` contract: per-instance banks
    shard with the fleet, a single broadcast bank replicates to every
    device.  ``lam_total`` (scalar demand) always replicates.  Traces
    under ``dispatch.fleet_dispatch(mesh)`` so ``dispatch.state_key()``
    covers the mesh shape and cached jitted consumers never alias
    executables across meshes.
    """
    if not isinstance(banks, UtilityBank):
        banks = stack_banks(list(banks))
    costfn = resolve_cost(cost)
    if mesh is None:
        mesh = fleet_mesh()
    axis = fleet_axis(mesh)
    n_shards = int(mesh.shape[axis])
    B = batch.n_instances
    B_pad = fleet_padded_size(B, n_shards)

    bank_sharded = _bank_axis(banks) == 0
    sharded_in = (batch, banks if bank_sharded else None, state, phi0, lam0)
    sharded_in = pad_fleet(sharded_in, n_shards)
    batch_p, banks_p, state_p, phi0_p, lam0_p = sharded_in
    if B_pad != B:
        # pad_fleet grows the pytree leaves; the static instance count is
        # aux data that must follow suit for the batch view to stay honest
        batch_p = dataclasses.replace(batch_p, n_instances=B_pad)
    if not bank_sharded:
        banks_p = banks

    def body(batch, banks, state, phi0, lam0, lam_total):
        return _vmapped_run(batch, banks, lam_total, config, iters=iters,
                            costfn=costfn, state=state, phi0=phi0, lam0=lam0)

    args = (batch_p, banks_p, state_p, phi0_p, lam0_p,
            jnp.asarray(lam_total, jnp.float32))
    in_specs = (fleet_specs(batch_p, axis),
                fleet_specs(banks_p, axis, shard=bank_sharded),
                fleet_specs(state_p, axis),
                fleet_specs(phi0_p, axis),
                fleet_specs(lam0_p, axis),
                fleet_specs(args[-1], axis, shard=False))
    with dispatch.fleet_dispatch(mesh):
        out_specs = fleet_specs(jax.eval_shape(body, *args), axis)
        result = shard_map_compat(body, mesh, in_specs, out_specs)(*args)
    if B_pad != B:
        result = unpad_fleet(result, B)
    return result


def solve_jowr_batch(
    batch: CECGraphBatch | CECGraphSparseBatch,
    banks: UtilityBank | Sequence[UtilityBank],
    lam_total: float,
    *,
    method: Method = "single",
    cost_name: str = "exp",
    delta: float = 0.5,
    eta_outer: float = 0.05,
    eta_inner: float = 0.05,
    outer_iters: int = 100,
    inner_iters: int = 50,
    phi0: Array | None = None,
    lam0: Array | None = None,
) -> JOWRResult:
    """Solve every instance of ``batch`` in one vmapped program.

    Legacy shim over :func:`run_batch` (same banks/overrides contract).
    Returns a ``JOWRResult`` whose fields are stacked over instances:
    ``lam`` [B, W], ``phi`` [B, W, Nb, Nb], ``utility_traj`` [B, T],
    ``lam_traj`` [B, T, W].
    """
    config = SolverConfig.from_legacy(method=method, delta=delta,
                                      eta_outer=eta_outer,
                                      eta_inner=eta_inner,
                                      inner_iters=inner_iters)
    res = run_batch(batch, banks, lam_total, config, iters=outer_iters,
                    cost=cost_name, phi0=phi0, lam0=lam0)
    return JOWRResult.from_result(res)


def solve_routing_batch(
    batch: CECGraphBatch | CECGraphSparseBatch,
    cost: _costs.CostFn,
    lam: Array,
    phi0: Array,
    eta: float,
    n_iters: int,
    *,
    method: str = "omd",
) -> tuple[Array, Array]:
    """Vmapped routing oracle: OMD-RT (or SGP) over the instance axis.

    ``lam`` is [W] (broadcast) or [B, W]; ``phi0`` is [B, W, Nb, Nb] (use
    ``batch.uniform_phi()``).  Returns (φ [B, W, Nb, Nb], cost trajectories
    [B, n_iters]).
    """
    solver = {"omd": solve_routing, "sgp": solve_routing_sgp}[method]

    def one(graph, lam, phi0):
        return solver(graph, cost, lam, phi0, eta, n_iters)

    lam = jnp.asarray(lam)
    lam_axis = 0 if lam.ndim == 2 else None
    return jax.vmap(one, in_axes=(0, lam_axis, 0))(
        batch.stacked_graph(), lam, phi0)
