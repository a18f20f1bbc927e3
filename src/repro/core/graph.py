"""Augmented CEC flow graph (paper §II-A, §II-C).

Builds the augmented graph Ḡ = (N̄, Ē) from a physical topology:

* a virtual source ``S`` (the admission controller) with edges to every
  device deploying the *smallest* model version ``D(1)`` (paper §II-C);
* one virtual sink ``D_w`` per model version ``w`` with edges from every
  device in ``D(w)``;  the computation cost of node ``i`` becomes the link
  cost of the virtual edge ``(i, D_w)`` (paper eq. (6)).

Loop-freedom (required by Gallager routing variables) is enforced
structurally: physical edges are oriented along a BFS-layer total order from
``S``, so any row-stochastic φ is automatically loop-free and the flow
propagation fixed point is reached in ≤ ``depth_max`` relaxation steps
(DESIGN.md §3).  Per-session edge masks additionally encode:

* nodes in ``D(w)`` forward session ``w`` only to ``D_w`` (paper constr. (3):
  a deploying node processes, never relays, its own session);
* edges are kept only if the head can still reach ``D_w`` ("useful" nodes),
  so every unit of admitted traffic provably drains into its sink.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import numpy as np
import jax.numpy as jnp


class InfeasibleTopology(RuntimeError):
    """Raised when some session has no S→D_w path in the oriented DAG."""


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CECGraph:
    """Static description of the augmented CEC graph.

    Array fields are pytree leaves; scalar metadata is static (hashable) so a
    ``CECGraph`` can be closed over or passed through ``jax.jit``.
    """

    # --- data (pytree leaves) ---
    out_mask: jax.Array      # [W, Nb, Nb] float {0,1}: session-w allowed out-edges
    edge_mask: jax.Array     # [Nb, Nb]    float {0,1}: union of session masks
    capacity: jax.Array      # [Nb, Nb]    link/compute capacities (1 where unused)
    deploy: jax.Array        # [W, N]      bool: node i hosts version w
    sinks: jax.Array         # [W]         int: index of virtual sink D_w
    # --- static metadata ---
    n_phys: int = dataclasses.field(metadata=dict(static=True))
    n_sessions: int = dataclasses.field(metadata=dict(static=True))
    n_bar: int = dataclasses.field(metadata=dict(static=True))
    depth_max: int = dataclasses.field(metadata=dict(static=True))
    src: int = dataclasses.field(metadata=dict(static=True))

    @property
    def W(self) -> int:
        return self.n_sessions

    def uniform_phi(self) -> jax.Array:
        """Uniform routing over allowed out-edges (Alg. 2 line 1)."""
        rowsum = self.out_mask.sum(-1, keepdims=True)
        return self.out_mask / jnp.where(rowsum > 0, rowsum, 1.0)

    def injection(self, lam: jax.Array) -> jax.Array:
        """[W, Nb] exogenous injection: session w's rate λ_w enters at S."""
        inject = jnp.zeros((self.n_sessions, self.n_bar), lam.dtype)
        return inject.at[:, self.src].set(lam)


class _AugmentedStructure(NamedTuple):
    """Numpy scaffolding shared by the dense and sparse assemblers.

    ``_analyze`` performs every topology decision exactly once — alive
    masking, BFS layering, DAG orientation, per-session usefulness — so
    ``build_augmented`` (dense ``[W, N̄, N̄]`` masks) and
    ``build_augmented_sparse`` (padded edge lists, DESIGN.md §12) cannot
    drift apart structurally.
    """

    adj: np.ndarray       # [N, N] alive-masked physical adjacency
    deploy: np.ndarray    # [W, N] alive-masked deployment
    dag: np.ndarray       # [N, N] BFS-layer oriented physical edges
    useful: np.ndarray    # [W, N] node can still deliver session w to D_w
    d1: np.ndarray        # [N] admission points D(1)
    key: np.ndarray       # [N] total-order key of the DAG orientation


def _bfs_depth(adj: np.ndarray, sources: np.ndarray) -> np.ndarray:
    n = adj.shape[0]
    depth = np.full(n, np.inf)
    depth[sources] = 0.0
    frontier = list(np.nonzero(sources)[0])
    d = 0
    while frontier:
        d += 1
        nxt = []
        for i in frontier:
            for j in np.nonzero(adj[i])[0]:
                if depth[j] == np.inf:
                    depth[j] = d
                    nxt.append(j)
        frontier = nxt
    return depth


def _analyze(adj_undirected: np.ndarray, deploy: np.ndarray,
             alive: np.ndarray | None) -> _AugmentedStructure:
    """Alive masking + BFS layering + DAG orientation + usefulness pruning."""
    adj = np.asarray(adj_undirected, bool)
    deploy = np.asarray(deploy, bool)
    W, N = deploy.shape
    if not (deploy.sum(0) == 1).all():
        raise ValueError("each node must deploy exactly one model version")
    relaxed = alive is not None
    alive = np.ones(N, bool) if alive is None else np.asarray(alive, bool)
    adj = adj & alive[:, None] & alive[None, :]
    deploy = deploy & alive[None, :]
    if (deploy.sum(1) == 0).any():
        raise InfeasibleTopology("some model version has no (alive) deployment")

    # BFS layering from the admission points D(1); S sits at depth -1.
    d1 = deploy[0]
    depth = _bfs_depth(adj, d1)
    unreachable = np.isinf(depth)
    if unreachable.any() and not relaxed:
        raise InfeasibleTopology("physical graph is not connected")
    # Total order key → DAG orientation (strict, ties broken by index).
    # Unreachable/dead nodes sort after every reachable node (max reachable
    # key is < N², edgeless anyway for dead ones).
    key = np.where(unreachable, float(N * N), depth * N) + np.arange(N)
    dag = adj & (key[:, None] < key[None, :])

    # usefulness: can node i still deliver session-w traffic to D_w?
    order = np.argsort(key)                      # topological order of the DAG
    useful = np.zeros((W, N), bool)
    for w in range(W):
        useful[w, deploy[w]] = True
        for i in order[::-1]:
            if deploy[w, i]:
                continue                         # D(w) nodes never relay w
            useful[w, i] = bool((dag[i] & useful[w]).any())

    return _AugmentedStructure(adj=adj, deploy=deploy, dag=dag,
                               useful=useful, d1=d1, key=key)


def _relaxation_depth(any_edge: np.ndarray, key: np.ndarray, N: int,
                      W: int) -> int:
    """Longest path in the augmented union DAG + 1 — the exact Jacobi
    relaxation step count (shared by both assemblers)."""
    n_bar = N + 1 + W
    akey = np.concatenate([key, [-1.0], key.max() + 1 + np.arange(W)])
    aorder = np.argsort(akey)
    lp = np.zeros(n_bar)
    for i in aorder:
        heads = np.nonzero(any_edge[:, i])[0]
        if heads.size:
            lp[i] = lp[heads].max() + 1
    return int(lp.max()) + 1


def build_augmented(
    adj_undirected: np.ndarray,
    deploy: np.ndarray,
    link_capacity: np.ndarray,
    compute_capacity: np.ndarray,
    src_capacity: float = 1e4,
    alive: np.ndarray | None = None,
) -> CECGraph:
    """Build the augmented DAG from a physical topology.

    Args:
      adj_undirected: [N, N] bool symmetric physical adjacency.
      deploy: [W, N] bool, exactly one version per node (paper §II-A).
      link_capacity: [N, N] symmetric positive capacities C_ij.
      compute_capacity: [N] node compute capacities C_i.
      src_capacity: capacity of the virtual admission links (S, i).
      alive: optional [N] bool node-liveness mask (scenario engine,
        DESIGN.md §10).  Dead nodes stay in the index space but get no
        edges and no deployment — exactly the isolated-pad-node convention
        of ``core/batch.pad_graph`` — so iterates warm-start across
        fail/join events without any index remapping.  With an explicit
        ``alive`` the physical graph may be disconnected: unreachable
        nodes are ordered after all reachable ones and usefulness pruning
        inerts them; only session-level reachability from S is enforced.
    """
    s = _analyze(adj_undirected, deploy, alive)
    deploy = s.deploy
    W, N = deploy.shape

    src = N
    sinks = np.arange(W) + N + 1
    n_bar = N + 1 + W

    out_mask = np.zeros((W, n_bar, n_bar), np.float32)
    for w in range(W):
        relay = ~deploy[w]
        # physical relays: DAG edges whose head is still useful for w
        m = s.dag & relay[:, None] & s.useful[w][None, :]
        # ... and whose tail can receive w-traffic at all
        m &= s.useful[w][:, None]
        out_mask[w, :N, :N] = m
        out_mask[w, np.nonzero(deploy[w])[0], sinks[w]] = 1.0  # D(w) → D_w
        out_mask[w, src, :N] = (s.d1 & s.useful[w]).astype(np.float32)
        if out_mask[w, src].sum() == 0:
            raise InfeasibleTopology(f"session {w} unreachable from S")

    edge_mask = (out_mask.sum(0) > 0).astype(np.float32)

    cap = np.ones((n_bar, n_bar), np.float32)
    cap[:N, :N] = np.asarray(link_capacity, np.float32)
    for w in range(W):
        cap[:N, sinks[w]] = np.asarray(compute_capacity, np.float32)
    cap[src, :N] = src_capacity

    depth_max = _relaxation_depth(edge_mask > 0, s.key, N, W)

    return CECGraph(
        out_mask=jnp.asarray(out_mask),
        edge_mask=jnp.asarray(edge_mask),
        capacity=jnp.asarray(cap),
        deploy=jnp.asarray(deploy),
        sinks=jnp.asarray(sinks),
        n_phys=N,
        n_sessions=W,
        n_bar=n_bar,
        depth_max=depth_max,
        src=src,
    )


def random_deployment(n: int, n_versions: int, rng: np.random.Generator) -> np.ndarray:
    """Random one-version-per-node deployment with every version present."""
    assign = rng.integers(0, n_versions, size=n)
    assign[:n_versions] = np.arange(n_versions)    # guarantee coverage
    rng.shuffle(assign)
    deploy = np.zeros((n_versions, n), bool)
    deploy[assign, np.arange(n)] = True
    return deploy


class InstanceDraw(NamedTuple):
    """A feasible random instance: the built graph plus the raw numpy state
    (``deploy``, ``link_capacity``, ``compute_capacity``) the scenario
    engine mutates between segments (DESIGN.md §10)."""

    graph: CECGraph
    deploy: np.ndarray
    link_capacity: np.ndarray
    compute_capacity: np.ndarray


def draw_instance(
    adj: np.ndarray,
    n_versions: int,
    mean_link_capacity: float,
    seed: int,
    mean_compute_capacity: float | None = None,
    max_tries: int = 50,
) -> InstanceDraw:
    """Randomized capacities + deployment (paper §IV experiment setup).

    Link capacities C_ij ~ U[0, 2·C̄] (floored at 0.05·C̄ for numerical
    sanity of the exp link cost), retried until the instance is feasible.
    """
    n = adj.shape[0]
    mean_cc = mean_compute_capacity or mean_link_capacity
    for t in range(max_tries):
        rng = np.random.default_rng(seed + 1000 * t)
        cap = rng.uniform(0.05, 2.0, size=(n, n)) * mean_link_capacity
        cap = np.maximum(cap, cap.T)  # symmetric draw per undirected link
        comp = rng.uniform(0.5, 1.5, size=n) * mean_cc
        deploy = random_deployment(n, n_versions, rng)
        try:
            graph = build_augmented(adj, deploy, cap, comp)
        except InfeasibleTopology:
            continue
        return InstanceDraw(graph, deploy, cap, comp)
    raise InfeasibleTopology(f"no feasible instance after {max_tries} tries")


def build_random_cec(
    adj: np.ndarray,
    n_versions: int,
    mean_link_capacity: float,
    seed: int,
    mean_compute_capacity: float | None = None,
    max_tries: int = 50,
) -> CECGraph:
    """``draw_instance`` returning only the built graph (the common case)."""
    return draw_instance(adj, n_versions, mean_link_capacity, seed,
                         mean_compute_capacity, max_tries).graph


# ---------------------------------------------------------------------------
# sparse edge-list representation (DESIGN.md §12)
# ---------------------------------------------------------------------------

class SparsePhi(NamedTuple):
    """Edge-slot field over a :class:`CECGraphSparse` — routing variables φ,
    and (by structural identity) the marginal-cost field δ.

    ``rows[w, i, d]`` sits on the edge ``(i, nbr[i, d])`` — physical relay
    edges plus each deploying node's compute edge; ``src[w, d]`` sits on the
    admission edge ``(S, src_nbr[d])``.  The virtual source's fan-out is
    Θ(N/W) (every node deploying the smallest version), so it gets its own
    dense row instead of inflating the per-node slot count ``d_max`` — the
    hub-row exception that keeps the padded layout O(E) (DESIGN.md §12.1).
    Invariant: entries on invalid slots (mask 0) are exactly zero.
    """

    rows: jax.Array      # [W, Nb, D]
    src: jax.Array       # [W, Ds]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CECGraphSparse:
    """Sparse (padded edge-list) twin of :class:`CECGraph`.

    Same augmented-node index space (physical ``[0, N)``, source ``N``,
    sinks ``N+1+w`` — ``pad_graph``-compatible, alive-mask-compatible) and
    the same static-metadata/jit contract, but state is O(E) instead of
    O(N̄²): a CSR-style padded out-edge list per node (``nbr``/``out_mask``/
    ``capacity``, ``d_max`` slots), a dedicated admission row for the
    virtual source (``src_*``, ``d_src`` slots), and a padded CSC in-edge
    list over the physical relay edges (``in_*``, ``d_in_max`` slots) that
    turns flow propagation into a gather + row-sum instead of a scatter.
    Compute (sink) edges live in their tail's row (slot ``sink_slot[i]``);
    sink inflow is accumulated analytically (W scalars), never via the
    in-lists, so virtual-node hubs cannot inflate the padded degree.
    The marginal recursion reads the out-rows in two blocks
    (:func:`marginal_split`): the first ``d_light`` slots of every row, and
    the ``n_heavy`` rows listed in ``heavy`` at full width.
    Solvers accept either representation (``core.flow`` / ``core.marginal``
    / ``core.routing`` dispatch on the type); ``core.dispatch.
    maybe_sparsify`` converts automatically past the (N, density)
    threshold.
    """

    # --- CSR out-edge rows: physical relay + compute edges ---
    nbr: jax.Array          # [Nb, D] int32 head of slot (i,d); pad → i
    out_mask: jax.Array     # [W, Nb, D] float {0,1} session-allowed slots
    edge_mask: jax.Array    # [Nb, D] float {0,1} union of session slots
    capacity: jax.Array     # [Nb, D] capacities (1 where unused)
    sink_slot: jax.Array    # [N] int32 slot of node i's compute edge (else 0)
    # --- virtual-source admission row ---
    src_nbr: jax.Array      # [Ds] int32 heads of S→D(1) edges; pad → src
    src_out_mask: jax.Array  # [W, Ds]
    src_edge_mask: jax.Array  # [Ds]
    src_capacity: jax.Array  # [Ds]
    # --- CSC in-edge lists over physical relay edges only ---
    in_src: jax.Array       # [Nb, Din] int32 tail; pad → 0
    in_slot: jax.Array      # [Nb, Din] int32 slot in the tail's row; pad → 0
    in_mask: jax.Array      # [Nb, Din] float {0,1}
    # --- rows the marginal recursion gathers at full width ---
    heavy: jax.Array        # [n_heavy] int32 rows wider than d_light
    # --- shared with the dense twin ---
    deploy: jax.Array       # [W, N] bool
    sinks: jax.Array        # [W] int
    # --- static metadata ---
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

    @property
    def W(self) -> int:
        return self.n_sessions

    @property
    def density(self) -> float:
        """Union edge count over the dense N̄² slot budget."""
        return self.n_edges / float(self.n_bar * self.n_bar)

    def uniform_phi(self) -> SparsePhi:
        """Uniform routing over allowed slots (Alg. 2 line 1)."""
        rowsum = self.out_mask.sum(-1, keepdims=True)
        rows = self.out_mask / jnp.where(rowsum > 0, rowsum, 1.0)
        ssum = self.src_out_mask.sum(-1, keepdims=True)
        return SparsePhi(rows=rows,
                         src=self.src_out_mask / jnp.where(ssum > 0, ssum, 1.0))

    def injection(self, lam: jax.Array) -> jax.Array:
        """[W, Nb] exogenous injection: session w's rate λ_w enters at S."""
        inject = jnp.zeros((self.n_sessions, self.n_bar), lam.dtype)
        return inject.at[:, self.src].set(lam)


def marginal_split(edge_mask) -> tuple[int, np.ndarray]:
    """``(d_light, heavy)``: how the marginal recursion splits the out-rows.

    Rows are packed left, so a row's out-degree is one past its last slot
    in use.  Each recursion step gathers ``N̄·d + n_heavy(d)·d_max`` slots:
    the first ``d`` slots of every row, and the rows with more edges than
    that at full width.  A gather on the TPU costs per index element, not
    per byte (DESIGN.md §12.1), so ``d`` minimises that count; the split
    engages only where it at most halves the full width's ``N̄·d_max``, and
    is otherwise ``(d_max, no rows)``: the full-width step.
    """
    em = np.asarray(edge_mask) > 0
    n_bar, d_max = em.shape
    deg = np.where(em.any(1), d_max - np.argmax(em[:, ::-1], axis=1), 0)
    n_wider = n_bar - np.cumsum(np.bincount(deg, minlength=d_max + 1))[1:]
    gathered = n_bar * np.arange(1, d_max + 1) + n_wider * d_max
    d_light = int(np.argmin(gathered)) + 1
    if 2 * gathered[d_light - 1] > n_bar * d_max:
        return d_max, np.zeros(0, np.int32)
    return d_light, np.nonzero(deg > d_light)[0].astype(np.int32)


def _pack_sparse(row_heads, row_sess, row_caps, src_heads, src_sess,
                 src_caps, deploy, depth_max: int) -> CECGraphSparse:
    """Assemble a :class:`CECGraphSparse` from per-node edge lists.

    ``row_heads[i]`` is node i's sorted head array (relay heads first,
    compute edge last — sink indices exceed every physical index);
    ``row_sess[i]`` is the matching [W, k] session-membership block and
    ``row_caps[i]`` the [k] capacities.  The source row comes as flat
    arrays.  Padding conventions: out slots point at their own row
    (``nbr`` pad → i), in slots at (0, 0) — all gathers stay in-bounds and
    every padded entry is killed by a zero mask.
    """
    W, N = np.asarray(deploy, bool).shape
    src = N
    n_bar = N + 1 + W

    d_max = max([1] + [len(h) for h in row_heads])
    nbr = np.tile(np.arange(n_bar, dtype=np.int32)[:, None], (1, d_max))
    out_mask = np.zeros((W, n_bar, d_max), np.float32)
    edge_mask = np.zeros((n_bar, d_max), np.float32)
    capacity = np.ones((n_bar, d_max), np.float32)
    sink_slot = np.zeros(N, np.int32)
    in_lists: list[list[tuple[int, int]]] = [[] for _ in range(N)]
    for i, heads in enumerate(row_heads):
        k = len(heads)
        if k == 0:
            continue
        nbr[i, :k] = heads
        out_mask[:, i, :k] = row_sess[i]
        edge_mask[i, :k] = (np.asarray(row_sess[i]).sum(0) > 0)
        capacity[i, :k] = row_caps[i]
        for d, j in enumerate(heads):
            if j > src:                      # compute edge → virtual sink
                sink_slot[i] = d
            elif j < N:                      # physical relay edge
                in_lists[j].append((i, d))

    d_src = max(1, len(src_heads))
    src_nbr = np.full(d_src, src, np.int32)
    src_out_mask = np.zeros((W, d_src), np.float32)
    src_edge_mask = np.zeros(d_src, np.float32)
    src_capacity = np.ones(d_src, np.float32)
    k = len(src_heads)
    if k:
        src_nbr[:k] = src_heads
        src_out_mask[:, :k] = src_sess
        src_edge_mask[:k] = (np.asarray(src_sess).sum(0) > 0)
        src_capacity[:k] = src_caps

    d_in = max([1] + [len(l) for l in in_lists])
    in_src = np.zeros((n_bar, d_in), np.int32)
    in_slot = np.zeros((n_bar, d_in), np.int32)
    in_mask = np.zeros((n_bar, d_in), np.float32)
    for j, lst in enumerate(in_lists):
        for d, (i, sl) in enumerate(lst):
            in_src[j, d], in_slot[j, d], in_mask[j, d] = i, sl, 1.0

    n_edges = int(edge_mask.sum() + src_edge_mask.sum())
    d_light, heavy = marginal_split(edge_mask)
    return CECGraphSparse(
        nbr=jnp.asarray(nbr), out_mask=jnp.asarray(out_mask),
        edge_mask=jnp.asarray(edge_mask), capacity=jnp.asarray(capacity),
        sink_slot=jnp.asarray(sink_slot),
        src_nbr=jnp.asarray(src_nbr), src_out_mask=jnp.asarray(src_out_mask),
        src_edge_mask=jnp.asarray(src_edge_mask),
        src_capacity=jnp.asarray(src_capacity),
        in_src=jnp.asarray(in_src), in_slot=jnp.asarray(in_slot),
        in_mask=jnp.asarray(in_mask), heavy=jnp.asarray(heavy),
        deploy=jnp.asarray(np.asarray(deploy, bool)),
        sinks=jnp.asarray(N + 1 + np.arange(W)),
        n_phys=N, n_sessions=W, n_bar=n_bar, depth_max=depth_max, src=src,
        d_max=d_max, d_src=d_src, d_in_max=d_in, n_edges=n_edges,
        d_light=d_light, n_heavy=len(heavy))


def sparsify(graph: CECGraph) -> CECGraphSparse:
    """Convert a dense :class:`CECGraph` to the edge-list layout.

    Exactly equivalent (``tests/test_sparse_parity.py``): same index
    space, same ``depth_max``, and slot order matching
    :func:`build_augmented_sparse` (heads ascending — the compute edge,
    whose sink index exceeds every physical index, lands last).
    """
    om = np.asarray(graph.out_mask)
    em = np.asarray(graph.edge_mask)
    cap = np.asarray(graph.capacity)
    N, src = graph.n_phys, graph.src

    row_heads, row_sess, row_caps = [], [], []
    for i in range(N):
        heads = np.nonzero(em[i] > 0)[0].astype(np.int32)
        row_heads.append(heads)
        row_sess.append(om[:, i, heads].astype(np.float32))
        row_caps.append(cap[i, heads].astype(np.float32))
    src_heads = np.nonzero(em[src] > 0)[0].astype(np.int32)
    return _pack_sparse(row_heads, row_sess, row_caps,
                        src_heads, om[:, src, src_heads].astype(np.float32),
                        cap[src, src_heads].astype(np.float32),
                        np.asarray(graph.deploy), graph.depth_max)


def build_augmented_sparse(
    adj_undirected: np.ndarray,
    deploy: np.ndarray,
    link_capacity: np.ndarray,
    compute_capacity: np.ndarray,
    src_capacity: float = 1e4,
    alive: np.ndarray | None = None,
) -> CECGraphSparse:
    """Build the augmented DAG directly in the edge-list layout.

    Same arguments and semantics as :func:`build_augmented` but never
    materializes a ``[W, N̄, N̄]`` tensor — O(N² bool + E) working memory —
    so fleet-scale topologies (N ≥ 1024, ``topo.topologies`` generators)
    build without the dense detour.  ``sparsify(build_augmented(x)) ==
    build_augmented_sparse(x)`` array-for-array (tested).
    """
    s = _analyze(adj_undirected, deploy, alive)
    W, N = s.deploy.shape
    sinks = N + 1 + np.arange(W)
    link_capacity = np.asarray(link_capacity, np.float32)
    compute_capacity = np.asarray(compute_capacity, np.float32)

    row_heads, row_sess, row_caps = [], [], []
    for i in range(N):
        heads = np.nonzero(s.dag[i])[0]
        sess = np.zeros((W, len(heads)), bool)
        for w in range(W):
            if not s.deploy[w, i] and s.useful[w, i]:
                sess[w] = s.useful[w][heads]
        keep = sess.any(0)
        heads, sess = heads[keep], sess[:, keep]
        caps = link_capacity[i, heads]
        wdep = np.nonzero(s.deploy[:, i])[0]
        if wdep.size:                            # compute edge D(w) → D_w
            w = int(wdep[0])
            heads = np.concatenate([heads, [sinks[w]]])
            col = np.zeros((W, 1), bool)
            col[w] = True
            sess = np.concatenate([sess, col], axis=1)
            caps = np.concatenate([caps, [compute_capacity[i]]])
        row_heads.append(heads.astype(np.int32))
        row_sess.append(sess.astype(np.float32))
        row_caps.append(caps.astype(np.float32))

    src_sess = np.stack([s.d1 & s.useful[w] for w in range(W)])   # [W, N]
    for w in range(W):
        if src_sess[w].sum() == 0:
            raise InfeasibleTopology(f"session {w} unreachable from S")
    src_heads = np.nonzero(src_sess.any(0))[0].astype(np.int32)

    any_edge = np.zeros((N + 1 + W, N + 1 + W), bool)
    for i in range(N):
        any_edge[i, row_heads[i]] = True
    any_edge[N, src_heads] = True
    depth_max = _relaxation_depth(any_edge, s.key, N, W)

    return _pack_sparse(
        row_heads, row_sess, row_caps, src_heads,
        src_sess[:, src_heads].astype(np.float32),
        np.full(len(src_heads), src_capacity, np.float32),
        s.deploy, depth_max)
