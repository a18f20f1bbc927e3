"""Fleet-scale distributed control plane (beyond-paper extension).

The paper evaluates ≤40-node networks; a production CEC fleet has 10³–10⁵
devices.  Here the OMD-RT state itself is sharded over the TPU mesh:

  φ  [W, N, N]  → P(None, 'data', 'model')   (row-blocks × col-blocks)
  t  [W, N]     → P(None, 'data')            (node blocks)
  δ  [W, N, N]  → like φ

One control iteration is then three SPMD phases, each mapping onto mesh
collectives exactly the way the paper's message passing maps onto the
physical network:

  1. flow propagation  t·Φ  — contraction over the 'data'-sharded node
     axis → reduce-scatter (the "workload forwarding" messages);
  2. marginal-cost broadcast — the same contraction on the reversed graph
     (the paper's hop-by-hop broadcast protocol);
  3. exponentiated-gradient row update — row-local softmax, no comms.

``solve_routing_sharded`` jits the full loop with those shardings; the
Pallas kernels (flow_step / omd_update) are the per-shard compute bodies
on real TPUs.  Tested on a fake 8-device mesh in
tests/test_distributed_core.py.

Sharding and sparsity are complementary scale axes: this module shards the
*dense* [W, N, N] state across a mesh, while ``core/sparse.py`` shrinks
the state itself to O(E) (``CECGraphSparse``, DESIGN.md §12) — the right
tool for single-host fleet topologies whose density is ≪ 1.  The
``dispatch.use_sparse`` policy picks the representation; a sharded sparse
layout (edge-partitioned segments) is the natural composition once both
axes are needed at once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .costs import CostFn
from .graph import CECGraph
from .routing import solve_routing


def routing_shardings(mesh):
    """(φ/δ sharding, t sharding) for the control-plane state."""
    return (NamedSharding(mesh, P(None, "data", "model")),
            NamedSharding(mesh, P(None, "data")))


def solve_routing_sharded(graph: CECGraph, cost: CostFn, lam, phi0,
                          eta: float, n_iters: int, mesh):
    """pjit'd OMD-RT with mesh-sharded state. Semantics identical to
    core.routing.solve_routing (tested); layout sharded for fleet scale."""
    sh_phi, sh_t = routing_shardings(mesh)
    sh_graph = CECGraph(
        out_mask=sh_phi, edge_mask=NamedSharding(mesh, P("data", "model")),
        capacity=NamedSharding(mesh, P("data", "model")),
        deploy=NamedSharding(mesh, P()), sinks=NamedSharding(mesh, P()),
        n_phys=graph.n_phys, n_sessions=graph.n_sessions,
        n_bar=graph.n_bar, depth_max=graph.depth_max, src=graph.src)

    fn = jax.jit(
        lambda g, l, p: solve_routing(g, cost, l, p, eta, n_iters),
        in_shardings=(sh_graph, NamedSharding(mesh, P()), sh_phi),
        out_shardings=(sh_phi, None),
        static_argnames=())
    with mesh:
        return fn(graph, jnp.asarray(lam), phi0)


def lower_control_plane(n_nodes: int, n_sessions: int, mesh, eta=1.0,
                        n_iters=10):
    """Dry-run lowering of the control plane at fleet scale (no data):
    proves the sharded CEC iteration compiles on the production mesh."""
    import numpy as np

    n_bar = n_nodes + 1 + n_sessions
    pad = (-n_bar) % int(np.prod([mesh.shape[a] for a in ("data",)]) * 1)
    n_bar += pad

    from .costs import get as get_cost

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    graph = CECGraph(
        out_mask=sds((n_sessions, n_bar, n_bar)),
        edge_mask=sds((n_bar, n_bar)), capacity=sds((n_bar, n_bar)),
        deploy=sds((n_sessions, n_nodes), jnp.bool_),
        sinks=sds((n_sessions,), jnp.int32),
        n_phys=n_nodes, n_sessions=n_sessions, n_bar=n_bar,
        depth_max=16, src=n_nodes)
    sh_phi, sh_t = routing_shardings(mesh)
    sh_graph = CECGraph(
        out_mask=sh_phi, edge_mask=NamedSharding(mesh, P("data", "model")),
        capacity=NamedSharding(mesh, P("data", "model")),
        deploy=NamedSharding(mesh, P()), sinks=NamedSharding(mesh, P()),
        n_phys=n_nodes, n_sessions=n_sessions, n_bar=n_bar,
        depth_max=16, src=n_nodes)
    cost = get_cost("exp")
    fn = jax.jit(lambda g, l, p: solve_routing(g, cost, l, p, eta, n_iters),
                 in_shardings=(sh_graph, NamedSharding(mesh, P()), sh_phi))
    with mesh:
        lowered = fn.lower(graph, sds((n_sessions,)),
                           sds((n_sessions, n_bar, n_bar)))
        return lowered.compile()
