"""Augmented-graph invariants: DAG/loop-freedom, per-session masks,
feasibility, topology generators."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    # only the property tests skip; the rest of the module still runs
    from hypothesis_stub import given, settings, st

from repro.core import build_random_cec
from repro.core.graph import InfeasibleTopology, build_augmented, random_deployment
from repro.topo import (abilene, balanced_tree, connected_er, fog, geant,
                        make_fleet, make_topology, power_law)


def _is_dag(edge_mask: np.ndarray) -> bool:
    n = edge_mask.shape[0]
    indeg = (edge_mask > 0).sum(0)
    stack = [i for i in range(n) if indeg[i] == 0]
    seen = 0
    while stack:
        i = stack.pop()
        seen += 1
        for j in np.nonzero(edge_mask[i])[0]:
            indeg[j] -= 1
            if indeg[j] == 0:
                stack.append(int(j))
    return seen == n


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(8, 40),
       w=st.integers(2, 5))
def test_augmented_graph_invariants(seed, n, w):
    g = build_random_cec(connected_er(n, 0.3, seed=seed), w, 10.0, seed=seed)
    out = np.asarray(g.out_mask)
    edge = np.asarray(g.edge_mask)
    deploy = np.asarray(g.deploy)
    sinks = np.asarray(g.sinks)

    # structural loop-freedom: ANY routing in the mask is cycle-free
    assert _is_dag(edge)
    # every session admits traffic at S
    assert (out[:, g.src].sum(-1) > 0).all()
    # deploying nodes forward their session only to the virtual sink
    for ww in range(w):
        rows = np.nonzero(deploy[ww])[0]
        assert (out[ww, rows].sum(-1) == 1).all()
        assert (out[ww, rows, sinks[ww]] == 1).all()
    # sinks have no out-edges
    assert (out[:, sinks].sum(-1) == 0).all()
    # every edge head with session-w in-flow potential has out-capacity for w
    for ww in range(w):
        recv = out[ww].sum(0) > 0            # nodes that can receive w
        phys = recv[: g.n_phys]
        can_fwd = out[ww, : g.n_phys].sum(-1) > 0
        assert (~phys | can_fwd).all(), "received traffic must be forwardable"


def test_each_version_must_be_deployed():
    adj = connected_er(10, 0.4, seed=0)
    deploy = np.zeros((3, 10), bool)
    deploy[0, :5] = True
    deploy[1, 5:] = True        # version 2 missing
    with pytest.raises(InfeasibleTopology):
        build_augmented(adj, deploy, np.ones((10, 10)), np.ones(10))


def test_random_deployment_covers_all_versions():
    rng = np.random.default_rng(0)
    for _ in range(20):
        d = random_deployment(12, 4, rng)
        assert (d.sum(0) == 1).all()
        assert (d.sum(1) >= 1).all()


@pytest.mark.parametrize("name,n,degmin", [
    ("abilene", 11, 1), ("balanced_tree", 14, 1), ("fog", 15, 2),
    ("geant", 22, 2), ("connected_er", 25, 1),
])
def test_topology_generators(name, n, degmin):
    adj, cbar = make_topology(name)
    assert adj.shape[0] == n
    assert (adj == adj.T).all()
    assert not adj.diagonal().any()
    assert (adj.sum(0) >= degmin).all()
    assert cbar > 0


def _is_connected(adj: np.ndarray) -> bool:
    seen, frontier = {0}, [0]
    while frontier:
        for j in np.nonzero(adj[frontier.pop()])[0]:
            if int(j) not in seen:
                seen.add(int(j))
                frontier.append(int(j))
    return len(seen) == adj.shape[0]


# (kind, n, m, seed): grid_2d and random_geometric through make_fleet;
# power_law with its attachment count m, which make_fleet fixes at 2
FLEET_CASES = [("grid_2d", 64, None, 0), ("grid_2d", 256, None, 0),
               *[("random_geometric", n, None, s)
                 for n in (64, 256) for s in (0, 1)],
               *[("power_law", 300, m, s) for m in (2, 3) for s in (0, 1)]]


def _fleet(kind, n, m, seed):
    if kind == "power_law":
        return power_law(n, m, seed=seed)
    return make_fleet(kind, n, seed=seed)


@pytest.mark.parametrize("kind,n,m,seed", FLEET_CASES,
                         ids=[f"{k}-n{n}" + (f"-m{m}" if m else "")
                              + f"-s{s}" for k, n, m, s in FLEET_CASES])
def test_fleet_generators(kind, n, m, seed):
    """The fleet-scale generators: n nodes, a symmetric boolean matrix
    with an empty diagonal, connected, and the same matrix from the same
    seed; Barabási–Albert has its exact link count (a clique of m+1
    seeds, then m links per later node)."""
    adj = _fleet(kind, n, m, seed)
    assert adj.dtype == bool and adj.shape == (n, n)
    assert (adj == adj.T).all()
    assert not adj.diagonal().any()
    assert _is_connected(adj)
    np.testing.assert_array_equal(adj, _fleet(kind, n, m, seed))
    if kind == "power_law":
        assert adj.sum() // 2 == (m + 1) * m // 2 + m * (n - m - 1)


def test_paper_table2_shapes():
    """Paper Table II node counts."""
    assert abilene().shape[0] == 11
    assert balanced_tree().shape[0] == 14
    assert fog().shape[0] == 15
    assert geant().shape[0] == 22
    # Abilene has exactly 14 physical links
    assert abilene().sum() // 2 == 14


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_uniform_phi_is_feasible(seed):
    g = build_random_cec(connected_er(12, 0.35, seed=seed), 3, 10.0,
                         seed=seed)
    phi = np.asarray(g.uniform_phi())
    rows = phi.sum(-1)
    has_out = np.asarray(g.out_mask).sum(-1) > 0
    np.testing.assert_allclose(rows[has_out], 1.0, atol=1e-6)
    assert (phi[np.asarray(g.out_mask) == 0] == 0).all()
