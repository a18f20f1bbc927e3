"""Dense↔sparse representation parity (DESIGN.md §12).

The edge-list path must be *the same solver* in a different layout:
property-based checks over random graphs, random alive-masks and random φ
for flow propagation, total cost, the marginal-cost broadcast, one OMD
step, and a full ``solve_jowr`` run — all within 1e-5 of the dense path —
plus structural identity of the two sparse constructors, pad/batch
equivalence, the marginal recursion's light/heavy split of the out-rows,
and the Pallas sparse-kernel dispatch.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from hypothesis_stub import given, settings, st

from repro.core import (CECGraphSparse, CECGraphSparseBatch, SparsePhi,
                        build_augmented, build_augmented_sparse, dispatch,
                        get_cost, make_bank, omd_step, pad_sparse_graph,
                        propagate, solve_jowr, solve_routing,
                        solve_routing_batch, sparsify, total_cost)
from repro.core import serving_defaults
from repro.core import solver as S
from repro.core import sparse as sp
from repro.core.flow import cost_and_state
from repro.core.graph import draw_instance
from repro.core.marginal import marginals
from repro.core.problem import Problem
from repro.topo import (abilene, balanced_tree, connected_er, fog, geant,
                        grid_2d, power_law, random_geometric)

from conftest import random_phi

COST = get_cost("exp")


def _instance(n, p, seed):
    return draw_instance(connected_er(n, p, seed=seed), 3, 10.0, seed)


def _alive_instance(n, seed, n_dead):
    """A feasible alive-masked instance (retrying the kill set)."""
    from repro.core import InfeasibleTopology

    inst = _instance(n, 0.35, seed)
    rng = np.random.default_rng(seed)
    for _ in range(30):
        alive = np.ones(n, bool)
        alive[rng.choice(n, size=n_dead, replace=False)] = False
        try:
            g = build_augmented(connected_er(n, 0.35, seed=seed),
                                inst.deploy, inst.link_capacity,
                                inst.compute_capacity, alive=alive)
            return g, alive
        except InfeasibleTopology:
            continue
    pytest.skip("no feasible alive-mask draw")


def _lam(graph, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(2, 25, graph.n_sessions), jnp.float32)


def _sparse_pair(graph, seed):
    gs = sparsify(graph)
    phi = random_phi(graph, seed)
    return gs, phi, sp.phi_to_sparse(gs, phi)


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(8, 40))
def test_sparse_builders_identical(seed, n):
    """sparsify(build_augmented(x)) == build_augmented_sparse(x), leafwise."""
    inst = _instance(n, 0.35, seed)
    adj = connected_er(n, 0.35, seed=seed)
    a = sparsify(inst.graph)
    b = build_augmented_sparse(adj, inst.deploy, inst.link_capacity,
                               inst.compute_capacity)
    assert (a.d_max, a.d_src, a.d_in_max, a.depth_max, a.n_edges) == \
           (b.d_max, b.d_src, b.d_in_max, b.depth_max, b.n_edges)
    for la, lb in zip(jax.tree_util.tree_leaves(a),
                      jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_phi_layout_roundtrip(seed):
    """dense→sparse→dense is the identity on masked routing tensors."""
    g = _instance(14, 0.35, seed).graph
    gs = sparsify(g)
    phi = random_phi(g, seed)
    back = sp.phi_to_dense(gs, sp.phi_to_sparse(gs, phi))
    np.testing.assert_allclose(np.asarray(back), np.asarray(phi), atol=1e-6)


def test_sparse_density_metadata(small_cec):
    gs = sparsify(small_cec)
    assert gs.n_edges == int(np.asarray(small_cec.edge_mask).sum())
    assert 0.0 < gs.density < 1.0


# ---------------------------------------------------------------------------
# flow / cost / marginals / OMD-step parity
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(8, 48))
def test_flow_and_cost_parity(seed, n):
    g = _instance(n, 0.35, seed).graph
    gs, phi, phis = _sparse_pair(g, seed)
    lam = _lam(g, seed)
    t_d = np.asarray(propagate(g, phi, lam))
    t_s = np.asarray(propagate(gs, phis, lam))
    np.testing.assert_allclose(t_d, t_s, rtol=1e-5, atol=1e-5)
    D_d = float(total_cost(g, COST, phi, lam))
    D_s = float(total_cost(gs, COST, phis, lam))
    np.testing.assert_allclose(D_d, D_s, rtol=1e-5)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000), n_dead=st.integers(1, 3))
def test_flow_parity_under_alive_mask(seed, n_dead):
    """Dead nodes (scenario churn) behave identically in both layouts."""
    g, _ = _alive_instance(16, seed, n_dead)
    gs, phi, phis = _sparse_pair(g, seed)
    lam = _lam(g, seed)
    np.testing.assert_allclose(np.asarray(propagate(g, phi, lam)),
                               np.asarray(propagate(gs, phis, lam)),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000),
       cost_name=st.sampled_from(["exp", "mm1", "linear", "quad"]))
def test_marginal_parity(seed, cost_name):
    """δφ and ∂D/∂r agree edge-for-edge across representations."""
    cost = get_cost(cost_name)
    g = _instance(16, 0.35, seed).graph
    gs, phi, phis = _sparse_pair(g, seed)
    lam = _lam(g, seed)
    _, t_d, F_d = cost_and_state(g, cost, phi, lam)
    delta_d, dDdr_d = marginals(g, cost, phi, t_d, F_d)
    _, t_s, F_s = cost_and_state(gs, cost, phis, lam)
    delta_s, dDdr_s = marginals(gs, cost, phis, t_s, F_s)
    np.testing.assert_allclose(np.asarray(dDdr_d), np.asarray(dDdr_s),
                               rtol=1e-5, atol=1e-5)
    m = np.asarray(g.out_mask) > 0
    dense_of_sparse = np.asarray(sp.phi_to_dense(gs, delta_s))
    np.testing.assert_allclose(np.asarray(delta_d)[m], dense_of_sparse[m],
                               rtol=1e-5, atol=1e-5)


# graph kinds for the split: a Barabási–Albert fleet with hubs, on which
# the rule engages (d_light, n_heavy as computed from its degrees), and
# Connected-ER draws of near-uniform degree, on which it does not
SPLIT_KINDS = {"power_law": (power_law(300, 2), 0, (36, 6, 16)),
               "er16": (connected_er(16, 0.35, seed=0), 0, None),
               "er40": (connected_er(40, 0.35, seed=1), 1, None)}


def _full_width(gs):
    """The same graph with the split off: every row at full width."""
    return dataclasses.replace(gs, heavy=jnp.zeros(0, jnp.int32),
                               d_light=gs.d_max, n_heavy=0)


@pytest.mark.parametrize("kind", sorted(SPLIT_KINDS))
def test_marginal_split_parity(kind):
    """The split recursion equals the full-width one (1e-6 relative) and
    the dense path (1e-5); the split engages only where it at most halves
    the slots each step gathers."""
    adj, seed, split = SPLIT_KINDS[kind]
    g = draw_instance(adj, 3, 10.0, seed).graph
    gs, phi, phis = _sparse_pair(g, seed)
    if split is None:
        assert (gs.d_light, gs.n_heavy) == (gs.d_max, 0)
    else:
        assert (gs.d_max, gs.d_light, gs.n_heavy) == split
        assert 2 * (gs.n_bar * gs.d_light + gs.n_heavy * gs.d_max) \
            <= gs.n_bar * gs.d_max
    # every row wider than the light block is gathered at full width
    em = np.asarray(gs.edge_mask) > 0
    wide = np.nonzero(em[:, gs.d_light:].any(1))[0]
    assert set(wide) <= set(np.asarray(gs.heavy).tolist())
    lam = _lam(g, seed)
    _, t_d, F_d = cost_and_state(g, COST, phi, lam)
    delta_d, dDdr_d = marginals(g, COST, phi, t_d, F_d)
    _, t_s, F_s = cost_and_state(gs, COST, phis, lam)
    delta_s, dDdr_s = marginals(gs, COST, phis, t_s, F_s)
    delta_f, dDdr_f = marginals(_full_width(gs), COST, phis, t_s, F_s)
    np.testing.assert_allclose(np.asarray(dDdr_s), np.asarray(dDdr_f),
                               rtol=1e-6, atol=0)
    for a, b in zip(delta_s, delta_f):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=0)
    np.testing.assert_allclose(np.asarray(dDdr_d), np.asarray(dDdr_s),
                               rtol=1e-5, atol=1e-5)
    m = np.asarray(g.out_mask) > 0
    np.testing.assert_allclose(np.asarray(delta_d)[m],
                               np.asarray(sp.phi_to_dense(gs, delta_s))[m],
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), eta=st.floats(0.1, 5.0))
def test_omd_step_parity(seed, eta):
    g = _instance(14, 0.35, seed).graph
    gs, phi, phis = _sparse_pair(g, seed)
    lam = _lam(g, seed)
    st_d = omd_step(g, COST, phi, lam, float(eta))
    st_s = omd_step(gs, COST, phis, lam, float(eta))
    np.testing.assert_allclose(float(st_d.cost), float(st_s.cost), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(st_d.phi),
                               np.asarray(sp.phi_to_dense(gs, st_s.phi)),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_solve_routing_trajectory_parity(seed):
    g = _instance(20, 0.3, seed).graph
    gs = sparsify(g)
    lam = _lam(g, seed)
    _, tr_d = solve_routing(g, COST, lam, g.uniform_phi(), 1.0, 40)
    _, tr_s = solve_routing(gs, COST, lam, gs.uniform_phi(), 1.0, 40)
    np.testing.assert_allclose(np.asarray(tr_d), np.asarray(tr_s),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the named topologies, stage by stage
# ---------------------------------------------------------------------------

# the paper's topologies and the fleet generators at a size a CPU run
# affords; the Barabási–Albert draws are hub-skewed, so the marginal
# recursion's light/heavy split of the out-rows engages on them
NAMED_TOPOLOGIES = {
    "abilene": abilene, "geant": geant, "fog": fog,
    "balanced_tree": balanced_tree,
    "grid_2d": lambda: grid_2d(64),
    "rgg-s0": lambda: random_geometric(64, seed=0),
    "rgg-s1": lambda: random_geometric(64, seed=1),
    "power_law-m2": lambda: power_law(300, 2),
    "power_law-m3": lambda: power_law(300, 3),
}
STAGES = ("flow_cost", "marginals_exp", "marginals_mm1", "solver_step",
          "alive")


@functools.lru_cache(maxsize=None)
def _named(name):
    """(adjacency, instance draw, dense graph) of one named topology."""
    adj = NAMED_TOPOLOGIES[name]()
    inst = draw_instance(adj, 3, 10.0, 0)
    return adj, inst, inst.graph


def _one_dead(adj, inst):
    """The instance with the first node (in a seeded order) whose loss
    leaves it feasible marked dead."""
    from repro.core import InfeasibleTopology

    for node in np.random.default_rng(0).permutation(adj.shape[0]):
        alive = np.ones(adj.shape[0], bool)
        alive[node] = False
        try:
            return build_augmented(adj, inst.deploy, inst.link_capacity,
                                   inst.compute_capacity, alive=alive)
        except InfeasibleTopology:
            continue
    raise AssertionError("no single node can fail feasibly")


@jax.jit
def _flow_cost(graph, phi, lam):
    return propagate(graph, phi, lam), total_cost(graph, COST, phi, lam)


@functools.partial(jax.jit, static_argnums=1)
def _marginals(graph, cost_name, phi, lam):
    cost = get_cost(cost_name)
    _, t, F = cost_and_state(graph, cost, phi, lam)
    return marginals(graph, cost, phi, t, F)


_STEP = jax.jit(S.step, static_argnums=1)


def _assert_marginals_agree(g, gs, got_d, got_s):
    delta_d, dDdr_d = got_d
    delta_s, dDdr_s = got_s
    np.testing.assert_allclose(np.asarray(dDdr_d), np.asarray(dDdr_s),
                               rtol=1e-5, atol=1e-5)
    m = np.asarray(g.out_mask) > 0
    np.testing.assert_allclose(np.asarray(delta_d)[m],
                               np.asarray(sp.phi_to_dense(gs, delta_s))[m],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("stage", STAGES)
@pytest.mark.parametrize("name", sorted(NAMED_TOPOLOGIES))
def test_named_topology_parity(name, stage):
    """Each stage of the solver agrees across layouts on the named
    topologies, at the single-step parity tolerances: flow and cost,
    the marginals under two costs, one ``solver.step``, and flow with
    one node dead."""
    adj, inst, g = _named(name)
    if stage == "alive":
        g = _one_dead(adj, inst)
    gs, phi, phis = _sparse_pair(g, 0)
    lam = _lam(g, 0)
    if stage in ("flow_cost", "alive"):
        t_d, D_d = _flow_cost(g, phi, lam)
        t_s, D_s = _flow_cost(gs, phis, lam)
        np.testing.assert_allclose(np.asarray(t_d), np.asarray(t_s),
                                   rtol=1e-5, atol=1e-5)
        if stage == "flow_cost":
            np.testing.assert_allclose(float(D_d), float(D_s), rtol=1e-5)
    elif stage.startswith("marginals_"):
        cost_name = stage.removeprefix("marginals_")
        _assert_marginals_agree(g, gs, _marginals(g, cost_name, phi, lam),
                                _marginals(gs, cost_name, phis, lam))
    else:
        bank = make_bank("log", 3, seed=0)
        config = serving_defaults()
        out = []
        for graph in (g, gs):
            problem = Problem.create(graph, bank, lam_total=60.0)
            state = S.init(problem, config, phi0=phi)
            task_u = jax.vmap(bank.total)(
                S.perturbed_allocations(state.lam, config.delta))
            out.append(_STEP(problem, config, state, task_u))
        (st_d, info_d), (st_s, info_s) = out
        np.testing.assert_allclose(np.asarray(st_d.lam),
                                   np.asarray(st_s.lam), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(float(info_d.cost), float(info_s.cost),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(st_d.phi),
                                   np.asarray(sp.phi_to_dense(gs, st_s.phi)),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# full solver + auto-dispatch
# ---------------------------------------------------------------------------

@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000),
       kind=st.sampled_from(["log", "sqrt", "linear"]))
def test_solve_jowr_parity(seed, kind):
    """Full OMAD run at N≤64: forced-sparse == dense to 1e-5."""
    n = 24 + seed % 40                      # spans up to N=63
    g = _instance(n, 0.3, seed).graph
    bank = make_bank(kind, 3, seed=seed)
    kw = dict(method="single", outer_iters=8, eta_inner=3.0)
    res_d = solve_jowr(g, bank, 60.0, **kw)
    with dispatch.sparse_dispatch(1):
        res_s = solve_jowr(g, bank, 60.0, **kw)
    np.testing.assert_allclose(np.asarray(res_d.utility_traj),
                               np.asarray(res_s.utility_traj),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(res_d.lam), np.asarray(res_s.lam),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(res_d.phi), np.asarray(res_s.phi),
                               rtol=1e-5, atol=1e-5)


def test_auto_dispatch_policy(small_cec):
    """maybe_sparsify honors the (N, density) policy and tracer guards."""
    assert dispatch.maybe_sparsify(small_cec) is small_cec   # below threshold
    with dispatch.sparse_dispatch(1):
        gs = dispatch.maybe_sparsify(small_cec)
        assert isinstance(gs, CECGraphSparse)
        assert dispatch.maybe_sparsify(gs) is gs             # idempotent
        # tracer companions disable conversion (inside-jit safety)
        traced = []

        @jax.jit
        def probe(x):
            traced.append(dispatch.maybe_sparsify(small_cec, x))
            return x

        probe(jnp.zeros(3))
        assert traced[0] is small_cec
    # density guard: a dense-enough graph stays dense even past the size bar
    with dispatch.sparse_dispatch(1, density_max=1e-9):
        assert dispatch.maybe_sparsify(small_cec) is small_cec


def test_state_key_tracks_sparse_policy(small_cec):
    k0 = dispatch.state_key()
    with dispatch.sparse_dispatch(1):
        assert dispatch.state_key() != k0
    assert dispatch.state_key() == k0


# ---------------------------------------------------------------------------
# padding / batching / kernels
# ---------------------------------------------------------------------------

def test_pad_sparse_graph_solve_equivalent(small_cec):
    gs = sparsify(small_cec)
    padded = pad_sparse_graph(gs, gs.n_phys + 7, depth_max=gs.depth_max + 3,
                              d_max=gs.d_max + 2, d_src=gs.d_src + 2,
                              d_in_max=gs.d_in_max + 2)
    lam = _lam(gs, 0)
    t0 = np.asarray(propagate(gs, gs.uniform_phi(), lam))
    t1 = np.asarray(propagate(padded, padded.uniform_phi(), lam))
    # original physical nodes keep their indices; virtual nodes relocate
    np.testing.assert_allclose(t0[:, : gs.n_phys], t1[:, : gs.n_phys],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(t0[:, gs.src], t1[:, padded.src], rtol=1e-5)
    np.testing.assert_allclose(
        t0[np.arange(3), np.asarray(gs.sinks)],
        t1[np.arange(3), np.asarray(padded.sinks)], rtol=1e-5)
    _, tr0 = solve_routing(gs, COST, lam, gs.uniform_phi(), 1.0, 20)
    _, tr1 = solve_routing(padded, COST, lam, padded.uniform_phi(), 1.0, 20)
    np.testing.assert_allclose(np.asarray(tr0), np.asarray(tr1),
                               rtol=1e-5, atol=1e-5)


def test_pad_sparse_graph_keeps_heavy_rows():
    """Relocation keeps the heavy rows (physical nodes keep their indices),
    and a wider split (a batch's) pads them with row 0 and stays exact."""
    g = draw_instance(power_law(300, 2), 3, 10.0, 0).graph
    gs, _, phis = _sparse_pair(g, 0)
    n_pad = 7
    padded = pad_sparse_graph(gs, gs.n_phys + n_pad, d_max=gs.d_max + 3,
                              d_light=gs.d_light + 1,
                              n_heavy=gs.n_heavy + 5)
    assert (padded.d_light, padded.n_heavy) == (gs.d_light + 1,
                                                gs.n_heavy + 5)
    heavy = np.asarray(padded.heavy)
    np.testing.assert_array_equal(heavy[: gs.n_heavy], np.asarray(gs.heavy))
    assert (heavy[gs.n_heavy:] == 0).all()
    em = np.asarray(padded.edge_mask) > 0
    assert set(np.nonzero(em[:, padded.d_light:].any(1))[0]) <= set(heavy)
    lam = _lam(gs, 0)
    _, t0, F0 = cost_and_state(gs, COST, phis, lam)
    _, dDdr0 = marginals(gs, COST, phis, t0, F0)
    phip = padded.uniform_phi()
    phip = SparsePhi(
        rows=phip.rows.at[:, : gs.n_phys, : gs.d_max].set(
            phis.rows[:, : gs.n_phys]),
        src=phip.src.at[:, : gs.d_src].set(phis.src))
    _, t1, F1 = cost_and_state(padded, COST, phip, lam)
    _, dDdr1 = marginals(padded, COST, phip, t1, F1)
    np.testing.assert_allclose(np.asarray(dDdr0)[:, : gs.n_phys],
                               np.asarray(dDdr1)[:, : gs.n_phys],
                               rtol=1e-6, atol=1e-6)


def test_sparse_batch_with_rows_heavy_in_one_instance():
    """A batch whose instances split their out-rows differently solves as
    each instance does alone: a row heavy in one is light in another."""
    graphs = [sparsify(draw_instance(adj, 3, 10.0, s).graph) for adj, s in
              [(power_law(300, 2, seed=0), 0), (power_law(260, 2, seed=1), 1),
               (connected_er(40, 0.35, seed=1), 1)]]
    heavy = [set(np.asarray(g.heavy).tolist()) for g in graphs]
    n = min(g.n_phys for g in graphs[:2])
    assert any(r < n for r in heavy[0] - heavy[1])
    assert any(r < n for r in heavy[1] - heavy[0])
    sb = CECGraphSparseBatch.from_graphs(graphs)
    assert sb.d_light == max(g.d_light for g in graphs)
    assert sb.n_heavy == max(g.n_heavy for g in graphs)
    lam = jnp.array([8.0, 12.0, 16.0])
    _, tr_b = solve_routing_batch(sb, COST, lam, sb.uniform_phi(), 1.0, 15)
    for b, g in enumerate(graphs):
        _, tr = solve_routing(g, COST, lam, g.uniform_phi(), 1.0, 15)
        np.testing.assert_allclose(np.asarray(tr_b[b]), np.asarray(tr),
                                   rtol=1e-5, atol=1e-5)


def test_sparse_batch_matches_dense_batch():
    from repro.core import CECGraphBatch

    graphs = [draw_instance(connected_er(n, 0.35, seed=s), 3, 10.0, s).graph
              for s, n in [(0, 12), (1, 16), (2, 10)]]
    db = CECGraphBatch.from_graphs(graphs)
    sb = CECGraphSparseBatch.from_graphs([sparsify(g) for g in graphs])
    lam = jnp.array([8.0, 12.0, 16.0])
    _, tr_d = solve_routing_batch(db, COST, lam, db.uniform_phi(), 1.0, 25)
    _, tr_s = solve_routing_batch(sb, COST, lam, sb.uniform_phi(), 1.0, 25)
    np.testing.assert_allclose(np.asarray(tr_d), np.asarray(tr_s),
                               rtol=1e-5, atol=1e-5)
    inst = sb.instance(1)
    assert isinstance(inst, CECGraphSparse)
    assert inst.n_phys == 16
    # per-instance metadata is exact, not the batch-level upper bound
    for b, g in enumerate(graphs):
        assert sb.instance(b).n_edges == sparsify(g).n_edges


def test_remap_phi_matches_edges_not_slots():
    """Churn can repack CSR slots at unchanged widths — φ must follow the
    *edge*, not the slot position (regression: the router's sparse
    warm-start once reused slot values positionally)."""
    from repro.core import build_augmented
    from repro.topo.churn import rewire_links

    adj = connected_er(20, 0.3, seed=2)
    inst = draw_instance(adj, 3, 10.0, 0)
    g_old = inst.graph
    adj_new = rewire_links(adj, 0.2, seed=1)
    g_new = build_augmented(adj_new, inst.deploy, inst.link_capacity,
                            inst.compute_capacity)
    s_old, s_new = sparsify(g_old), sparsify(g_new)
    phi_old = sp.phi_to_sparse(s_old, random_phi(g_old, 5))
    phi_new = sp.remap_phi(s_old, s_new, phi_old)   # widths may differ
    # edge-identity check against the dense layouts: surviving edges keep
    # their mass, new edges start at zero
    dense_old = np.asarray(sp.phi_to_dense(s_old, phi_old))
    dense_new = np.asarray(sp.phi_to_dense(s_new, phi_new))
    both = (np.asarray(g_old.out_mask) > 0) & (np.asarray(g_new.out_mask) > 0)
    only_new = (np.asarray(g_new.out_mask) > 0) & ~both
    np.testing.assert_allclose(dense_new[both], dense_old[both], atol=1e-6)
    assert (dense_new[only_new] == 0).all()


def test_router_sparse_warm_start_survives_rewire():
    """CECRouter on the sparse path: post-churn φ mass sits on real edges
    of the *new* graph, aligned by identity."""
    from repro.core import build_augmented, make_bank
    from repro.serve.cec_router import CECRouter
    from repro.topo.churn import rewire_links

    adj = connected_er(20, 0.3, seed=2)
    inst = draw_instance(adj, 3, 10.0, 0)
    bank = make_bank("log", 3, seed=0)

    def measured(lams):
        return np.asarray([float(bank.total(jnp.asarray(r)))
                           for r in np.atleast_2d(lams)], np.float32)

    with dispatch.sparse_dispatch(1):
        router = CECRouter(inst.graph, lam_total=45.0)
        for _ in range(3):
            router.control_step(measured)
        pre = sp.phi_to_dense(router.graph, router.phi)
        g_old = router.graph
        adj_new = rewire_links(adj, 0.2, seed=1)
        router.on_topology_change(build_augmented(
            adj_new, inst.deploy, inst.link_capacity, inst.compute_capacity))
        assert isinstance(router.phi, SparsePhi)
        post = np.asarray(sp.phi_to_dense(router.graph, router.phi))
        new_mask = np.asarray(sp.phi_to_dense(
            router.graph, SparsePhi(router.graph.out_mask,
                                    router.graph.src_out_mask))) > 0
        # row-stochastic on the new mask, zero off it
        assert (post[~new_mask] == 0).all()
        rows = post.sum(-1)
        has = new_mask.sum(-1) > 0
        np.testing.assert_allclose(rows[has], 1.0, atol=1e-5)
        # surviving edges dominate their rows' warm-start mass: identity
        # alignment means the (1−ε) component follows the old iterate
        both = new_mask & (np.asarray(sp.phi_to_dense(
            g_old, SparsePhi(g_old.out_mask, g_old.src_out_mask))) > 0)
        pre = np.asarray(pre)
        agree = np.abs(post[both] - pre[both])
        assert np.median(agree) < 0.15      # ε-mix, not a scramble
        router.control_step(measured)       # and the fused step still runs


def test_sparse_kernel_dispatch_parity(small_cec):
    """Pallas sparse kernels (interpret) == jnp sparse path in the solver."""
    gs = sparsify(small_cec)
    phis = gs.uniform_phi()
    lam = _lam(gs, 3)
    t_jnp = propagate(gs, phis, lam)
    st_jnp = omd_step(gs, COST, phis, lam, 1.0)
    with dispatch.kernel_dispatch(1):
        t_k = propagate(gs, phis, lam)
        st_k = omd_step(gs, COST, phis, lam, 1.0)
    np.testing.assert_allclose(np.asarray(t_jnp), np.asarray(t_k),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(st_jnp.phi, st_k.phi):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    assert isinstance(st_k.phi, SparsePhi)
