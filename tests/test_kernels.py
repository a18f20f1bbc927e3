"""Pallas kernels vs jnp oracles: shape/dtype sweeps in interpret mode."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import (flow_in_edges_sparse, flow_step_op,
                               flow_step_sparse_op, omd_update_op,
                               omd_update_sparse_op)
from repro.topo import geant, grid_2d, power_law

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("W,N", [(3, 29), (1, 128), (4, 200), (2, 384)])
def test_flow_step_matches_ref(W, N, dtype):
    ks = jax.random.split(KEY, 3)
    t = jnp.abs(_rand(ks[0], (W, N), dtype))
    phi = jnp.abs(_rand(ks[1], (W, N, N), dtype))
    inj = jnp.abs(_rand(ks[2], (W, N), dtype))
    got = flow_step_op(t, phi, inj, interpret=True)
    want = ref.flow_step_ref(t, phi, inj)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("W,N,eta", [(3, 29, 0.5), (2, 128, 3.0),
                                     (1, 257, 1.0)])
def test_omd_update_matches_ref(W, N, eta):
    ks = jax.random.split(KEY, 3)
    mask = (jax.random.uniform(ks[0], (W, N, N)) > 0.5).astype(jnp.float32)
    raw = jnp.abs(_rand(ks[1], (W, N, N), jnp.float32)) * mask
    s = raw.sum(-1, keepdims=True)
    phi = jnp.where(s > 0, raw / jnp.where(s > 0, s, 1), 0.0)
    delta = jnp.abs(_rand(ks[2], (W, N, N), jnp.float32)) * 5
    got = omd_update_op(phi, delta, mask, eta, interpret=True)
    want = ref.omd_update_ref(phi, delta, mask, eta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # rows remain stochastic
    rows = np.asarray(got).sum(-1)
    has = np.asarray(mask).sum(-1) > 0
    np.testing.assert_allclose(rows[has], 1.0, atol=1e-5)


def test_omd_kernel_agrees_with_core_routing_step(er25_cec):
    """End-to-end: the kernel reproduces core.routing.omd_step's update."""
    from repro.core import get_cost, omd_step
    from repro.core.flow import cost_and_state
    from repro.core.marginal import marginals

    g = er25_cec
    cost = get_cost("exp")
    lam = jnp.array([20.0, 20.0, 20.0])
    phi = g.uniform_phi()
    _, t, F = cost_and_state(g, cost, phi, lam)
    delta, _ = marginals(g, cost, phi, t, F)
    want = omd_step(g, cost, phi, lam, 1.0).phi
    got = omd_update_op(phi, delta, g.out_mask, 1.0, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_flow_kernel_agrees_with_core_propagate(er25_cec):
    from repro.core.flow import propagate

    g = er25_cec
    lam = jnp.array([10.0, 20.0, 30.0])
    phi = g.uniform_phi()
    inject = g.injection(lam)
    t = inject
    for _ in range(g.depth_max):
        t = flow_step_op(t, phi, inject, interpret=True)
    want = propagate(g, phi, lam)
    np.testing.assert_allclose(np.asarray(t), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# edge-list layouts of real topologies beside the random in-lists: a
# hub-skewed Barabási–Albert fleet, a lattice and GEANT
SPARSE_TOPOLOGIES = {"power_law": lambda: power_law(300, 2),
                     "grid_2d": lambda: grid_2d(64), "geant": geant}


@functools.lru_cache(maxsize=None)
def _sparse_graph(name):
    from repro.core import build_random_cec, sparsify

    return sparsify(build_random_cec(SPARSE_TOPOLOGIES[name](), 3, 10.0,
                                     seed=0))


def _layouts(random_cases):
    return [*[pytest.param(c, id="-".join(map(str, c))) for c in random_cases],
            *[pytest.param(n, id=n) for n in SPARSE_TOPOLOGIES]]


@pytest.mark.parametrize("layout", _layouts([(3, 29, 6, 5), (1, 128, 16, 16),
                                             (2, 200, 9, 3),
                                             (3, 64, 130, 140)]))
def test_flow_step_sparse_matches_ref(layout):
    """Sparse gather step vs oracle over random in-lists (incl. >128 slots)
    and over the in-lists of real edge-list layouts."""
    gs = _sparse_graph(layout) if isinstance(layout, str) else None
    W, N, D, Din = ((gs.n_sessions, gs.n_bar, gs.d_max, gs.d_in_max)
                    if gs is not None else layout)
    rng = np.random.default_rng(N * 7 + D)
    t = jnp.asarray(rng.uniform(0, 2, (W, N)), jnp.float32)
    rows = jnp.asarray(rng.uniform(0, 1, (W, N, D)), jnp.float32)
    base = jnp.asarray(rng.uniform(0, 1, (W, N)), jnp.float32)
    if gs is None:
        in_src = jnp.asarray(rng.integers(0, N, (N, Din)), jnp.int32)
        in_slot = jnp.asarray(rng.integers(0, D, (N, Din)), jnp.int32)
        in_mask = jnp.asarray(rng.random((N, Din)) > 0.4, jnp.float32)
    else:
        in_src, in_slot, in_mask = gs.in_src, gs.in_slot, gs.in_mask
    got = flow_step_sparse_op(
        t, base, flow_in_edges_sparse(rows, in_src, in_slot, in_mask),
        interpret=True)
    want = ref.flow_step_sparse_ref(t, rows, base, in_src, in_slot, in_mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layout", _layouts([(3, 29, 7, 0.5),
                                             (2, 128, 130, 3.0),
                                             (1, 257, 2, 1.0),
                                             (3, 1, 40, 1.0)]))
def test_omd_update_sparse_matches_ref(layout):
    """Rectangular [W, R, C] slot rows (incl. the 1-row source layout), and
    the out-slot masks of real edge-list layouts."""
    ks = jax.random.split(KEY, 3)
    if isinstance(layout, str):
        mask = _sparse_graph(layout).out_mask
        (W, R, C), eta = mask.shape, 1.0
    else:
        W, R, C, eta = layout
        mask = (jax.random.uniform(ks[0], (W, R, C)) > 0.5).astype(
            jnp.float32)
    raw = jnp.abs(_rand(ks[1], (W, R, C), jnp.float32)) * mask
    s = raw.sum(-1, keepdims=True)
    phi = jnp.where(s > 0, raw / jnp.where(s > 0, s, 1), 0.0)
    delta = jnp.abs(_rand(ks[2], (W, R, C), jnp.float32)) * 5
    got = omd_update_sparse_op(phi, delta, mask, eta, interpret=True)
    want = ref.omd_update_sparse_ref(phi, delta, mask, eta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    rows = np.asarray(got).sum(-1)
    has = np.asarray(mask).sum(-1) > 0
    np.testing.assert_allclose(rows[has], 1.0, atol=1e-5)


@pytest.mark.parametrize("graph", ["er25", "power_law", "grid_2d"])
def test_sparse_kernels_agree_with_core_sparse_step(graph, request):
    """End-to-end: kernels reproduce core.sparse's jnp relay/update math."""
    from repro.core import get_cost, sparsify
    from repro.core import sparse as sp
    from repro.core.flow import cost_and_state
    from repro.core.marginal import marginals

    gs = (sparsify(request.getfixturevalue("er25_cec")) if graph == "er25"
          else _sparse_graph(graph))
    cost = get_cost("exp")
    lam = jnp.array([20.0, 20.0, 20.0])
    phi = gs.uniform_phi()
    base = sp.source_inflow(gs, phi, lam)
    t0 = gs.injection(lam)
    in_edges = flow_in_edges_sparse(phi.rows, gs.in_src, gs.in_slot,
                                    gs.in_mask)
    got = flow_step_sparse_op(t0, base, in_edges, interpret=True)
    want = base + sp._relay_inflow(gs, phi.rows, t0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    _, t, F = cost_and_state(gs, cost, phi, lam)
    delta, _ = marginals(gs, cost, phi, t, F)
    upd = omd_update_sparse_op(phi.rows, delta.rows, gs.out_mask, 1.0,
                               interpret=True)
    want_upd = sp.eg_update(phi.rows, delta.rows, gs.out_mask, 1.0)
    np.testing.assert_allclose(np.asarray(upd), np.asarray(want_upd),
                               rtol=1e-5, atol=1e-5)
