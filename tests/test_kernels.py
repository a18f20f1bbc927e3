"""Pallas kernels vs jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import (flash_attention_op, flow_in_edges_sparse,
                               flow_step_op, flow_step_sparse_op,
                               omd_update_op, omd_update_sparse_op)

KEY = jax.random.PRNGKey(0)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KH,S,T,hd,causal", [
    (1, 4, 4, 128, 128, 64, True),      # MHA causal
    (2, 8, 2, 256, 256, 64, True),      # GQA
    (1, 4, 1, 64, 192, 128, False),     # MQA, non-causal, S != T
    (2, 6, 3, 96, 96, 32, True),        # non-pow2 heads, padded blocks
    (1, 2, 2, 8, 1024, 128, True),      # short q, long kv (decode-ish)
])
def test_flash_attention_matches_ref(B, H, KH, S, T, hd, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, H, S, hd), dtype)
    k = _rand(ks[1], (B, KH, T, hd), dtype)
    v = _rand(ks[2], (B, KH, T, hd), dtype)
    got = flash_attention_op(q, k, v, causal=causal, interpret=True)
    want = ref.mha_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_q_offset_and_kv_len():
    """Decode semantics: queries placed at the cache tail, padding masked."""
    B, H, S, T, hd = 1, 4, 8, 256, 64
    ks = jax.random.split(KEY, 3)
    q = _rand(ks[0], (B, H, S, hd), jnp.float32)
    k = _rand(ks[1], (B, H, T, hd), jnp.float32)
    v = _rand(ks[2], (B, H, T, hd), jnp.float32)
    got = flash_attention_op(q, k, v, causal=True, q_offset=100, kv_len=108,
                             interpret=True)
    want = ref.mha_ref(q, k, v, causal=True, q_offset=100, kv_len=108)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


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


@pytest.mark.parametrize("W,N,D,Din", [(3, 29, 6, 5), (1, 128, 16, 16),
                                       (2, 200, 9, 3), (3, 64, 130, 140)])
def test_flow_step_sparse_matches_ref(W, N, D, Din):
    """Sparse gather step vs oracle over random in-lists (incl. >128 slots)."""
    rng = np.random.default_rng(N * 7 + D)
    t = jnp.asarray(rng.uniform(0, 2, (W, N)), jnp.float32)
    rows = jnp.asarray(rng.uniform(0, 1, (W, N, D)), jnp.float32)
    base = jnp.asarray(rng.uniform(0, 1, (W, N)), jnp.float32)
    in_src = jnp.asarray(rng.integers(0, N, (N, Din)), jnp.int32)
    in_slot = jnp.asarray(rng.integers(0, D, (N, Din)), jnp.int32)
    in_mask = jnp.asarray(rng.random((N, Din)) > 0.4, jnp.float32)
    got = flow_step_sparse_op(
        t, base, flow_in_edges_sparse(rows, in_src, in_slot, in_mask),
        interpret=True)
    want = ref.flow_step_sparse_ref(t, rows, base, in_src, in_slot, in_mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("W,R,C,eta", [(3, 29, 7, 0.5), (2, 128, 130, 3.0),
                                       (1, 257, 2, 1.0), (3, 1, 40, 1.0)])
def test_omd_update_sparse_matches_ref(W, R, C, eta):
    """Rectangular [W, R, C] slot rows (incl. the 1-row source layout)."""
    ks = jax.random.split(KEY, 3)
    mask = (jax.random.uniform(ks[0], (W, R, C)) > 0.5).astype(jnp.float32)
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


def test_sparse_kernels_agree_with_core_sparse_step(er25_cec):
    """End-to-end: kernels reproduce core.sparse's jnp relay/update math."""
    from repro.core import get_cost, sparsify
    from repro.core import sparse as sp
    from repro.core.flow import cost_and_state
    from repro.core.marginal import marginals

    gs = sparsify(er25_cec)
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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,di,ds", [(2, 128, 128, 16), (1, 256, 64, 8),
                                       (2, 96, 200, 16)])
def test_mamba_scan_matches_ref(B, S, di, ds, dtype):
    from repro.kernels.ops import mamba_scan_op

    ks = jax.random.split(KEY, 5)
    u = _rand(ks[0], (B, S, di), dtype)
    dt = jnp.abs(_rand(ks[1], (B, S, di), dtype)) * 0.1
    A = -jnp.abs(_rand(ks[2], (di, ds), jnp.float32))
    Bm = _rand(ks[3], (B, S, ds), dtype)
    Cm = _rand(ks[4], (B, S, ds), dtype)
    got = mamba_scan_op(u, dt, A, Bm, Cm, interpret=True)
    want = ref.mamba_scan_ref(u, dt, A, Bm, Cm)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_mamba_kernel_matches_model_layer_scan():
    """The kernel agrees with the layers._mamba_scan training path."""
    from repro.kernels.ops import mamba_scan_op
    from repro.models.layers import _mamba_scan

    ks = jax.random.split(KEY, 5)
    B, S, di, ds = 2, 128, 64, 16
    u = _rand(ks[0], (B, S, di), jnp.float32)
    dt = jnp.abs(_rand(ks[1], (B, S, di), jnp.float32)) * 0.1
    A = -jnp.abs(_rand(ks[2], (di, ds), jnp.float32))
    Bm = _rand(ks[3], (B, S, ds), jnp.float32)
    Cm = _rand(ks[4], (B, S, ds), jnp.float32)
    want, _ = _mamba_scan(u, dt, A, Bm, Cm, None)
    got = mamba_scan_op(u, dt, A, Bm, Cm, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
