"""The control-plane kernels compile for a TPU v5e, at deployment shapes.

Interpret mode cannot see what the chip's compiler refuses: blocks that
break the (8, 128) tiling rule, gathers that span more than one vreg,
more VMEM than a kernel may use.  These tests compile each kernel that
the default dispatch picks on the chip, for a v5e described here with no
chip attached, at the shapes ``chip_smoke.py`` runs:

* dense megakernel: Connected-ER(250), W = 8 (n̄ = 259), alone and
  vmapped over K = 8 tenants, and the largest shape ``megakernel_fits``
  admits at W = 16, f32 and bf16 storage;
* stitched dense kernels: ER(1000), W = 16 (n̄ = 1017, above the fit),
  vmapped over K = 2 tenants as ``RouterFleet`` runs them;
* stitched sparse kernels: power-law N = 1024, W = 3 and N = 4096,
  W = 16, with the slot widths those graphs have.

The topology is described inside a fixture (never at import), and the
persistent compilation cache is off around the compiles: an entry
written for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dispatch
from repro.core.costs import EXP
from repro.core.graph import CECGraph
from repro.kernels import ops

F32, I32 = jnp.float32, jnp.int32
STEP = dict(k_iters=1, delta=0.5, eta_outer=0.05, eta_inner=3.0, cost=EXP,
            interpret=False)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sharding, tree, lead=()):
    """ShapeDtypeStructs on ``sharding`` for a tree of (shape, dtype)."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(lead + s.shape, s.dtype,
                                       sharding=sharding),
        tree, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _sds(shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _dense_step_args(n_phys, n_sessions, depth):
    nb = n_phys + 1 + n_sessions
    graph = CECGraph(
        out_mask=_sds((n_sessions, nb, nb)), edge_mask=_sds((nb, nb)),
        capacity=_sds((nb, nb)), deploy=_sds((n_sessions, n_phys), bool),
        sinks=_sds((n_sessions,), I32), n_phys=n_phys,
        n_sessions=n_sessions, n_bar=nb, depth_max=depth, src=n_phys)
    return (_sds((n_sessions,)), _sds((n_sessions, nb, nb)),
            _sds((2 * n_sessions,)), _sds(()), graph)


def _compile(fn, args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text       # the Pallas kernel is in it
    return text


def _largest_admitted_n_bar(n_sessions, itemsize):
    n_bar = 128
    while dispatch.megakernel_fits(n_sessions, n_bar + 128, itemsize):
        n_bar += 128
    assert dispatch.megakernel_fits(n_sessions, n_bar, itemsize)
    return n_bar


@pytest.mark.parametrize("tenants", [None, 8])
def test_dense_megakernel_compiles_at_dense_mega_shape(one_chip, tenants):
    def step(lam, phi, tau, tot, graph):
        return ops.control_step_op(lam, phi, tau, tot, graph, **STEP)

    fn = step if tenants is None else jax.vmap(step)
    lead = () if tenants is None else (tenants,)
    _compile(fn, _shapes(one_chip, _dense_step_args(250, 8, 19), lead))


@pytest.mark.parametrize("phi_dtype,itemsize",
                         [("float32", 4), ("bfloat16", 2)])
def test_dense_megakernel_compiles_at_largest_admitted_shape(
        one_chip, phi_dtype, itemsize):
    n_sessions = 16
    n_bar = _largest_admitted_n_bar(n_sessions, itemsize)
    assert not dispatch.megakernel_fits(n_sessions, n_bar + 1, itemsize)

    def step(lam, phi, tau, tot, graph):
        return ops.control_step_op(lam, phi, tau, tot, graph,
                                   phi_dtype=phi_dtype, **STEP)

    args = _dense_step_args(n_bar - 1 - n_sessions, n_sessions, 25)
    _compile(step, _shapes(one_chip, args))


def test_stitched_dense_kernels_compile_at_dense_stitched_shape(one_chip):
    tenants, n_sessions, nb = 2, 16, 1017
    assert not dispatch.megakernel_fits(n_sessions, nb)
    vec, mat = _sds((n_sessions, nb)), _sds((n_sessions, nb, nb))
    _compile(jax.vmap(lambda t, p, i: ops.flow_step_op(
        t, p, i, interpret=False)),
        _shapes(one_chip, (vec, mat, vec), (tenants,)))
    _compile(jax.vmap(lambda p, d, m: ops.omd_update_op(
        p, d, m, 3.0, interpret=False)),
        _shapes(one_chip, (mat, mat, mat), (tenants,)))


# (N, W, slots per row, source fan-out, in-slots) of the power-law graphs
# ``topo.make_fleet("power_law", N)`` builds with W versions, and of the
# benchmark's metro fleet (``chipbench/configs/metro-ba-w3.json``: the
# Barabási–Albert builder at ``structure_seed`` 0, N = 3233, W = 3)
@pytest.mark.parametrize("n_phys,n_sessions,d_max,d_src,d_in",
                         [(1024, 3, 55, 346, 22), (4096, 16, 139, 267, 13),
                          (3233, 3, 106, 1082, 54)])
def test_stitched_sparse_kernels_compile_at_sparse_shapes(
        one_chip, n_phys, n_sessions, d_max, d_src, d_in):
    """Each kernel's op carries its own name, which the chip's device
    trace shows (``chipbench/metrics/edge_kernels_ms.py`` reads it)."""
    nb = n_phys + 1 + n_sessions
    vec, rows = _sds((n_sessions, nb)), _sds((n_sessions, nb, d_max))
    inl = _sds((nb, d_in), I32)
    text = _compile(lambda t, r, b, s, sl, m: ops.flow_step_sparse_op(
        t, b, ops.flow_in_edges_sparse(r, s, sl, m), interpret=False),
        _shapes(one_chip, (vec, rows, vec, inl, inl, _sds((nb, d_in)))))
    assert "%edge_flow_step" in text
    for part in (rows, _sds((n_sessions, d_src, 1))):
        text = _compile(lambda p, d, m: ops.omd_update_sparse_op(
            p, d, m, 3.0, interpret=False),
            _shapes(one_chip, (part, part, part)))
        assert "%edge_omd_update" in text
