"""Sharded fleet solving (DESIGN.md §14): parity, padding, properties.

``run_batch_sharded`` must be a *drop-in* for ``run_batch`` — same
``Result``, lane for lane.  This module is the contract:

* **1-device parity is bit-identical** (the shard_map body is the same
  vmapped program; a 1-device mesh adds no reduction reordering).
* **Forced multi-device parity is ≤ 1e-6** (subprocess with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8``; XLA may fuse
  differently per shard), covering dense and sparse fleets, uneven fleet
  sizes that need shard padding, carried ``SolverState``s, and a full
  scenario timeline.
* **Property tests** pin the promoted sharding helpers: fleet-axis spec
  construction over arbitrary ranks, the pad/unpad roundtrip over
  arbitrary (fleet size, shard count), and the ``shard_map_compat`` shim.

In-process tests run on the single conftest-pinned CPU device — they are
the coverage carriers for the new paths; the subprocess tier proves the
multi-device story on every PR (CI job ``sharded-multidevice``).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:
    from hypothesis_stub import given, settings, st

from repro.core.batch import (CECGraphBatch, CECGraphSparseBatch, run_batch,
                              run_batch_sharded)
from repro.core.graph import build_random_cec, sparsify
from repro.core.solver import SolverConfig
from repro.core.utility import make_bank
from repro.core.mesh import (FLEET_AXIS, fleet_axis, fleet_mesh,
                             fleet_padded_size, fleet_spec, fleet_specs,
                             pad_fleet, unpad_fleet)
from repro.topo import make_fleet

CONFIG = SolverConfig(method="single", delta=0.5, eta_outer=0.05,
                      eta_inner=3.0, inner_iters=1)

# On the conftest-pinned single CPU device the sharded driver traces to the
# same vmapped executable — parity is bit-identical.  The CI job
# ``sharded-multidevice`` re-runs this module under
# XLA_FLAGS=--xla_force_host_platform_device_count=8, where per-shard XLA
# fusion may reorder float ops: tolerance relaxes to 1e-6.
TOL = 1e-12 if jax.device_count() == 1 else 1e-6


def _graphs(n_instances=3, n=8, sparse=False):
    gs = [build_random_cec(make_fleet("power_law", n, seed=s), 2, 10.0,
                           seed=s) for s in range(n_instances)]
    return [sparsify(g) for g in gs] if sparse else gs


def _dense_batch(n_instances=3):
    return CECGraphBatch.from_graphs(_graphs(n_instances))


def _sparse_batch(n_instances=3):
    return CECGraphSparseBatch.from_graphs(_graphs(n_instances, sparse=True))


def _max_abs_diff(a, b) -> float:
    return jax.tree_util.tree_reduce(
        max, jax.tree_util.tree_map(
            lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b))


# ---------------------------------------------------------------------------
# in-process parity (1 real CPU device — bit-identical)
# ---------------------------------------------------------------------------

def test_sharded_matches_vmap_dense_bitwise():
    batch = _dense_batch()
    banks = [make_bank("log", 2, seed=s) for s in range(3)]
    ref = run_batch(batch, banks, 4.0, CONFIG, iters=10)
    got = run_batch_sharded(batch, banks, 4.0, CONFIG, iters=10,
                            mesh=fleet_mesh())
    assert _max_abs_diff(ref, got) <= TOL


def test_sharded_matches_vmap_sparse_broadcast_bank():
    batch = _sparse_batch()
    bank = make_bank("log", 2, seed=0)        # single bank, broadcast
    ref = run_batch(batch, bank, 4.0, CONFIG, iters=8)
    got = run_batch_sharded(batch, bank, 4.0, CONFIG, iters=8)
    assert _max_abs_diff(ref, got) <= TOL


def test_sharded_state_threading_matches_vmap():
    """A Result.state from one driver warm-starts the other exactly."""
    batch = _dense_batch()
    bank = make_bank("log", 2, seed=0)
    first = run_batch_sharded(batch, bank, 4.0, CONFIG, iters=6)
    ref = run_batch(batch, bank, 4.0, CONFIG, iters=6, state=first.state)
    got = run_batch_sharded(batch, bank, 4.0, CONFIG, iters=6,
                            state=first.state)
    assert _max_abs_diff(ref, got) <= TOL
    assert float(jnp.max(jnp.abs(got.state.t - first.state.t - 6))) == 0


def test_sharded_phi0_lam0_overrides_match_vmap():
    batch = _dense_batch()
    bank = make_bank("log", 2, seed=0)
    phi0 = batch.uniform_phi()
    lam0 = jnp.full((3, 2), 2.0, jnp.float32)
    ref = run_batch(batch, bank, 4.0, CONFIG, iters=5, phi0=phi0, lam0=lam0)
    got = run_batch_sharded(batch, bank, 4.0, CONFIG, iters=5, phi0=phi0,
                            lam0=lam0)
    assert _max_abs_diff(ref, got) <= TOL


def test_scenario_sharded_driver_matches_unsharded():
    from repro.core.scenario import named_scenarios, run_scenario

    sc = named_scenarios(horizon=12, n=8)["link_churn"]
    ref = run_scenario(sc, seeds=(0, 1, 2))
    got = run_scenario(sc, seeds=(0, 1, 2), mesh=fleet_mesh())
    assert float(jnp.max(jnp.abs(ref.utility_traj - got.utility_traj))) \
        <= TOL
    assert float(jnp.max(jnp.abs(ref.lam - got.lam))) <= TOL
    assert float(jnp.max(jnp.abs(ref.phi - got.phi))) <= TOL


def test_fleet_mesh_shape_and_validation():
    mesh = fleet_mesh()
    assert mesh.axis_names == (FLEET_AXIS,)
    assert fleet_axis(mesh) == FLEET_AXIS
    assert mesh.shape[FLEET_AXIS] == jax.device_count()
    try:
        fleet_mesh(n_devices=jax.device_count() + 1)
    except ValueError:
        pass
    else:
        raise AssertionError("oversubscribed fleet_mesh must raise")


def test_state_key_covers_fleet_mesh():
    """Jit caches keyed on dispatch.state_key() must not alias meshes."""
    from repro.core import dispatch

    base = dispatch.state_key()
    mesh = fleet_mesh()
    with dispatch.fleet_dispatch(mesh):
        inside = dispatch.state_key()
        assert inside != base
        assert inside[-1] == dispatch.mesh_fingerprint(mesh)
    assert dispatch.state_key() == base


# ---------------------------------------------------------------------------
# forced multi-device parity (subprocess, 8 fake CPU devices)
# ---------------------------------------------------------------------------

def _run_subprocess(code: str, ndev: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
               PYTHONPATH="src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_multidevice_parity_dense_sparse_uneven():
    """8-device mesh: dense + sparse fleets, uneven B=5 needing padding,
    state threading — all within 1e-6 of the vmap reference."""
    out = _run_subprocess("""
import jax, jax.numpy as jnp
assert jax.device_count() == 8, jax.device_count()
from repro.core.batch import (CECGraphBatch, CECGraphSparseBatch, run_batch,
                              run_batch_sharded)
from repro.core.graph import build_random_cec, sparsify
from repro.core.solver import SolverConfig
from repro.core.utility import make_bank
from repro.core.mesh import fleet_mesh
from repro.topo import make_fleet

cfg = SolverConfig(method="single", delta=0.5, eta_outer=0.05,
                   eta_inner=3.0, inner_iters=1)
mesh = fleet_mesh()
diff = lambda a, b: jax.tree_util.tree_reduce(
    max, jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b))

# uneven fleet: B=5 on 8 shards pads to 8, sliced back to 5
gs = [build_random_cec(make_fleet("power_law", 8, seed=s), 2, 10.0, seed=s)
      for s in range(5)]
batch = CECGraphBatch.from_graphs(gs)
banks = [make_bank("log", 2, seed=s) for s in range(5)]
ref = run_batch(batch, banks, 4.0, cfg, iters=8)
got = run_batch_sharded(batch, banks, 4.0, cfg, iters=8, mesh=mesh)
d = diff(ref, got)
assert d <= 1e-6, f"dense uneven parity {d}"
assert got.lam.shape == ref.lam.shape == (5, 2)

# state threading across the sharded boundary
ref2 = run_batch(batch, banks, 4.0, cfg, iters=8, state=ref.state)
got2 = run_batch_sharded(batch, banks, 4.0, cfg, iters=8, state=got.state,
                         mesh=mesh)
d = diff(ref2, got2)
assert d <= 1e-6, f"state-threaded parity {d}"

# sparse fleet, even B=8, broadcast bank
gs = [sparsify(build_random_cec(make_fleet("power_law", 8, seed=s), 2, 10.0,
                                seed=s)) for s in range(8)]
sbatch = CECGraphSparseBatch.from_graphs(gs)
bank = make_bank("log", 2, seed=0)
ref = run_batch(sbatch, bank, 4.0, cfg, iters=8)
got = run_batch_sharded(sbatch, bank, 4.0, cfg, iters=8, mesh=mesh)
d = diff(ref, got)
assert d <= 1e-6, f"sparse parity {d}"
print("MULTIDEV_OK")
""")
    assert "MULTIDEV_OK" in out


def test_multidevice_scenario_timeline():
    """run_scenario(mesh=...) on 8 devices tracks the unsharded run
    through warm-started event boundaries (B=3 seeds pad to 8)."""
    out = _run_subprocess("""
import jax, jax.numpy as jnp
assert jax.device_count() == 8
from repro.core.scenario import named_scenarios, run_scenario
from repro.core.mesh import fleet_mesh

sc = named_scenarios(horizon=10, n=8)["link_churn"]
ref = run_scenario(sc, seeds=(0, 1, 2))
got = run_scenario(sc, seeds=(0, 1, 2), mesh=fleet_mesh())
assert ref.utility_traj.shape == got.utility_traj.shape
d = float(jnp.max(jnp.abs(ref.utility_traj - got.utility_traj)))
assert d <= 1e-6, f"scenario parity {d}"
d = float(jnp.max(jnp.abs(ref.lam - got.lam)))
assert d <= 1e-6, f"scenario lam parity {d}"
print("SCENARIO_OK")
""")
    assert "SCENARIO_OK" in out


def test_multidevice_submesh():
    """A fleet mesh over a strict subset of the devices still agrees."""
    out = _run_subprocess("""
import jax, jax.numpy as jnp
assert jax.device_count() == 8
from repro.core.batch import CECGraphBatch, run_batch, run_batch_sharded
from repro.core.graph import build_random_cec
from repro.core.solver import SolverConfig
from repro.core.utility import make_bank
from repro.core.mesh import fleet_mesh
from repro.topo import make_fleet

cfg = SolverConfig(method="single", delta=0.5, eta_outer=0.05,
                   eta_inner=3.0, inner_iters=1)
gs = [build_random_cec(make_fleet("power_law", 8, seed=s), 2, 10.0, seed=s)
      for s in range(3)]
batch = CECGraphBatch.from_graphs(gs)
bank = make_bank("log", 2, seed=0)
ref = run_batch(batch, bank, 4.0, cfg, iters=6)
got = run_batch_sharded(batch, bank, 4.0, cfg, iters=6,
                        mesh=fleet_mesh(n_devices=3))
d = jax.tree_util.tree_reduce(
    max, jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))), ref, got))
assert d <= 1e-6, f"submesh parity {d}"
print("SUBMESH_OK")
""")
    assert "SUBMESH_OK" in out


# ---------------------------------------------------------------------------
# property tests for the promoted sharding helpers
# ---------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(ndim=st.integers(0, 6))
def test_fleet_spec_shards_leading_axis_only(ndim):
    spec = fleet_spec(ndim)
    if ndim == 0:
        assert tuple(spec) == ()
    else:
        assert spec[0] == FLEET_AXIS
        assert all(e is None for e in tuple(spec)[1:])


@settings(max_examples=50, deadline=None)
@given(size=st.integers(1, 64), n_shards=st.integers(1, 16))
def test_fleet_padded_size_properties(size, n_shards):
    p = fleet_padded_size(size, n_shards)
    assert p % n_shards == 0
    assert size <= p < size + n_shards


@settings(max_examples=30, deadline=None)
@given(size=st.integers(1, 12), n_shards=st.integers(1, 8),
       trailing=st.integers(0, 2), seed=st.integers(0, 1000))
def test_pad_unpad_roundtrip_is_bit_exact(size, n_shards, trailing, seed):
    """unpad(pad(x)) == x bitwise; pad lanes replicate the last row."""
    rng = np.random.default_rng(seed)
    shape = (size,) + (3,) * trailing
    tree = {"a": jnp.asarray(rng.normal(size=shape), jnp.float32),
            "b": jnp.asarray(rng.integers(0, 9, size=(size, 2)))}
    padded = pad_fleet(tree, n_shards)
    p = fleet_padded_size(size, n_shards)
    assert padded["a"].shape[0] == p
    back = unpad_fleet(padded, size)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))
        # every pad lane is the last real instance, so shards stay feasible
        for i in range(size, p):
            np.testing.assert_array_equal(np.asarray(padded[k][i]),
                                          np.asarray(tree[k][-1]))


@settings(max_examples=30, deadline=None)
@given(ndims=st.lists(st.integers(0, 4), min_size=1, max_size=4),
       shard=st.booleans())
def test_fleet_specs_tree_matches_leaf_ranks(ndims, shard):
    tree = [jnp.zeros((2,) * n) for n in ndims]
    specs = fleet_specs(tree, shard=shard)
    for n, spec in zip(ndims, specs):
        want = fleet_spec(n) if shard and n else None
        if want is None:
            assert tuple(spec) == ()
        else:
            assert spec == want


def test_shard_map_compat_identity_on_one_device():
    """The compat shim runs the body and honours specs on any jax version."""
    from repro.core.mesh import shard_map_compat

    mesh = fleet_mesh(n_devices=1)
    x = jnp.arange(12.0).reshape(4, 3)
    out = shard_map_compat(lambda a: a * 2, mesh,
                           fleet_specs(x), fleet_specs(x))(x)
    np.testing.assert_allclose(np.asarray(out), 2 * np.asarray(x))


def test_fleet_sizes_in_process():
    """B=1 and B=4 through the full driver (1-device mesh: n_shards=1 is
    the no-pad fast path; the size bookkeeping must stay exact)."""
    bank = make_bank("log", 2, seed=0)
    for rows in (1, 4):
        batch = CECGraphBatch.from_graphs(_graphs(n_instances=rows))
        ref = run_batch(batch, bank, 4.0, CONFIG, iters=3)
        got = run_batch_sharded(batch, bank, 4.0, CONFIG, iters=3)
        assert got.lam.shape[0] == rows
        assert _max_abs_diff(ref, got) <= TOL
