"""Fleet-scale sharded control plane == single-device reference."""
import os
import subprocess
import sys


def _run(code: str, ndev: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
               PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, cwd="/root/repo")
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_sharded_routing_matches_reference():
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import build_random_cec, get_cost, solve_routing
from repro.core.distributed import solve_routing_sharded
from repro.topo import connected_er

# n chosen so n_bar = 29 pads awkwardly → exercises uneven shard fallback?
# use 28 phys nodes → n_bar = 32, divisible by the 4×2 mesh
g = build_random_cec(connected_er(28, 0.25, seed=3), 3, 10.0, seed=0)
assert g.n_bar % 8 == 0, g.n_bar
mesh = jax.make_mesh((4, 2), ("data", "model"))
cost = get_cost("exp")
lam = jnp.array([15.0, 20.0, 25.0])
phi0 = g.uniform_phi()

ref_phi, ref_traj = solve_routing(g, cost, lam, phi0, 2.0, 40)
got_phi, got_traj = solve_routing_sharded(g, cost, lam, phi0, 2.0, 40, mesh)
np.testing.assert_allclose(np.asarray(got_traj), np.asarray(ref_traj),
                           rtol=1e-4, atol=1e-4)
np.testing.assert_allclose(np.asarray(got_phi), np.asarray(ref_phi),
                           rtol=1e-3, atol=1e-4)
print("SHARDED_OK")
""")
    assert "SHARDED_OK" in out


def test_control_plane_lowers_at_fleet_scale():
    """N=2048-node control plane compiles SPMD on an 8-device mesh."""
    out = _run("""
import jax
from repro.core.distributed import lower_control_plane
mesh = jax.make_mesh((4, 2), ("data", "model"))
compiled = lower_control_plane(2045, 3, mesh, n_iters=5)
ca = compiled.cost_analysis()
ca = ca[0] if isinstance(ca, (list, tuple)) else ca
assert ca.get("flops", 0) > 0
print("FLEET_OK", ca.get("flops"))
""")
    assert "FLEET_OK" in out
