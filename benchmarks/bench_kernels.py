"""Kernel micro-benchmarks: jnp reference path timings on CPU + control-
plane step scaling with fleet size (the Pallas kernels themselves target
TPU; interpret-mode timing is not meaningful, so we time the jnp
execution paths that the kernels replace and report the roofline-model
speedup the fused kernel buys on v5e)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import build_random_cec, get_cost, omd_step
from repro.kernels import ref
from repro.kernels.ops import flow_step_op, omd_update_op
from repro.topo import connected_er

from . import common
from .common import dump, emit, timeit


def _pallas_interpret_rows() -> list[dict]:
    """Execute the Pallas control-plane kernels (interpret mode off-TPU)
    against their einsum oracles — the CI smoke proof that the kernel
    path itself still runs, not just the jnp path it replaces."""
    W, N = 3, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    t = jnp.abs(jax.random.normal(ks[0], (W, N)))
    phi = jnp.abs(jax.random.normal(ks[1], (W, N, N)))
    inj = jnp.abs(jax.random.normal(ks[2], (W, N)))
    got = flow_step_op(t, phi, inj, interpret=True)
    err_flow = float(jnp.abs(got - ref.flow_step_ref(t, phi, inj)).max())
    mask = (phi > 0.5).astype(jnp.float32)
    got2 = omd_update_op(phi * mask, phi, mask, 1.0, interpret=True)
    err_omd = float(jnp.abs(
        got2 - ref.omd_update_ref(phi * mask, phi, mask, 1.0)).max())
    assert err_flow < 1e-4 and err_omd < 1e-4, (err_flow, err_omd)
    emit("kernels.pallas_interpret", 0.0,
         f"flow_err={err_flow:.2e};omd_err={err_omd:.2e}")
    return [{"bench": "pallas_interpret", "n": N,
             "flow_step_err": err_flow, "omd_update_err": err_omd}]


def main() -> list[dict]:
    rows = _pallas_interpret_rows()
    cost = get_cost("exp")
    lam3 = jnp.array([20.0, 20.0, 20.0])

    # control-plane iteration vs fleet size (dense masked-tensor path)
    for n in common.scaled((25, 50, 100, 200, 400), (25, 50)):
        g = build_random_cec(connected_er(n, min(0.2, 8.0 / n), seed=1), 3,
                             10.0, seed=0)
        phi = g.uniform_phi()
        stepf = jax.jit(lambda p, g=g: omd_step(g, cost, p, lam3, 3.0).phi)
        _, secs = timeit(stepf, phi, warmup=1, iters=5)
        nb = g.n_bar
        # HBM-bound estimate for the fused omd_update kernel on v5e:
        # one read+write of phi/delta/mask [W,N,N] f32 at 819 GB/s
        bytes_moved = 4 * 3 * nb * nb * 4
        v5e_est = bytes_moved / 819e9
        rows.append({"bench": "omd_step", "n": n, "cpu_s": secs,
                     "v5e_kernel_est_s": v5e_est})
        emit(f"kernels.omd_step.n{n}", secs,
             f"v5e_fused_est_us={v5e_est*1e6:.2f}")

    dump("bench_kernels", rows)
    return rows


if __name__ == "__main__":
    main()
