"""Fleet-scale ensemble solving: sharded vs vmap throughput (DESIGN.md §14).

The paper's evaluation sweeps thousands of random instance draws; PR-1
made that one vmapped XLA program (``run_batch``) and §14 shards the
instance axis over a device mesh (``run_batch_sharded``).  This bench
answers the operational question — *how many power-law fleets does each
driver solve per wall-clock second?* — on a batch of distinct
``topo.make_fleet("power_law")`` draws tiled to fleet size.

The headline row asserts the smoke bar: on the single CPU device CI runs
on, the sharded driver's 1-device mesh traces to the *same* vmapped
executable plus shard_map bookkeeping, so its throughput must stay
within noise of the vmap path (≥ 0.75× on a 1-warmup smoke run — an
honest bound: CPU CI timing jitter makes a strict ≥ 1× assert flaky,
and any real dispatch pathology lands far below it).  Multi-device
speedups are reported when the process actually has devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8`` reproduces the
CI sharding job locally); CPU fake devices share the same cores, so the
number is a scaling *proof*, not a perf claim — real fleets shard over
real accelerators.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.batch import CECGraphBatch, run_batch, run_batch_sharded
from repro.core.graph import build_random_cec
from repro.core.solver import SolverConfig
from repro.core.utility import make_bank
from repro.core.mesh import fleet_mesh
from repro.topo import make_fleet

from . import common
from .common import dump, emit, timeit

W = 2                       # sessions per instance
N_NODES = 12                # physical nodes per power-law fleet draw
N_DISTINCT = 8              # distinct seeds tiled to the batch size
SMOKE_RATIO_FLOOR = 0.75    # 1-device sharded/vmap throughput bar (see doc)


def _fleet_batch(n_instances: int) -> tuple[CECGraphBatch, list]:
    graphs = [build_random_cec(make_fleet("power_law", N_NODES, seed=s),
                               W, 10.0, seed=s) for s in range(N_DISTINCT)]
    tiled = [graphs[i % N_DISTINCT] for i in range(n_instances)]
    banks = [make_bank("log", W, seed=i % N_DISTINCT)
             for i in range(n_instances)]
    return CECGraphBatch.from_graphs(tiled), banks


def main() -> list[dict]:
    iters = common.scaled(20, 3)
    fleet_sizes = common.scaled([1024, 4096], [8])
    config = SolverConfig(method="single", delta=0.5, eta_outer=0.05,
                          eta_inner=3.0, inner_iters=1)
    mesh = fleet_mesh()
    ndev = mesh.shape["fleet"]

    rows = []
    for B in fleet_sizes:
        batch, banks = _fleet_batch(B)

        vmap_fn = jax.jit(lambda b, bk: run_batch(
            b, bk, 4.0, config, iters=iters))
        sharded_fn = jax.jit(lambda b, bk: run_batch_sharded(
            b, bk, 4.0, config, iters=iters, mesh=mesh))

        from repro.core.batch import stack_banks
        stacked = stack_banks(banks)
        ref, t_vmap = timeit(vmap_fn, batch, stacked)
        got, t_shard = timeit(sharded_fn, batch, stacked)

        # the two drivers must be solving the same fleet
        drift = float(jnp.max(jnp.abs(ref.lam - got.lam)))
        assert drift <= 1e-6, f"sharded/vmap drift {drift} at B={B}"

        vmap_ips = B / t_vmap
        shard_ips = B / t_shard
        ratio = shard_ips / vmap_ips
        rec = {"fleet_size": B, "iters": iters, "n_devices": int(ndev),
               "vmap_instances_per_s": vmap_ips,
               "sharded_instances_per_s": shard_ips,
               "sharded_over_vmap": ratio}
        emit(f"fleet.B{B}.vmap_solve", t_vmap,
             f"ips={vmap_ips:.0f};iters={iters}")
        emit(f"fleet.B{B}.sharded_solve", t_shard,
             f"ips={shard_ips:.0f};ratio={ratio:.2f};ndev={ndev}")
        rows.append(rec)

    if common.SMOKE and int(ndev) == 1:
        r = rows[0]["sharded_over_vmap"]
        assert r >= SMOKE_RATIO_FLOOR, (
            f"1-device sharded throughput fell to {r:.2f}x of vmap — "
            f"shard_map dispatch overhead regression (floor "
            f"{SMOKE_RATIO_FLOOR}x)")

    dump("bench_fleet", rows)
    return rows
