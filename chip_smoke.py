#!/usr/bin/env python3
"""Bring-up smoke test: the CEC control plane on a TPU, end to end.

    python chip_smoke.py                # five phases on one chip
    python chip_smoke.py --four-chips   # the sharded fleet engine, 4 chips

With no option, five phases run in this one process.  Each builds a
deployment at the size the default dispatch needs to reach one control
step implementation, drives it through the entry point a user calls
(``RouterFleet`` for K dense tenants, ``CECRouter`` for one sparse
tenant) with the default dispatch, warms up, runs ``INTERVALS`` control
intervals with a host measurement callback, and then runs the same
intervals again on the plain jnp path (the Pallas paths forced off with
``kernel_dispatch``/``megakernel_dispatch``, f32 matmuls at "highest"
precision) on the same chip.  Each phase prints one line: the dispatch
path it reached (from the ``solver.dispatch:*`` trace instant), its
shapes, its set-up and warm-up seconds, the steady seconds per interval
(a smoke figure, not a benchmark) and its largest deviation from the
reference.  A phase fails if it reached another path than its expected
one, or if a deviation passes ``TOL``.

``--four-chips`` runs only ``run_batch_sharded`` over a 4-chip fleet
mesh against ``run_batch`` on one chip: B = 256 dense instances, parity
to 1e-6 (the bound the README claims), and B/4 instances on each chip.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Any failure exits non-zero before it.  Without a TPU (JAX falls back to
the CPU when the TPU runtime fails to start) the script exits non-zero
before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

INTERVALS = 10
LAM_TOTAL = 60.0            # the paper's total input rate (§IV)

# Largest deviation from the jnp reference, relative to its scale: λ_total
# for Λ, |D| for the cost D and for the net utility U = u − D.  Both paths
# compute in f32 (the reference at "highest" matmul precision, the
# kernels with f32 dots and f32 accumulation); they differ only in the
# order of their sums.  An exp-cost total over ~10³ edges then carries a
# few ulps, ~1e-7 of |D|, per observation.  The two-point gradient divides
# cost differences by 2δ = 1, and mirror ascent moves Λ by η_outer·λ_w·Δg
# per interval, so over 11 intervals the drift stays well inside 1e-4 of
# scale.  A real defect (a lost edge, a session dropped, a wrong sign)
# moves these by 1e-2 or more.
TOL = 1e-4
FOUR_CHIP_TOL = 1e-6


# ---------------------------------------------------------------------------
# deployments
# ---------------------------------------------------------------------------

def _log_utility(a, b):
    """Host measurement callback: Σ_w a_w·log(1 + b_w·λ_w) of an admission
    stack [..., m, W] (the paper's log utilities), numpy on the host."""
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)

    def measure(lams):
        lams = np.asarray(lams, np.float64)
        if a.ndim == 2:                   # fleet-batched: [K, m, W] → [K, m]
            return (a[:, None] * np.log1p(b[:, None] * lams)).sum(-1)
        return (a * np.log1p(b * lams)).sum(-1)

    return measure


def _banks(n, n_sessions):
    import numpy as np
    from repro.core import make_bank

    banks = [make_bank("log", n_sessions, seed=s, lam_total=LAM_TOTAL)
             for s in range(n)]
    return (np.stack([np.asarray(b.a) for b in banks]),
            np.stack([np.asarray(b.b) for b in banks]))


def _dense_er(n_phys, n_sessions, seed, mean_degree=8.0):
    from repro.core import build_random_cec
    from repro.topo import connected_er

    adj = connected_er(n_phys, mean_degree / (n_phys - 1), seed=seed)
    return build_random_cec(adj, n_sessions, 10.0, seed=seed)


def _sparse_power_law(n_phys, n_sessions, seed=0):
    """A feasible draw on ``topo.make_fleet("power_law", n)``, built
    directly in the edge-list layout (the dense build of a 4k-node graph
    would hold ~1 GB of masks)."""
    import numpy as np
    from repro.core import InfeasibleTopology, build_augmented_sparse
    from repro.core.graph import random_deployment
    from repro.topo import make_fleet

    adj = make_fleet("power_law", n_phys, seed=seed)
    for t in range(20):
        rng = np.random.default_rng(seed + 1000 * t)
        link = rng.uniform(0.05, 2.0, (n_phys, n_phys)).astype(np.float32)
        link = np.maximum(link, link.T) * 10.0
        comp = (rng.uniform(0.5, 1.5, n_phys) * 10.0).astype(np.float32)
        try:
            return build_augmented_sparse(
                adj, random_deployment(n_phys, n_sessions, rng), link, comp)
        except InfeasibleTopology:
            continue
    raise InfeasibleTopology(f"no feasible power-law draw at n={n_phys}")


def paper():
    """K = 32 tenants of the paper's §IV instance (Connected-ER(25, 0.2),
    W = 3, λ = 60): the jnp path's side of every threshold."""
    from repro.configs import cec_paper
    from repro.serve import RouterFleet

    K = 32
    graphs, a, b = [], [], []
    for s in range(K):
        g, bank = cec_paper.build(cec_paper.PAPER, seed=s)
        graphs.append(g)
        a.append(bank.a)
        b.append(bank.b)
    return (lambda: RouterFleet(graphs, [LAM_TOTAL] * K),
            _log_utility(a, b), {"K": K, "N": 25, "W": 3})


def dense_mega():
    """K = 8 tenants of Connected-ER(250), mean degree 8, W = 8
    (n̄ = 259): the vmapped dense megakernel."""
    from repro.serve import RouterFleet

    K, n, W = 8, 250, 8
    graphs = [_dense_er(n, W, seed=s) for s in range(K)]
    return (lambda: RouterFleet(graphs, [LAM_TOTAL] * K),
            _log_utility(*_banks(K, W)), {"K": K, "N": n, "W": W})


def dense_stitched():
    """K = 2 tenants of Connected-ER(1000), mean degree 8, W = 16
    (n̄ = 1017): above the megakernel's VMEM fit, so the per-phase
    ``flow_step`` + ``omd_update`` kernels."""
    from repro.serve import RouterFleet

    K, n, W = 2, 1000, 16
    graphs = [_dense_er(n, W, seed=s) for s in range(K)]
    return (lambda: RouterFleet(graphs, [LAM_TOTAL] * K),
            _log_utility(*_banks(K, W)), {"K": K, "N": n, "W": W})


def _sparse_phase(n, W):
    from repro.serve import CECRouter

    g = _sparse_power_law(n, W)
    a, b = _banks(1, W)
    return (lambda: CECRouter(graph=g, lam_total=LAM_TOTAL),
            _log_utility(a[0], b[0]),
            {"K": 1, "N": n, "W": W, "d_max": g.d_max, "d_src": g.d_src,
             "d_in_max": g.d_in_max})


def sparse_mega():
    """``make_fleet("power_law", 1024)``, W = 3 through ``CECRouter``.  The
    sparse megakernel does not compile for a TPU (its gathers span more
    than one vreg), so the dispatch rule sends this graph to the stitched
    sparse kernels."""
    return _sparse_phase(1024, 3)


def sparse_stitched():
    """``make_fleet("power_law", 4096)``, W = 16 through ``CECRouter``:
    ``flow_step_sparse`` + ``omd_update_sparse``."""
    return _sparse_phase(4096, 16)


# (name, deployment, expected path, sparse)
PHASES = (("paper", paper, "jnp", False),
          ("dense-mega", dense_mega, "megakernel", False),
          ("dense-stitched", dense_stitched, "stitched", False),
          ("sparse-mega", sparse_mega, "stitched", True),
          ("sparse-stitched", sparse_stitched, "stitched", True))


# ---------------------------------------------------------------------------
# running a phase
# ---------------------------------------------------------------------------

def _drive(make_router, measure):
    """Build, warm up, run INTERVALS intervals; records and seconds."""
    t0 = time.perf_counter()
    router = make_router()
    t1 = time.perf_counter()
    recs = [router.control_step(measure)]           # compiles the step
    t2 = time.perf_counter()
    steady = []
    for _ in range(INTERVALS):
        t = time.perf_counter()
        recs.append(router.control_step(measure))   # records are host
        steady.append(time.perf_counter() - t)      # arrays: synced
    return recs, {"setup_s": t1 - t0, "warmup_s": t2 - t1,
                  "steady_s_per_interval": statistics.median(steady)}


def _path(args):
    if args["mode"] == "megakernel":
        return "megakernel"
    return "stitched" if args["kernels"] else "jnp"


def _deviation(recs, refs, lam_total):
    import numpy as np

    dev = {"lam": 0.0, "cost": 0.0, "utility": 0.0}
    for r, q in zip(recs, refs, strict=True):
        scale = np.maximum(np.abs(np.asarray(q["cost"], np.float64)), 1.0)
        for key in ("lam", "cost", "utility"):
            got = np.asarray(r[key], np.float64)
            want = np.asarray(q[key], np.float64)
            if not (np.all(np.isfinite(got)) and np.all(np.isfinite(want))):
                raise FloatingPointError(f"non-finite {key}")
            d = np.abs(got - want)
            d = d / lam_total if key == "lam" else d / scale
            dev[key] = max(dev[key], float(np.max(d)))
    return dev


def run_phase(name, deployment, expect, sparse) -> None:
    import jax
    from repro.core import dispatch
    from repro.obs import trace

    make_router, measure, shape = deployment()
    tracer = trace.install_tracer()
    try:
        recs, secs = _drive(make_router, measure)
    finally:
        trace.uninstall_tracer()
    reached = {(_path(e["args"]), e["args"]["sparse"], e["args"]["n_bar"])
               for e in tracer.events
               if e["name"].startswith("solver.dispatch:")}
    with dispatch.kernel_dispatch(10**9), \
            dispatch.megakernel_dispatch(10**9), \
            jax.default_matmul_precision("highest"):
        refs, ref_secs = _drive(make_router, measure)
    dev = _deviation(recs, refs, LAM_TOTAL)
    paths = sorted({p for p, _, _ in reached})
    ok = (paths == [expect] and {s for _, s, _ in reached} == {sparse}
          and max(dev.values()) <= TOL)
    line = {"phase": name, "ok": ok, "path": paths, "expected": expect,
            "sparse": sparse, "n_bar": sorted({n for _, _, n in reached}),
            **shape, "intervals": INTERVALS, **secs,
            "reference_steady_s_per_interval":
                ref_secs["steady_s_per_interval"],
            "max_deviation": max(dev.values()), "deviation": dev,
            "tol": TOL}
    print("phase " + json.dumps(line), flush=True)
    if not ok:
        raise SystemExit(f"phase {name} failed: {line}")


# ---------------------------------------------------------------------------
# four chips: the sharded fleet engine
# ---------------------------------------------------------------------------

def four_chips() -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import CECGraphBatch, make_bank, run_batch
    from repro.core.batch import run_batch_sharded
    from repro.core.solver import serving_defaults
    from repro.core.mesh import fleet_mesh
    from repro.obs import trace

    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--four-chips needs 4 chips, found {len(devices)}")
    B, n, W, iters = 256, 250, 8, 20
    batch = CECGraphBatch.from_graphs(
        [_dense_er(n, W, seed=s) for s in range(B)])
    banks = [make_bank("log", W, seed=s, lam_total=LAM_TOTAL)
             for s in range(B)]
    config = serving_defaults()
    mesh = fleet_mesh()

    tracer = trace.install_tracer()
    try:
        t0 = time.perf_counter()
        sharded = run_batch_sharded(batch, banks, LAM_TOTAL, config,
                                    iters=iters, mesh=mesh)
        jax.block_until_ready(sharded)
        t_sharded = time.perf_counter() - t0
    finally:
        trace.uninstall_tracer()
    with jax.default_device(devices[0]):
        t0 = time.perf_counter()
        single = run_batch(batch, banks, LAM_TOTAL, config, iters=iters)
        jax.block_until_ready(single)
        t_single = time.perf_counter() - t0

    diff = jax.tree_util.tree_reduce(max, jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))), sharded, single))
    rows = {d.id: 0 for d in devices}
    per_device_bytes = {d.id: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves(sharded):
        for shard in leaf.addressable_shards:
            per_device_bytes[shard.device.id] += shard.data.nbytes
    for shard in sharded.lam.addressable_shards:
        rows[shard.device.id] += shard.data.shape[0]
    paths = sorted({_path(e["args"]) for e in tracer.events
                    if e["name"].startswith("solver.dispatch:")})
    ok = (diff <= FOUR_CHIP_TOL
          and sorted(rows.values()) == [B // 4] * 4
          and len(set(per_device_bytes.values())) == 1)
    line = {"phase": "four-chips", "ok": ok, "path": paths, "B": B, "N": n,
            "W": W, "iters": iters, "max_abs_diff": diff,
            "tol": FOUR_CHIP_TOL, "lam_sharding": str(sharded.lam.sharding),
            "instances_per_device": rows,
            "bytes_per_device": per_device_bytes,
            "sharded_s_incl_compile": t_sharded,
            "single_chip_s_incl_compile": t_single}
    print("phase " + json.dumps(line), flush=True)
    if not ok:
        raise SystemExit(f"four-chip phase failed: {line}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only run_batch_sharded on 4 chips against "
                         "run_batch on one")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache

    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    cached = sum(1 for f in pathlib.Path(cache).glob("*") if f.is_file()) \
        if pathlib.Path(cache).is_dir() else 0
    print(f"devices: {len(devices)} x {devices[0].device_kind}; compile "
          f"cache: {cache} ({cached} entries at start)", flush=True)
    if args.four_chips:
        four_chips()
    else:
        for phase in PHASES:
            run_phase(*phase)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
