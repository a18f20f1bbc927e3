"""Compiled-cost extraction for the roofline (§Roofline methodology).

**Control-kernel roofline** (DESIGN.md §17.5): lower and compile the
fused control megakernel (``kernels/control_megakernel.py``) and the
stitched ``solver.step`` it replaces on the *same* problem shape, read
attained FLOPs and HBM bytes from ``compiled.cost_analysis()``, and
place both programs on the §Roofline axes (arithmetic intensity vs the
ridge point ``PEAK_FLOPS / HBM_BW``).  :func:`control_step_costs` returns
the raw per-program records; :func:`control_roofline_rows` turns them
into trajectory-schema rows that ``benchmarks/bench_megakernel.py``
publishes into ``benchmarks/trajectory/BENCH_<sha>.json``.

Importing this module has **no side effects**: earlier revisions
mutated ``XLA_FLAGS`` at import time (a 512-device host platform),
poisoning every later jax user in the process
(``tests/test_roofline_extract.py`` pins the clean import).

``python -m repro.roofline.extract --out DIR`` writes the rows to
``DIR/control_step.json``.
"""
from __future__ import annotations

import json
import os
import pathlib

# NOTE: no ``os.environ`` writes at import — see the module docstring.
import jax

from repro.roofline.analysis import HBM_BW, PEAK_FLOPS, roofline_terms


# --------------------------------------------------------------------------
# control-kernel roofline (primary): megakernel vs stitched control step
# --------------------------------------------------------------------------

def _cost_record(compiled) -> dict:
    """FLOPs / HBM bytes / arithmetic intensity of one compiled program."""
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    return {"flops": flops, "bytes": hbm,
            "intensity": flops / hbm if hbm else 0.0}


def control_step_costs(n_nodes: int = 24, n_sessions: int = 6, *,
                       k_iters: int = 3, phi_dtype: str = "float32",
                       seed: int = 0) -> dict:
    """Compile the fused megakernel and the stitched step on one shape.

    Builds a random CEC instance (``n_nodes`` physical nodes,
    ``n_sessions`` sessions, K = ``k_iters`` oracle iterations), traces
    both control-step programs on it, and reads each
    ``compiled.cost_analysis()``.  Returns::

        {"megakernel": {flops, bytes, intensity},
         "stitched":   {flops, bytes, intensity},
         "shape": {...}}

    Dispatch overrides are scoped to tracing (``megakernel_dispatch`` and
    the φ-dtype env knob are restored on exit); nothing is executed, so
    this is cheap enough for CI.  Off-TPU the megakernel lowers in
    interpret mode, where ``cost_analysis`` sees the *interpreter*
    program, not the Mosaic kernel — the FLOP/byte record is exact only
    on a real TPU backend and indicative elsewhere (the bench gates its
    real bar on TPU accordingly).
    """
    import jax.numpy as jnp

    from repro.core import build_random_cec, dispatch, solver
    from repro.core.problem import Problem
    from repro.topo import connected_er

    g = build_random_cec(connected_er(n_nodes, 0.35, seed=seed),
                         n_sessions, 10.0, seed=seed)
    problem = Problem.create(g, lam_total=8.0, cost="exp")
    config = solver.SolverConfig(method="nested", delta=0.5, eta_outer=0.05,
                                 eta_inner=0.05, inner_iters=k_iters,
                                 grad_mode="sampled")
    state = solver.init(problem, config)
    tau = jnp.ones((2 * g.n_sessions,), jnp.float32)

    def mega(state, tau):
        return solver._megakernel_step(problem, config, state, tau)

    def stitched(state, tau):
        return solver._sampled_step(problem, config, state, tau,
                                    config.eta_outer, config.eta_inner)

    prev_dtype = os.environ.get("REPRO_MEGAKERNEL_PHI_DTYPE")
    try:
        os.environ["REPRO_MEGAKERNEL_PHI_DTYPE"] = phi_dtype
        with dispatch.megakernel_dispatch(1):
            mk = jax.jit(mega).lower(state, tau).compile()
    finally:
        if prev_dtype is None:
            os.environ.pop("REPRO_MEGAKERNEL_PHI_DTYPE", None)
        else:
            os.environ["REPRO_MEGAKERNEL_PHI_DTYPE"] = prev_dtype
    st = jax.jit(stitched).lower(state, tau).compile()

    return {"megakernel": _cost_record(mk),
            "stitched": _cost_record(st),
            "shape": {"n_nodes": n_nodes, "n_bar": int(g.n_bar),
                      "n_sessions": n_sessions, "k_iters": k_iters,
                      "phi_dtype": phi_dtype,
                      "backend": jax.default_backend()}}


def control_roofline_rows(costs: dict | None = None, **shape_kw) -> list:
    """Trajectory-schema roofline rows for the two control-step programs.

    Each row carries the raw ``cost_analysis`` FLOPs/bytes, the
    arithmetic intensity, its position against the ridge point
    ``PEAK_FLOPS / HBM_BW`` (v5e: ~240 FLOP/byte), and the three-term
    roofline split from :func:`analysis.roofline_terms` (wire bytes are
    zero — the control step is single-chip).  ``attained_peak_fraction``
    is the fraction of peak compute the program can reach at its
    intensity assuming it hits the memory roof — the number the §17
    speedup claim is checked against.
    """
    costs = costs or control_step_costs(**shape_kw)
    ridge = PEAK_FLOPS / HBM_BW
    rows = []
    for variant in ("megakernel", "stitched"):
        c = costs[variant]
        t = roofline_terms(c["flops"], c["bytes"], 0.0, 1)
        rows.append({
            "metric": f"roofline.control_step.{variant}",
            "variant": variant, **costs["shape"],
            "flops": c["flops"], "hbm_bytes": c["bytes"],
            "intensity_flop_per_byte": c["intensity"],
            "ridge_flop_per_byte": ridge,
            "bound": "compute" if c["intensity"] >= ridge else "memory",
            "attained_peak_fraction": min(c["intensity"] / ridge, 1.0),
            "compute_s": t["compute_s"], "memory_s": t["memory_s"],
        })
    mk, st = costs["megakernel"], costs["stitched"]
    if mk["bytes"] and st["bytes"]:
        rows.append({
            "metric": "roofline.control_step.bytes_ratio",
            **costs["shape"],
            "value": st["bytes"] / mk["bytes"],
            "note": "stitched/megakernel HBM-byte ratio — the fused "
                    "kernel's VMEM residency removes per-phase HBM "
                    "round-trips (DESIGN.md §17.2)"})
    return rows


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/roofline")
    args = ap.parse_args()

    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    rows = control_roofline_rows()
    (out / "control_step.json").write_text(json.dumps(rows, indent=1))
    for r in rows:
        if "intensity_flop_per_byte" in r:
            print(f"[control_step × {r['variant']}] "
                  f"flops={r['flops']:.3g} bytes={r['hbm_bytes']:.3g} "
                  f"intensity={r['intensity_flop_per_byte']:.2f} "
                  f"({r['bound']}-bound)", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
