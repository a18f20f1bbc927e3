"""Compiled-cost extraction for the roofline (§Roofline methodology).

Primary mode — **control-kernel roofline** (DESIGN.md §17.5): lower and
compile the fused control megakernel (``kernels/control_megakernel.py``)
and the stitched ``solver.step`` it replaces on the *same* problem shape,
read attained FLOPs and HBM bytes from ``compiled.cost_analysis()``, and
place both programs on the §Roofline axes (arithmetic intensity vs the
ridge point ``PEAK_FLOPS / HBM_BW``).  :func:`control_step_costs` returns
the raw per-program records; :func:`control_roofline_rows` turns them
into trajectory-schema rows that ``benchmarks/bench_megakernel.py``
publishes into ``benchmarks/trajectory/BENCH_<sha>.json``.

Legacy mode — the scan-aware LM-stack analyzer this module started as,
kept because ``benchmarks/perf_iterations.py`` drives it through the
``python -m repro.roofline.extract --arch A --shape S --out DIR`` CLI.
``compiled.cost_analysis()`` counts a ``lax.scan`` body ONCE, so a
62-layer stack reports ~1 layer of FLOPs; every stack is a homogeneous
repetition of one period, so every cost is affine in the period count —
X(L) = X(1) + (L−1)·ΔX — and we compile two shallow variants (1 and 2
periods) and extrapolate.  Analysis mode additionally unchunks attention
so the full O(S²) FLOPs are visible; FLOPs inside per-token recurrent
scans stay counted once (<10% for every assigned arch, EXPERIMENTS.md).

Importing this module has **no side effects**: the legacy path needs a
512-device host platform (``make_production_mesh``), and earlier
revisions forced it by mutating ``XLA_FLAGS`` at import time — poisoning
every later jax user in the process (the CPU backend would shard tiny
control-plane arrays across 512 fake devices).  The forced-device flag
is now scoped to a subprocess: :func:`main` re-execs itself with
``XLA_FLAGS`` set in the child's environment when the legacy sweep needs
it, and in-process callers of :func:`analyze_cell` must pass a ``mesh``
(or arrange the flag themselves *before* jax initialises).
"""
from __future__ import annotations

import dataclasses
import json
import os
import pathlib

# NOTE: no ``os.environ`` writes at import — see the module docstring.
import jax

from repro.roofline.analysis import (HBM_BW, PEAK_FLOPS, model_flops,
                                     roofline_terms)

#: host-platform device count the legacy LM-stack meshes require; applied
#: only inside the re-exec'd CLI subprocess, never to the importing process
FORCED_DEVICE_FLAG = "--xla_force_host_platform_device_count=512"


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


# --------------------------------------------------------------------------
# legacy LM-stack analyzer (scan-aware affine extrapolation)
# --------------------------------------------------------------------------

def _variant(cfg, n_periods: int):
    kw = dict(n_layers=len(cfg.period) * n_periods)
    if cfg.enc_dec:
        kw["n_enc_layers"] = n_periods
    cfg = dataclasses.replace(cfg, **kw)
    # §Perf variant: tighter MoE capacity factor (1.25 → 1.0)
    if "cf10" in os.environ.get("REPRO_PERF_VARIANT", "") and cfg.moe:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=1.0))
    return cfg


def _cell_costs(arch: str, shape_name: str, n_periods: int, mesh,
                unchunk_attention: bool) -> dict:
    """One analysis compile.

    ``unchunk_attention=True`` exposes the full O(S²) attention FLOPs to
    the cost model but lets GSPMD form (and reshard) S² score tensors that
    the production chunked/flash path never materializes — so FLOPs come
    from the unchunked compile and collective wire bytes from the chunked
    (production) compile.
    """
    from repro.configs import get_config
    from repro.launch import dryrun as D
    from repro.launch.mesh import dp_axes
    from repro.models import layers as L
    from repro.models import model as M
    from repro.parallel.annotate import activation_sharding
    from repro.roofline.hlo import parse_collectives

    cfg_full = get_config(arch)
    cfg = _variant(cfg_full, n_periods)

    # monkey-patch dryrun's registry handle so input_specs builds the variant
    orig = D.get_config
    D.get_config = lambda a, smoke=False: cfg if a == arch else orig(a, smoke)
    old_chunk = L.multihead_attention.__defaults__
    try:
        # layer scan unrolled → exact per-period costs
        M.UNROLL_SCAN = True
        if unchunk_attention:
            L.multihead_attention.__defaults__ = (0, None, 1 << 30)
        cfg2, step, args, kinds = D.input_specs(arch, shape_name)
        in_sh = D.shardings_for(cfg2, mesh, args, kinds)
        pv = os.environ.get("REPRO_PERF_VARIANT", "")
        if "fsdp256" in pv:
            ctx = activation_sharding(mesh, tuple(mesh.axis_names),
                                      model_axis=None)
        else:
            ctx = activation_sharding(mesh, dp_axes(mesh))
        with mesh, ctx:
            compiled = jax.jit(step, in_shardings=in_sh).lower(*args).compile()
    finally:
        M.UNROLL_SCAN = False
        D.get_config = orig
        L.multihead_attention.__defaults__ = old_chunk

    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    coll = parse_collectives(compiled.as_text(),
                             default_group=mesh.shape["model"])
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "wire": float(coll["total"]["wire_bytes"])}


def analyze_cell(arch: str, shape_name: str, outdir="experiments/roofline",
                 mesh=None) -> dict | None:
    """Scan-corrected roofline record for one LM arch × shape cell.

    In-process callers must pass ``mesh`` (the production mesh needs a
    512-device host platform; arrange ``XLA_FLAGS`` before jax
    initialises, or go through the CLI, which scopes the flag to a
    subprocess).  With ``mesh=None`` this builds
    ``make_production_mesh()`` against whatever devices exist and will
    raise on a plain CPU host — by design, instead of silently mutating
    global process state the way earlier revisions did.
    """
    from repro.configs import SHAPES, applicable, get_config
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    ok, why = applicable(cfg, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": "single"}
    out = pathlib.Path(outdir)
    out.mkdir(parents=True, exist_ok=True)
    if not ok:
        rec.update(status="skipped", reason=why)
        (out / f"{arch}__{shape_name}.json").write_text(json.dumps(rec))
        return rec

    mesh = mesh or make_production_mesh(multi_pod=False)
    has_attn = any(m == "attn" for m, _ in cfg.period)
    shape_k = SHAPES[shape_name].kind
    need_unchunked = has_attn and shape_k in ("train", "prefill")
    x1 = _cell_costs(arch, shape_name, 1, mesh, need_unchunked)
    x2 = _cell_costs(arch, shape_name, 2, mesh, need_unchunked)
    n = cfg.n_periods
    total = {k: x1[k] + (n - 1) * (x2[k] - x1[k]) for k in x1}
    if need_unchunked:
        # wire bytes from the production (chunked) path: the unchunked
        # compile reshards S² score tensors that never exist on TPU
        w1 = _cell_costs(arch, shape_name, 1, mesh, False)
        w2 = _cell_costs(arch, shape_name, 2, mesh, False)
        total["wire"] = w1["wire"] + (n - 1) * (w2["wire"] - w1["wire"])
        total["wire_unchunked"] = (x1["wire"]
                                   + (n - 1) * (x2["wire"] - x1["wire"]))

    shape = SHAPES[shape_name]
    chips = mesh.devices.size
    # analytic correction: FLOPs inside per-token recurrent scans are
    # counted once by cost analysis and cannot be unrolled (S=4k steps);
    # add the state-update arithmetic explicitly (<10% of any cell)
    rec_flops = 0.0
    per_layer = {"mamba": 10.0 * cfg.d_inner * cfg.d_state,
                 "mlstm": 5.0 * cfg.n_heads * cfg.hd ** 2,
                 "slstm": 8.0 * cfg.n_heads * cfg.hd ** 2}
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind in ("train", "prefill") else shape.global_batch)
    mult = 4.0 if shape.kind == "train" else 1.0  # fwd+bwd+remat
    for mixer, _ in cfg.period:
        if mixer in per_layer:
            rec_flops += per_layer[mixer] * n * tokens * mult
    total["flops"] += rec_flops / chips

    # flash-aware attention adjustments (train/prefill, self-attn):
    # the analysis compile materializes S² scores in HBM and computes the
    # full (non-causal-skipped) score matrix; the Pallas flash kernel
    # (kernels/flash_attention.py) keeps scores in VMEM and skips masked
    # blocks.  Record both raw and flash-adjusted numbers.
    n_attn = sum(1 for m, _ in cfg.period if m == "attn") * n
    B, S = shape.global_batch, shape.seq_len
    adj = dict(total)
    if shape.kind in ("train", "prefill") and n_attn and S > 1:
        H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        attn_flops = 4.0 * B * S * S * H * hd * n_attn * mult
        scores_bytes = 4.0 * 4.0 * B * H * S * S * n_attn * mult  # f32 r/w
        bq = 512
        flash_bytes = (2 * B * S * H * hd * 2 +
                       max(S // bq, 1) * B * S * KH * hd * 2) * n_attn * mult
        adj["flops"] = total["flops"] - 0.5 * attn_flops / chips  # causal skip
        adj["bytes"] = max(total["bytes"] - scores_bytes / chips
                           + flash_bytes / chips, flash_bytes / chips)
    rec["flash_adjusted"] = {k: adj[k] for k in ("flops", "bytes", "wire")}
    rec.update(
        status="ok", chips=int(chips),
        flops=total["flops"],                 # per chip, scan-corrected
        hbm_bytes=total["bytes"],
        wire_bytes_per_chip=total["wire"],
        per_period={k: x2[k] - x1[k] for k in x1},
        model_flops=model_flops(cfg, shape.kind, shape.seq_len,
                                shape.global_batch) / chips,
    )
    (out / f"{arch}__{shape_name}.json").write_text(json.dumps(rec, indent=1))
    return rec


def _needs_forced_devices() -> bool:
    return "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", "")


def _reexec_with_forced_devices() -> int:
    """Run the legacy CLI sweep in a child whose env carries the flag.

    This is the *only* place the 512-device host platform is requested,
    and it never leaks into the invoking process (the import-purity
    contract pinned by tests/test_roofline_extract.py).
    """
    import subprocess
    import sys

    flags = (os.environ.get("XLA_FLAGS", "") + " " + FORCED_DEVICE_FLAG)
    # the child lowers for a fake host mesh only; pinned to the CPU it
    # never reaches for a chip that the invoking process may hold
    env = dict(os.environ, XLA_FLAGS=flags.strip(), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-m", "repro.roofline.extract",
                        *sys.argv[1:]], env=env)
    return r.returncode


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default="experiments/roofline")
    ap.add_argument("--control", action="store_true",
                    help="control-kernel mode: megakernel-vs-stitched "
                         "roofline rows for the CEC control step (single "
                         "chip — no forced-device subprocess needed)")
    args = ap.parse_args()

    if args.control:
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

    # legacy LM-stack sweep: the production mesh needs 512 host devices —
    # request them in a child process, never in this one
    if _needs_forced_devices():
        return _reexec_with_forced_devices()

    from repro.configs import ARCH_IDS, SHAPES
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=False)
    archs = (args.arch,) if args.arch else ARCH_IDS
    shapes = (args.shape,) if args.shape else tuple(SHAPES)
    failed = []
    for a in archs:
        for s in shapes:
            try:
                r = analyze_cell(a, s, args.out, mesh)
                if r and r.get("status") == "ok":
                    print(f"[{a} × {s}] flops/chip={r['flops']:.3g} "
                          f"bytes/chip={r['hbm_bytes']:.3g} "
                          f"wire/chip={r['wire_bytes_per_chip']:.3g}",
                          flush=True)
                else:
                    print(f"[{a} × {s}] skipped", flush=True)
            except Exception as e:  # noqa: BLE001
                print(f"[{a} × {s}] FAILED: {e!r}", flush=True)
                failed.append(f"{a} × {s}")
    if failed:
        print(f"{len(failed)} cell(s) failed: {', '.join(failed)}",
              flush=True)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
