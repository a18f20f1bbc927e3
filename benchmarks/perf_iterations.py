"""§Perf hillclimbing harness: baseline vs optimization variants for the
three selected cells (EXPERIMENTS.md §Perf).

Each iteration re-lowers the cell with one optimization flag flipped
(REPRO_PERF_VARIANT) in a fresh subprocess, extracts the scan-corrected
roofline inputs, and logs hypothesis → before → after → verdict.

Cells (selection rationale in EXPERIMENTS.md):
  deepseek-coder-33b × train_4k   — most collective-bound baseline
  qwen2-vl-72b × decode_32k       — worst roofline fraction (serving)
  jamba-1.5-large-398b × train_4k — paper-scale MoE/hybrid, memory-bound

Plus one control-plane cell on the batched JOWR path: sequential jitted
per-instance solves vs one vmapped ``run_batch`` program over the
same ensemble (hypothesis: vmap amortizes per-solve dispatch and compiles
one fused scan → per-instance time drops).
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

from . import common
from .common import dump, emit

CELLS = [
    ("deepseek-coder-33b", "train_4k",
     ["bf16params", "attnbatch", "fsdp256+bf16params"]),
    ("qwen2-vl-72b", "decode_32k",
     ["tpserve", "int8kv", "tpserve+int8kv"]),
    ("jamba-1.5-large-398b", "train_4k",
     ["attnbatch", "cf10", "hybridshard"]),
]

HYPOTHESES = {
    "bf16params": "bf16 weights halve every FSDP all-gather / grad "
                  "reduce payload → wire ≈ −45%",
    "attnbatch": "explicit batch-only attention sharding replaces GSPMD "
                 "involuntary replication of mid-attention tensors → "
                 "wire down on attn-heavy cells",
    "tpserve": "TP-only serving weights: zero per-step parameter "
               "all-gathers → decode wire ≈ −90%",
    "int8kv": "int8 KV cache halves decode cache traffic → memory ≈ −45%",
    "cf10": "MoE capacity 1.25→1.0 cuts expert compute/memory ≈ −20%",
    "fsdp256": "pure ZeRO-3 over all 256 chips removes per-layer TP "
               "partial-sum all-reduces (~2 TB/chip) for ~3× param "
               "gathers (~200 GB) → wire ≈ −75%",
    "hybridshard": "FSDP dense weights + expert-parallel MoE: drops TP "
                   "activation all-reduces on the non-expert 78%% of the "
                   "model → wire ≈ −25%",
    "batched_vmap": "one vmapped run_batch program over B instances "
                    "amortizes per-solve dispatch vs a Python loop of "
                    "jitted solves → per-instance time drops",
}


def control_plane_rows(B: int = 8, outer_iters: int = 20) -> list[dict]:
    """Batched control-plane cell: sequential vs vmapped JOWR ensemble."""
    from .bench_batched import measure_seq_vs_batched

    t_seq, t_bat = measure_seq_vs_batched(B, outer_iters=outer_iters)

    verdict = "confirmed" if t_bat < t_seq * 0.95 else (
        "neutral" if t_bat < t_seq * 1.05 else "refuted")
    rows = [
        {"arch": "cec_control_plane", "shape": f"omad_B{B}",
         "variant": "sequential", "s_per_instance": t_seq / B},
        {"arch": "cec_control_plane", "shape": f"omad_B{B}",
         "variant": "batched_vmap", "hypothesis": HYPOTHESES["batched_vmap"],
         "verdict": verdict, "s_per_instance": t_bat / B,
         "speedup": t_seq / t_bat},
    ]
    emit(f"perf.cec_control_plane.omad_B{B}.sequential", t_seq / B, "baseline")
    emit(f"perf.cec_control_plane.omad_B{B}.batched_vmap", t_bat / B,
         f"speedup={t_seq/t_bat:.2f}x;{verdict}")
    return rows


def run_variant(arch: str, shape: str, variant: str,
                out_root: str = "experiments/perf") -> dict:
    out = pathlib.Path(out_root) / variant.replace("+", "_")
    f = out / f"{arch}__{shape}.json"
    if not f.exists():
        # the child lowers for a fake CPU mesh; pinned to the CPU it never
        # reaches for a chip that this process holds
        env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
                   REPRO_PERF_VARIANT=variant)
        r = subprocess.run(
            [sys.executable, "-m", "repro.roofline.extract", "--arch", arch,
             "--shape", shape, "--out", str(out)],
            env=env, capture_output=True, text=True, cwd=".")
        if not f.exists():
            raise RuntimeError(f"{arch}/{shape}/{variant}: "
                               + r.stdout[-500:] + r.stderr[-500:])
    return json.loads(f.read_text())


def main() -> list[dict]:
    from repro.configs import SHAPES, get_config
    from repro.roofline.analysis import analytic_bytes, roofline_terms

    if common.SMOKE:
        # the roofline cells re-lower multi-hundred-B models in
        # subprocesses — far past the smoke budget; the batched
        # control-plane cell alone exercises this module's solve paths
        return control_plane_rows(B=2, outer_iters=3)

    rows = control_plane_rows()
    for arch, shape, variants in CELLS:
        base = run_variant(arch, shape, "baseline")
        cfg = get_config(arch)

        base_flops = base["flash_adjusted"]["flops"]

        def terms(rec, variant):
            big = cfg.approx_params() > 100e9
            train = shape == "train_4k"
            pb = 2 if (big or not train or "bf16params" in variant) else 4
            kb = 1 if "int8kv" in variant else 2
            hbm = analytic_bytes(cfg, SHAPES[shape], rec["chips"],
                                 param_bytes=pb, kv_bytes=kb,
                                 moment_bytes=2 if big else 4)
            # compute is sharding-invariant: use the baseline measurement
            # (per-chip flops under exotic shardings reflect partitioner
            # replication choices, not useful work)
            return roofline_terms(base_flops, hbm,
                                  rec["wire_bytes_per_chip"], 1)

        t0 = terms(base, "baseline")
        rows.append({"arch": arch, "shape": shape, "variant": "baseline",
                     **{k: v for k, v in t0.items()}})
        emit(f"perf.{arch}.{shape}.baseline",
             max(t0["compute_s"], t0["memory_s"], t0["collective_s"]),
             f"bottleneck={t0['bottleneck']};frac={t0['roofline_fraction']:.3f}")
        for v in variants:
            rec = run_variant(arch, shape, v)
            t = terms(rec, v)
            dom0 = max(t0["compute_s"], t0["memory_s"], t0["collective_s"])
            dom = max(t["compute_s"], t["memory_s"], t["collective_s"])
            verdict = "confirmed" if dom < dom0 * 0.95 else (
                "neutral" if dom < dom0 * 1.05 else "refuted")
            rows.append({"arch": arch, "shape": shape, "variant": v,
                         "hypothesis": " + ".join(
                             HYPOTHESES[p] for p in v.split("+")),
                         "verdict": verdict, **{k: vv for k, vv in t.items()}})
            emit(f"perf.{arch}.{shape}.{v}", dom,
                 f"dom {dom0*1e3:.1f}ms→{dom*1e3:.1f}ms;"
                 f"bneck={t['bottleneck']};{verdict}")
    dump("perf_iterations", rows)
    return rows


if __name__ == "__main__":
    main()
