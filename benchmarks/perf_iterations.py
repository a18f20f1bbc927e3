"""§Perf hillclimbing harness for the control plane: one cell on the
batched JOWR path, sequential jitted per-instance solves vs one vmapped
``run_batch`` program over the same ensemble (hypothesis: vmap amortizes
per-solve dispatch and compiles one fused scan → per-instance time
drops).
"""
from __future__ import annotations

from . import common
from .common import dump, emit

HYPOTHESIS = ("one vmapped run_batch program over B instances amortizes "
              "per-solve dispatch vs a Python loop of jitted solves → "
              "per-instance time drops")


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
         "variant": "batched_vmap", "hypothesis": HYPOTHESIS,
         "verdict": verdict, "s_per_instance": t_bat / B,
         "speedup": t_seq / t_bat},
    ]
    emit(f"perf.cec_control_plane.omad_B{B}.sequential", t_seq / B, "baseline")
    emit(f"perf.cec_control_plane.omad_B{B}.batched_vmap", t_bat / B,
         f"speedup={t_seq/t_bat:.2f}x;{verdict}")
    return rows


def main() -> list[dict]:
    if common.SMOKE:
        return control_plane_rows(B=2, outer_iters=3)
    rows = control_plane_rows()
    dump("perf_iterations", rows)
    return rows


if __name__ == "__main__":
    main()
