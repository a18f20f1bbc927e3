"""Three-term roofline for compiled programs.

Hardware model (TPU v5e, per chip):
  peak bf16 compute   197 TFLOP/s
  HBM bandwidth       819 GB/s
  ICI                 ~50 GB/s per link

Terms (seconds per step, per chip):
  compute    = HLO_FLOPs / (chips · 197e12)
  memory     = HLO_bytes / (chips · 819e9)
  collective = per-chip wire bytes / 50e9

``roofline.extract`` places the control-step programs on these axes.
"""
from __future__ import annotations

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def roofline_terms(flops: float, hbm_bytes: float, wire_bytes: float,
                   chips: int) -> dict:
    compute = flops / (chips * PEAK_FLOPS)
    memory = hbm_bytes / (chips * HBM_BW)
    collective = wire_bytes / ICI_BW     # wire bytes are already per-chip
    terms = {"compute_s": compute, "memory_s": memory,
             "collective_s": collective}
    dom = max(terms, key=terms.get)
    terms["bottleneck"] = dom.replace("_s", "")
    total = max(compute, memory, collective)
    terms["roofline_fraction"] = compute / total if total > 0 else 0.0
    return terms
