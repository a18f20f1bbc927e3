"""Kernels / whole control step: the least time the interval's work
needs at the chip's peaks (``work.py``) over the device-busy time per
interval."""

from chipbench import work


def read(ctx: dict):
    tr = ctx["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    least, _ = work.least_seconds(ctx["work"], ctx["peaks"])
    return 100.0 * least / (tr["busy_s"] / tr["n_intervals"])
