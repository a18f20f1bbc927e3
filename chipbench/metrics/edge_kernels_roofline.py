"""Edge-list kernels: the least time the interval's work needs at the
chip's peaks (``work.py``) over the kernels' device time per interval
(``edge_kernels_ms``).

The count is the algorithm's one count of the interval's work, the same
for every dispatch path.  It also holds the cost and the marginals,
which XLA computes outside these kernels, so it credits the kernels with
more work than they do: the share reads high by that much.
"""
from chipbench import work
from chipbench.metrics import edge_kernels_ms


def read(ctx: dict):
    seconds = edge_kernels_ms.kernel_seconds(ctx["trace"])
    if not seconds:
        return None
    least, _ = work.least_seconds(ctx["work"], ctx["peaks"])
    return 100.0 * least / (seconds / ctx["trace"]["n_intervals"])
