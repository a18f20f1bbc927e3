"""Serve host path: the 95th percentile of the interval's wall time, over
the intervals of a traced run that began once the profiler had stopped
(those under the profiler, and the one that collects its trace, run
slower).  A host stall on a shared machine moves it from run to run by
more than an end-to-end bound can hold, so it is read here, beside
``interval_ms``, and holds no bound."""
from chipbench import bench


def read(ctx: dict):
    times = [iv["t1"] - iv["t0"] for iv in ctx["intervals"][ctx["traced"]:]]
    if not times:
        return None
    return 1e3 * bench.p95(times)
