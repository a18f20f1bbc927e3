"""Host path (serve/fleet.py, serve/cec_router.py): wall time per interval
spent inside the benchmark's own utility callback, over the window."""


def read(ctx: dict):
    n = len(ctx["intervals"])
    if not n:
        return None
    return 1e3 * sum(iv["measure_s"] for iv in ctx["intervals"]) / n
