"""Solver step on the device: device-busy time per interval, from the
traced window."""


def read(ctx: dict):
    tr = ctx["trace"]
    if tr is None:
        return None
    return 1e3 * tr["busy_s"] / tr["n_intervals"]
