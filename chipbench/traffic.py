"""The one traffic generator: a mix file's parameters and the seed in,
per-interval tenant demands out.

A mix (``traffic/<name>.json``) holds a ``demand`` block and the
controller's ``grad_policy``.  The demand block's ``kind`` names the
arrival process, ``arrivals/<kind>.py``, found by file name: its
``factors(demand, n_tenants, rng)`` gives [horizon, K] multiplicative
factors that scale the tenants' provisioned demand λ.  A later mix with
another process adds its own file there.

Interval i of a run uses row i mod horizon; warm-up uses rows counted
back from the end, so the window starts at row 0 on every seed.
"""
from __future__ import annotations

import numpy as np


def factors(demand: dict, n_tenants: int, seed: int, load) -> np.ndarray:
    """``load(kind)`` is the harness's loader of ``arrivals/<kind>.py``."""
    rng = np.random.default_rng([seed % 2**64, 7])
    f = load(demand["kind"]).factors(demand, n_tenants, rng)
    f = np.asarray(f, np.float32)
    if f.ndim != 2 or f.shape[1] != n_tenants or not np.all(f > 0):
        raise ValueError(f"arrival process {demand['kind']!r} gave factors "
                         f"of shape {f.shape}; need [horizon, {n_tenants}], "
                         "all positive")
    return f
