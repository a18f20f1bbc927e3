"""Constant demand: every tenant offers its provisioned λ in every
interval (arXiv:2406.19613 §IV: λ fixed, the utilities unknown and
measured online), so the controller is never handed a demand change."""
import numpy as np


def factors(demand: dict, n_tenants: int, rng) -> np.ndarray:
    return np.ones((1, n_tenants), np.float32)
