"""K tenants of a Connected Erdős–Rényi deployment (arXiv:2406.19613 §IV).

Each tenant is its own draw from the seed: a connected ER graph, link
capacities U[0.05, 2]·C̄ per undirected link, compute capacities
U[0.5, 1.5]·C̄, one model version per node (every version placed), and
the log utilities u_w(λ) = a_w·log(1 + b_w·λ) of the paper's evaluation.
A draw whose augmented graph leaves a version unreachable, or is deeper
than the layout's ``depth_max``, is drawn again, so every seed gives the
program the same shapes.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference


def connected_er(n: int, p: float, rng: np.random.Generator) -> np.ndarray:
    for _ in range(200):
        adj = np.triu(rng.random((n, n)) < p, 1)
        adj = adj | adj.T
        if (reference._bfs_layers(adj, np.arange(n) == 0) >= 0).all():
            return adj
    raise RuntimeError(f"no connected ER({n}, {p}) graph in 200 draws")


def deployment(n: int, n_versions: int, rng: np.random.Generator):
    assign = rng.integers(0, n_versions, size=n)
    assign[:n_versions] = np.arange(n_versions)     # every version placed
    rng.shuffle(assign)
    deploy = np.zeros((n_versions, n), bool)
    deploy[assign, np.arange(n)] = True
    return deploy


def log_bank(n_versions: int, rng: np.random.Generator):
    """a_w, b_w of the paper's log utilities; larger versions earn more."""
    a = np.linspace(1.0, 2.0, n_versions) * rng.uniform(15.0, 25.0,
                                                         n_versions)
    return a, rng.uniform(0.2, 0.5, n_versions)


def capacities(n: int, mean_link: float, mean_compute: float,
               rng: np.random.Generator):
    link = rng.uniform(0.05, 2.0, (n, n)) * mean_link
    return np.maximum(link, link.T), rng.uniform(0.5, 1.5, n) * mean_compute


def build(params: dict, seed: int) -> list[dict]:
    n, W = int(params["n_nodes"]), int(params["n_versions"])
    p = float(params["mean_degree"]) / (n - 1)
    out = []
    for k in range(int(params["tenants"])):
        for attempt in range(100):
            rng = np.random.default_rng([seed, k, attempt])
            adj = connected_er(n, p, rng)
            link, comp = capacities(n, params["mean_link_capacity"],
                                    params["mean_compute_capacity"], rng)
            deploy = deployment(n, W, rng)
            try:
                aug = reference.augment(adj, deploy, link, comp,
                                        params["src_capacity"])
            except reference.Infeasible:
                continue
            if aug.depth <= int(params["depth_max"]):
                break
        else:
            raise RuntimeError(f"tenant {k}: no feasible draw in 100")
        a, b = log_bank(W, rng)
        out.append({"adj": adj, "deploy": deploy, "link_cap": link,
                    "comp_cap": comp, "a": a, "b": b, "aug": aug})
    return out
