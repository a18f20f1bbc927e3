"""One tenant on a Barabási–Albert power-law fleet (Barabási & Albert,
Science 286:509, 1999; m links per new node).

The topology and the placement of versions are the configuration's
(``structure_seed``), so every run hands the program the same edge-list
shapes; the run's seed draws the link and compute capacities and the log
utilities (arXiv:2406.19613 §IV).
"""
from __future__ import annotations

import numpy as np

from chipbench import reference
from chipbench.builders.connected_er import capacities, deployment, log_bank


def barabasi_albert(n: int, m: int, rng: np.random.Generator) -> np.ndarray:
    adj = np.zeros((n, n), bool)
    adj[: m + 1, : m + 1] = True                   # a connected seed clique
    np.fill_diagonal(adj, False)
    ends = [v for v in range(m + 1) for _ in range(m)]
    for v in range(m + 1, n):
        chosen: set[int] = set()
        while len(chosen) < m:
            chosen.add(ends[rng.integers(len(ends))])
        for u in chosen:
            adj[u, v] = adj[v, u] = True
        ends.extend(chosen)
        ends.extend([v] * m)
    return adj


def build(params: dict, seed: int) -> list[dict]:
    n, W = int(params["n_nodes"]), int(params["n_versions"])
    srng = np.random.default_rng(int(params["structure_seed"]))
    adj = barabasi_albert(n, int(params["m"]), srng)
    for _ in range(100):
        deploy = deployment(n, W, srng)
        try:
            reference.augment(adj, deploy, np.ones((n, n)), np.ones(n), 1.0)
            break
        except reference.Infeasible:
            continue
    else:
        raise RuntimeError("no feasible placement in 100 draws")
    rng = np.random.default_rng([seed, 0])
    link, comp = capacities(n, params["mean_link_capacity"],
                            params["mean_compute_capacity"], rng)
    a, b = log_bank(W, rng)
    aug = reference.augment(adj, deploy, link, comp, params["src_capacity"])
    return [{"adj": adj, "deploy": deploy, "link_cap": link,
             "comp_cap": comp, "a": a, "b": b, "aug": aug}]
