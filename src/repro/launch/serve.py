"""End-to-end CEC serving driver — the paper's system, live.

A fleet of edge devices (Connected-ER topology) hosts three versions of a
small LM (quality ladder).  Batched requests arrive; the CEC router runs
the OMAD single-loop online — observing only realized quality-weighted
goodput minus network cost — and steers (i) the admission split across
versions (workload allocation Λ) and (ii) per-replica dispatch (routing
φ).  Real decode steps execute on CPU through the continuous-batching
engines.

``python -m repro.launch.serve --intervals 12 --requests 24``
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_config
from repro.core import build_random_cec
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serve import CECRouter, InferenceEngine, Request
from repro.topo import connected_er


def version_ladder() -> list[ModelConfig]:
    """Three sizes of the same family = the paper's DNN version set."""
    base = get_config("smollm-135m", smoke=True)
    return [
        dataclasses.replace(base, name="smol-v0", n_layers=2, d_model=32,
                            n_heads=2, n_kv_heads=2, d_ff=64),
        dataclasses.replace(base, name="smol-v1", n_layers=2, d_model=48,
                            n_heads=3, n_kv_heads=3, d_ff=96),
        dataclasses.replace(base, name="smol-v2", n_layers=4, d_model=64,
                            n_heads=4, n_kv_heads=4, d_ff=128),
    ]


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--intervals", type=int, default=12)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--nodes", type=int, default=12)
    ap.add_argument("--fail-node-at", type=int, default=-1)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    configs = version_ladder()
    W = len(configs)
    quality = np.array([1.0, 1.6, 2.4])          # per-version QoE weight

    adj = connected_er(args.nodes, 0.35, seed=2)
    graph = build_random_cec(adj, W, mean_link_capacity=30.0, seed=0)
    router = CECRouter(graph, lam_total=float(args.requests))

    engines = [InferenceEngine(c, M.init(c, jax.random.PRNGKey(i)),
                               max_batch=8, max_len=48)
               for i, c in enumerate(configs)]

    rid = 0
    for it in range(args.intervals):
        if it == args.fail_node_at:
            adj2 = adj.copy()
            victim = args.nodes - 1
            adj2[victim, :] = adj2[:, victim] = False
            adj2 = adj2[:victim, :victim]
            graph = build_random_cec(adj2, W, 30.0, seed=0)
            router.on_topology_change(graph)
            print(f"[serve] node {victim} failed — re-meshed to "
                  f"{victim} devices, router re-targeted")

        split = router.admission_split()
        counts = rng.multinomial(args.requests, split)
        replicas = router.replica_weights()

        # serve this interval's batch for real
        for w, n in enumerate(counts):
            for _ in range(n):
                prompt = rng.integers(0, configs[w].vocab, size=8)
                rep = rng.choice(graph.n_phys, p=_safe(replicas[w]))
                engines[w].submit(Request(rid, prompt.astype(np.int32),
                                          max_new_tokens=8, version=w,
                                          replica=int(rep)))
                rid += 1
        served = [0] * W
        for w, e in enumerate(engines):
            before = e.tokens_served
            e.drain()
            served[w] = e.tokens_served - before

        # the unknown utility the router observes: quality-weighted goodput
        def utility_fn(lam, served=tuple(served)):
            lam = np.asarray(lam)
            return float((quality * np.minimum(lam, sum(served) * lam
                                               / max(lam.sum(), 1e-6))).sum())

        rec = router.control_step(utility_fn)
        print(f"[serve] interval {it:02d} split={np.round(split, 2)} "
              f"served={served} net_cost={rec['cost']:.2f} "
              f"lam={np.round(rec['lam'], 2)}")

    print(f"[serve] done: {rid} requests, "
          f"{sum(e.tokens_served for e in engines)} tokens generated")


def _safe(p: np.ndarray) -> np.ndarray:
    s = p.sum()
    return p / s if s > 0 else np.ones_like(p) / len(p)


if __name__ == "__main__":
    main()
