"""One tenant through ``CECRouter.control_step`` (any graph layout; the
edge-list layout is the one only this entry serves).

The interval's outputs are the router's new state, Λ [W] and φ, and the
step's cost; the router does not donate its state, so the arrays it
held before and after a step stay valid and a snapshot copies nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class Entry:
    per_tenant_callback = True    # the callback takes one tenant's [m, W]
    step_publishes = 0            # the router publishes no replica weights
    demand_publishes = 0

    def __init__(self, deployment: dict, solver: dict, tenants: list[dict]):
        from repro.core import build_augmented, build_augmented_sparse
        from repro.core.solver import SolverConfig
        from repro.serve import CECRouter

        (t,) = tenants
        build = (build_augmented_sparse if deployment["layout"] == "edges"
                 else build_augmented)
        graph = build(t["adj"], t["deploy"], t["link_cap"], t["comp_cap"],
                      src_capacity=deployment["src_capacity"])
        self.router = CECRouter(graph=graph,
                                lam_total=float(deployment["lam_total"]),
                                cost_name=deployment["cost"],
                                config=SolverConfig(**solver))

    def set_demand(self, totals: np.ndarray) -> None:
        self.router.on_demand_change(float(totals[0]))

    def step(self, measure) -> dict:
        return self.router.control_step(measure)

    def ready_outputs(self):
        return jax.block_until_ready(self.router.state)

    def snapshot(self):
        st = self.router.state
        return st.lam[None], st.phi

    def outputs(self, rec: dict, snap) -> dict:
        return {"lam": snap[0], "cost": np.asarray([rec["cost"]]),
                "phi": snap[1]}

    def dense_phi(self, phi):
        """[1, W, N̄, N̄] of the router's φ, whichever layout it keeps."""
        g = self.router.graph
        if not hasattr(phi, "rows"):
            return jnp.asarray(phi)[None]
        W, n_bar = g.n_sessions, g.n_bar
        rows_i = jnp.broadcast_to(jnp.arange(n_bar)[:, None], g.nbr.shape)
        out = jnp.zeros((W, n_bar, n_bar), phi.rows.dtype)
        out = out.at[:, rows_i, g.nbr].add(phi.rows)
        return out.at[:, g.src, g.src_nbr].add(phi.src)[None]
