"""K dense tenants through ``RouterFleet.control_step``.

The interval's outputs are the published ``FleetView``: Λ [K, W] and the
replica weights [K, W, N]; the per-tenant cost comes from the step's
record.  The fleet donates its state into the step, so a checked
interval copies the state on the device before and after it.
"""
from __future__ import annotations

import jax
import numpy as np


class Entry:
    per_tenant_callback = False   # the callback takes [K, m, W] stacks
    step_publishes = 1            # a FleetView per control_step ...
    demand_publishes = 1          # ... and another per set_demand

    def __init__(self, deployment: dict, solver: dict, tenants: list[dict]):
        from repro.core import build_augmented
        from repro.core.solver import SolverConfig
        from repro.serve import RouterFleet

        graphs = [build_augmented(t["adj"], t["deploy"], t["link_cap"],
                                  t["comp_cap"],
                                  src_capacity=deployment["src_capacity"])
                  for t in tenants]
        self.fleet = RouterFleet(
            graphs, [deployment["lam_total"]] * len(tenants),
            cost_name=deployment["cost"], config=SolverConfig(**solver),
            depth_max=int(deployment["depth_max"]))
        self.copy = jax.jit(lambda lam, phi: (lam + 0.0, phi + 0.0))

    def set_demand(self, totals: np.ndarray) -> None:
        self.fleet.set_demand(totals)

    def step(self, measure) -> dict:
        return self.fleet.control_step(measure)

    def ready_outputs(self):
        view = self.fleet.view
        return jax.block_until_ready((view.lam, view.weights))

    def snapshot(self):
        """A device copy of the working (Λ, φ)."""
        return self.copy(self.fleet.state.lam, self.fleet.state.phi)

    def outputs(self, rec: dict, snap) -> dict:
        view = self.fleet.view
        return {"lam": view.lam, "weights": view.weights,
                "cost": np.asarray(rec["cost"]), "phi": snap[1]}

    def dense_phi(self, phi):
        return phi
