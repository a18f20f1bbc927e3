"""repro — JAX/Pallas reproduction of JOWR for collaborative edge inference.

The public surface is one solver core (DESIGN.md §13): describe the
instance as a ``Problem``, pick a ``SolverConfig`` (or a named preset),
and drive it with ``init``/``step``/``run``::

    from repro import Problem, SolverConfig, run
    result = run(Problem.create(graph, bank, lam_total=60.0),
                 SolverConfig(method="single", eta_inner=3.0), iters=200)

``solve_jowr`` / ``gs_oma`` / ``omad`` / ``solve_jowr_batch`` are
keyword-compatible shims over the same engine; ``run_scenario`` threads
its state across non-stationary segments and ``CECRouter`` serves it
live.  Everything is re-exported lazily so ``import repro`` stays cheap
— the serving plane loads only when touched.

``tests/test_public_api.py`` pins ``__all__`` and the entry-point
signatures; extend both together.
"""
from __future__ import annotations

import importlib

# names resolved from repro.core on first access
_CORE_EXPORTS = (
    "Problem", "SolverConfig", "SolverState", "StepInfo", "Result",
    "init", "step", "run", "fused_step", "run_batch", "run_batch_sharded",
    "paper_defaults", "serving_defaults",
    "solve_jowr", "gs_oma", "omad", "solve_jowr_batch", "solve_routing",
    "run_scenario", "Scenario", "scenario_metrics", "named_scenarios",
    "CECGraph", "CECGraphSparse", "CECGraphBatch", "UtilityBank",
    "build_random_cec", "build_augmented", "build_augmented_sparse",
    "make_bank", "get_cost", "resolve_cost",
    "UtilityFamily", "get_family", "fit_utilities", "OnlineFitter",
    "fixed_point_solve", "tune_etas",
)
# names resolved from repro.serve on first access
_SERVE_EXPORTS = ("CECRouter",)
_SUBMODULES = ("core", "configs", "topo", "kernels", "serve", "roofline",
               "obs")

__all__ = [*_CORE_EXPORTS, *_SERVE_EXPORTS, *_SUBMODULES]


def __getattr__(name: str):
    if name in _CORE_EXPORTS:
        return getattr(importlib.import_module("repro.core"), name)
    if name in _SERVE_EXPORTS:
        return getattr(importlib.import_module("repro.serve"), name)
    if name in _SUBMODULES:
        return importlib.import_module(f"repro.{name}")
    raise AttributeError(f"module 'repro' has no attribute {name!r}")


def __dir__():
    return sorted(__all__)
