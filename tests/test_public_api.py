"""API-surface snapshot: the public entry points are a contract.

Pins ``repro.__all__`` and the exact ``inspect.signature`` of every
public solver entry point, so signature drift (a renamed keyword, a
changed default, a dropped parameter) fails tier-1 instead of silently
breaking downstream callers.  Intentional changes must update the
snapshot here *and* the DESIGN.md §13 migration table in the same
commit.
"""
import dataclasses
import inspect

import repro


def _sig(fn) -> str:
    return str(inspect.signature(fn))


# The pinned surface: name -> exact signature string.  These are the
# entry points the shim-parity suite (tests/test_solver_core.py) proves
# equivalent; their keywords are load-bearing for examples/, benchmarks/
# and external callers.
PINNED_SIGNATURES = {
    # -- the solver core --------------------------------------------------
    "init": "(problem: 'Problem', config: 'SolverConfig', *, phi0=None, "
            "lam0: 'Array | None' = None) -> 'SolverState'",
    "step": "(problem: 'Problem', config: 'SolverConfig', "
            "state: 'SolverState', task_utilities: 'Array', telemetry=None) "
            "-> 'tuple[SolverState, StepInfo] | tuple'",
    "run": "(problem: 'Problem', config: 'SolverConfig', *, iters: 'int', "
           "state: 'SolverState | None' = None, phi0=None, "
           "lam0: 'Array | None' = None) -> 'Result'",
    "fused_step": "(config: 'SolverConfig', *, donate: 'bool' = False)",
    "run_batch": "(batch: 'CECGraphBatch | CECGraphSparseBatch', "
                 "banks: 'UtilityBank | Sequence[UtilityBank]', lam_total, "
                 "config: 'SolverConfig', *, iters: 'int', cost='exp', "
                 "state: 'SolverState | None' = None, "
                 "phi0: 'Array | None' = None, "
                 "lam0: 'Array | None' = None) -> '_solver.Result'",
    "run_batch_sharded": "(batch: 'CECGraphBatch | CECGraphSparseBatch', "
                         "banks: 'UtilityBank | Sequence[UtilityBank]', "
                         "lam_total, config: 'SolverConfig', *, "
                         "iters: 'int', cost='exp', mesh=None, "
                         "state: 'SolverState | None' = None, "
                         "phi0: 'Array | None' = None, "
                         "lam0: 'Array | None' = None) -> '_solver.Result'",
    # -- legacy shims (keyword-compatible, frozen) ------------------------
    "solve_jowr":
        "(graph: 'CECGraph', bank: 'UtilityBank', lam_total: 'float', *, "
        "method: 'Method' = 'single', cost_name: 'str' = 'exp', "
        "delta: 'float' = 0.5, eta_outer: 'float' = 0.05, "
        "eta_inner: 'float' = 0.05, outer_iters: 'int' = 100, "
        "inner_iters: 'int' = 50, phi0=None, lam0=None) -> 'JOWRResult'",
    "gs_oma":
        "(graph: 'CECGraph', cost: 'CostFn', bank: 'UtilityBank', "
        "lam_total: 'float', *, delta: 'float' = 0.5, "
        "eta_outer: 'float' = 0.05, eta_inner: 'float' = 0.05, "
        "outer_iters: 'int' = 100, inner_iters: 'int' = 50, "
        "phi0: 'Array | None' = None, lam0: 'Array | None' = None) "
        "-> 'JOWRResult'",
    "omad":
        "(graph: 'CECGraph', cost: 'CostFn', bank: 'UtilityBank', "
        "lam_total: 'float', *, delta: 'float' = 0.5, "
        "eta_outer: 'float' = 0.05, eta_inner: 'float' = 0.05, "
        "outer_iters: 'int' = 100, phi0=None, lam0=None) -> 'JOWRResult'",
    "solve_jowr_batch":
        "(batch: 'CECGraphBatch | CECGraphSparseBatch', "
        "banks: 'UtilityBank | Sequence[UtilityBank]', lam_total: 'float', "
        "*, method: 'Method' = 'single', cost_name: 'str' = 'exp', "
        "delta: 'float' = 0.5, eta_outer: 'float' = 0.05, "
        "eta_inner: 'float' = 0.05, outer_iters: 'int' = 100, "
        "inner_iters: 'int' = 50, phi0: 'Array | None' = None, "
        "lam0: 'Array | None' = None) -> 'JOWRResult'",
    "solve_routing":
        "(graph: 'CECGraph | CECGraphSparse', cost: 'CostFn', "
        "lam: 'Array', phi0, eta: 'float', n_iters: 'int') "
        "-> 'tuple[Array, Array]'",
    "run_scenario":
        "(scenario: 'Scenario', *, seeds: 'Sequence[int]' = (0,), "
        "method: 'Method' = 'single', cost_name: 'str' = 'exp', "
        "delta: 'float' = 0.5, eta_outer: 'float' = 0.05, "
        "eta_inner: 'float' = 3.0, inner_iters: 'int' = 1, "
        "explore: 'float' = 0.1, config: 'SolverConfig | None' = None, "
        "mesh=None) -> 'ScenarioResult'",
}

PINNED_ALL = [
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
    "CECRouter",
    "core", "configs", "topo", "kernels", "serve", "roofline", "obs",
]

PINNED_SOLVER_CONFIG_FIELDS = (
    "method", "delta", "eta_outer", "eta_inner", "inner_iters", "grad_mode",
    "telemetry")
PINNED_SOLVER_STATE_FIELDS = ("lam", "phi", "t")
PINNED_RESULT_FIELDS = ("lam", "phi", "utility_traj", "lam_traj",
                        "cost_traj", "grad_traj", "state", "telemetry")
PINNED_ROUTER_FIELDS = ("graph", "lam_total", "delta", "eta_outer",
                        "eta_inner", "inner_iters", "cost_name", "config",
                        "grad_policy", "util_family", "telemetry")


def test_repro_all_is_pinned():
    assert list(repro.__all__) == PINNED_ALL


def test_every_exported_name_resolves():
    for name in repro.__all__:
        assert getattr(repro, name) is not None, name


def test_entry_point_signatures_are_pinned():
    drift = {}
    for name, want in PINNED_SIGNATURES.items():
        got = _sig(getattr(repro, name))
        if got != want:
            drift[name] = (want, got)
    assert not drift, (
        "public entry-point signature drift (update this snapshot AND the "
        f"DESIGN.md §13 migration table intentionally): {drift}")


def test_dataclass_and_state_fields_are_pinned():
    from repro.core import Result, SolverConfig, SolverState
    from repro.serve import CECRouter

    assert tuple(f.name for f in dataclasses.fields(SolverConfig)) == \
        PINNED_SOLVER_CONFIG_FIELDS
    assert SolverState._fields == PINNED_SOLVER_STATE_FIELDS
    assert Result._fields == PINNED_RESULT_FIELDS
    assert tuple(f.name for f in dataclasses.fields(CECRouter)) == \
        PINNED_ROUTER_FIELDS


def test_legacy_result_shapes_are_pinned():
    from repro.core import ControlStep, JOWRResult

    assert JOWRResult._fields == ("lam", "phi", "utility_traj", "lam_traj")
    # t rides at the END so positional unpacking of the first four legacy
    # fields keeps working (the t-threading bugfix — legacy loops used to
    # have their counter silently reset to 0 every call)
    assert ControlStep._fields == ("lam", "phi", "grad", "cost", "t")


def test_solver_core_is_the_only_update_site():
    """The bandit engine's mirror-ascent exp-reweighting lives exactly
    once in src/ — in core/solver.py.  The genie comparator
    (core/opt_baseline.py, true-gradient, no box projection) is a
    deliberately *different* algorithm and the one allowed look-alike;
    the pre-PR-3 host loop preserved in benchmarks/bench_router.py is
    the one allowed copy outside src/.  The control megakernel
    (kernels/control_megakernel.py, DESIGN.md §17) is the one allowed
    copy *inside* src/: the fused kernel must carry the update in its
    own body by construction, and tests/test_megakernel.py pins it to
    solver.step at ≤1e-5 so the copies cannot drift apart silently."""
    import pathlib

    src = pathlib.Path(repro.__file__).parent
    hits = [p.relative_to(src).as_posix()
            for p in sorted(src.rglob("*.py"))
            if "jnp.exp(z)" in p.read_text()]
    assert hits == ["core/opt_baseline.py", "core/solver.py",
                    "kernels/control_megakernel.py"], hits


# the control plane's packages; nothing they load lies outside them
CONTROL_PLANE = ("core", "kernels", "topo", "obs", "serve", "configs")


def test_control_plane_loads_only_itself():
    """Importing the control plane (solver core, kernels, observability,
    serving) in a fresh interpreter loads no ``repro`` module outside its
    own packages."""
    import os
    import subprocess
    import sys

    code = ("import sys\n"
            "import repro.core, repro.serve, repro.kernels, repro.obs\n"
            "print('\\n'.join(m for m in sys.modules\n"
            "                 if m.startswith('repro.')))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(repro.__file__)),
         env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    loaded = r.stdout.split()
    assert {m.split(".")[1] for m in loaded} >= {"core", "serve", "kernels",
                                                 "obs"}
    outside = [m for m in loaded if m.split(".")[1] not in CONTROL_PLANE]
    assert not outside, outside
