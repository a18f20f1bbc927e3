"""Fit-then-switch: learned utility gradients on a live router.

The serving control plane normally pays 2W+1 measured traffic admissions
per control interval (the two-point perturbation sweep).  With
``grad_policy="auto"`` the router fits a parametric utility surrogate to
what it measures anyway, and — once the fitter's held-out error clears
its bar — migrates live to ``grad_mode="learned"``: one admission per
interval, gradient taken analytically through the implicit routing layer
(DESIGN.md §16).  When the measured environment then moves from under
the surrogate, the drift monitor demotes the router back to sampling.

The measured utility here is synthetic: a log utility bank evaluated on
each admission the router asks about, through the same batched callback
contract every serving entry point uses (a [m, W] stack of admission
splits in, m measured utilities out).  Midway the environment drifts:
every measured utility is scaled 2.5x.

    PYTHONPATH=src python examples/learned_utilities.py

(REPRO_EXAMPLES_SMOKE=1 shrinks the run for the CI examples-smoke job.)
"""
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import build_random_cec, make_bank
from repro.serve import CECRouter
from repro.topo import connected_er

SMOKE = bool(os.environ.get("REPRO_EXAMPLES_SMOKE"))
T = 16 if SMOKE else 30
DRIFT_AT = T - 6

graph = build_random_cec(connected_er(10 if SMOKE else 12, 0.35, seed=2), 3,
                         20.0, seed=0)
W = graph.n_sessions
bank = make_bank("log", W, seed=0)
scale = 1.0


def measured_utility(lams):
    """û over a [m, W] admission stack: the environment's utility."""
    lams = jnp.atleast_2d(jnp.asarray(lams))
    return scale * np.asarray(jax.vmap(bank.total)(lams))


router = CECRouter(graph, lam_total=12.0, grad_policy="auto",
                   util_family="log")
# earn the switch quickly on a short horizon: the defaults are tuned for
# long-running fleets, not a demo of a few dozen intervals
router.fitter.min_samples = 20
router.fitter.refit_every = 8
router.fitter.fit_steps = 800

print(f"\n{T} control intervals, W={W} sessions "
      f"(sampled interval = {2 * W + 1} measured admissions); "
      f"measured utilities scale 2.5x from t={DRIFT_AT}")
print(f"{'t':>3s} {'mode':>8s} {'admissions':>10s} {'net utility':>12s}")
modes = []
for t in range(T):
    if t == DRIFT_AT:
        scale = 2.5
    rec = router.control_step(measured_utility)
    modes.append(rec["mode"])
    print(f"{t:3d} {rec['mode']:>8s} {rec['oracle_calls']:10d} "
          f"{rec['utility']:12.3f}")

total_calls = sum(h["oracle_calls"] for h in router.history if "mode" in h)
sampled_cost = T * (2 * W + 1)
print(f"\nmeasured admissions: {total_calls} "
      f"(all-sampled would be {sampled_cost}; "
      f"{sampled_cost / total_calls:.1f}x reduction)")
print(f"fitter: holdout_error={router.fitter.holdout_error:.4f} "
      f"fits={router.fitter.n_fits} drift={router.fitter.drift:.3f}")
assert modes[0] == "sampled"
assert "learned" in modes[:DRIFT_AT], "never migrated to learned mode"
assert "sampled" in modes[DRIFT_AT:], "drift never demoted the router"
assert np.isfinite([h["utility"] for h in router.history]).all()
