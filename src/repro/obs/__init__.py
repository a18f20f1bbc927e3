"""Device-resident observability: telemetry rings, paper-invariant
monitors, control-plane tracing (DESIGN.md §18).

The paper's value proposition is *online* optimization — sublinear
dynamic regret, Theorem-4 monotone descent, KKT-optimal fixed points —
and this package turns those analysis-section claims into always-on
signals instead of after-the-fact test assertions:

* :mod:`repro.obs.telemetry` — a :class:`~repro.obs.telemetry.Telemetry`
  frozen-pytree ring buffer updated *inside* the jitted control step by
  a pure ``record``; composes with donation, ``vmap`` (the RouterFleet's
  ``[K]`` tenant stacking) and ``shard_map`` (the fleet mesh), host sync
  deferred to an explicit export.
* :mod:`repro.obs.monitors` — paper-derived invariant monitors as pure
  functions over the ring and the live iterates, each with warn/trip
  thresholds and a fleet-vmapped batch form.
* :mod:`repro.obs.trace` — host spans on the profiler's clock:
  Chrome-trace (trace-event JSON) timelines of control intervals,
  scenario segments and kernel-dispatch decisions, per-phase aggregates
  of each ``control_step`` and the ``host_syncs`` counter of
  :func:`~repro.obs.trace.to_host`.
* :mod:`repro.obs.export` — host-side ring export + JSON-lines metrics
  aligned with the perf-trajectory schema rows.

Import discipline: ``telemetry``/``trace`` depend only on jax/numpy so
``core.solver`` can import them without a cycle; ``monitors``/``export``
may import ``repro.core`` and are therefore loaded lazily here.
"""
from __future__ import annotations

import importlib

from .telemetry import Telemetry, Verdict, annotate, init_ring, record
from .trace import (Tracer, current_tracer, install_tracer, instant, phase,
                    span, to_host, uninstall_tracer)

_LAZY = {
    # monitors / export pull repro.core — resolve on first access so that
    # `import repro.obs` from inside core.solver never cycles
    "monitors": "repro.obs.monitors",
    "export": "repro.obs.export",
}
_LAZY_NAMES = {
    "monotone_descent": "monitors", "dynamic_regret": "monitors",
    "budget_feasibility": "monitors", "flow_conservation": "monitors",
    "capacity_slack": "monitors", "kkt_gap": "monitors",
    "check_state": "monitors", "fleet_verdicts": "monitors",
    "export_ring": "export", "metrics_rows": "export",
    "write_metrics_jsonl": "export", "write_chrome_trace": "export",
}

__all__ = [
    "Telemetry", "Verdict", "init_ring", "record", "annotate",
    "Tracer", "install_tracer", "uninstall_tracer", "current_tracer",
    "span", "instant", "phase", "to_host",
    *sorted(_LAZY), *sorted(_LAZY_NAMES),
]


def __getattr__(name: str):
    if name in _LAZY:
        return importlib.import_module(_LAZY[name])
    if name in _LAZY_NAMES:
        mod = importlib.import_module(_LAZY[_LAZY_NAMES[name]])
        return getattr(mod, name)
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")


def __dir__():
    return sorted(__all__)
