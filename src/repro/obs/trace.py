"""Control-plane tracing — spans on the profiler's clock, phase aggregates,
Chrome trace-event timelines (DESIGN.md §18.3).

A :class:`Tracer` collects host-side spans and instants of the control
plane.  While one is installed, every :func:`span` also enters a
``jax.profiler.TraceAnnotation`` (the interval spans a
``StepTraceAnnotation`` whose ``step_num`` is the interval index), so
whenever a profiler trace is running the program's spans land in its
``.xplane.pb`` on the same clock as the device's operations, and every
span of one control interval carries that interval's index ``t``.

Spans come in two kinds:

* **Chrome events** — interval spans (``router.interval`` /
  ``fleet.interval``), ``scenario.segment``, ``sim.serve``, and the
  instants (churn events, ``solver.dispatch:*`` decisions, which fire at
  *trace* time, once per compilation).  They are kept as Chrome
  trace-event JSON (the ``chrome://tracing`` / Perfetto format: a
  ``{"traceEvents": [...]}`` object whose entries carry
  ``name``/``cat``/``ph``/``ts``/``pid``/``tid``; complete spans
  ``ph: "X"``, instants ``ph: "i"``).  Timestamps are
  ``time.perf_counter`` microseconds relative to tracer construction.
* **Phases** (category :data:`PHASE`) — the named steps inside one
  ``control_step`` (:data:`PHASES`).  A control plane runs for days, so
  a phase appends no event: the tracer keeps per name the count, total
  and self seconds (self = minus the spans nested in it), the longest
  single span (where a stall falls), and the jaxpr traces and backend
  compiles that fired while it was the innermost span (from a
  ``jax.monitoring`` listener that lives exactly as long as the
  installation).  :func:`to_host` — the one helper through which a
  ``control_step`` reads a device array — opens a ``control.sync``
  phase and bumps the ``host_syncs`` counter.

With no tracer installed, :func:`span` is one global read returning a
shared null context and :func:`to_host` a plain copy: neither touches
``jax``.  Like :mod:`repro.obs.telemetry`, this module must stay
importable from ``repro.core``: ``jax`` is imported only once a tracer
is built.
"""
from __future__ import annotations

import contextlib
import json
import pathlib
import time
from typing import Any

import numpy as np

TRACE_EVENT_KEYS = ("name", "cat", "ph", "ts", "pid", "tid")

#: category of the control-step phase spans (aggregated, never an event)
PHASE = "phase"
#: the phases of one control step, shared by ``CECRouter`` and
#: ``RouterFleet`` so that a reader never needs to know the entry point
PHASES = ("control.perturb", "control.measure", "control.dispatch",
          "control.sync", "control.fit", "control.publish",
          "control.record")
SYNC = "control.sync"

_MONITORED = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}


def _new_phase() -> dict[str, float]:
    return {"count": 0, "seconds": 0.0, "self_seconds": 0.0,
            "longest_s": 0.0, "traces": 0, "compiles": 0}


class _Span:
    """One open span: enters the profiler's annotation, times itself, and
    on exit becomes a Chrome event or folds into its phase's aggregate."""

    __slots__ = ("tracer", "name", "cat", "args", "ann", "t0", "nested",
                 "outer_step")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: dict[str, Any] | None):
        self.tracer, self.name, self.cat, self.args = tracer, name, cat, args

    def __enter__(self) -> None:
        tr = self.tracer
        self.outer_step = tr.step
        if self.cat == "interval" and self.args and "t" in self.args:
            tr.step = int(self.args["t"])
            self.ann = tr._profiler.StepTraceAnnotation(self.name,
                                                        step_num=tr.step)
        elif tr.step is not None:
            self.ann = tr._profiler.TraceAnnotation(self.name, t=tr.step)
        else:
            self.ann = tr._profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.nested = 0.0
        tr._open.append(self)
        self.t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        tr = self.tracer
        tr._open.pop()
        self.ann.__exit__(*exc)
        tr.step = self.outer_step
        dur = t1 - self.t0
        if tr._open:
            tr._open[-1].nested += dur
        if self.cat == PHASE:
            p = tr.phases.get(self.name)
            if p is None:
                p = tr.phases[self.name] = _new_phase()
            p["count"] += 1
            p["seconds"] += dur
            p["self_seconds"] += dur - self.nested
            if dur > p["longest_s"]:
                p["longest_s"] = dur
        else:
            tr.events.append({
                "name": self.name, "cat": self.cat, "ph": "X",
                "ts": (self.t0 - tr._t0) * 1e6, "dur": dur * 1e6,
                "pid": tr.pid, "tid": tr._tid(self.cat),
                "args": dict(self.args or {}),
            })


class Tracer:
    """Chrome events, phase aggregates and counters of the control plane;
    write the events with :meth:`write` / :meth:`to_chrome`, read the
    aggregates with :meth:`snapshot`.

    Not thread-safe by design — the control plane is a single host loop
    (one interval at a time); a fleet wanting per-worker timelines
    installs one tracer per process (``pid`` disambiguates on merge).
    """

    def __init__(self, *, pid: int = 0) -> None:
        import jax.profiler

        self._profiler = jax.profiler
        self.events: list[dict[str, Any]] = []
        self.pid = int(pid)
        self._t0 = time.perf_counter()
        self._tids: dict[str, int] = {}
        self.phases: dict[str, dict[str, float]] = {}
        self.counters = {"host_syncs": 0, "traces": 0, "compiles": 0}
        self.step: int | None = None      # innermost open interval's index
        self._open: list[_Span] = []      # open spans, innermost last

    # -- low-level emitters ------------------------------------------------
    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _tid(self, cat: str) -> int:
        return self._tids.setdefault(cat, len(self._tids))

    def instant(self, name: str, *, cat: str = "event",
                args: dict[str, Any] | None = None) -> None:
        self.events.append({
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": self._now_us(), "pid": self.pid, "tid": self._tid(cat),
            "args": dict(args or {}),
        })

    def span(self, name: str, *, cat: str = "interval",
             args: dict[str, Any] | None = None) -> _Span:
        """A span; ``cat=PHASE`` aggregates it instead of appending an
        event."""
        return _Span(self, name, cat, args)

    def on_duration(self, event: str, duration: float, **_) -> None:
        """``jax.monitoring`` duration listener: counts jaxpr traces and
        backend compiles, in total and under the innermost open phase."""
        key = _MONITORED.get(event)
        if key is None:
            return
        self.counters[key] += 1
        if self._open and self._open[-1].cat == PHASE:
            name = self._open[-1].name
            p = self.phases.get(name)
            if p is None:
                p = self.phases[name] = _new_phase()
            p[key] += 1

    # -- aggregates --------------------------------------------------------
    def snapshot(self, *, restart_longest: bool = False) -> dict[str, Any]:
        """A copy of the counters and the per-phase aggregates.  With
        ``restart_longest`` each phase's longest span starts again from 0,
        so that a later snapshot's ``longest_s`` is the longest since this
        one (what :func:`delta` reports for a window)."""
        snap = {**self.counters,
                "phases": {n: dict(p) for n, p in self.phases.items()}}
        if restart_longest:
            for p in self.phases.values():
                p["longest_s"] = 0.0
        return snap

    # -- serialization -----------------------------------------------------
    def to_chrome(self) -> dict[str, Any]:
        """The trace-event JSON object (``traceEvents`` sorted by ts); the
        phase aggregates and counters ride in ``otherData``."""
        return {
            "traceEvents": sorted(self.events, key=lambda e: e["ts"]),
            "displayTimeUnit": "ms",
            "otherData": {"format": "repro.obs.trace", "version": 1,
                          **self.snapshot()},
        }

    def write(self, path) -> pathlib.Path:
        """Serialize to ``path``; open the file in ``chrome://tracing`` or
        https://ui.perfetto.dev to see the timeline."""
        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(self.to_chrome(), indent=1))
        return p


def delta(before: dict[str, Any], after: dict[str, Any]) -> dict[str, Any]:
    """What happened between two :meth:`Tracer.snapshot`\\ s: counters and
    phase counts, seconds, traces and compiles as differences;
    ``longest_s`` as ``after`` has it (take ``before`` with
    ``restart_longest=True`` to make it the window's longest)."""
    out = {k: after[k] - before.get(k, 0) for k in after if k != "phases"}
    out["phases"] = {}
    for name, p in after["phases"].items():
        q = before["phases"].get(name, _new_phase())
        d = {k: p[k] - q[k] for k in p if k != "longest_s"}
        if d["count"]:
            out["phases"][name] = {**d, "longest_s": p["longest_s"]}
    return out


# ---------------------------------------------------------------------------
# the installed tracer — module-global so call sites need no plumbing
# ---------------------------------------------------------------------------

_TRACER: Tracer | None = None
_NULL = contextlib.nullcontext()


def install_tracer(tracer: Tracer | None = None) -> Tracer:
    """Install (and return) the process-wide tracer and its
    ``jax.monitoring`` listener.  Instrumented call sites start emitting
    immediately; install before building routers if you want their
    compile-time dispatch instants."""
    global _TRACER
    import jax.monitoring

    uninstall_tracer()
    _TRACER = tracer if tracer is not None else Tracer()
    jax.monitoring.register_event_duration_secs_listener(_TRACER.on_duration)
    return _TRACER


def uninstall_tracer() -> Tracer | None:
    """Remove and return the installed tracer, and its listener
    (idempotent)."""
    global _TRACER
    t, _TRACER = _TRACER, None
    if t is not None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(t.on_duration)
    return t


def current_tracer() -> Tracer | None:
    return _TRACER


def instant(name: str, *, cat: str = "event",
            args: dict[str, Any] | None = None) -> None:
    """Emit an instant on the installed tracer; no-op when none is."""
    if _TRACER is not None:
        _TRACER.instant(name, cat=cat, args=args)


def span(name: str, *, cat: str = "interval",
         args: dict[str, Any] | None = None):
    """Span on the installed tracer; a shared null context when none is."""
    if _TRACER is None:
        return _NULL
    return _Span(_TRACER, name, cat, args)


def phase(name: str):
    """A control-step phase span (:data:`PHASES`): aggregated, no event."""
    if _TRACER is None:
        return _NULL
    return _Span(_TRACER, name, PHASE, None)


def to_host(x, dtype=None) -> np.ndarray:
    """Blocking device-to-host read of ``x``: a fresh, writable numpy
    array.  Under a tracer it is a ``control.sync`` phase and counts one
    ``host_syncs``; every read of a device array inside a
    ``control_step`` goes through here."""
    if _TRACER is None:
        return np.array(x, dtype=dtype)
    _TRACER.counters["host_syncs"] += 1
    with _Span(_TRACER, SYNC, PHASE, None):
        return np.array(x, dtype=dtype)
