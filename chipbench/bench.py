"""One run of one cell: set-up, the measured window, the traced window's
reduction, and the comparison with the plain reference.

Everything that belongs to one configuration, traffic mix, builder,
entry or per-layer metric lives in its own file and is found here by the
name ``BENCHMARK.json`` gives it.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import pathlib
import resource
import statistics
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_SECONDS = 10.0     # a traced run profiles the window's first 10 s


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# the cell, from files found by name
# ---------------------------------------------------------------------------

def load_cell(workload: str, root=ROOT) -> dict:
    """The cell's entry, configuration and mix, read under ``root`` (the
    checkout: ``BENCHMARK.json`` and ``chipbench/``)."""
    root = pathlib.Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((root / "chipbench" / "traffic" /
                      f"{cell['traffic']}.json").read_text())

    def metric_names(kind):
        return {m["name"]: m["unit"] for m in bench[kind]
                if workload in m.get("workloads", [workload])}

    return {"name": workload, "root": root, "chips": int(cell["chips"]),
            "config": config,
            "mix": mix, "end_to_end": metric_names("end_to_end"),
            "per_layer": metric_names("per_layer")}


def module(kind: str, name: str, root=ROOT):
    """``chipbench/<kind>/<name>.py`` under ``root``, loaded by file name."""
    path = pathlib.Path(root) / "chipbench" / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chip(chips: int):
    """The device JAX runs on; exits non-zero without a TPU in the table."""
    import jax

    devices = jax.devices()
    peaks = json.loads((HERE / "peaks.json").read_text())
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise NoChip(f"chipbench: JAX found no TPU (platform "
                     f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    if kind not in peaks:
        raise NoChip(f"chipbench: no peaks for device kind {kind!r} in "
                     f"peaks.json")
    return devices[:chips], peaks[kind]


# ---------------------------------------------------------------------------
# what the program is handed
# ---------------------------------------------------------------------------

def utility(a, b, lams):
    """Measured task utility Σ_w a_w·log(1 + b_w·λ_w) of [K, m, W] stacks."""
    lams = np.asarray(lams, np.float64)
    return (a[:, None] * np.log1p(b[:, None] * lams)).sum(-1)


class Measure:
    """The host callback the entry point calls; times itself."""

    def __init__(self, a, b, per_tenant: bool):
        self.a, self.b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        self.per_tenant = per_tenant
        self.seconds = 0.0

    def __call__(self, lams):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.measure"):
            if self.per_tenant:           # one tenant: [m, W] -> [m]
                out = utility(self.a, self.b, np.asarray(lams)[None])[0]
            else:                         # fleet: [K, m, W] -> [K, m]
                out = utility(self.a, self.b, lams)
        self.seconds += time.perf_counter() - t0
        return out


class Counters:
    """Compilations and traces, counted through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.n = {"compiles": 0, "cache_hits": 0, "traces": 0}
        events = {"/jax/core/compile/backend_compile_duration": "compiles",
                  "/jax/core/compile/jaxpr_trace_duration": "traces"}

        def on_duration(event, duration, **_):
            if event in events:
                self.n[events[event]] += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.n["cache_hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return dict(self.n)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _interval(entry, measure, totals, annotate):
    """One interval; ``totals`` is None where the demand has not changed
    (the controller is handed a demand only when it changes)."""
    with annotate("bench.interval"):
        t0 = time.perf_counter()
        measure.seconds = 0.0
        if totals is not None:
            with annotate("bench.set_demand"):
                entry.set_demand(totals)
        with annotate("bench.control_step"):
            rec = entry.step(measure)
        with annotate("bench.block"):
            entry.ready_outputs()
        t1 = time.perf_counter()
    return rec, t0, t1


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, control: bool = False, root=ROOT,
        chip_check=require_chip, keep_trace=None, out=print,
        err=None) -> dict:
    """Runs the cell and returns the result object (also printed)."""
    import jax

    err = err or (lambda s: print(s, file=sys.stderr, flush=True))
    cell = load_cell(workload, root)
    devices, peaks = chip_check(cell["chips"])
    cfg, mix = cell["config"], cell["mix"]
    dep = cfg["deployment"]

    from chipbench import traffic, work
    from repro.obs import trace as program_trace

    tenants = module("builders", cfg["builder"], root).build(dep, seed)
    K = len(tenants)
    a = np.stack([t["a"] for t in tenants])
    b = np.stack([t["b"] for t in tenants])
    factors = traffic.factors(mix["demand"], K, seed,
                              lambda kind: module("arrivals", kind, root))
    totals = float(dep["lam_total"]) * factors
    horizon = totals.shape[0]
    limits = cfg["check"].get("limits")
    if not limits:
        raise SystemExit(f"configuration {cfg['name']!r} states no limits "
                         "for the comparison; set them from readings first")

    counters = Counters()
    tracer = program_trace.install_tracer()
    Entry = module("entries", cfg["entry"], root).Entry
    if mix["grad_policy"] != "sampled":
        raise SystemExit("the reference covers sampled-gradient intervals "
                         "only; a learned mix needs its own reference")
    entry = Entry(dep, cfg["solver"], tenants)
    measure = Measure(a, b, per_tenant=Entry.per_tenant_callback)
    annotate = jax.profiler.TraceAnnotation

    # warm-up: every shape the window uses, on rows counted back from the
    # end of the trace, so that the window starts at row 0 on every seed;
    # a mix that ever changes the demand has it applied in every warm-up
    # interval, so that its path is warm too
    n_warm = int(cfg["warmup_intervals"])
    current = np.full(K, float(dep["lam_total"]), np.float32)
    changes = not np.all(totals == current)
    for k in range(n_warm):
        row = totals[(horizon - 1 - k) % horizon]
        _interval(entry, measure, row if changes else None, annotate)
        jax.block_until_ready(entry.snapshot())
        current = row if changes else current
    warm_instants = len(tracer.events)
    setup_s = time.perf_counter() - t_start

    # the window
    every = int(cfg["check"]["every"])
    offset = int(np.random.default_rng([seed % 2**64, 11]).integers(every))
    before = counters.snapshot()
    intervals, checks = [], []
    trace_dir = pathlib.Path(root) / ".chipbench_trace"
    if trace:
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir), profiler_options=_options())
    gc_pauses = _gc_pauses()
    r0 = resource.getrusage(resource.RUSAGE_SELF)
    w0 = time.perf_counter()
    end = w0 + seconds
    traced = None
    i = 0
    while time.perf_counter() < end:
        checked = (i + offset) % every == 0
        pre = entry.snapshot() if checked else None
        now = totals[i % horizon]
        applied = not np.array_equal(now, current)
        rec, t0, t1 = _interval(entry, measure, now if applied else None,
                                annotate)
        if t1 > end:
            break
        intervals.append({"t0": t0, "t1": t1, "measure_s": measure.seconds,
                          "demand_applied": applied})
        if checked:
            checks.append({"i": i, "pre": pre, "old": current, "new": now,
                           "applied": applied,
                           "out": entry.outputs(rec, entry.snapshot())})
        current = now
        i += 1
        if trace and traced is None and t1 - w0 >= min(TRACE_SECONDS,
                                                         seconds / 2):
            jax.profiler.stop_trace()
            traced = len(intervals)
    if trace and traced is None:
        jax.profiler.stop_trace()
        traced = len(intervals)
    r1 = resource.getrusage(resource.RUSAGE_SELF)
    gc.callbacks.remove(gc_pauses)
    after = counters.snapshot()
    program_trace.uninstall_tracer()
    n = len(intervals)
    window = intervals[-1]["t1"] - w0 if n else 0.0

    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devices)
    paths = sorted({json.dumps(e["args"], sort_keys=True)
                    for e in tracer.events
                    if e["name"].startswith("solver.dispatch:")})
    window_paths = [e for e in tracer.events[warm_instants:]
                    if e["name"].startswith("solver.dispatch:")]
    out("dispatch: " + json.dumps([json.loads(p) for p in paths]))
    times = [iv["t1"] - iv["t0"] for iv in intervals]
    out("window: " + json.dumps({
        "intervals": n, "warmup_intervals": n_warm,
        "slowest_ms": 1e3 * max(times, default=0.0),
        "slowest_at": int(np.argmax(times)) if times else None,
        "fastest_ms": 1e3 * min(times, default=0.0),
        "outside_ms": 1e3 * (window - sum(times)),
        "compiles": after["compiles"] - before["compiles"],
        "cache_hits": after["cache_hits"] - before["cache_hits"],
        "traces": after["traces"] - before["traces"],
        "dispatch_instants": len(window_paths),
        "demand_changes": sum(iv["demand_applied"] for iv in intervals),
        "gc_pauses": gc_pauses.count, "gc_longest_ms": 1e3 * gc_pauses.longest,
        # host CPU the process had over the window, and how often the OS
        # took a core from it: a run slowed by a shared host shows here
        "cpu_s": (r1.ru_utime + r1.ru_stime) - (r0.ru_utime + r0.ru_stime),
        "involuntary_switches": r1.ru_nivcsw - r0.ru_nivcsw,
        "p50_ms": 1e3 * statistics.median(times) if times else None,
        "p95_ms": 1e3 * p95(times) if times else None,
        "checked": [c["i"] for c in checks]}))

    # the per-layer metrics of a traced run
    augs = [t["aug"] for t in tenants]
    shapes = [work.tenant_shapes(g) for g in augs]
    W = shapes[0]["W"]
    changed = sum(iv["demand_applied"] for iv in intervals) / max(n, 1)
    publishes = Entry.step_publishes + changed * Entry.demand_publishes
    ctx = {"intervals": intervals, "traced": traced if trace else 0,
           "trace": None, "peaks": peaks,
           "work": work.interval_work(shapes, 2 * W + 1, publishes)}
    least, bound = work.least_seconds(ctx["work"], peaks)
    out("work: " + json.dumps({**ctx["work"], "least_s": least,
                               "bound": bound}))
    breakdown = None
    if trace:
        from chipbench import trace_reduce

        files = sorted(trace_dir.glob("**/*.xplane.pb"))
        ev = trace_reduce.events(files[-1])
        if keep_trace:
            pathlib.Path(keep_trace).write_text(json.dumps(
                trace_reduce.excerpt(ev, 3)))
        ctx["trace"] = trace_reduce.reduce(ev, traced)
        if ctx["trace"] is not None:
            breakdown = {"device_ops": ctx["trace"]["device_ops"],
                         "idle_gaps": ctx["trace"]["idle_gaps"]}
        import shutil

        shutil.rmtree(trace_dir, ignore_errors=True)

    # the comparison, once the program's state is freed
    for c in checks:
        c["pre_phi"] = entry.dense_phi(c["pre"][1])
        c["pre_lam"] = c["pre"][0]
        c["out"]["phi"] = entry.dense_phi(c["out"]["phi"])
        del c["pre"]
    del entry
    gc.collect()
    gaps, control_gaps = compare(checks, augs, a, b, cfg["solver"],
                                 control=control)
    # with --control the control's outputs stand in the program's place
    # and are judged by the same limits
    judged, key = (control_gaps, "control_gaps") if control else (gaps,
                                                                  "gaps")
    rows = {k: {"value": judged.get(k, float("nan")), "limit": v}
            for k, v in limits.items()}
    failed = sum(1 for c in checks if any(
        not c[key].get(k, math.inf) <= limits[k] for k in limits))
    correct = bool(checks) and failed == 0

    metrics = {}
    if trace:
        for name, unit in cell["per_layer"].items():
            value = module("metrics", name, root).read(ctx)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit}
    else:
        e2e = {"interval_ms": 1e3 * window / n if n else None,
               "interval_p95_ms": 1e3 * p95(times) if n else None,
               "setup_s": setup_s}
        for name, unit in cell["end_to_end"].items():
            if e2e.get(name) is not None:
                metrics[name] = {"value": e2e[name], "unit": unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    if trace and ctx["trace"] is not None:
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
    result = {"correct": correct, "attempted": n, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    out("gaps: " + json.dumps(gaps))
    if control:
        out("control: " + json.dumps(control_gaps))
    result["checks"] = rows
    out(json.dumps(result))
    for k, r in rows.items():
        err(f"check {k} {r['value']!r} limit {r['limit']!r}")
    err(f"check intervals_compared {len(checks)} of {n}; "
        f"{'control' if control else 'program'} judged; correct {correct}")
    return result


def _gc_pauses():
    """A ``gc.callbacks`` hook that counts collections and keeps the
    longest pause (host time the program's garbage takes in the window)."""
    def hook(phase, info):
        if phase == "start":
            hook.t0 = time.perf_counter()
        else:
            hook.count += 1
            hook.longest = max(hook.longest, time.perf_counter() - hook.t0)

    hook.count, hook.longest, hook.t0 = 0, 0.0, 0.0
    gc.callbacks.append(hook)
    return hook


def _options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def p95(values):
    return statistics.quantiles(values, n=20, method="inclusive")[18] \
        if len(values) > 1 else values[0]


# ---------------------------------------------------------------------------
# the comparison with the plain reference
# ---------------------------------------------------------------------------

def compare(checks, augs, a, b, solver, *, control=False):
    """Widest gaps between the program's outputs and the reference's over
    the checked intervals (each check's own under ``gaps``); with
    ``control``, also those of the reference computed in the three-pass
    bfloat16 products at the same inputs (``control_gaps``)."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference

    if not checks:
        return {}, {}
    stacked = reference.stack(augs)
    graph = reference.graph_leaves(stacked)
    demand, step = reference.make_interval(stacked["meta"], solver)
    if control:
        _, step_low = reference.make_interval(stacked["meta"], solver,
                                              mode="bf16x3")
    delta = float(solver["delta"])
    widest, widest_low = {}, {}

    def gaps_of(got, ref, floor, new):
        """Widest gaps over tenants.  φ and the weights are counted in
        units of the tenant's rounding floor: the gap that the reference
        itself shows between its inputs and the same inputs moved by one
        rounding step (``reference.nudge``), plus one float32 step of 1.
        A tenant whose routing amplifies rounding (a link near capacity
        under the exp cost makes the EG exponents span tens) then reads
        as many floors as a quiet one, where an absolute gap would let it
        set the limit alone."""
        lam, phi, D, wts = ref
        one = 2.0 ** -23

        def per_tenant(x, axes):
            return np.asarray(jnp.max(jnp.abs(x), axes))

        dphi = per_tenant(got["phi"] - reference.scatter_phi(stacked, phi),
                          (1, 2, 3))
        fphi = per_tenant(floor[1] - phi, (1, 2))
        dlam = per_tenant((jnp.asarray(got["lam"]) - lam) / new[:, None],
                          (1,))
        flam = per_tenant((floor[0] - lam) / new[:, None], (1,))
        # the cost relative to the reference's, per tenant; a link near
        # capacity under the exp cost amplifies the flows' rounding by
        # F/C, so it too is counted in the tenant's floors
        D = np.asarray(D, np.float64)
        dcost = np.abs(np.asarray(got["cost"], np.float64) - D) / np.abs(D)
        fcost = np.abs(np.asarray(floor[2], np.float64) - D) / np.abs(D)
        g = {"phi_gap": float(np.max(dphi / (fphi + one))),
             "lam_gap": float(np.max(dlam / (flam + one / 2))),
             "lam_gap_abs": float(np.max(dlam)),
             "cost_gap": float(np.max(dcost / (fcost + one))),
             "cost_gap_rel": float(np.max(dcost)),
             "phi_gap_abs": float(np.max(dphi)),
             "phi_floor_max": float(np.max(fphi))}
        if "weights" in got:
            dw = per_tenant(got["weights"] - wts, (1, 2))
            fw = per_tenant(floor[4] - wts, (1, 2))
            g["weights_gap"] = float(np.max(dw / (fw + one)))
            g["weights_gap_abs"] = float(np.max(dw))
        return g

    for c in checks:
        old = jnp.asarray(np.asarray(c["old"], np.float32))
        new = jnp.asarray(np.asarray(c["new"], np.float32))
        lam = jnp.asarray(c["pre_lam"], jnp.float32)
        if c["applied"]:
            lam = demand(lam, old, new)
        rows = np.asarray(reference.perturbations(lam, delta))
        task_u = jnp.asarray(utility(a, b, rows), jnp.float32)
        phi_e = reference.gather_phi(stacked, jnp.asarray(c["pre_phi"]))
        lam_r, phi_r, D_r, _, w_r = step(graph, lam, phi_e, new, task_u)
        ref = (lam_r, phi_r, D_r, w_r)
        floor = step(graph, reference.nudge(lam), reference.nudge(phi_e),
                     new, task_u)
        c["gaps"] = gaps_of(c["out"], ref, floor, new)
        for k, v in c["gaps"].items():
            widest[k] = max(widest.get(k, 0.0), v) if math.isfinite(v) \
                else float("nan")
        if control:
            lo = step_low(graph, lam, phi_e, new, task_u)
            got = {"lam": lo[0], "phi": reference.scatter_phi(stacked, lo[1]),
                   "cost": lo[2], "weights": lo[4]}
            if "weights" not in c["out"]:
                del got["weights"]
            c["control_gaps"] = gaps_of(got, ref, floor, new)
            for k, v in c["control_gaps"].items():
                widest_low[k] = max(widest_low.get(k, 0.0), v) \
                    if math.isfinite(v) else float("nan")
    return widest, widest_low
