"""From a profiler trace to device busy time, idle time and a breakdown.

``events(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
and keeps two lists: every device operation ([start_ns, end_ns, name],
from the "XLA Ops" and "XLA Modules" lines of each ``/device:TPU:n``
plane) and every host span the benchmark itself opened (names starting
``bench.``).  ``reduce`` works on those lists alone, so it is tested on
a recorded fixture without a chip:

* the window runs from the start of the first to the end of the last
  completed ``bench.interval`` span;
* busy time is the union of the device's operation intervals inside the
  window (averaged over the devices), idle time the rest;
* ``device_ops`` sums each operation's time by its HLO name;
* ``idle_gaps`` gives each part of every stretch in which the device ran
  nothing to the innermost ``bench.*`` span open in it, and sums by span.
"""
from __future__ import annotations

OP_LINES = ("XLA Ops", "XLA Modules")
HOST_PREFIX = "bench."


def events(path) -> dict:
    """{"devices": [[[s, e, name], ...] per device], "host": [...]}."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    kind = "module" if line.name == "XLA Modules" else "op"
                    ops.extend([e.start_ns, e.end_ns, kind, e.name]
                               for e in line.events)
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.start_ns, e.end_ns, e.name]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def _union(spans, lo, hi):
    """Sorted disjoint [s, e] covering spans clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in spans
                       if e > lo and s < hi):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _labelled(host, lo, hi):
    """[lo, hi) cut at every host span's edges, each piece named by the
    innermost ``bench.*`` span open in it (spans nest: the innermost is
    the one opened last), or ``bench.interval`` where no other is."""
    spans = sorted((s, e, n) for s, e, n in host if n != "bench.interval"
                   and e > lo and s < hi)
    cuts = sorted({lo, hi, *(max(lo, min(hi, x)) for s, e, _ in spans
                             for x in (s, e))})
    out, open_, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][0] <= a:
            open_.append(spans[j])
            j += 1
        open_ = [sp for sp in open_ if sp[1] > a]
        out.append((a, b, max(open_)[2] if open_ else "bench.interval"))
    return out


def _op_name(name: str) -> str:
    """'%fusion.12 = f32[...] fusion(...)' -> 'fusion.12'."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce(ev: dict, n_intervals: int, top: int = 10) -> dict | None:
    """Busy and window seconds, and the breakdown, of the traced window.

    Returns None where the trace holds no completed interval or no
    device operation inside it.
    """
    spans = sorted((s, e) for s, e, n in ev["host"] if n == "bench.interval")
    spans = spans[:n_intervals]
    if not spans or not ev["devices"]:
        return None
    lo, hi = spans[0][0], spans[-1][1]
    busy, ops = 0.0, {}
    for dev in ev["devices"]:
        union = _union([(s, e) for s, e, _, _ in dev], lo, hi)
        busy += sum(e - s for s, e in union)
        for s, e, kind, name in dev:
            if kind == "op" and e > lo and s < hi:
                key = _op_name(name)
                ops[key] = ops.get(key, 0) + min(e, hi) - max(s, lo)
    busy /= len(ev["devices"])
    if busy <= 0:
        return None
    # idle stretches of the first device, split by the innermost host
    # span open in each part
    idle = []
    t = lo
    for s, e in _union([(s, e) for s, e, _, _ in ev["devices"][0]], lo, hi):
        if s > t:
            idle.append((t, s))
        t = e
    if t < hi:
        idle.append((t, hi))
    gaps: dict[str, float] = {}
    k = 0
    for s, e, name in _labelled(ev["host"], lo, hi):   # both lists sorted
        while k < len(idle) and idle[k][1] <= s:
            k += 1
        for a, b in idle[k:]:
            if a >= e:
                break
            gaps[name] = gaps.get(name, 0) + min(b, e) - max(a, s)
    ns = 1e-9
    return {
        "busy_s": busy * ns,
        "window_s": (hi - lo) * ns,
        "n_intervals": len(spans),
        "device_ops": [[k, v * ns] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }


def excerpt(ev: dict, n_intervals: int) -> dict:
    """The events of the first ``n_intervals`` completed intervals: the
    size of trace the reduction's recorded fixture keeps."""
    spans = sorted((s, e) for s, e, n in ev["host"] if n == "bench.interval")
    lo, hi = spans[0][0], spans[n_intervals - 1][1]

    def inside(rows):
        return [r for r in rows if r[1] > lo and r[0] < hi]

    return {"n_intervals": n_intervals, "host": inside(ev["host"]),
            "devices": [inside(d) for d in ev["devices"]]}
