"""The reduction from a profiler trace to busy, idle and the breakdown."""
import json
import pathlib

import pytest

from chipbench import trace_reduce
from chipbench.metrics import device_busy_ms, device_idle_pct

FIXTURE = pathlib.Path(__file__).resolve().parent / "data" / "trace_er250.json"


def _hand_events():
    """Two intervals of 100 ns each on one device, by hand:
    interval 1 [0, 100): ops [10, 40) and [30, 60) overlap, [80, 90);
    interval 2 [100, 200): op [120, 150); a module [150, 170) on the
    modules line; an op [190, 230) runs past the window's end (200).
    The host was in set_demand [0, 20) and [100, 130), in measure
    [60, 80) and [150, 190), in block [190, 200)."""
    ops = [[10, 40, "op", "%fusion.1 = f32[8] fusion(x)"],
           [30, 60, "op", "%fusion.2 = f32[8] fusion(y)"],
           [80, 90, "op", "%fusion.1 = f32[8] fusion(x)"],
           [120, 150, "op", "%copy.3 = f32[8] copy(z)"],
           [150, 170, "module", "jit_step(123)"],
           [190, 230, "op", "%fusion.1 = f32[8] fusion(x)"]]
    host = [[0, 100, "bench.interval"], [100, 200, "bench.interval"],
            [200, 300, "bench.interval"],         # a third, not completed
            [0, 20, "bench.set_demand"], [100, 130, "bench.set_demand"],
            [60, 80, "bench.measure"], [150, 190, "bench.measure"],
            [190, 200, "bench.block"]]
    return {"devices": [ops], "host": host}


def test_reduction_by_hand():
    got = trace_reduce.reduce(_hand_events(), n_intervals=2)
    # busy: [10, 60) + [80, 90) + [120, 170) + [190, 200) = 50+10+50+10
    assert got["busy_s"] == pytest.approx(120e-9)
    assert got["window_s"] == pytest.approx(200e-9)
    assert got["n_intervals"] == 2
    assert dict(got["device_ops"]) == pytest.approx(
        {"fusion.1": 50e-9, "fusion.2": 30e-9, "copy.3": 30e-9})
    # idle: [0,10) set_demand, [60,80) measure, [90,100) interval,
    # [100,120) set_demand, [170,190) measure
    assert dict(got["idle_gaps"]) == pytest.approx(
        {"bench.set_demand": 30e-9, "bench.measure": 40e-9,
         "bench.interval": 10e-9})
    ctx = {"trace": got}
    assert device_idle_pct.read(ctx) == pytest.approx(40.0)
    assert device_busy_ms.read(ctx) == pytest.approx(60e-6)


def test_nothing_to_read_gives_nothing():
    assert trace_reduce.reduce({"devices": [], "host": []}, 3) is None
    assert trace_reduce.reduce(_hand_events(), n_intervals=0) is None
    assert device_idle_pct.read({"trace": None}) is None


@pytest.mark.skipif(not FIXTURE.exists(), reason="fixture not recorded")
def test_recorded_trace():
    """A window of an ER(250), K = 8 fleet (the megakernel path) recorded
    on one v5e."""
    ev = json.loads(FIXTURE.read_text())
    got = trace_reduce.reduce(ev, ev["n_intervals"])
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["n_intervals"] == ev["n_intervals"]
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
    assert got == pytest.approx(ev["expected"]) if "expected" in ev else True


def test_p95_reads_the_untraced_intervals():
    """The tail is read over the intervals that began once the profiler
    had stopped; a run with none of them gives nothing."""
    from chipbench.metrics import p95_interval_ms

    slow = [{"t0": 0.0, "t1": 1.0}] * 3          # under the profiler
    fast = [{"t0": 0.0, "t1": 1e-3 * (k + 1)} for k in range(20)]
    ctx = {"intervals": slow + fast, "traced": 3}
    # inclusive quantiles of 1..20 ms: the 19th cut lies at 19.05 ms
    assert p95_interval_ms.read(ctx) == pytest.approx(19.05)
    assert p95_interval_ms.read({"intervals": slow, "traced": 3}) is None
