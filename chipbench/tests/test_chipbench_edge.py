"""The edge-list path: the stitched sparse kernels against the reference,
and the readers of their device time."""
import json

import pytest

from chipbench import trace_reduce, work
from chipbench.builders import power_law
from chipbench.metrics import edge_kernels_ms, edge_kernels_roofline
from chipbench.tests.helpers import ROOT, line, make_root, run_cell


@pytest.fixture(scope="module")
def edge_root(tmp_path_factory):
    """The tiny cells' checkout with the kernel-sized tenant's cell added
    to its BENCHMARK.json, as a new cell comes in by name."""
    root = make_root(tmp_path_factory.mktemp("checkout"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-ba-kernels", "source": "test",
                             "file": "chipbench/tests/data/"
                                     "tiny-ba-kernels.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-ba-kernels.sampled",
                               "config": "tiny-ba-kernels",
                               "traffic": "paper-steady", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_metro_edge_list_shapes():
    """The metro fleet's edge-list shapes, which the structure seed fixes
    on every run's seed: ``tests/test_tpu_compile.py`` compiles the
    stitched sparse kernels at exactly these (N, W, d_max, d_src, d_in)."""
    from repro.core import build_augmented_sparse

    cfg = json.loads((ROOT / "chipbench/configs/metro-ba-w3.json")
                     .read_text())
    dep = cfg["deployment"]
    (t,) = power_law.build(dep, 2**31 + 17)
    g = build_augmented_sparse(t["adj"], t["deploy"], t["link_cap"],
                               t["comp_cap"],
                               src_capacity=dep["src_capacity"])
    assert (g.n_phys, g.n_sessions, g.nbr.shape[1], g.src_nbr.shape[0],
            g.in_src.shape[1]) == (3233, 3, 106, 1082, 54)


@pytest.mark.parametrize("control", [False, True])
def test_sparse_kernel_path_against_reference(edge_root, control):
    """A Barabási–Albert tenant whose augmented graph clears the kernel
    threshold (n̄ = 304) runs through CECRouter on the edge-list layout
    with the stitched sparse kernels (interpret mode here): the program
    reads correct, and the three-pass bfloat16 control in its place reads
    incorrect through the same limits."""
    from repro.core import dispatch

    with dispatch.kernel_dispatch(1):
        result, lines = run_cell(edge_root, "tiny-ba-kernels.sampled",
                                 seconds=0.3, control=control)
    assert line(lines, "dispatch: ") == [{
        "kernels": True, "mode": "sampled", "n_bar": 304,
        "n_sessions": 3, "sparse": True}]
    assert result["attempted"] > 0
    assert result["correct"] is (not control)
    if control:
        assert result["failed"] > 0


def _events():
    """One device, two intervals of 100 ns: the flow kernel's two call
    sites [10, 40) and [110, 130), the EG kernel [50, 55) and [150, 160),
    the pv gather's fusion [0, 10), a while loop around all of the first
    interval's ops [0, 60), and the parent's name of the flow kernel
    [170, 180)."""
    ops = [[0, 60, "op", "%while.7 = (f32[3]) while(x)"],
           [0, 10, "op", "%fusion.249 = f32[3952,3] fusion(r, i)"],
           [10, 40, "op", "%edge_flow_step.20 = f32[3,1,384] custom-call(t)"],
           [50, 55, "op", "%edge_omd_update.34 = f32[3,384,128] custom-call"],
           [110, 130, "op", "%edge_flow_step.21 = f32[3,1,384] custom-call"],
           [150, 160, "op", "%edge_omd_update.34 = f32[3,384,128] custom"],
           [170, 180, "op", "%flow_step_sparse_op.7 = f32[3,1,384] custom"]]
    host = [[0, 100, "bench.interval"], [100, 200, "bench.interval"]]
    return {"devices": [ops], "host": host}


def _ctx(trace):
    # 35 bytes at 1e9 B/s: 35 ns, memory-bound
    return {"trace": trace, "work": {"flops": 0.0, "bytes": 35.0},
            "peaks": {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}}


def test_edge_kernel_readers_by_hand():
    trace = trace_reduce.reduce(_events(), n_intervals=2)
    ctx = _ctx(trace)
    # (30 + 20) + (5 + 10) ns of the two kernels over 2 intervals
    assert edge_kernels_ms.read(ctx) == pytest.approx(1e3 * 65e-9 / 2)
    assert work.least_seconds(ctx["work"], ctx["peaks"]) == \
        pytest.approx((35e-9, "memory"))
    # 35 ns of least time over 32.5 ns of kernel time an interval
    assert edge_kernels_roofline.read(ctx) == pytest.approx(
        100 * 35 / 32.5)


@pytest.mark.parametrize("reader", [edge_kernels_ms, edge_kernels_roofline])
@pytest.mark.parametrize("case", ["names_absent", "no_trace"])
def test_edge_kernel_readers_find_nothing(reader, case):
    """No op of the kernels' names (the jnp path, or the parent's
    program, which names them after their wrappers), or no trace at all:
    the reader gives nothing, never 0."""
    if case == "no_trace":
        trace = None
    else:
        ev = _events()
        ev["devices"][0] = [op for op in ev["devices"][0]
                            if "edge_" not in op[3]]
        trace = trace_reduce.reduce(ev, n_intervals=2)
        assert trace is not None
    assert reader.read(_ctx(trace)) is None
