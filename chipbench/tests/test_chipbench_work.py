"""The roofline count: by hand at a small shape, and the same whichever
dispatch path the program takes."""
import numpy as np

from chipbench import reference, work
from chipbench.tests.helpers import line, run_cell


def test_count_by_hand():
    """Line 0-1-2; version 0 on node 0 (the admission point), version 1
    on nodes 1 and 2.  Session edges: S->0 (both versions), 0->D_0,
    0->1, 1->D_1, 2->D_1 (version 1) = 6; union edges 5; the longest
    path S->0->1->D_1 has 3 edges, so 4 relaxation steps."""
    adj = np.zeros((3, 3), bool)
    adj[0, 1] = adj[1, 0] = adj[1, 2] = adj[2, 1] = True
    deploy = np.array([[1, 0, 0], [0, 1, 1]], bool)
    aug = reference.augment(adj, deploy, np.ones((3, 3)), np.ones(3), 1e4)
    shapes = work.tenant_shapes(aug)
    assert shapes == {"S": 6.0, "U": 5.0, "d": 4, "W": 2, "N": 3}
    got = work.interval_work([shapes], observations=5, publishes=2)
    # 5 observations x (6 (7*4 + 12) + 11*5) + 2 publishes x (2*4*6 + 3*2*3)
    assert got["flops"] == 5 * 295 + 2 * 66 == 1607
    # 12 S + 12 U + 2 publishes x 4 W N
    assert got["bytes"] == 72 + 60 + 48 == 180
    peaks = {"flops_per_s": 1000.0, "hbm_bytes_per_s": 100.0}
    assert work.least_seconds(got, peaks) == (1.8, "memory")


def test_same_count_on_jnp_and_kernel_paths(tiny_root):
    """The stitched Pallas kernels (interpret mode here) and the jnp path
    reach different dispatch paths but one count, and both are correct."""
    from repro.core import dispatch

    plain, plain_lines = run_cell(tiny_root, "tiny-er.sampled", seconds=0.5)
    with dispatch.kernel_dispatch(1):
        kern, kern_lines = run_cell(tiny_root, "tiny-er.sampled",
                                    seconds=0.5)
    paths = [{p["kernels"] for p in line(ls, "dispatch: ")}
             for ls in (plain_lines, kern_lines)]
    assert paths == [{False}, {True}]
    assert line(plain_lines, "work: ") == line(kern_lines, "work: ")
    assert plain["correct"] and kern["correct"]
