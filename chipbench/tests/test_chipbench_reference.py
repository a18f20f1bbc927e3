"""The plain reference against the program, at small sizes on the CPU."""
import numpy as np
import pytest

from chipbench import reference
from chipbench.tests.helpers import run_cell


def _draw(n, W, seed):
    from chipbench.builders import connected_er as er

    rng = np.random.default_rng(seed)
    adj = er.connected_er(n, 4.0 / (n - 1), rng)
    link, comp = er.capacities(n, 10.0, 10.0, rng)
    return adj, er.deployment(n, W, rng), link, comp


@pytest.mark.parametrize("seed", range(8))
def test_augmented_graph_matches_the_programs(seed):
    """The reference's own augmentation gives the program's edge sets,
    capacities and relaxation depth."""
    from repro.core import InfeasibleTopology, build_augmented

    adj, deploy, link, comp = _draw(18, 3, seed)
    try:
        g = build_augmented(adj, deploy, link, comp, src_capacity=1e4)
    except InfeasibleTopology:
        with pytest.raises(reference.Infeasible):
            reference.augment(adj, deploy, link, comp, 1e4)
        return
    aug = reference.augment(adj, deploy, link, comp, 1e4)
    dense = np.zeros((3, aug.n_bar, aug.n_bar), np.float32)
    dense[:, aug.tail, aug.head] = aug.smask
    np.testing.assert_array_equal(dense, np.asarray(g.out_mask))
    cap = np.ones((aug.n_bar, aug.n_bar), np.float32)
    cap[aug.tail, aug.head] = aug.cap
    used = np.asarray(g.edge_mask) > 0
    np.testing.assert_array_equal(cap[used], np.asarray(g.capacity)[used])
    assert aug.depth == g.depth_max


def test_projection_is_the_euclidean_one():
    """Against a brute-force bisection on the dual, in float64."""
    rng = np.random.default_rng(0)
    for _ in range(50):
        W = int(rng.integers(2, 9))
        total = float(rng.uniform(5, 80))
        delta = 0.5
        y = rng.normal(total / W, total / 3, W)
        lo, hi = -1e4, 1e4
        for _ in range(200):
            tau = (lo + hi) / 2
            s = np.clip(y - tau, delta, total - delta).sum()
            lo, hi = (tau, hi) if s > total else (lo, tau)
        want = np.clip(y - (lo + hi) / 2, delta, total - delta)
        got = np.asarray(reference.project(
            np.asarray(y, np.float32), np.float32(total), delta))
        np.testing.assert_allclose(got, want, atol=2e-5 * total)


@pytest.mark.parametrize("workload", ["tiny-er.sampled", "tiny-pl.sampled"])
def test_program_step_matches_reference(tiny_root, workload):
    """Every checked interval of a tiny run agrees with the reference
    (dense RouterFleet and edge-list CECRouter), within the limits."""
    result, lines = run_cell(tiny_root, workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 2 and result["failed"] == 0
    for row in result["checks"].values():
        assert 0 <= row["value"] < row["limit"]
