"""What must come out as not correct: the lower-precision control, and
the timed path broken underneath a run."""
import jax
import jax.numpy as jnp
import pytest

from chipbench.tests.helpers import line, run_cell


@pytest.mark.parametrize("workload", ["tiny-er.sampled", "tiny-pl.sampled"])
def test_control_fails_a_limit(tiny_root, workload):
    """The reference in three-pass bfloat16 products, put in the
    program's place at the program's own inputs, comes out as not
    correct through the harness's own comparison; the program, read in
    the same run, stays within every limit."""
    result, lines = run_cell(tiny_root, workload, control=True)
    limits = {k: r["limit"] for k, r in result["checks"].items()}
    assert result["correct"] is False
    assert result["failed"] > 0
    control = line(lines, "control: ")
    assert {k: r["value"] for k, r in result["checks"].items()} == \
        {k: control[k] for k in limits}
    program = line(lines, "gaps: ")
    assert all(program[k] <= limits[k] for k in limits), (program, limits)


def _copy(tree):
    return jax.tree_util.tree_map(lambda x: x + 0, tree)


def _fleet_fault(monkeypatch, kind):
    """Break RouterFleet's fused step, or its publish, underneath."""
    from repro.serve import fleet

    orig_step, orig_pub = fleet.fused_step_batch, fleet._publisher

    def step_factory(*a, **k):
        fn = orig_step(*a, **k)

        def broken(graph, totals, state, u, *rest):
            old = _copy(state)
            new, info = fn(graph, totals, state, u, *rest)
            if kind == "unchanged":
                return old, info
            keep = jnp.arange(old.lam.shape[0]) < old.lam.shape[0] // 2
            pick = (lambda n, o: jnp.where(
                keep.reshape((-1,) + (1,) * (n.ndim - 1)), n, o))
            return jax.tree_util.tree_map(pick, new, old), info
        return broken

    def pub_factory(*a, **k):
        fn = orig_pub(*a, **k)

        def altered(graph, state, *rest):
            lam, weights, *more = fn(graph, state, *rest)
            return (lam.at[0].set(lam[0, ::-1]), weights, *more)
        return altered

    if kind == "altered":
        monkeypatch.setattr(fleet, "_publisher", pub_factory)
    else:
        monkeypatch.setattr(fleet, "fused_step_batch", step_factory)


def _router_fault(monkeypatch, kind):
    """Break CECRouter's fused step underneath."""
    from repro.core import solver

    orig = solver.fused_step

    def factory(*a, **k):
        fn = orig(*a, **k)

        def broken(problem, state, u, *rest):
            new, info = fn(problem, state, u, *rest)
            if kind == "unchanged":
                return state, info
            # the routing answer: sessions' rows handed to each other
            phi = jax.tree_util.tree_map(lambda x: x[::-1], new.phi)
            return new._replace(phi=phi), info
        return broken

    monkeypatch.setattr(solver, "fused_step", factory)


@pytest.mark.parametrize("workload,kind", [
    ("tiny-er.sampled", "unchanged"), ("tiny-er.sampled", "half"),
    ("tiny-er.sampled", "altered"), ("tiny-pl.sampled", "unchanged"),
    ("tiny-pl.sampled", "altered")])
def test_broken_step_is_not_correct(tiny_root, monkeypatch, workload, kind):
    """A step that returns its state unchanged, one that advances only
    half of the tenants, and an answer altered where it is produced (the
    fleet: one tenant's published split reversed, which the next
    interval measures around; the router: its routing rows handed from
    one version to another) each read incorrect.  One chip holds each
    cell, so there is no exchange between chips to drop."""
    (_fleet_fault if workload.startswith("tiny-er") else _router_fault)(
        monkeypatch, kind)
    result, _ = run_cell(tiny_root, workload, seconds=0.5)
    assert not result["correct"]
    assert result["failed"] > 0
