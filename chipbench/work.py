"""The algorithm's work in one control interval, from the cell's shapes.

Counted on the augmented DAG as the paper defines it, per tenant:
S = Σ_w |E_w| session edges (edges session w may use), U union edges,
d the relaxation depth (longest path + 1, the Jacobi steps that reach the
fixed point exactly), W versions, N physical nodes.  The count is of the
algorithm, not of an implementation: a dense [W, N̄, N̄] tensor, a padded
edge list or a kernel's tiles all do at least this, so one count judges
every dispatch path.

One oracle observation (an OMD-RT step and the cost at its result):
  propagation at the current φ        2·d·S   (a multiply-add per session
                                               edge and relaxation step)
  link flows                          2·S
  cost and its derivative             7·U     (divide, exp, sums)
  marginal-cost recursion             3·d·S
  marginals and the EG step           8·S     (add; scale, max, exp,
                                               multiply, sum, divide)
  propagation, flows, cost at new φ   2·d·S + 2·S + 4·U
  = S·(7·d + 12) + 11·U

An interval makes 2W + 1 observations (sampled gradients) and publishes
``publishes`` times a set of replica weights: a propagation (2·d·S) and
a normalisation (3·W·N).  Bytes are the least the interval must move:
φ read and written (8·S), the session masks (4·S), each union edge's
two endpoints and capacity (12·U), and the published weights (4·W·N per
publish).
"""
from __future__ import annotations


def tenant_shapes(aug) -> dict:
    """The counts of one ``reference.Augmented`` tenant."""
    return {"S": float(aug.smask.sum()),
            "U": float((aug.smask.sum(0) > 0).sum()),
            "d": aug.depth, "W": aug.n_sessions, "N": aug.n_phys}


def interval_work(shapes: list[dict], observations: int,
                  publishes: int) -> dict:
    """{"flops": ..., "bytes": ...} of one interval over all tenants."""
    flops = nbytes = 0.0
    for t in shapes:
        S, U, d, W, N = t["S"], t["U"], t["d"], t["W"], t["N"]
        flops += observations * (S * (7 * d + 12) + 11 * U)
        flops += publishes * (2 * d * S + 3 * W * N)
        nbytes += 12 * S + 12 * U + publishes * 4 * W * N
    return {"flops": flops, "bytes": nbytes}


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The larger of ops over peak FLOP/s and bytes over peak bytes/s,
    and which of the two bounds it."""
    t_ops = work["flops"] / peaks["flops_per_s"]
    t_mem = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
