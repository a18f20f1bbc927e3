import os

# Tests must see the single real CPU device (multi-device tests run in
# subprocesses that set XLA_FLAGS before importing jax).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True, scope="module")
def _bound_compiled_executable_state():
    """Drop compiled executables at every test-module boundary.

    A single -x -q run of the whole suite keeps every jitted executable
    of every module alive in one process; past ~320 tests the
    accumulated compiler state makes jaxlib's CPU backend_compile
    segfault deterministically on the next large scan (observed on
    jaxlib 0.4.36 — the faulting test is innocent and passes in any
    shorter run).  Modules never share compiled artifacts on purpose
    (cross-module caches are keyed on configs rebuilt per module), so
    clearing between modules only costs recompiles, not correctness.
    """
    yield
    import jax

    jax.clear_caches()


@pytest.fixture(scope="session")
def small_cec():
    """A small feasible CEC instance shared across core tests."""
    from repro.core import build_random_cec
    from repro.topo import connected_er

    adj = connected_er(15, 0.3, seed=3)
    return build_random_cec(adj, 3, 10.0, seed=0)


@pytest.fixture(scope="session")
def er25_cec():
    """The paper's main Connected-ER(25, 0.2) instance."""
    from repro.core import build_random_cec
    from repro.topo import connected_er

    adj = connected_er(25, 0.2, seed=1)
    return build_random_cec(adj, 3, 10.0, seed=0)


def random_phi(graph, seed=0):
    """A random feasible routing configuration (row-stochastic on mask)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    raw = rng.uniform(0.1, 1.0, size=graph.out_mask.shape).astype(np.float32)
    raw = raw * np.asarray(graph.out_mask)
    s = raw.sum(-1, keepdims=True)
    return jnp.asarray(np.where(s > 0, raw / np.where(s > 0, s, 1.0), 0.0))
