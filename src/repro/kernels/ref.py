"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each kernel's test sweeps shapes/dtypes and asserts allclose against these.
They are also the CPU execution paths.
"""
from __future__ import annotations

import jax.numpy as jnp

Array = jnp.ndarray


def flow_step_ref(t: Array, phi: Array, inject: Array) -> Array:
    """One flow-propagation relaxation step: t' = inject + t·Φ (per session).

    t, inject [W, N]; phi [W, N, N] (pre-masked row-stochastic).
    """
    return inject + jnp.einsum("wi,wij->wj", t, phi)


def omd_update_ref(phi: Array, delta: Array, mask: Array, eta: float) -> Array:
    """Exponentiated-gradient routing update (paper eq. (22)), row-stabilized."""
    logits = jnp.where(mask > 0, -eta * delta, -1e30)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    w = phi * jnp.exp(logits) * mask
    s = w.sum(-1, keepdims=True)
    return jnp.where(s > 0, w / jnp.where(s > 0, s, 1.0), phi)


def flow_step_sparse_ref(t: Array, rows: Array, base: Array, in_src: Array,
                         in_slot: Array, in_mask: Array) -> Array:
    """Sparse relaxation step: gather + masked in-segment sum.

    t, base [W, N]; rows (φ slots) [W, N, D]; in_src/in_slot/in_mask
    [N, Din].  Returns base + Σ_d t[:, in_src]·rows[:, in_src, in_slot]
    — the relay half of ``core.sparse.propagate``'s step (virtual-sink
    entries are overlaid by the caller).
    """
    vals = t[:, in_src] * rows[:, in_src, in_slot]
    return base + (vals * in_mask).sum(-1)


def omd_update_sparse_ref(phi: Array, delta: Array, mask: Array,
                          eta: float) -> Array:
    """Exponentiated-gradient update over [W, R, C] edge-slot rows.

    Same contract as :func:`omd_update_ref` — the row update is
    representation-agnostic; only the trailing-axis meaning differs.
    """
    return omd_update_ref(phi, delta, mask, eta)
