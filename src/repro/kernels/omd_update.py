"""Pallas kernel: fused exponentiated-gradient routing update (eq. (22)).

One VMEM pass per row block: mask → shift by row max → exp → row sum →
renormalize.  Fusing the five elementwise/reduction ops avoids four HBM
round-trips of the [W,N,N] routing tensor — the dominant data movement of
a control-plane iteration at fleet scale.

This kernel is live in the solver: ``core.routing.omd_step`` dispatches the
exponentiated-gradient update here when ``dispatch.use_kernels(n_bar)``
holds — threshold cleared (default 256) on TPU, or an explicit override
(see core/dispatch.py) — via ``kernels.ops.omd_update_op`` which zero-pads
both
node axes to the 128-row block constraint asserted below (padded rows have
all-zero mask and fall through to ``phi`` unchanged before being sliced
off).  η is a static kernel parameter — a Python float, baked into the
compiled grid program.  Off-TPU the dispatch passes ``interpret=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dispatch import VMEM_BYTES

NEG = -1e30


def _omd_kernel(phi_ref, delta_ref, mask_ref, o_ref, *, eta: float):
    phi = phi_ref[0].astype(jnp.float32)         # [br, N]
    delta = delta_ref[0].astype(jnp.float32)
    mask = mask_ref[0].astype(jnp.float32)
    logits = jnp.where(mask > 0, -eta * delta, NEG)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    w = phi * jnp.exp(logits) * mask
    s = w.sum(-1, keepdims=True)
    out = jnp.where(s > 0, w / jnp.where(s > 0, s, 1.0), phi)
    o_ref[0] = out.astype(o_ref.dtype)


def omd_update(phi, delta, mask, eta: float, *, br: int = 128,
               interpret: bool):
    """phi, delta, mask [W, N, N] → updated phi.  Rows N multiple of br."""
    W, N, _ = phi.shape
    br = min(br, N)
    assert N % br == 0
    spec = pl.BlockSpec((1, br, N), lambda w, i: (w, i, 0))
    return pl.pallas_call(
        functools.partial(_omd_kernel, eta=eta),
        grid=(W, N // br),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(phi.shape, phi.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
    )(phi, delta, mask)
