"""Pallas kernel: exponentiated-gradient update over padded edge slots.

Identical math to ``omd_update`` (eq. (22), row-stabilized) but over the
sparse slot layout: rows are [R, C] blocks where C is the padded slot
count — ``d_max`` for the per-node CSR rows, ``d_src`` for the virtual
source's admission row — so one VMEM pass costs O(E) instead of O(N̄²).
Rectangular [W, R, C] operands are first-class (the dense kernel assumes
square [W, N, N]); rows whose mask is all zero fall through to the input
φ, which also makes slot padding exact.

Dispatched by ``core.sparse.omd_phi_update`` when ``dispatch.
use_kernels(n_bar)`` holds, through ``kernels.ops.omd_update_sparse_op``
(pads R to the row-block multiple and C to 128 lanes).  η is a static
kernel parameter (Python float), as on the dense path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dispatch import VMEM_BYTES

NEG = -1e30


def _omd_sparse_kernel(phi_ref, delta_ref, mask_ref, o_ref, *, eta: float):
    phi = phi_ref[0].astype(jnp.float32)           # [br, C]
    delta = delta_ref[0].astype(jnp.float32)
    mask = mask_ref[0].astype(jnp.float32)
    logits = jnp.where(mask > 0, -eta * delta, NEG)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    w = phi * jnp.exp(logits) * mask
    s = w.sum(-1, keepdims=True)
    o_ref[0] = jnp.where(s > 0, w / jnp.where(s > 0, s, 1.0),
                         phi).astype(o_ref.dtype)


def omd_update_sparse(phi, delta, mask, eta: float, *, br: int = 128,
                      interpret: bool):
    """phi, delta, mask [W, R, C] → updated phi.  R multiple of br."""
    W, R, C = phi.shape
    br = min(br, R)
    assert R % br == 0
    spec = pl.BlockSpec((1, br, C), lambda w, i: (w, i, 0))
    return pl.pallas_call(
        functools.partial(_omd_sparse_kernel, eta=eta),
        grid=(W, R // br),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(phi.shape, phi.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
        name="edge_omd_update",     # a device trace shows edge_omd_update.N
    )(phi, delta, mask)
