"""Pallas kernel: one flow-propagation relaxation step (control plane).

t' = inject + t·Φ for all sessions — a batched vector×matrix product, the
inner-loop hot spot of OMD-RT at fleet scale (N = 10³–10⁵ nodes).  Tiled
128×128 over Φ with an f32 VMEM accumulator; the session axis is the
outermost grid dim.

This kernel is live in the solver: ``core.flow.propagate`` dispatches each
relaxation step here when ``dispatch.use_kernels(n_bar)`` holds — threshold
cleared (default 256) on TPU, or an explicit override (see
core/dispatch.py).  Callers go through ``kernels.ops.flow_step_op``, which
zero-pads the node axes to the 128-block constraint asserted below and
slices the result back; off-TPU the dispatch passes ``interpret=True``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dispatch import VMEM_BYTES


def _flow_kernel(t_ref, phi_ref, inj_ref, o_ref, acc_ref):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = inj_ref[0].astype(jnp.float32)

    t = t_ref[0].astype(jnp.float32)             # [1, bk]
    phi = phi_ref[0].astype(jnp.float32)         # [bk, bj]
    acc_ref[...] += jax.lax.dot_general(
        t, phi, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def flow_step(t, phi, inject, *, bj: int = 128, bk: int = 128,
              interpret: bool):
    """t, inject [W, 1, N]; phi [W, N, N] → [W, 1, N].  N multiple of blocks.

    The rate vectors carry the session axis outside their last two
    dimensions, so each (1, 1, b) block spans whole trailing dimensions
    as the TPU tiling rule asks; a (1, b) block of a [W, N] array would
    not.
    """
    W, _, N = t.shape
    bj, bk = min(bj, N), min(bk, N)
    assert N % bj == 0 and N % bk == 0
    return pl.pallas_call(
        _flow_kernel,
        grid=(W, N // bj, N // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bk), lambda w, j, k: (w, 0, k)),
            pl.BlockSpec((1, bk, bj), lambda w, j, k: (w, k, j)),
            pl.BlockSpec((1, 1, bj), lambda w, j, k: (w, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bj), lambda w, j, k: (w, 0, j)),
        out_shape=jax.ShapeDtypeStruct((W, 1, N), t.dtype),
        scratch_shapes=[pltpu.VMEM((1, bj), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
    )(t, phi, inject)
