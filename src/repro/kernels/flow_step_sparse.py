"""Pallas segment kernel: one sparse flow-propagation relaxation step.

The edge-list counterpart of ``flow_step``: instead of a [W, N, N] mat-vec,
each output node j accumulates its padded in-edge segment

    t'[w, j] = base[w, j] + Σ_d t[w, in_src[j, d]] · pv[w, j, d]

— a gather + masked column reduction, O(E) work per step.  ``pv`` holds
the routing share φ[w, in_src[j, d], in_slot[j, d]] of each in-edge,
masked.  Its index is two-dimensional, which no TPU kernel gathers, so
``kernels.ops.flow_step_sparse_op`` gathers it in XLA and the kernel
gathers the rate vector t.  ``base`` is the precomputed constant inflow
(exogenous injection + the virtual source's admission flow,
``core.sparse.source_inflow``); the W virtual-sink entries are overlaid
by the caller from the analytic compute-edge reduction, so no hub row
ever enters the padded in-lists (DESIGN.md §12.1).

Mosaic gathers only within one vreg (128 lanes), so the t gather runs in
128-lane tiles: for each output tile and each 128-node chunk of t, a
lane gather by ``in_src mod 128`` is kept where ``in_src div 128`` names
that chunk.  That is (N/128)² vreg-sized gathers per session and step.
The in-lists are laid out [Din, N] (nodes on lanes) so the reduction
over in-slots runs along sublanes.  Dispatched by
``core.sparse.propagate`` when ``dispatch.use_kernels(n_bar)`` holds,
through ``kernels.ops.flow_step_sparse_op``, which does that layout and
pads nodes to 128 and in-slots to 8.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dispatch import VMEM_BYTES

LANES = 128


def _flow_sparse_kernel(t_ref, pv_ref, base_ref, lane_ref, chunk_ref,
                        o_ref):
    din, n = lane_ref.shape
    n_chunks = n // LANES

    def out_tile(o, carry):
        off = pl.multiple_of(o * LANES, LANES)
        lane = lane_ref[:, pl.ds(off, LANES)]           # [Din, 128]
        chunk = chunk_ref[:, pl.ds(off, LANES)]

        def src_chunk(c, tv):
            src = pl.multiple_of(c * LANES, LANES)
            x = jnp.broadcast_to(t_ref[0, :, pl.ds(src, LANES)],
                                 (din, LANES))
            got = jnp.take_along_axis(x, lane, axis=1)
            return jnp.where(chunk == c, got, tv)

        tv = jax.lax.fori_loop(0, n_chunks, src_chunk,
                               jnp.zeros((din, LANES), jnp.float32))
        inflow = jnp.sum(tv * pv_ref[0, :, pl.ds(off, LANES)], axis=0,
                         keepdims=True)
        o_ref[0, :, pl.ds(off, LANES)] = (
            base_ref[0, :, pl.ds(off, LANES)] + inflow).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n_chunks, out_tile, 0)


def flow_step_sparse(t, pv, base, lane, chunk, *, interpret: bool):
    """t, base [W, 1, N]; pv [W, Din, N]; lane, chunk [Din, N] → [W, 1, N].

    N a multiple of 128 and Din of 8 (``ops.py`` pads).  ``lane``/``chunk``
    split each in-edge's tail node into its lane and its 128-node chunk;
    padded in-slots carry pv 0 (their mask is folded into ``pv``).
    """
    W, _, N = t.shape
    din = lane.shape[0]
    assert N % LANES == 0 and din % 8 == 0
    node = pl.BlockSpec((1, 1, N), lambda w: (w, 0, 0))
    inlist = pl.BlockSpec((din, N), lambda w: (0, 0))
    return pl.pallas_call(
        _flow_sparse_kernel,
        grid=(W,),
        in_specs=[node, pl.BlockSpec((1, din, N), lambda w: (w, 0, 0)),
                  node, inlist, inlist],
        out_specs=node,
        out_shape=jax.ShapeDtypeStruct((W, 1, N), t.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
    )(t, pv, base, lane, chunk)
