"""Distributed-optimization collectives: compression + explicit ring AR.

* ``shard_map_compat`` — the one ``jax.shard_map`` entry point of this
  repo, with replication checking off.  The solver core's fleet engine
  (``core/batch.run_batch_sharded``) and the ring all-reduce below both
  go through it.
* ``quantize_dequantize_int8`` — symmetric per-tensor int8 gradient
  compression.  Hooked in before pjit's gradient reduction it cuts the
  cross-pod all-reduce payload 2× vs bf16 / 4× vs f32 (§Perf iteration 3
  uses it on the collective-bound MoE cell).  Error feedback keeps the
  quantization noise unbiased across steps.
* ``ring_all_reduce`` — a bucketized ring all-reduce built from
  shard_map + ppermute: 2(n−1) steps of reduce-scatter + all-gather whose
  per-hop payloads XLA can overlap with compute (each hop is an async
  collective-permute).  This is the hand-rolled schedule used when the
  default all-reduce sits on the critical path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def shard_map_compat(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled.

    The fleet engine's per-shard solves are embarrassingly parallel (no
    cross-shard collectives), which the checker cannot always prove
    through a scanned solver body.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def quantize_int8(x):
    """x → (int8 payload, f32 scale)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_int8(q, scale, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_dequantize_int8(x):
    """Straight-through int8 round trip (what the wire would carry)."""
    if x.ndim == 0:
        return x
    q, s = quantize_int8(x)
    return dequantize_int8(q, s, x.dtype)


def error_feedback_compress(x, residual):
    """(compressed value, new residual): EF-SGD style error feedback."""
    y = x + residual
    out = quantize_dequantize_int8(y)
    return out, y - out


def ring_all_reduce(x, mesh, axis: str = "data"):
    """All-reduce over one mesh axis via reduce-scatter + all-gather rings.

    x must be divisible by the axis size along dim 0.
    """
    n = mesh.shape[axis]

    def ring(block):
        idx = jax.lax.axis_index(axis)
        chunks = jnp.reshape(block, (n, -1))
        perm = [(i, (i + 1) % n) for i in range(n)]

        # reduce-scatter: after n-1 hops, chunk (idx+1) holds the full sum
        def rs_step(k, ch):
            send = (idx - k) % n
            val = ch[send]
            recv = jax.lax.ppermute(val, axis, perm)
            return ch.at[(idx - k - 1) % n].add(recv)

        chunks = jax.lax.fori_loop(0, n - 1, rs_step, chunks)

        # all-gather ring: circulate each node's reduced chunk
        def ag_step(k, ch):
            send = (idx + 1 - k) % n
            recv = jax.lax.ppermute(ch[send], axis, perm)
            return ch.at[(idx - k) % n].set(recv)

        chunks = jax.lax.fori_loop(0, n - 1, ag_step, chunks)
        return jnp.reshape(chunks, block.shape)

    return shard_map_compat(ring, mesh, P(), P())(x)
