"""Flash attention for TPU (Pallas): online-softmax tiling, causal, GQA.

Grid (B, H, nq, nk); the innermost kv axis iterates sequentially on TPU so
the running (max, denom, acc) state lives in VMEM scratch across kv blocks.
Fully-masked causal blocks are skipped with ``pl.when`` (≈2× prefill win).
BlockSpecs keep one (bq×hd) query tile + one (bk×hd) KV tile + the f32
accumulator in VMEM: for bq=bk=512, hd=128 that is ≈0.9 MB — well under
the compiler's default 16 MiB scoped-VMEM limit (a v5e core has 128 MiB,
``core.dispatch.VMEM_BYTES``), and all matmul dims are 128-multiples.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, q_offset: int, kv_len: int,
                  bq: int, bk: int):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_first = q_offset + qi * bq            # first query position of block
    k_first = ki * bk
    # causal skip: whole kv block strictly in the future of every query row
    live = (k_first <= q_first + bq - 1) if causal else (ki >= 0)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len
        if causal:
            qpos = q_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            mask &= kpos <= qpos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                         # [bq, 1]
        m_cur = jnp.max(s, -1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)               # rescale old state
        p = jnp.exp(s - m_new)                        # [bq, bk]
        l_new = alpha * l_ref[:, :1] + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                    kv_len: int | None = None, bq: int = 512, bk: int = 512,
                    interpret: bool = False):
    """q [B,H,S,hd]; k,v [B,KH,T,hd] → [B,H,S,hd].  S, T multiples of blocks.

    ``kv_len`` masks trailing cache padding; GQA handled via the K/V index
    map (query head h reads kv head h//G — no materialized repeat).
    """
    B, H, S, hd = q.shape
    KH, T = k.shape[1], k.shape[2]
    G = H // KH
    bq, bk = min(bq, S), min(bk, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    kv_len = T if kv_len is None else kv_len
    grid = (B, H, S // bq, T // bk)

    kern = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        q_offset=q_offset, kv_len=kv_len, bq=bq, bk=bk)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max (col 0 used)
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom
            pltpu.VMEM((bq, hd), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
