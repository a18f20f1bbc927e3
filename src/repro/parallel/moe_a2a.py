"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

The GSPMD path (models/layers.moe_apply) replicates each data-shard's
tokens across the 'model' axis so every expert owner sees them — simple,
but the token activations ride the wire E-owners times.  The GShard/Switch
production schedule shards tokens over *both* mesh axes and moves only the
routed tokens, twice, with all-to-alls:

  tokens [T_loc, D] per device
    → route locally (top-1 here; capacity per (device, expert))
    → dispatch buffers [n_exp_shards, E_loc, cap, D]
    → all_to_all over 'model'  (tokens travel to their expert's owner)
    → local expert FFN [E_loc, n_exp_shards·cap, D]
    → all_to_all back, combine with gate weights

Wire per layer ≈ 2 × routed-token bytes — independent of E — vs the
replicated path's (model_axis−1)× token bytes.  This is the "next lever"
identified for the jamba cell in EXPERIMENTS.md §Perf.

Implemented as a standalone layer (top-1 routing) with a dense oracle
test on an 8-device mesh (tests/test_moe_a2a.py); integration into the
jamba config is left switchable (the GSPMD path remains the default).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .collectives import shard_map_compat


def moe_a2a_apply(mesh, params, x, *, capacity_factor: float = 1.5):
    """x [B, S, D] (batch over 'data'); params: router [D,E],
    wi/wo [E, D, F]/[E, F, D] (experts over 'model').  Top-1 routing."""
    E = params["router"].shape[1]
    mp = mesh.shape["model"]
    assert E % mp == 0, (E, mp)
    E_loc = E // mp

    def body(x_loc, router, wi, wo):
        # x_loc [b_loc, S, D] ; wi [E_loc, D, F] ; tokens also sharded on
        # 'model' by splitting the local batch sequence-wise
        b, S, D = x_loc.shape
        T = b * S
        xt = x_loc.reshape(T, D)
        midx = jax.lax.axis_index("model")

        gates = jax.nn.softmax((xt @ router).astype(jnp.float32), -1)
        gval = gates.max(-1)
        gidx = gates.argmax(-1)                                # [T]

        cap = max(int(capacity_factor * T / E), 4)
        # slot of each token within its expert's queue (local capacity)
        onehot = jax.nn.one_hot(gidx, E, dtype=jnp.int32)
        slot = jnp.sum(jnp.cumsum(onehot, 0) * onehot, -1) - 1  # [T]
        keep = slot < cap
        gval = gval * keep
        dest_shard = gidx // E_loc
        dest_exp = gidx % E_loc

        # dispatch buffer [mp, E_loc, cap, D] → all_to_all over 'model'
        buf = jnp.zeros((mp, E_loc, cap + 1, D), x_loc.dtype)
        s_ix = jnp.where(keep, slot, cap)
        buf = buf.at[dest_shard, dest_exp, s_ix].add(xt)
        buf = buf[:, :, :cap]
        recv = jax.lax.all_to_all(buf, "model", 0, 0, tiled=False)
        # recv [mp, E_loc, cap, D]: tokens from every peer for MY experts

        h = jax.nn.silu(jnp.einsum("pecd,edf->pecf", recv, wi))
        ye = jnp.einsum("pecf,efd->pecd", h, wo)

        back = jax.lax.all_to_all(ye, "model", 0, 0, tiled=False)
        # back [mp, E_loc, cap, D]: my tokens, processed, per dest shard
        yt = back[dest_shard, dest_exp, jnp.minimum(s_ix, cap - 1)]
        yt = yt * gval[:, None].astype(x_loc.dtype)
        return yt.reshape(b, S, D)

    fn = shard_map_compat(
        body, mesh,
        in_specs=(P("data", None, None), P(None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=P("data", None, None))
    return fn(x, params["router"], params["wi"], params["wo"])


def moe_dense_oracle(params, x):
    """Dense top-1 reference: every expert on every token, gate-combined."""
    B, S, D = x.shape
    xt = x.reshape(-1, D)
    gates = jax.nn.softmax((xt @ params["router"]).astype(jnp.float32), -1)
    gval = gates.max(-1)
    gidx = gates.argmax(-1)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, params["wi"]))
    ye = jnp.einsum("tef,efd->ted", h, params["wo"])
    y = jnp.take_along_axis(
        ye, gidx[:, None, None].repeat(D, -1), 1)[:, 0]
    return (y * gval[:, None].astype(x.dtype)).reshape(B, S, D)
