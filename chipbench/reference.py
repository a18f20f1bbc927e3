"""The plain reference: one control interval of the CEC controller.

Written from the paper (arXiv:2406.19613, §II-§III: flow eqs. (1)-(4),
the exp link cost of §IV, Gallager's marginal-cost recursion (19)-(21),
the exponentiated-gradient routing step (22), and OMAD, Alg. 3: a single
oracle step per observation, two-point gradient over the 2W perturbed
admissions, mirror ascent on the scaled simplex, exact projection onto
{δ <= λ_w <= λ - δ, Σ λ_w = λ}).  It imports nothing of the program under
test and builds its own augmented graph from the physical deployment.

One layout serves every deployment: an edge list (tail, head) over the
augmented nodes (physical 0..N-1, the virtual source S = N, one virtual
sink per version at N+1+w), with a per-session mask of the edges that
session may use.  Sums over edges are segment sums, so the same code
checks a program that keeps φ as dense [W, N̄, N̄] tensors and one that
keeps padded edge lists.

Every product of the propagation, the link flows and the marginal
recursion goes through :func:`mul`.  In mode ``"f32"`` it is the float32
product; in mode ``"bf16x3"`` it is the three-pass bfloat16 product a
TPU computes for a float32 dot at precision ``HIGH`` (each operand split
into a bfloat16 head and tail, the tail·tail term dropped), emulated
here so that the control reads the same on any backend.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

EXP_CLIP = 25.0       # the exp cost is continued linearly past F/C = 25
NEG = -1e30


class Infeasible(ValueError):
    """A deployment in which some version cannot be reached from S."""


# ---------------------------------------------------------------------------
# the augmented graph, from the physical deployment (numpy, host)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Augmented:
    """One tenant's augmented DAG as an edge list."""

    n_phys: int
    n_sessions: int
    tail: np.ndarray      # [E] int
    head: np.ndarray      # [E] int
    cap: np.ndarray       # [E] float32
    smask: np.ndarray     # [W, E] float32: session w may use edge e
    deploy: np.ndarray    # [W, N] bool
    depth: int            # longest path in edges, + 1

    @property
    def n_bar(self) -> int:
        return self.n_phys + 1 + self.n_sessions

    @property
    def n_edges(self) -> int:
        return int(self.tail.shape[0])


def _bfs_layers(adj: np.ndarray, start: np.ndarray) -> np.ndarray:
    n = adj.shape[0]
    layer = np.full(n, -1)
    layer[start] = 0
    frontier = np.nonzero(start)[0]
    d = 0
    while frontier.size:
        d += 1
        nxt = np.nonzero(adj[frontier].any(0) & (layer < 0))[0]
        layer[nxt] = d
        frontier = nxt
    return layer


def augment(adj, deploy, link_cap, comp_cap, src_cap: float) -> Augmented:
    """The augmented DAG of one physical deployment.

    Physical links are oriented from lower to higher (BFS layer from the
    admission points D(1), node index), so any routing is loop-free.  A
    node deploying version w hands w only to its sink (it never relays
    its own version); every other node may relay w along an oriented
    link whose head can still deliver w to its sink.  S admits every
    session at the D(1) nodes that can deliver it.
    """
    adj = np.asarray(adj, bool)
    deploy = np.asarray(deploy, bool)
    W, N = deploy.shape
    if not (deploy.sum(0) == 1).all() or (deploy.sum(1) == 0).any():
        raise Infeasible("each node deploys one version, each version a node")
    layer = _bfs_layers(adj, deploy[0])
    if (layer < 0).any():
        raise Infeasible("physical graph is not connected")
    key = layer * N + np.arange(N)
    dag = adj & (key[:, None] < key[None, :])
    order = np.argsort(key)
    useful = deploy.copy()
    for w in range(W):
        for i in order[::-1]:
            if not deploy[w, i]:
                useful[w, i] = bool((dag[i] & useful[w]).any())

    src, sink = N, N + 1 + np.arange(W)
    tails, heads, caps, masks = [], [], [], []
    ti, hj = np.nonzero(dag)
    m = np.stack([useful[w][ti] & useful[w][hj] & ~deploy[w][ti]
                  for w in range(W)])
    keep = m.any(0)
    tails.append(ti[keep]); heads.append(hj[keep])
    caps.append(np.asarray(link_cap, np.float64)[ti[keep], hj[keep]])
    masks.append(m[:, keep])
    node_w = deploy.argmax(0)                          # compute edges i -> D_w
    tails.append(np.arange(N)); heads.append(sink[node_w])
    caps.append(np.asarray(comp_cap, np.float64))
    masks.append(deploy.copy())
    admit = np.stack([deploy[0] & useful[w] for w in range(W)])
    if not admit.any(1).all():
        raise Infeasible("some version cannot be reached from S")
    d1 = np.nonzero(admit.any(0))[0]                   # S -> D(1)
    tails.append(np.full(d1.size, src)); heads.append(d1)
    caps.append(np.full(d1.size, src_cap)); masks.append(admit[:, d1])

    tail = np.concatenate(tails).astype(np.int32)
    head = np.concatenate(heads).astype(np.int32)
    # longest path over the edges: S first, physical nodes by key, sinks
    rank = np.empty(N + 1 + W)
    rank[:N], rank[src], rank[sink] = key, -1, key.max() + 1 + np.arange(W)
    longest = np.zeros(N + 1 + W, int)
    for e in np.argsort(rank[tail], kind="stable"):
        longest[head[e]] = max(longest[head[e]], longest[tail[e]] + 1)
    return Augmented(n_phys=N, n_sessions=W, tail=tail, head=head,
                     cap=np.concatenate(caps).astype(np.float32),
                     smask=np.concatenate(masks, 1).astype(np.float32),
                     deploy=deploy, depth=int(longest.max()) + 1)


def stack(augs: list[Augmented]) -> dict:
    """K tenants' edge lists padded to one length, as device arrays.

    Padded edges run from and to a dummy node (index N̄) with every mask
    0, so they add nothing to any sum.
    """
    n_bar, W, N = augs[0].n_bar, augs[0].n_sessions, augs[0].n_phys
    E = max(a.n_edges for a in augs)

    def pad(x, fill, axis=-1):
        width = [(0, 0)] * x.ndim
        width[axis] = (0, E - x.shape[axis])
        return np.pad(x, width, constant_values=fill)

    return {
        "tail": jnp.asarray(np.stack([pad(a.tail, n_bar) for a in augs])),
        "head": jnp.asarray(np.stack([pad(a.head, n_bar) for a in augs])),
        "cap": jnp.asarray(np.stack([pad(a.cap, 1.0) for a in augs])),
        "smask": jnp.asarray(np.stack([pad(a.smask, 0.0) for a in augs])),
        "umask": jnp.asarray(np.stack(
            [pad((a.smask.sum(0) > 0).astype(np.float32), 0.0)
             for a in augs])),
        "deploy": jnp.asarray(np.stack([a.deploy for a in augs])
                              .astype(np.float32)),
        "meta": (n_bar, W, N, max(a.depth for a in augs)),
    }


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _bf16(x):
    """x rounded to bfloat16 (to nearest, ties to even), kept in float32.

    Done on the bits: a float32 -> bfloat16 -> float32 round trip may be
    folded away by a compiler allowed excess precision (XLA on a TPU
    is), which would leave the control in float32."""
    u = jnp.uint32
    bits = jax.lax.bitcast_convert_type(x, u)
    bits = (bits + u(0x7FFF) + ((bits >> u(16)) & u(1))) & u(0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def mul(a, b, mode: str):
    """a·b elementwise: float32, or the three-pass bfloat16 product."""
    if mode == "f32":
        return a * b
    if mode == "bf16x3":
        ah, bh = _bf16(a), _bf16(b)
        al, bl = _bf16(a - ah), _bf16(b - bh)
        return ah * bh + (ah * bl + al * bh)
    raise ValueError(f"unknown product mode {mode!r}")


def _seg(x, seg, n):
    """Σ over the last axis of x [..., E] into n segments by seg [E]."""
    moved = jnp.moveaxis(x, -1, 0)
    return jnp.moveaxis(jax.ops.segment_sum(moved, seg, num_segments=n),
                        0, -1)


def _seg_max(x, seg, n):
    moved = jnp.moveaxis(x, -1, 0)
    return jnp.moveaxis(jax.ops.segment_max(moved, seg, num_segments=n),
                        0, -1)


def exp_cost(F, C, umask):
    """Σ_e D_e(F_e): the excess over D_e(0) = 1 summed first, then the
    edge count added once (a float32 sum of ~10³ terms that each carry
    the constant 1 would lose the cost's differences)."""
    z = F / C
    zs = jnp.minimum(z, EXP_CLIP)
    value = jnp.where(z <= EXP_CLIP, jnp.exp(zs), jnp.exp(zs) * (1 + z - zs))
    return jnp.sum(umask * (value - 1.0)) + jnp.sum(umask)


def exp_cost_deriv(F, C):
    return jnp.exp(jnp.minimum(F / C, EXP_CLIP)) / C


# ---------------------------------------------------------------------------
# one tenant, one interval (jit + vmap over tenants)
# ---------------------------------------------------------------------------

def _tenant_fns(meta, mode: str):
    n_bar, W, N, depth = meta
    n_seg = n_bar + 1                          # + the dummy node of padding

    def propagate(g, phi, lam):
        inject = jnp.zeros((W, n_seg)).at[:, N].set(lam)

        def relax(t, _):
            return inject + _seg(mul(t[:, g["tail"]], phi, mode), g["head"],
                                 n_seg), None

        t, _ = jax.lax.scan(relax, inject, None, length=depth)
        return t

    def flows(g, phi, t):
        return mul(t[:, g["tail"]], phi, mode).sum(0)

    def cost(g, phi, lam):
        t = propagate(g, phi, lam)
        return exp_cost(flows(g, phi, t), g["cap"], g["umask"]), t

    def omd(g, phi, lam, eta):
        """One OMD-RT step (eq. (22)) at the marginals of the current φ."""
        _, t = cost(g, phi, lam)
        dp = g["umask"] * exp_cost_deriv(flows(g, phi, t), g["cap"])
        on = phi * g["smask"]

        def back(r, _):
            return _seg(mul(on, dp[None] + r[:, g["head"]], mode),
                        g["tail"], n_seg), None

        r, _ = jax.lax.scan(back, jnp.zeros((W, n_seg)), None, length=depth)
        delta = g["smask"] * (dp[None] + r[:, g["head"]])
        logit = jnp.where(g["smask"] > 0, -eta * delta, NEG)
        logit = logit - _seg_max(logit, g["tail"], n_seg)[:, g["tail"]]
        new = phi * jnp.exp(logit) * g["smask"]
        s = _seg(new, g["tail"], n_seg)[:, g["tail"]]
        return jnp.where(s > 0, new / jnp.where(s > 0, s, 1.0), phi)

    def observe(g, phi, lam, eta):
        phi = omd(g, phi, lam, eta)
        return phi, cost(g, phi, lam)[0]

    def weights(g, phi, lam):
        t = propagate(g, phi, lam)[:, :N] * g["deploy"]
        tot = t.sum(-1, keepdims=True)
        return t / jnp.where(tot > 0, tot, 1.0)

    return observe, weights


def project(y, total, delta):
    """Euclidean projection onto {δ <= x_w <= total - δ, Σ x = total}.

    x(τ) = clip(y - τ, δ, total - δ) is piecewise linear in τ with knots
    at y - δ and y - (total - δ); the sum is evaluated at every knot and
    τ found by linear interpolation on the bracketing piece.
    """
    lo, hi = delta, total - delta
    knots = jnp.sort(jnp.concatenate([y - lo, y - hi]))
    sums = jnp.clip(y[None, :] - knots[:, None], lo, hi).sum(-1)
    # sums fall with τ; the first knot at which the sum is <= total
    j = jnp.clip(jnp.argmax(sums <= total), 1, knots.shape[0] - 1)
    t0, t1, s0, s1 = knots[j - 1], knots[j], sums[j - 1], sums[j]
    frac = jnp.where(s0 > s1, (s0 - total) / jnp.where(s0 > s1, s0 - s1, 1),
                     0.0)
    return jnp.clip(y - (t0 + frac * (t1 - t0)), lo, hi)


def perturbations(lam, delta):
    """[2W, W]: rows 2w and 2w+1 are Λ + δe_w and Λ - δe_w (the order in
    which the oracle observes them, carrying φ from one to the next)."""
    W = lam.shape[-1]
    eye = jnp.eye(W, dtype=lam.dtype)
    signs = jnp.tile(jnp.asarray([1.0, -1.0], lam.dtype), W)
    return lam[..., None, :] + delta * signs[:, None] * jnp.repeat(eye, 2, 0)


def make_interval(meta, solver: dict, mode: str = "f32"):
    """Jitted (demand step, control step) over K stacked tenants.

    ``demand(lam, old_total, new_total)`` rescales Λ onto the new demand
    and projects it; ``perturbations`` of the result are what the host
    measures.  ``step(graph, lam, phi, total, task_u)`` is one sampled
    OMAD interval and returns (Λ', φ', D(Λ', φ'), ĝ, replica weights).
    """
    delta = float(solver["delta"])
    eta_o, eta_i = float(solver["eta_outer"]), float(solver["eta_inner"])
    if solver["method"] != "single" or int(solver["inner_iters"]) != 1:
        raise ValueError("the reference implements OMAD (one oracle step)")
    observe, weights = _tenant_fns(meta, mode)
    W = meta[1]

    def demand_one(lam, old, new):
        return project(lam * (new / old), new, delta)

    def step_one(g, lam, phi, total, task_u):
        rows = perturbations(lam, delta)
        signs = jnp.tile(jnp.asarray([1.0, -1.0]), W)

        def obs(carry, inp):
            grad, phi = carry
            row, sign, w, u = inp
            phi, D = observe(g, phi, row, eta_i)
            grad = grad.at[w].add(sign * (u - D) / (2.0 * delta))
            return (grad, phi), None

        (grad, phi), _ = jax.lax.scan(
            obs, (jnp.zeros(W), phi),
            (rows, signs, jnp.repeat(jnp.arange(W), 2), task_u))
        z = eta_o * grad
        w = lam * jnp.exp(z - z.max())
        lam_new = project(total * w / w.sum(), total, delta)
        phi, D = observe(g, phi, lam_new, eta_i)
        return lam_new, phi, D, grad, weights(g, phi, lam_new)

    graph_axes = {"tail": 0, "head": 0, "cap": 0, "smask": 0, "umask": 0,
                  "deploy": 0}
    demand = jax.jit(jax.vmap(demand_one))
    step = jax.jit(jax.vmap(step_one, in_axes=(graph_axes, 0, 0, 0, 0)))
    return demand, step


def nudge(x):
    """x with every entry moved by one float32 rounding step (2^-24 of
    itself), up and down alternately: inputs that differ from x as
    rounding makes them differ."""
    sign = jnp.where(jnp.arange(x.size).reshape(x.shape) % 2 == 0, 1.0, -1.0)
    return x * (1.0 + sign * 2.0 ** -24)


def graph_leaves(stacked: dict) -> dict:
    return {k: v for k, v in stacked.items() if k != "meta"}


def gather_phi(stacked: dict, dense_phi) -> jax.Array:
    """[K, W, E] edge values of dense [K, W, N̄, N̄] routing tensors."""
    padded = jnp.pad(dense_phi, ((0, 0), (0, 0), (0, 1), (0, 1)))
    return jax.vmap(lambda p, t, h: p[:, t, h])(
        padded, stacked["tail"], stacked["head"])


def scatter_phi(stacked: dict, phi_e) -> jax.Array:
    """Dense [K, W, N̄, N̄] tensors of [K, W, E] edge values."""
    n_bar = stacked["meta"][0]

    def one(p, t, h):
        out = jnp.zeros((p.shape[0], n_bar + 1, n_bar + 1), p.dtype)
        return out.at[:, t, h].add(p)[:, :n_bar, :n_bar]

    return jax.vmap(one)(phi_e, stacked["tail"], stacked["head"])
