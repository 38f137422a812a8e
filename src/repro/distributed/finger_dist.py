"""Distributed FINGER: edge-sharded Q / s_max / power iteration.

The paper's O(n + m) algorithms are reductions over nodes and edges, so
they distribute trivially: shard the edge list over the "data" mesh axis,
compute local partial sums, and `psum`/`pmax` — O(m/p + n) per device
plus one small all-reduce. The power-iteration matvec shards the same
way: each device owns an edge shard, scatter-adds its partial W·x, and a
psum completes the product (x is replicated — the standard 1D SpMV
decomposition for billion-edge graphs on a pod).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.state import FingerState
from repro.core.vnge import c_from_s_total
from repro.graphs.types import EdgeList


def _partials(senders, receivers, weights, mask, n):
    w = weights * mask
    s = jnp.zeros((n,), weights.dtype)
    s = s.at[senders].add(w, mode="drop")
    s = s.at[receivers].add(w, mode="drop")
    return s, jnp.sum(w * w)


def distributed_finger_state(g: EdgeList, mesh: Mesh,
                             axis: str = "data") -> FingerState:
    """FingerState of an edge-sharded graph (one pass + one all-reduce).

    The padded edge arrays are sharded along the edge axis over `axis`;
    node-indexed inputs/outputs (the optional node mask, the strengths)
    are replicated. Edges touching a masked-inactive node slot are gated
    to zero, matching the single-device mask-aware layout.
    """
    n = g.n_nodes

    def local(senders, receivers, weights, mask, node_mask):
        if node_mask is not None:
            mask = mask * node_mask[senders] * node_mask[receivers]
        s_part, w2_part = _partials(senders, receivers, weights, mask, n)
        s = jax.lax.psum(s_part, axis)  # (n,) full strengths
        if node_mask is not None:
            s = s * node_mask
        sum_w2 = jax.lax.psum(w2_part, axis)
        s_total = jnp.sum(s)
        c = c_from_s_total(s_total)
        q = 1.0 - c * c * (jnp.sum(s * s) + 2.0 * sum_w2)
        return q, s_total, jnp.max(s), s

    shard = P(axis)
    # P() for the node-mask slot is correct whether it is an (n,)
    # replicated array or None (an empty pytree matches any leaf spec).
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(shard, shard, shard, shard, P()),
        out_specs=(P(), P(), P(), P()),
    )
    q, s_total, s_max, strengths = fn(g.senders, g.receivers, g.weights,
                                      g.mask, g.node_mask)
    return FingerState(q=q, s_total=s_total, s_max=s_max,
                       strengths=strengths, node_mask=g.node_mask,
                       layout=g.layout if g.node_mask is not None
                       else None)


def distributed_power_iteration(
    g: EdgeList, mesh: Mesh, axis: str = "data",
    num_iters: int = 100, tol: float = 1e-7, seed: int = 0,
) -> jax.Array:
    """λ_max of L_N with the edge list sharded over `axis`."""
    n = g.n_nodes

    def run(senders, receivers, weights, mask):
        w = weights * mask
        s_part = jnp.zeros((n,), weights.dtype)
        s_part = s_part.at[senders].add(w, mode="drop")
        s_part = s_part.at[receivers].add(w, mode="drop")
        s = jax.lax.psum(s_part, axis)
        s_total = jnp.sum(s)
        c = c_from_s_total(s_total)

        def ln_mv(x):
            wx_part = jnp.zeros_like(x)
            wx_part = wx_part.at[senders].add(w * x[receivers], mode="drop")
            wx_part = wx_part.at[receivers].add(w * x[senders], mode="drop")
            wx = jax.lax.psum(wx_part, axis)
            return c * (s * x - wx)

        x0 = jax.random.normal(jax.random.PRNGKey(seed), (n,), jnp.float32)
        x0 = x0 / jnp.linalg.norm(x0)

        def cond(carry):
            i, _, lam, lam_prev = carry
            rel = jnp.abs(lam - lam_prev) / jnp.maximum(jnp.abs(lam), 1e-30)
            return jnp.logical_and(i < num_iters, rel > tol)

        def body(carry):
            i, x, lam, _ = carry
            y = ln_mv(x)
            norm = jnp.linalg.norm(y)
            x_new = jnp.where(norm > 0, y / jnp.maximum(norm, 1e-30), x)
            lam_new = jnp.dot(x_new, ln_mv(x_new))
            return i + 1, x_new, lam_new, lam

        lam0 = jnp.dot(x0, ln_mv(x0))
        _, _, lam, _ = jax.lax.while_loop(cond, body,
                                          (0, x0, lam0, lam0 + 1.0))
        return jnp.maximum(lam, 0.0)

    shard = P(axis)
    fn = jax.shard_map(run, mesh=mesh,
                       in_specs=(shard, shard, shard, shard),
                       out_specs=P(), check_vma=False)
    return fn(g.senders, g.receivers, g.weights, g.mask)


def shard_edge_list(g: EdgeList, mesh: Mesh, axis: str = "data") -> EdgeList:
    """Pad the edge arrays to the axis size and device_put them sharded."""
    size = mesh.shape[axis]
    m_pad = ((g.m_pad + size - 1) // size) * size
    pad = m_pad - g.m_pad

    def padded(x):
        return jnp.pad(x, (0, pad))

    sharding = NamedSharding(mesh, P(axis))
    return EdgeList(
        senders=jax.device_put(padded(g.senders), sharding),
        receivers=jax.device_put(padded(g.receivers), sharding),
        weights=jax.device_put(padded(g.weights), sharding),
        mask=jax.device_put(padded(g.mask), sharding),
        n_nodes=g.n_nodes,
    )
