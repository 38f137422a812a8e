"""Public op: Lemma-1 Q from dense W via the fused Pallas reduction.

Pads W up to the block grid, dispatches to the kernel on TPU and to
interpret mode elsewhere (CPU CI), and exposes a drop-in `quadratic_q`
replacement for `DenseGraph` hot paths (Hi-C maps).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.vnge_q.kernel import vnge_q_stats_pallas
from repro.kernels.vnge_q.ref import q_from_stats, vnge_q_stats_ref


def _pad_to_blocks(w: jax.Array, bm: int, bn: int) -> jax.Array:
    n = w.shape[0]
    b = max(bm, bn)
    n_pad = ((n + b - 1) // b) * b
    if n_pad == n:
        return w
    return jnp.pad(w, ((0, n_pad - n), (0, n_pad - n)))


def _apply_node_mask(w: jax.Array, node_mask) -> jax.Array:
    """Zero inactive rows/columns — the mask-aware layout's contract that
    padded node slots contribute exactly nothing to any statistic."""
    if node_mask is None:
        return w
    m = node_mask.astype(w.dtype)
    return w * m[:, None] * m[None, :]


def vnge_q_stats(w: jax.Array, bm: int = 128, bn: int = 128,
                 use_pallas: bool = True,
                 node_mask=None) -> jax.Array:
    """(n, n) W → (4,) [S, Σs², Σ_E w², s_max]. Zero-padding is exact for
    every statistic (padded rows have zero strength; s_max over a
    nonnegative graph is unaffected). ``node_mask`` zeroes inactive
    rows/columns first — the lane padding and the mask-aware node layout
    are the same mechanism."""
    w = _apply_node_mask(w, node_mask)
    if not use_pallas:
        return vnge_q_stats_ref(w)
    wp = _pad_to_blocks(w.astype(jnp.float32), bm, bn)
    return vnge_q_stats_pallas(wp, bm=bm, bn=bn,
                               interpret=dispatch.default_interpret())


def quadratic_q_dense(w: jax.Array, use_pallas: bool = True,
                      node_mask=None) -> jax.Array:
    """Lemma-1 Q of a dense graph in one fused HBM pass."""
    return q_from_stats(vnge_q_stats(w, use_pallas=use_pallas,
                                     node_mask=node_mask))


def vnge_tilde_dense(w: jax.Array, use_pallas: bool = True,
                     node_mask=None) -> jax.Array:
    """FINGER-H̃ (eq. 2) of a dense graph in one fused HBM pass."""
    from repro.core.vnge import _lemma1_cq

    stats = vnge_q_stats(w, use_pallas=use_pallas, node_mask=node_mask)
    s_total, s_max = stats[0], stats[3]
    c, q = _lemma1_cq(s_total, stats[1], stats[2])
    h = -q * jnp.log(jnp.clip(2.0 * c * s_max, 1e-30, None))
    return jnp.where(s_total > 0, h, 0.0)  # empty graph: H̃ = 0
