"""Jensen–Shannon graph distance: Algorithms 1 (Fast) and 2 (Incremental).

  JSdiv(G, G')  = H(Ḡ) - ½ [H(G) + H(G')],   Ḡ = (G ⊕ G')/2
  JSdist(G, G') = sqrt(JSdiv)                 (a valid metric)

Algorithm 1 evaluates the three entropies with FINGER-Ĥ (eq. 1);
Algorithm 2 uses FINGER-H̃ with Theorem-2 updates for the ΔG/2 and ΔG
graphs — O(Δn + Δm) per step of a stream.
"""
from __future__ import annotations

from typing import Callable, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.incremental import delta_moments, update_state
from repro.core.state import FingerState
from repro.core.vnge import exact_vnge, vnge_hat, vnge_tilde
from repro.graphs.types import DenseGraph, EdgeList, GraphDelta

Graph = Union[DenseGraph, EdgeList]

__all__ = [
    "average_graph",
    "divergence_from_increments",
    "js_divergence_from_increments",
    "js_from_increments",
    "js_distance",
    "jsdist_fast",
    "jsdist_incremental",
    "jsdist_exact",
]


def average_graph(g: Graph, g2: Graph) -> Graph:
    """Ḡ = (G ⊕ G')/2 with W̄ = (W + W')/2 on a common node set.

    For mask-aware layouts the common node set is the *union* of the two
    active sets: a node present in either endpoint graph is present in Ḡ
    (possibly with only half-weight edges). Each operand's weights are
    gated by its *own* mask before the union — weight residue in a slot
    an endpoint graph holds inactive must not reappear in Ḡ just because
    the other endpoint activates that slot (the EdgeList branch gets
    this via `masked_weights` in `to_dense`; the dense branch must
    match it).
    """
    if isinstance(g, DenseGraph) and isinstance(g2, DenseGraph):
        m1, m2 = g.node_mask, g2.node_mask
        if m1 is None and m2 is None:
            mask = None
        else:
            ones = jnp.ones((g.n_nodes,), g.weights.dtype)
            mask = jnp.maximum(ones if m1 is None else m1,
                               ones if m2 is None else m2)
        return DenseGraph(
            weights=0.5 * (g.masked_weights() + g2.masked_weights()),
            n_nodes=g.n_nodes, node_mask=mask)
    if isinstance(g, EdgeList) and isinstance(g2, EdgeList):
        # Concatenate the two halved edge lists; duplicate (i, j) slots sum
        # in every downstream strength/weight reduction, except Σ w² which
        # requires physical merging — so merge via dense only if needed.
        # For exactness we go through dense here (host graphs are moderate);
        # the streaming path uses jsdist_incremental instead.
        return average_graph(g.to_dense(), g2.to_dense())
    raise TypeError("average_graph: mismatched graph representations")


def _js_from_entropies(h_avg, h_a, h_b):
    div = h_avg - 0.5 * (h_a + h_b)
    return jnp.sqrt(jnp.maximum(div, 0.0))  # clamp eigensolver/approx noise


def _log1p(z):
    """log(1 + z), by its series where |z| < 1e-2 (multiplies and adds
    only, exact to ~1e-11 relative on any device) and `jnp.log1p`
    beyond."""
    small = jnp.abs(z) < 1e-2
    zs = jnp.where(small, z, 0.0)
    series = zs * (1.0 + zs * (-0.5 + zs * (1.0 / 3.0 + zs * (
        -0.25 + zs * 0.2))))
    return jnp.where(small, series, jnp.log1p(jnp.where(small, 0.0, z)))


def divergence_from_increments(q0, s0, m0, s_half, m_half, s_full,
                               m_full, delta_s, a1, a2, whole):
    """H̃(Ḡ) - ½[H̃(G) + H̃(G')] of Algorithm 2 from the tick's increments.

    Scalars: G's (Q, S, s_max) = (``q0``, ``s0``, ``m0``), S and s_max
    of Ḡ = G ⊕ ΔG/2 and G' = G ⊕ ΔG, and the delta's moments
    (`core.incremental.delta_moments`). H̃ = Q ℓ with ℓ = ln(S / 2
    s_max). Along G ⊕ tΔG, S_t = S + tΔS and Q_t = 1 - A_t/S_t² with
    A_t = A + tA₁ + t²A₂, so with D₂f = f(½) - (f(0) + f(1))/2 and
    δX_t = X_t - X_0

      div = D₂[Q ℓ] = Q₀ D₂[δℓ] + ℓ₀ D₂[δQ] + D₂[δQ δℓ].

    Each term is formed from the increments, never as a difference of
    whole entropies (three numbers near ln n whose float32 difference
    is rounding once the graph is large and the tick small):
    D₂[log1p(tu)] = ½ log1p((u²/4)/(1 + u)) with u = ΔS/S; the s_max
    rise y_t = (s_max,t - s_max)/s_max enters as
    D₂[log1p(y)] = ½ log1p((2y_½ - y_1 + y_½²)/(1 + y_1)); and D₂[Q]
    sums A₀/S², A₁/S² and A₂/S² times closed forms in u. Where G, Ḡ or
    G' is empty the answer is ``whole``, the three entropies
    differenced as they are. Shared by the XLA ticks and the
    ``stream_tick``/``sparse_tick`` kernels.
    """
    ok = (s0 > 0) & (m0 > 0) & (s_half > 0) & (s_full > 0)
    s0 = jnp.where(ok, s0, 1.0)
    m0_safe = jnp.where(ok, m0, 1.0)
    u = delta_s / s0
    g0 = 1.0 - q0                       # A₀ / S²
    al = a1 / (s0 * s0)
    be = a2 / (s0 * s0)
    p2 = (1.0 + 0.5 * u) * (1.0 + 0.5 * u)   # (S_½ / S)²
    r2 = (1.0 + u) * (1.0 + u)               # (S_1 / S)²
    pr = p2 * r2
    d2q = -(g0 * (-u * u * (6.0 + u * (6.0 + u)) / (8.0 * pr))
            + al * (u * (1.0 + 0.75 * u) / (2.0 * pr))
            + be * ((0.5 * u * u - 1.0) / (4.0 * pr)))
    dq_half = (g0 * u * (1.0 + 0.25 * u) - (0.5 * al + 0.25 * be)) / p2
    dq_full = (g0 * u * (2.0 + u) - (al + be)) / r2
    y_half = (m_half - m0) / m0_safe
    y_full = (m_full - m0) / m0_safe
    dl_half = _log1p(0.5 * u) - _log1p(y_half)
    dl_full = _log1p(u) - _log1p(y_full)
    d2l = 0.5 * (_log1p(0.25 * u * u / (1.0 + u))
                 - _log1p((2.0 * y_half - y_full + y_half * y_half)
                          / (1.0 + y_full)))
    l0 = jnp.log(s0 / (2.0 * m0_safe))
    div = q0 * d2l + l0 * d2q + (dq_half * dl_half - 0.5 * dq_full * dl_full)
    return jnp.where(ok, div, whole)


def js_divergence_from_increments(pre: FingerState, half: FingerState,
                                  full: FingerState, delta_s, a1, a2):
    """`divergence_from_increments` of the states of G, Ḡ and G'."""
    whole = half.h_tilde() - 0.5 * (pre.h_tilde() + full.h_tilde())
    return divergence_from_increments(
        pre.q, pre.s_total, pre.s_max, half.s_total, half.s_max,
        full.s_total, full.s_max, delta_s, a1, a2, whole)


def js_from_increments(pre, half, full, delta_s, a1, a2):
    """JSdist = sqrt(max(div, 0)) of `js_divergence_from_increments`."""
    div = js_divergence_from_increments(pre, half, full, delta_s, a1, a2)
    return jnp.sqrt(jnp.maximum(div, 0.0))


def js_distance(g: Graph, g2: Graph, entropy_fn: Callable[[Graph], jax.Array]):
    """JSdist under an arbitrary entropy functional (H, Ĥ, H̃, baselines)."""
    gbar = average_graph(g, g2)
    return _js_from_entropies(entropy_fn(gbar), entropy_fn(g), entropy_fn(g2))


def jsdist_fast(g: Graph, g2: Graph, power_iters: int = 100) -> jax.Array:
    """Algorithm 1: FINGER-JSdist (Fast), linear complexity via Ĥ."""
    return js_distance(g, g2, lambda x: vnge_hat(x, power_iters=power_iters))


def jsdist_exact(g: Graph, g2: Graph) -> jax.Array:
    """Exact JSdist via full eigendecompositions (the O(n³) reference)."""
    return js_distance(g, g2, exact_vnge)


def jsdist_tilde(g: Graph, g2: Graph) -> jax.Array:
    """JSdist with H̃ on full graphs (batch counterpart of Algorithm 2)."""
    return js_distance(g, g2, vnge_tilde)


def jsdist_incremental(
    state: FingerState,
    delta: GraphDelta,
    exact_smax: bool = False,
    method: str = "dense",
) -> Tuple[jax.Array, FingerState]:
    """Algorithm 2: FINGER-JSdist (Incremental).

    Given state(G) and ΔG, returns (JSdist(G, G ⊕ ΔG), state(G ⊕ ΔG)).
    Uses two Theorem-2 updates (ΔG/2 and ΔG) — O(Δn + Δm) total — and
    scores them from the tick's increments
    (`js_divergence_from_increments`). ``method`` selects the
    Δ-statistics path (see `core.incremental`).

    Node joins/leaves in ΔG follow the union-node-set semantics of the
    JS divergence: `GraphDelta.scaled(0.5)` keeps joins but drops leaves
    for the Ḡ update (a leaving node is still in Ḡ with its half-weight
    edges), while the full ΔG update applies both.
    """
    half_state = update_state(state, delta.scaled(0.5),
                              exact_smax=exact_smax, method=method)
    full_state = update_state(state, delta, exact_smax=exact_smax,
                              method=method)
    dist = js_from_increments(state, half_state, full_state,
                              *delta_moments(state, delta, method))
    return dist, full_state


def jsdist_stream(
    init_state: FingerState,
    deltas: GraphDelta,
    exact_smax: bool = False,
    method: str = "dense",
) -> Tuple[jax.Array, FingerState]:
    """Scan Algorithm 2 over a batched stream of T deltas (leading axis).

    Lowers the whole online loop to a single XLA while-scan — the
    TPU-idiomatic form of the paper's streaming setting. Returns the (T,)
    distances and the final state.
    """

    def step(state, delta):
        dist, new_state = jsdist_incremental(state, delta,
                                             exact_smax=exact_smax,
                                             method=method)
        return new_state, dist

    final_state, dists = jax.lax.scan(step, init_state, deltas)
    return dists, final_state
