"""FingerState: the O(n) sufficient statistics for incremental FINGER.

Theorem 2 updates Q' from (Q, c, ΔG); eq. (3) additionally needs s_max
and (for exact Δs_max on the affected nodes) the current strength vector.
Carrying the (n,) strengths keeps the state linear in nodes and makes the
whole online loop a pure `lax.scan` over deltas.

The (n,) node dimension is a *layout* size: when the state was built
from a mask-aware graph it also carries the (n,) ``node_mask`` marking
which slots are live, so states of streams with different true node
counts share one pytree structure (and one compiled program) at a
common ``n_pad``. Every statistic is computed over active nodes only —
inactive slots have exactly zero strength.

The layout itself rides along as the static ``layout`` field (a
`repro.graphs.layout.NodeLayout`): it names the n_pad the state is
addressed in and the migration generation it was produced under, so a
delta built against a different (e.g. pre-`repad`) layout is rejected
at trace time instead of silently scattering into the wrong slots, and
checkpoints can record which layout generation they were taken under.
``layout=None`` is the legacy unmasked state.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.vnge import c_from_s_total, strength_stats
from repro.graphs.layout import NodeLayout
from repro.graphs.types import DenseGraph, EdgeList, _pytree_dataclass

Graph = Union[DenseGraph, EdgeList]


@_pytree_dataclass(static_fields=("layout",))
class FingerState:
    """Sufficient statistics of the current graph G for FINGER-H̃ updates."""

    q: jax.Array  # Lemma-1 quadratic proxy Q of G
    s_total: jax.Array  # S = trace(L) = 1/c
    s_max: jax.Array  # largest nodal strength
    strengths: jax.Array  # (n,) nodal strengths of G
    node_mask: Optional[jax.Array] = None  # (n,) 0/1; None = all active
    layout: Optional[NodeLayout] = None  # static; None = legacy unmasked

    @property
    def c(self) -> jax.Array:
        return c_from_s_total(self.s_total)

    @property
    def n_pad(self) -> int:
        """The (trailing) node-layout size of the carried strengths."""
        return int(self.strengths.shape[-1])

    def n_active(self) -> jax.Array:
        """Number of live node slots (layout size when unmasked)."""
        if self.node_mask is None:
            return jnp.asarray(self.strengths.shape[-1], jnp.int32)
        return jnp.sum(self.node_mask).astype(jnp.int32)

    def h_tilde(self) -> jax.Array:
        """H̃(G) = -Q ln(2 c s_max) from the carried statistics (eq. 2).

        An empty graph (trace L = 0) has H̃ = 0 by convention — the
        clipped log would otherwise report ≈69 nats.
        """
        arg = jnp.clip(2.0 * self.c * self.s_max, 1e-30, None)
        return jnp.where(self.s_total > 0, -self.q * jnp.log(arg), 0.0)


def finger_state(g: Graph,
                 layout: Optional[NodeLayout] = None) -> FingerState:
    """Build the state from a full graph (one O(n + m) pass).

    Mask-aware graphs stamp the state with their `NodeLayout` (pass
    ``layout=`` to carry a migration generation other than 0); legacy
    unmasked graphs keep ``layout=None``.
    """
    s_total, sum_s2, sum_w2, s_max = strength_stats(g)
    c = c_from_s_total(s_total)
    q = 1.0 - c * c * (sum_s2 + 2.0 * sum_w2)
    if layout is None and g.node_mask is not None:
        layout = g.layout
    if layout is not None and layout.n_pad != g.n_nodes:
        raise ValueError(
            f"finger_state: layout.n_pad={layout.n_pad} != graph "
            f"n_nodes={g.n_nodes}")
    return FingerState(q=q, s_total=s_total, s_max=s_max,
                       strengths=g.strengths(), node_mask=g.node_mask,
                       layout=layout)


def host_device() -> Optional[jax.Device]:
    """The host CPU device, or None (the default device) where JAX
    exposes no CPU backend."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def host_finger_state(g: Graph) -> FingerState:
    """`finger_state` computed on the host CPU device, leaves as numpy.

    For one-off host bookkeeping — admitting a tenant, seeding a slot
    map. On an accelerator each eager op of a graph of a new width
    would compile first (about a second apiece on a TPU v5e) only for
    the values to come straight back to the host.
    """
    with jax.default_device(host_device()):
        st = finger_state(jax.tree_util.tree_map(np.asarray, g))
    return jax.tree_util.tree_map(np.asarray, st)
