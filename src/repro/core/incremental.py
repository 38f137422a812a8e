"""Theorem 2: O(Δn + Δm) incremental update of the FINGER statistics.

Given the state of G and a delta ΔG (edge-weight changes carrying their
pre-change weights ``w_old``), computes the state of G' = G ⊕ ΔG:

  ΔS  = Σ_{i∈ΔV} Δs_i = 2 Σ_{ΔE} Δw_ij
  Δc  = -c² ΔS / (1 + c ΔS)
  ΔQ  = 2 Σ_{ΔV} s_i Δs_i + Σ_{ΔV} Δs_i² + 4 Σ_{ΔE} w_ij Δw_ij
        + 2 Σ_{ΔE} Δw_ij²
  Q'  = (Q - 1)/(1 + c ΔS)² - (c/(1 + c ΔS))² ΔQ + 1

and eq. (3): H̃(G ⊕ ΔG) = -Q' ln[2 (c + Δc)(s_max + Δs_max)], with
Δs_max = max(0, max_{i∈ΔV}(s_i + Δs_i) - s_max).

Beyond-paper edge handling (the paper assumes S, S' > 0): the c/(1+cΔS)
factor is computed as c' = 1/(S + ΔS) directly, which is identical for
S > 0 but stays exact when a delta *revives* an empty graph (c = 0); and
when a delta *empties* the graph (S' numerically ≈ 0 after float
cancellation) the state snaps to the canonical empty state (Q = 1,
S = s_max = 0, strengths = 0) instead of dividing by the ≈0 denominator
— without this, deleting every edge poisons Q with nan/±1e6 residue for
the rest of the stream.

Complexity notes. The edge sums are O(Δm). Δs_i on the affected node set
ΔV is a segment reduction over the 2Δm delta endpoints; we expose two
paths (``method=`` on every update entry point):

- ``compact``  — true O(Δn + Δm) work (modulo the O(Δm log Δm) endpoint
  sort): sort the 2Δm delta endpoints, segment-sum Δs per touched node,
  gather the O(Δn) affected strengths, and reduce ΔQ's node term and
  Δs_max over the segments — the (n,) strength vector is only touched by
  an O(Δm) scatter when carrying the state forward. This is the
  production streaming path; `repro.kernels.delta_stats` provides the
  fused single-pass Pallas TPU kernel for it (sharing
  `sorted_delta_endpoints` / `delta_stats_from_sorted` below).
- ``dense``    — scatter-add into a dense (n,) Δs vector; O(n) per step
  but branch-free and fastest under jit for the moderate n of the
  paper's pipelines.
- ``fused_tick`` — the compact statistics through the fused Pallas
  reduction (`repro.kernels.delta_stats`) on this per-stream entry
  point; the batched serving engines additionally fuse the *entire*
  tick — gating, node slots, statistics, state update, JSdist — into
  one kernel launch per tick under this method
  (`repro.kernels.stream_tick`).

All paths produce identical statistics (tested to 1e-5 over randomized
add/delete/re-weight streams, including deletions at the argmax node).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.state import FingerState
from repro.core.vnge import c_from_s_total
from repro.graphs.types import (
    GraphDelta,
    gate_delta_by_nodes,
    node_mask_after_joins,
    node_mask_after_leaves,
)

__all__ = [
    "delta_moments",
    "delta_stats",
    "delta_stats_compact",
    "delta_stats_from_sorted",
    "gate_delta_for_update",
    "sorted_delta_endpoints",
    "update_state",
    "h_tilde_after",
]

# A post-delta total strength below this fraction of the delta's own
# moved mass (2 Σ|Δw|) is float-cancellation residue of a
# delete-everything delta, not a real graph: f32 summation error is
# ~eps·Σ|Δw| (eps ≈ 1.2e-7), so 1e-6 gives ~8× headroom while a graph
# legitimately shrunk to any weight ≳ 1e-6 of the deleted mass survives.
_EMPTY_RESIDUE_TOL = 1e-6


def delta_stats(state: FingerState, delta: GraphDelta):
    """(ΔS, ΔQ, Δs dense vector, max_{ΔV}(s_i + Δs_i)) for Theorem 2."""
    m = delta.mask
    dw = delta.dw * m

    # Δs_i for all nodes (zero off ΔV). O(n) scatter; see module docstring.
    ds = state.strengths * 0.0
    ds = ds.at[delta.senders].add(dw, mode="drop")
    ds = ds.at[delta.receivers].add(dw, mode="drop")

    delta_s_total = 2.0 * jnp.sum(dw)

    s = state.strengths
    # Node terms of ΔQ: Δs is zero off ΔV, so summing over all i is exact.
    node_term = jnp.sum(2.0 * s * ds + ds * ds)
    # Edge terms of ΔQ over ΔE only (masked).
    edge_term = jnp.sum((4.0 * delta.w_old * dw + 2.0 * dw * dw) * m)
    delta_q_term = node_term + edge_term

    # max over ΔV of the *new* strength; -inf off ΔV so padding never wins.
    touched = jnp.zeros_like(s).at[delta.senders].max(m, mode="drop")
    touched = touched.at[delta.receivers].max(m, mode="drop")
    new_s_on_dv = jnp.where(touched > 0, s + ds, -jnp.inf)
    max_new_s = jnp.max(new_s_on_dv)

    return delta_s_total, delta_q_term, ds, max_new_s


def sorted_delta_endpoints(strengths: jax.Array, delta: GraphDelta):
    """GraphDelta → sorted-endpoint arrays for the compact reduction.

    Concatenates the 2Δm edge endpoints, maps masked slots to the
    sentinel node id n (sorts last), argsorts, and gathers the O(Δn)
    touched strengths (zeroed on sentinel slots). Shared by
    `delta_stats_compact` and the `kernels.delta_stats` fused kernel.
    """
    n = strengths.shape[0]
    m = delta.mask
    dw = delta.dw * m
    valid = m > 0

    nodes = jnp.concatenate([delta.senders, delta.receivers]).astype(jnp.int32)
    nodes = jnp.where(jnp.concatenate([valid, valid]), nodes, n)
    vals = jnp.concatenate([dw, dw])

    order = jnp.argsort(nodes)
    sorted_nodes = nodes[order]
    sorted_vals = vals[order]
    in_graph = sorted_nodes < n
    sorted_strengths = jnp.where(
        in_graph, strengths[jnp.minimum(sorted_nodes, n - 1)], 0.0)
    return sorted_nodes, sorted_vals, sorted_strengths, \
        in_graph.astype(jnp.float32)


def _segment_ds(sorted_nodes, sorted_vals, endpoint_valid):
    """(head, Δs): per sorted endpoint, whether it opens its node's
    segment, and the Δs of that node (its segment's sum)."""
    two_k = sorted_nodes.shape[0]
    head = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_nodes[1:] != sorted_nodes[:-1]])
    head = jnp.logical_and(head, endpoint_valid > 0)
    seg_id = jnp.cumsum(head) - 1
    seg_ds = jax.ops.segment_sum(sorted_vals, seg_id, num_segments=two_k)
    # Δs of the segment each endpoint belongs to, broadcast back per slot.
    return head, seg_ds[seg_id]


def delta_stats_from_sorted(
    sorted_nodes: jax.Array,      # (2k,) int32, ascending, sentinel last
    sorted_vals: jax.Array,       # (2k,) f32 masked Δw per endpoint
    sorted_strengths: jax.Array,  # (2k,) f32 s_i gathered at sorted_nodes
    endpoint_valid: jax.Array,    # (2k,) f32 0/1 (0 on sentinel slots)
    dw: jax.Array,                # (k,) f32 Δw per edge
    w_old: jax.Array,             # (k,) f32 pre-change weights
    mask: jax.Array,              # (k,) f32 0/1 edge validity
) -> jax.Array:
    """Sorted-endpoint segment reduction → (4,) [ΔS, ΔQ, max s', |ΔV|].

    The single jnp home of the compact reduction; the Pallas kernel in
    `kernels.delta_stats` must match it up to float accumulation order.
    The max is -inf for an all-masked delta (dense-path convention).
    """
    head, ds_here = _segment_ds(sorted_nodes, sorted_vals, endpoint_valid)
    node_term = jnp.sum(jnp.where(
        head,
        2.0 * sorted_strengths * ds_here + ds_here * ds_here,
        0.0))
    dwm = dw * mask
    edge_term = jnp.sum(4.0 * w_old * dwm + 2.0 * dwm * dwm)
    delta_s = 2.0 * jnp.sum(dwm)
    max_new = jnp.max(jnp.where(head, sorted_strengths + ds_here, -jnp.inf))
    n_touched = jnp.sum(head.astype(jnp.float32))
    return jnp.stack([delta_s, node_term + edge_term, max_new, n_touched])


def delta_stats_compact(state: FingerState, delta: GraphDelta):
    """(ΔS, ΔQ, max_{ΔV}(s_i + Δs_i)) without materializing a dense Δs.

    Sorted-endpoint segment sum over the 2Δm delta endpoints — work is
    O(Δm log Δm) for the sort plus O(Δn + Δm) for everything else,
    independent of n.
    """
    prep = sorted_delta_endpoints(state.strengths, delta)
    stats = delta_stats_from_sorted(*prep, delta.dw, delta.w_old,
                                    delta.mask)
    return stats[0], stats[1], stats[2]


def delta_moments(state: FingerState, delta: GraphDelta,
                  method: str = "dense"):
    """(ΔS, A₁, A₂): the moments that carry G along G ⊕ tΔG.

    With A = Σ s_i² + 2 Σ_E w² (Lemma 1: Q = 1 - A/S²), the graph
    G ⊕ tΔG has S_t = S + tΔS and A_t = A + tA₁ + t²A₂, where

      A₁ = 2 Σ_{ΔV} s_i Δs_i + 4 Σ_{ΔE} w_ij Δw_ij
      A₂ = Σ_{ΔV} Δs_i² + 2 Σ_{ΔE} Δw_ij²

    (Theorem 2's ΔQ term is A₁ + A₂). The delta is gated as
    `update_state` gates it; ``method`` picks the dense scatter or the
    compact sorted-endpoint reduction (``fused_tick`` takes the latter).
    """
    delta, _ = gate_delta_for_update(state.node_mask, delta)
    m = delta.mask
    dw = delta.dw * m
    if method == "dense":
        ds = jnp.zeros_like(state.strengths)
        ds = ds.at[delta.senders].add(dw, mode="drop")
        ds = ds.at[delta.receivers].add(dw, mode="drop")
        lin = jnp.sum(2.0 * state.strengths * ds)
        quad = jnp.sum(ds * ds)
    else:
        nodes, vals, strengths, valid = sorted_delta_endpoints(
            state.strengths, delta)
        head, ds = _segment_ds(nodes, vals, valid)
        lin = jnp.sum(jnp.where(head, 2.0 * strengths * ds, 0.0))
        quad = jnp.sum(jnp.where(head, ds * ds, 0.0))
    a1 = lin + jnp.sum(4.0 * delta.w_old * dw)
    a2 = quad + jnp.sum(2.0 * dw * dw)
    return 2.0 * jnp.sum(dw), a1, a2


def _apply_delta_strengths(strengths: jax.Array,
                           delta: GraphDelta) -> jax.Array:
    """strengths + Δs via an O(Δm) endpoint scatter (no dense Δs temp)."""
    dwm = delta.dw * delta.mask
    out = strengths.at[delta.senders].add(dwm, mode="drop")
    return out.at[delta.receivers].add(dwm, mode="drop")


def gate_delta_for_update(state_node_mask, delta: GraphDelta):
    """Resolve the node dimension of one Theorem-2 step.

    Returns ``(gated_delta, mask_after_joins)``: joins from the delta's
    node slots are applied to the state's node mask first (a joining
    node's first edges ride in the same delta), then edge slots touching
    any node inactive under that post-join mask are gated to zero — a
    padded slot can never contribute to ΔS/ΔQ/Δs_max. ``mask`` is None
    (and the delta untouched) in the legacy unmasked, slot-free case.
    Shared by `update_state` and the fused `kernels.delta_stats` op.
    """
    mask = state_node_mask
    if mask is None and delta.node_ids is None:
        return delta, None
    if mask is None:
        # Materializing a mask here would flip the FingerState pytree
        # structure (node_mask None -> array) mid-update, which blows up
        # a lax.scan carry with an opaque structure error — fail with a
        # named cause instead.
        raise ValueError(
            "node join/leave delta applied to a state without a "
            "node_mask; build the state from a mask-aware graph "
            "(g.pad_to(n) / DenseGraph.from_weights(..., n_pad=...) / "
            "StreamEngine.init_states) so the mask is part of the "
            "carried state")
    if delta.node_ids is not None:
        mask = node_mask_after_joins(mask, delta)
    return gate_delta_by_nodes(delta, mask), mask


def update_state(
    state: FingerState,
    delta: GraphDelta,
    exact_smax: bool = False,
    method: str = "dense",
) -> FingerState:
    """Theorem 2 update: state(G) ⊕ ΔG → state(G').

    ``exact_smax=False`` follows the paper's eq. (3) update, which never
    decreases s_max (deletions at the argmax node are upper-bounded).
    ``exact_smax=True`` recomputes max over the carried strength vector —
    an O(n) beyond-paper fix that keeps H̃ exact under deletions.

    ``method`` selects the Δ-statistics path: ``"dense"`` (O(n) scatter),
    ``"compact"`` (sorted-endpoint segment sum, O(Δn + Δm)), or
    ``"fused_tick"`` — the compact statistics through the fused
    `repro.kernels.delta_stats` Pallas reduction (interpret mode off
    TPU). All three produce identical statistics; the batched serving
    engines additionally fuse the *whole* tick into one kernel under
    ``"fused_tick"`` (`repro.kernels.stream_tick`).

    Mask-aware layout: when the state carries a ``node_mask``, joins
    from the delta's node slots activate before the edge changes, edge
    slots touching inactive nodes are gated to exactly zero, and leaves
    deactivate after them (zeroing any float residue in the left nodes'
    strength slots). A node-slot delta against a mask-less state raises
    (the mask must be part of the scan carry from the start). See
    `graphs.types` for the join/leave ordering and the isolated-leave
    contract.

    When the state carries a `NodeLayout`, a delta addressed in a
    *larger* layout is rejected at trace time: its node ids can point
    past this state's n_pad, and the ``mode="drop"`` scatters would
    silently ignore them — the exact failure mode `FingerService.repad`
    exists to migrate through.
    """
    if state.layout is not None and delta.n_nodes > state.layout.n_pad:
        raise ValueError(
            f"update_state: delta is addressed in an n_pad="
            f"{delta.n_nodes} layout but the state's layout is n_pad="
            f"{state.layout.n_pad} (generation "
            f"{state.layout.generation}); migrate the state first "
            "(FingerService.repad / serving.migrate.grow_stacked)")
    delta, mask_joined = gate_delta_for_update(state.node_mask, delta)
    if method == "dense":
        delta_s_total, delta_q_term, ds, max_new_s = delta_stats(state, delta)
        strengths_new = state.strengths + ds
    elif method == "compact":
        delta_s_total, delta_q_term, max_new_s = \
            delta_stats_compact(state, delta)
        strengths_new = _apply_delta_strengths(state.strengths, delta)
    elif method == "fused_tick":
        # Single-stream spelling of the fused path: the one-pass Pallas
        # delta-statistics kernel + the O(Δm) scatter carry-forward.
        # Imported lazily (kernels import this module at load time).
        from repro.kernels.delta_stats.ops import delta_stats_fused

        delta_s_total, delta_q_term, max_new_s = delta_stats_fused(
            state, delta, pre_gated=True)
        strengths_new = _apply_delta_strengths(state.strengths, delta)
    else:
        raise ValueError(f"unknown delta-stats method {method!r}")

    s_total_raw = state.s_total + delta_s_total
    # Deleting (numerically) all edges leaves cancellation residue that
    # must not reach 1/S'; snap to the canonical empty state instead.
    abs_moved = 2.0 * jnp.sum(jnp.abs(delta.dw) * delta.mask)
    empty = s_total_raw <= _EMPTY_RESIDUE_TOL * abs_moved

    c = state.c
    denom = 1.0 + c * delta_s_total
    denom = jnp.where(jnp.abs(denom) > 1e-30, denom, 1e-30)
    # c' = 1/(S + ΔS): equals c/denom for S > 0 and stays exact when the
    # delta revives an empty graph (c = 0 but S' = ΔS > 0).
    c_new = c_from_s_total(s_total_raw)
    q_new = (state.q - 1.0) / (denom * denom) \
        - c_new * c_new * delta_q_term + 1.0
    q_new = jnp.where(empty, 1.0, q_new)  # Q of the empty graph (Lemma 1)

    strengths_new = jnp.where(empty, 0.0, strengths_new)
    mask_new = mask_joined
    if mask_new is not None:
        if delta.node_ids is not None:
            mask_new = node_mask_after_leaves(mask_new, delta)
        # Inactive slots hold exactly zero strength (kills leave residue).
        strengths_new = strengths_new * mask_new
    if exact_smax:
        s_max_new = jnp.max(strengths_new)
    else:
        d_s_max = jnp.maximum(0.0, max_new_s - state.s_max)
        s_max_new = jnp.where(empty, 0.0, state.s_max + d_s_max)

    return FingerState(
        q=q_new,
        s_total=jnp.where(empty, 0.0, s_total_raw),
        s_max=s_max_new,
        strengths=strengths_new,
        node_mask=mask_new,
        layout=state.layout,
    )


def h_tilde_after(
    state: FingerState, delta: GraphDelta, exact_smax: bool = False,
    method: str = "dense",
) -> Tuple[jax.Array, FingerState]:
    """eq. (3): H̃(G ⊕ ΔG) and the updated state, in O(Δn + Δm)."""
    new_state = update_state(state, delta, exact_smax=exact_smax,
                             method=method)
    return new_state.h_tilde(), new_state
