"""Public op: fused compact Theorem-2 delta statistics.

`prepare_sorted_delta` lowers a GraphDelta + carried strengths to the
sorted-endpoint form (argsort + O(Δn) gather, pure XLA, jit-able);
`delta_stats_fused` dispatches the fused reduction to the Pallas kernel
on TPU and to interpret mode elsewhere (CPU CI), returning the same
(ΔS, ΔQ, max_{ΔV} s'_i) triple as `core.incremental.delta_stats_compact`.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.incremental import (
    gate_delta_for_update,
    sorted_delta_endpoints,
)
from repro.core.state import FingerState
from repro.graphs.types import GraphDelta
from repro.kernels import dispatch
from repro.kernels.delta_stats.kernel import delta_stats_sorted_pallas
from repro.kernels.delta_stats.ref import delta_stats_sorted_ref

_LANE = dispatch.LANE
# The fused kernel builds (2k, 2k) segment-indicator temporaries in VMEM
# (~3 × (2k)² × 4 B, 12 MB at 1024 endpoints); past this endpoint count
# they would outgrow the scoped VMEM a Mosaic kernel gets by default
# (16 MiB on v5e), so larger deltas take the XLA ref path instead.
_MAX_FUSED_ENDPOINTS = 1024


def _pad_edges(x: jax.Array, k_pad: int, value=0) -> jax.Array:
    k = x.shape[0]
    if k == k_pad:
        return x
    return jnp.pad(x, (0, k_pad - k), constant_values=value)


def prepare_sorted_delta(strengths: jax.Array, delta: GraphDelta):
    """GraphDelta → sorted-endpoint arrays, lane-aligned for the kernel.

    Pads the delta's edge axis to the lane multiple, then defers to the
    shared `core.incremental.sorted_delta_endpoints` preparation (masked
    slots map to the sentinel node id n and sort to the end).
    """
    k = delta.senders.shape[0]
    k_pad = ((k + _LANE - 1) // _LANE) * _LANE
    # Node join/leave slots are dropped: they carry no edge statistics,
    # and callers gate the edge mask by the post-join node mask first.
    padded = GraphDelta(
        senders=_pad_edges(delta.senders, k_pad),
        receivers=_pad_edges(delta.receivers, k_pad),
        dw=_pad_edges(delta.dw, k_pad),
        w_old=_pad_edges(delta.w_old, k_pad),
        mask=_pad_edges(delta.mask, k_pad),
        n_nodes=delta.n_nodes,
    )
    prep = sorted_delta_endpoints(strengths, padded)
    return (*prep, padded.dw * padded.mask, padded.w_old, padded.mask)


def delta_stats_fused(
    state: FingerState,
    delta: GraphDelta,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
    pre_gated: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(ΔS, ΔQ, max_{ΔV}(s_i + Δs_i)) via the fused one-pass kernel.

    Mask-aware: delta edges touching nodes inactive under the state's
    post-join node mask are gated to zero before the reduction, so
    padded node slots contribute exactly nothing (same gating as
    `core.incremental.update_state`). ``pre_gated=True`` skips that step
    for callers that already hold the gated delta (the
    ``method="fused_tick"`` branch of `update_state`; the gate is
    idempotent, so skipping only saves the duplicate work).
    """
    if not pre_gated:
        delta, _ = gate_delta_for_update(state.node_mask, delta)
    prep = prepare_sorted_delta(state.strengths, delta)
    if not use_pallas or prep[0].shape[0] > _MAX_FUSED_ENDPOINTS:
        stats = delta_stats_sorted_ref(*prep)
    else:
        interpret = dispatch.default_interpret(interpret)
        stats = delta_stats_sorted_pallas(
            *(x.reshape(1, -1) for x in prep), interpret=interpret)[0]
    return stats[0], stats[1], stats[2]
