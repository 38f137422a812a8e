"""Public op: the fused sparse serving tick (``method="sparse_tick"``).

`sparse_tick_fused` is the batched slot-space counterpart of
`stream_tick.stream_tick_fused`: one Pallas launch gridded over the B
stream slots, every temporary sized by the `SparseLayout` capacities
(n_slots, m_pad) and never by the virtual n_pad. Dispatch policy:

- Pallas on TPU, interpret mode elsewhere (CPU CI) — the shared
  `kernels.dispatch` contract;
- the VMEM size guard routes oversized (k_pad, n_slots, m_pad) tiles
  to the vmapped XLA oracle (`ref.sparse_tick_ref`);
- slot-space preconditions are checked by name at trace time: a delta
  without ``edge_slots`` (untranslated) or addressed in a different
  slot capacity is rejected instead of silently mis-scattering;
- numerics match the vmapped oracle — and through it the dense
  `stream_tick` path — to 1e-5 (see `tests/test_sparse_tick.py`).

Preparation is pure elementwise XLA: lane-align the edge/slot/store
axes, tile the per-edge payloads onto the 2k endpoint slots, and pad
the edge-slot lanes with the `EDGE_SLOT_SENTINEL` (matches no store
column in the kernel's scatter one-hot).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparse import EDGE_SLOT_SENTINEL, SparseStreamState
from repro.graphs.types import GraphDelta
from repro.kernels import dispatch
from repro.kernels.dispatch import as_rows, pack_scalar_slab
from repro.kernels.dispatch import ceil_to as _ceil_to
from repro.kernels.dispatch import pad_last as _pad_last
from repro.kernels.sparse_tick.kernel import (
    MAX_ENDPOINTS,
    sparse_tick_pallas,
    sparse_tick_pallas_stacked,
)
from repro.kernels.sparse_tick.ref import sparse_tick_ref

_LANE = dispatch.LANE
_SUBLANE = dispatch.SUBLANE


def sparse_tick_vmem_bytes(n_slots: int, m_pad: int, k_pad: int,
                           j_pad: Optional[int]) -> int:
    """Estimated VMEM footprint of one sparse-tick grid step."""
    two_k = 2 * _ceil_to(k_pad, _LANE)
    n = _ceil_to(n_slots, _LANE)
    m = _ceil_to(m_pad, _LANE)
    j = _ceil_to(j_pad or 1, _SUBLANE)
    # 4 x (2k, 2k) indicators + (2k, n) one-hot + 2 x (j, n) indicators
    # + 2 x (k, m) store one-hot/iota + the O(2k) / O(n) / O(m) vectors.
    return 4 * (4 * two_k * two_k + two_k * n + 2 * j * n
                + 2 * (two_k // 2) * m + 10 * two_k + 8 * n + 8 * m)


def fits_sparse_tick(n_slots: int, m_pad: int, k_pad: int,
                     j_pad: Optional[int]) -> bool:
    """Whether a (k_pad, n_slots, m_pad, j_pad) tile fits the fused
    kernel under the active `dispatch.vmem_budget_bytes()` budget; the
    caller falls back to the vmapped XLA tick otherwise."""
    if 2 * _ceil_to(k_pad, _LANE) > MAX_ENDPOINTS:
        return False
    return sparse_tick_vmem_bytes(n_slots, m_pad, k_pad, j_pad) \
        <= dispatch.vmem_budget_bytes()


def sparse_tick_stacked_bytes(s: int, b: int, n_slots: int, m_pad: int,
                              k_pad: int, j_pad: Optional[int]) -> int:
    """Total device-resident operand bytes (inputs + outputs) of one
    shard-stacked sparse launch over S shards of B streams each."""
    two_k = 2 * _ceil_to(k_pad, _LANE)
    n = _ceil_to(n_slots, _LANE)
    m = _ceil_to(m_pad, _LANE)
    j = _ceil_to(j_pad or 1, _SUBLANE)
    # state+delta+outputs per stream row, incl. the (m,) edge store
    per_row = 4 * (4 + 2 * n + 2 * m + 5 * two_k + two_k // 2 + 2 * j)
    return s * b * per_row


def fits_sparse_tick_stacked(s: int, b: int, n_slots: int, m_pad: int,
                             k_pad: int,
                             j_pad: Optional[int]) -> bool:
    """Stacked-launch admission: per-grid-step tile fits VMEM (stacking
    leaves each step's footprint unchanged) AND the S-stacked operand
    set fits `dispatch.stacked_budget_bytes()`. Callers route a failing
    group to sequential per-shard launches."""
    return fits_sparse_tick(n_slots, m_pad, k_pad, j_pad) \
        and dispatch.stacked_residency_bytes_ok(
            sparse_tick_stacked_bytes(s, b, n_slots, m_pad, k_pad,
                                      j_pad))


def _check_slot_space(states: SparseStreamState,
                      deltas: GraphDelta) -> None:
    if deltas.edge_slots is None:
        raise ValueError(
            "sparse_tick_fused: delta carries no edge_slots — sparse "
            "ticks need slot-space deltas; translate virtual deltas "
            "through each stream's SlotMap first (FingerService does "
            "this at ingest)")
    if deltas.n_nodes != states.layout.n_slots:
        raise ValueError(
            f"sparse_tick_fused: delta is addressed in an n_slots="
            f"{deltas.n_nodes} slot space but the state's layout has "
            f"n_slots={states.layout.n_slots} (generation "
            f"{states.layout.generation}); grow the capacity first "
            "(FingerService.grow_capacity)")


def prepare_sparse_tick(states: SparseStreamState, deltas: GraphDelta):
    """Stacked (state, delta) → the kernel's lane-aligned input arrays.

    Pads the edge axis to the lane multiple (mask 0, sentinel slot),
    the slot and store axes to the lane multiple (inactive zero slots —
    exact by padding invariance), and the node-slot axis to the sublane
    multiple (flag 0); scalars and rows take `stream_tick`'s slab and
    ``(…, 1, w)`` block forms.

    Leading-dim agnostic: every op works on the last axis, so the same
    preparation serves the per-batch ``(B, ·)`` spelling and the
    shard-stacked ``(S, B, ·)`` one.
    """
    *lead, n = states.strengths.shape
    m = states.edge_weights.shape[-1]
    k = deltas.dw.shape[-1]
    k_al = _ceil_to(k, _LANE)
    n_al = _ceil_to(n, _LANE)
    m_al = _ceil_to(m, _LANE)

    snd = _pad_last(deltas.senders.astype(jnp.int32), k_al)
    rcv = _pad_last(deltas.receivers.astype(jnp.int32), k_al)
    dw = _pad_last(deltas.dw, k_al)
    wold = _pad_last(deltas.w_old, k_al)
    emask = _pad_last(deltas.mask, k_al)
    eslot = _pad_last(deltas.edge_slots.astype(jnp.int32), k_al,
                      value=int(EDGE_SLOT_SENTINEL))
    ep_ids = jnp.concatenate([snd, rcv], axis=-1)
    ep_dw = jnp.concatenate([dw, dw], axis=-1)
    ep_wold = jnp.concatenate([wold, wold], axis=-1)
    ep_mask = jnp.concatenate([emask, emask], axis=-1)

    if deltas.node_ids is not None:
        j_al = _ceil_to(deltas.node_ids.shape[-1], _SUBLANE)
        nid = _pad_last(deltas.node_ids.astype(jnp.int32), j_al)
        nflag = _pad_last(deltas.node_flag, j_al)
    else:
        nid = jnp.zeros((*lead, _SUBLANE), jnp.int32)
        nflag = jnp.zeros((*lead, _SUBLANE), jnp.float32)

    return (pack_scalar_slab(states.q, states.s_total, states.s_max),
            *as_rows(_pad_last(states.strengths, n_al),
                     _pad_last(states.node_mask, n_al),
                     _pad_last(states.edge_weights, m_al),
                     ep_ids, ep_dw, ep_wold, ep_mask, eslot, nid, nflag))


def _unpack(sc2, str2, mask2, ew2, n, m, layout
            ) -> Tuple[jax.Array, SparseStreamState]:
    """Kernel outputs → (scores, SparseStreamState), any leading dims."""
    new_states = SparseStreamState(
        q=sc2[..., 0, 1], s_total=sc2[..., 0, 2], s_max=sc2[..., 0, 3],
        strengths=str2[..., 0, :n], node_mask=mask2[..., 0, :n],
        edge_weights=ew2[..., 0, :m], layout=layout)
    return sc2[..., 0, 0], new_states


def sparse_tick_fused(
    states: SparseStreamState,
    deltas: GraphDelta,
    exact_smax: bool = False,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, SparseStreamState]:
    """One batched sparse serving tick: (B,) JSdist + updated states.

    Fused single-kernel path when the (k_pad, n_slots, m_pad) tile fits
    VMEM; the vmapped XLA oracle otherwise. Slot-space preconditions
    are rejected by name at trace time either way.
    """
    _check_slot_space(states, deltas)
    n = int(states.strengths.shape[-1])
    m = int(states.edge_weights.shape[-1])
    k = int(deltas.dw.shape[-1])
    j = None if deltas.node_ids is None \
        else int(deltas.node_ids.shape[-1])
    if not use_pallas or not fits_sparse_tick(n, m, k, j):
        return sparse_tick_ref(states, deltas, exact_smax=exact_smax)
    interpret = dispatch.default_interpret(interpret)
    prep = prepare_sparse_tick(states, deltas)
    outs = sparse_tick_pallas(
        *prep, exact_smax=exact_smax, interpret=interpret)
    return _unpack(*outs, n, m, states.layout)


def sparse_tick_fused_stacked(
    states: SparseStreamState,
    deltas: GraphDelta,
    exact_smax: bool = False,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, SparseStreamState]:
    """Shard-stacked sparse tick: (S, B) scores + updated stacked
    states.

    ``states``/``deltas`` carry (S, B, ·) leaves — S same-capacity
    shards of B streams each, one whole fleet layout-group. The fused
    path is ONE `pallas_call` over the extended ``(S, B)`` grid (see
    `kernel.sparse_tick_pallas_stacked`); when the per-step tile does
    not fit VMEM, the shard axis is vmapped over the XLA oracle (plain
    XLA, so the vmap is exact and stays a single launch).

    The S-stacked *residency* guard (`fits_sparse_tick_stacked`) is the
    caller's concern: `fleet.pooltick` routes groups that fail it to
    sequential per-shard launches before building stacked operands.
    """
    _check_slot_space(states, deltas)
    n = int(states.strengths.shape[-1])
    m = int(states.edge_weights.shape[-1])
    k = int(deltas.dw.shape[-1])
    j = None if deltas.node_ids is None \
        else int(deltas.node_ids.shape[-1])
    if not use_pallas or not fits_sparse_tick(n, m, k, j):
        return jax.vmap(
            lambda st, d: sparse_tick_ref(st, d,
                                          exact_smax=exact_smax))(
            states, deltas)
    interpret = dispatch.default_interpret(interpret)
    prep = prepare_sparse_tick(states, deltas)
    outs = sparse_tick_pallas_stacked(
        *prep, exact_smax=exact_smax, interpret=interpret)
    return _unpack(*outs, n, m, states.layout)
