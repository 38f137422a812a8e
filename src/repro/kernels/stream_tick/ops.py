"""Public op: the single-pass batched serving tick (``method="fused_tick"``).

`stream_tick_fused` is the drop-in replacement for the vmapped
per-stream op chain a serving tick used to execute (mask gating →
join/leave scatters → delta statistics → state update → H̃/JSdist): one
Pallas kernel launch gridded over the B stream slots, with every
intermediate resident in VMEM. Dispatch policy:

- Pallas on TPU, interpret mode elsewhere (CPU CI) — same contract as
  the other kernel packages;
- the VMEM size guard routes oversized (k_pad, n_pad) tiles to the
  vmapped XLA reference path (`ref.stream_tick_ref`), as does a legacy
  mask-less stacked state (the kernel's gating needs the node mask to
  be part of the carried state);
- numerics match the vmapped reference to 1e-5 on every path (see
  `tests/test_stream_tick.py`).

Preparation is pure elementwise XLA: lane-align the edge/node axes and
tile the per-edge payloads onto the 2k endpoint slots — no argsort, no
(n,)-sized temporaries (the kernel's segment contraction is
order-independent, unlike the `delta_stats` sorted-endpoint form).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.state import FingerState
from repro.graphs.types import GraphDelta
from repro.kernels import dispatch
from repro.kernels.dispatch import as_rows, pack_scalar_slab
from repro.kernels.dispatch import ceil_to as _ceil_to
from repro.kernels.dispatch import pad_last as _pad_last
from repro.kernels.stream_tick.kernel import (
    MAX_ENDPOINTS,
    stream_tick_pallas,
    stream_tick_pallas_stacked,
)
from repro.kernels.stream_tick.ref import stream_tick_ref

_LANE = dispatch.LANE
_SUBLANE = dispatch.SUBLANE


def fused_tick_vmem_bytes(n_pad: int, k_pad: int,
                          j_pad: Optional[int]) -> int:
    """Estimated VMEM footprint of one fused-tick grid step."""
    two_k = 2 * _ceil_to(k_pad, _LANE)
    n = _ceil_to(n_pad, _LANE)
    j = _ceil_to(j_pad or 1, _SUBLANE)
    # 4 x (2k, 2k) f32 (same/partner/iota pair) + (2k, n) one-hot
    # + 2 x (j, n) indicators + the O(2k) / O(n) vectors.
    return 4 * (4 * two_k * two_k + two_k * n + 2 * j * n
                + 10 * two_k + 8 * n)


def fits_fused_tick(n_pad: int, k_pad: int,
                    j_pad: Optional[int]) -> bool:
    """Whether a (k_pad, n_pad, j_pad) tile fits the fused kernel under
    the active `dispatch.vmem_budget_bytes()` budget; the caller falls
    back to the vmapped XLA tick otherwise."""
    if 2 * _ceil_to(k_pad, _LANE) > MAX_ENDPOINTS:
        return False
    return fused_tick_vmem_bytes(n_pad, k_pad, j_pad) \
        <= dispatch.vmem_budget_bytes()


def fused_tick_stacked_bytes(s: int, b: int, n_pad: int, k_pad: int,
                             j_pad: Optional[int]) -> int:
    """Total device-resident operand bytes (inputs + outputs) of one
    shard-stacked fused launch over S shards of B streams each."""
    two_k = 2 * _ceil_to(k_pad, _LANE)
    n = _ceil_to(n_pad, _LANE)
    j = _ceil_to(j_pad or 1, _SUBLANE)
    per_row = 4 * (4 + 2 * n + 5 * two_k + 2 * j)  # state+delta+outputs
    return s * b * per_row


def fits_fused_tick_stacked(s: int, b: int, n_pad: int, k_pad: int,
                            j_pad: Optional[int]) -> bool:
    """Stacked-launch admission: the per-grid-step tile must fit VMEM
    exactly as in the per-batch spelling (stacking leaves each step's
    footprint unchanged), AND the S-stacked operand set must fit the
    `dispatch.stacked_budget_bytes()` residency budget. Callers route
    a failing group to sequential per-shard launches."""
    return fits_fused_tick(n_pad, k_pad, j_pad) \
        and dispatch.stacked_residency_bytes_ok(
            fused_tick_stacked_bytes(s, b, n_pad, k_pad, j_pad))


def prepare_stream_tick(states: FingerState, deltas: GraphDelta):
    """Stacked (state, delta) → the kernel's lane-aligned input arrays.

    Pads the edge axis to the lane multiple (mask 0), the node axis to
    the lane multiple (inactive, zero-strength slots — exact by padding
    invariance), the node-slot axis to the sublane multiple (flag 0),
    tiles the per-edge payloads onto the concatenated
    [senders | receivers] endpoint slots, packs (q, S, s_max) into the
    scalar slab, and gives every row operand its ``(…, 1, w)`` block
    axis.

    Leading-dim agnostic: every op works on the last axis, so the same
    preparation serves the per-batch ``(B, ·)`` spelling and the
    shard-stacked ``(S, B, ·)`` one.
    """
    *lead, n = states.strengths.shape
    k = deltas.dw.shape[-1]
    k_al = _ceil_to(k, _LANE)
    n_al = _ceil_to(n, _LANE)

    snd = _pad_last(deltas.senders.astype(jnp.int32), k_al)
    rcv = _pad_last(deltas.receivers.astype(jnp.int32), k_al)
    dw = _pad_last(deltas.dw, k_al)
    wold = _pad_last(deltas.w_old, k_al)
    emask = _pad_last(deltas.mask, k_al)
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
                     ep_ids, ep_dw, ep_wold, ep_mask, nid, nflag))


def _unpack(sc2, str2, mask2, n, layout) -> Tuple[jax.Array, FingerState]:
    """Kernel outputs → (scores, FingerState), any leading dims."""
    new_states = FingerState(
        q=sc2[..., 0, 1], s_total=sc2[..., 0, 2], s_max=sc2[..., 0, 3],
        strengths=str2[..., 0, :n], node_mask=mask2[..., 0, :n],
        layout=layout)
    return sc2[..., 0, 0], new_states


def stream_tick_fused(
    states: FingerState,
    deltas: GraphDelta,
    exact_smax: bool = False,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, FingerState]:
    """One batched serving tick: (B,) JSdist scores + updated states.

    Fused single-kernel path when the stacked state is mask-aware and
    the (k_pad, n_pad, j_pad) tile fits VMEM; the vmapped XLA reference
    otherwise. Same trace-time larger-layout-delta rejection as
    `core.incremental.update_state`.
    """
    if states.layout is not None \
            and deltas.n_nodes > states.layout.n_pad:
        raise ValueError(
            f"stream_tick_fused: delta is addressed in an n_pad="
            f"{deltas.n_nodes} layout but the state's layout is n_pad="
            f"{states.layout.n_pad} (generation "
            f"{states.layout.generation}); migrate the state first "
            "(FingerService.repad / serving.migrate.grow_stacked)")
    n = int(states.strengths.shape[-1])
    k = int(deltas.dw.shape[-1])
    j = None if deltas.node_ids is None \
        else int(deltas.node_ids.shape[-1])
    if states.node_mask is None or not use_pallas \
            or not fits_fused_tick(n, k, j):
        return stream_tick_ref(states, deltas, exact_smax=exact_smax,
                               method="dense")
    interpret = dispatch.default_interpret(interpret)
    prep = prepare_stream_tick(states, deltas)
    sc2, str2, mask2 = stream_tick_pallas(
        *prep, exact_smax=exact_smax, interpret=interpret)
    return _unpack(sc2, str2, mask2, n, states.layout)


def stream_tick_fused_stacked(
    states: FingerState,
    deltas: GraphDelta,
    exact_smax: bool = False,
    use_pallas: bool = True,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, FingerState]:
    """Shard-stacked fused tick: (S, B) scores + updated stacked states.

    ``states``/``deltas`` carry (S, B, ·) leaves — S same-layout shards
    of B streams each, one whole fleet layout-group. The fused path is
    ONE `pallas_call` over the extended ``(S, B)`` grid (see
    `kernel.stream_tick_pallas_stacked`); when the per-step tile does
    not fit VMEM or the state is mask-less, the shard axis is vmapped
    over the XLA reference instead — the reference is plain XLA, so the
    vmap is exact and stays a single XLA launch.

    The S-stacked *residency* guard (`fits_fused_tick_stacked`) is the
    caller's concern: `fleet.pooltick` routes groups that fail it to
    sequential per-shard launches before ever building stacked
    operands.
    """
    if states.layout is not None \
            and deltas.n_nodes > states.layout.n_pad:
        raise ValueError(
            f"stream_tick_fused_stacked: delta is addressed in an "
            f"n_pad={deltas.n_nodes} layout but the state's layout is "
            f"n_pad={states.layout.n_pad} (generation "
            f"{states.layout.generation}); migrate the state first")
    n = int(states.strengths.shape[-1])
    k = int(deltas.dw.shape[-1])
    j = None if deltas.node_ids is None \
        else int(deltas.node_ids.shape[-1])
    if states.node_mask is None or not use_pallas \
            or not fits_fused_tick(n, k, j):
        return jax.vmap(
            lambda st, d: stream_tick_ref(st, d, exact_smax=exact_smax,
                                          method="dense"))(states,
                                                           deltas)
    interpret = dispatch.default_interpret(interpret)
    prep = prepare_stream_tick(states, deltas)
    sc2, str2, mask2 = stream_tick_pallas_stacked(
        *prep, exact_smax=exact_smax, interpret=interpret)
    return _unpack(sc2, str2, mask2, n, states.layout)
