"""Pallas TPU megakernel: one full serving tick per stream, in VMEM.

Grid: (B,) over the stream slots of a stacked batch. Each grid step
loads ONE stream's FingerState row — the (q, S, s_max) scalars packed
into one lane slab plus the (n_pad,) strengths and node mask — and one
tick's delta in *tiled endpoint* form (ops.py concatenates the k_pad
senders and receivers into 2k_pad endpoint slots, duplicating the
per-edge Δw/w_old/mask payloads),
then fuses the whole Algorithm-2 step without writing any intermediate
back to HBM:

  1. node-slot mask updates: joins activate before the edge changes,
     leaves deactivate after them (computed as (j_pad, n_pad) indicator
     reductions — the scatter-free form of `node_mask_after_joins` /
     `node_mask_after_leaves`);
  2. edge gating by the post-join mask: the (2k, n) endpoint one-hot
     contracted against the mask on the MXU replaces the gather of
     `gate_delta_by_nodes`, and against the strengths it replaces the
     O(Δn) strength gather;
  3. Theorem-2 delta statistics for BOTH updates of a JSdist tick (ΔG/2
     and ΔG) from ONE segment reduction: a same-endpoint indicator
     matrix contracted against the endpoint Δw gives each slot its
     per-node Δs segment total (first-occurrence slots mark segment
     heads), and the half-delta statistics are closed-form rescalings
     of the full-delta segments (segment sums are linear in Δw);
  4. the scalar Q'/S'/s_max' updates, the empty-graph snap, the (n_pad,)
     strength carry-forward (one (1, 2k)x(2k, n) MXU contraction instead
     of a scatter), and H̃/JSdist — emitting the (B,) scores and the
     full updated state.

Unlike the `delta_stats` kernel this one needs NO host/XLA argsort
preparation: segment totals come from the full (2k, 2k) same-endpoint
contraction, which is order-independent — sortedness only matters for
`jax.ops.segment_sum` on the XLA path. The (2k, 2k) and (2k, n)
indicator temporaries bound VMEM; ops.py routes oversized (k_pad, n_pad)
tiles to the vmapped XLA path before reaching this kernel's asserts.

Block layout: every per-stream operand is passed as ``(B, 1, width)``
with block ``(None, 1, width)``, so each grid step sees a ``(1, width)``
ref whose trailing two block dims equal the array's — the form Mosaic
accepts for one-row blocks (a ``(1, width)`` block of a ``(B, width)``
array is refused: its sublane dim is neither 8-aligned nor the full
axis). The scalars travel as a ``(B, 1, 128)`` slab (lanes 0-2 in:
q, S, s_max; lanes 0-3 out: dist, q', S', s_max') because Mosaic
cannot store a scalar to VMEM; `dispatch.pack_lanes` builds the
outgoing row (the layout helpers live in `kernels.dispatch`).
The contractions that carry values (strength gather, segment sums,
Δs carry-forward) run at ``Precision.HIGHEST``. At the default
precision, Mosaic contracts f32 operands to about bf16 accuracy: on a
TPU v5e the weighted parity checks of this kernel, `sparse_tick` and
`delta_stats` then miss the XLA reference by 3e-3 to 6e-3 relative.
The 0/1 gate contractions are exact at any precision.

Adaptation note: the CUDA analogue would be a per-stream thread-block
chaining gather → sort → segmented-reduce → scatter kernels through
shared memory; on TPU the sequential grid plus MXU indicator
contractions collapse the whole chain into one kernel with O(Δm + n)
HBM traffic per stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.jsdist import divergence_from_increments
from repro.kernels.dispatch import (
    SCALAR_LANES,
    pack_lanes,
    row_spec,
    stacked_row_spec,
)

# VMEM ceiling on the endpoint axis: the (2k, 2k) indicator temporaries
# are ~4 x (2k)^2 x 4 B; ops.py enforces the full-tile estimate (incl.
# the (2k, n) one-hot) against `dispatch.vmem_budget_bytes()` before
# dispatching here, which binds long before this ceiling does.
MAX_ENDPOINTS = 2048

_HIGHEST = jax.lax.Precision.HIGHEST


def _h_tilde(q, s_total, s_max):
    """eq. (2) from the carried scalars, empty-graph convention H̃ = 0."""
    c = jnp.where(s_total > 0, 1.0 / s_total, 0.0)
    arg = jnp.maximum(2.0 * c * s_max, 1e-30)
    return jnp.where(s_total > 0, -q * jnp.log(arg), 0.0)


def _kernel(sc_ref, str_ref, mask_ref,
            ep_ids_ref, ep_dw_ref, ep_wold_ref, ep_mask_ref,
            nid_ref, nflag_ref,
            sco_ref, stro_ref, masko_ref,
            *, exact_smax: bool):
    f32 = jnp.float32
    strengths = str_ref[0, :]          # (n,) carried nodal strengths
    node_mask = mask_ref[0, :]         # (n,) 0/1 live slots
    ep_ids = ep_ids_ref[0, :]          # (2k,) int32 [senders | receivers]
    ep_dw = ep_dw_ref[0, :]            # (2k,) f32 per-edge Δw, tiled
    ep_wold = ep_wold_ref[0, :]        # (2k,) f32 pre-change w, tiled
    ep_mask = ep_mask_ref[0, :]        # (2k,) f32 0/1 edge validity, tiled
    nid = nid_ref[0, :]                # (j,) int32 node join/leave ids
    nflag = nflag_ref[0, :]            # (j,) f32 +1 join / -1 leave / 0
    n = strengths.shape[0]
    two_k = ep_ids.shape[0]
    j = nid.shape[0]

    # -- 1. node-slot mask updates (scatter-free join/leave) ------------
    slot_col = jax.lax.broadcasted_iota(jnp.int32, (j, n), 1)
    nid_b = jax.lax.broadcast_in_dim(nid, (j, n), (0,))
    hit = (nid_b == slot_col).astype(f32)
    flag_b = jax.lax.broadcast_in_dim(nflag, (j, n), (0,))
    join_any = jnp.max(hit * (flag_b > 0.0).astype(f32), axis=0)
    leave_any = jnp.max(hit * (flag_b < 0.0).astype(f32), axis=0)
    mask_joined = jnp.maximum(node_mask, join_any)   # gate + Ḡ mask
    mask_after = mask_joined * (1.0 - leave_any)     # G' mask

    # -- 2. endpoint one-hot: gates + strength gather on the MXU --------
    node_col = jax.lax.broadcasted_iota(jnp.int32, (two_k, n), 1)
    ep_b = jax.lax.broadcast_in_dim(ep_ids, (two_k, n), (0,))
    onehot = (ep_b == node_col).astype(f32)          # (2k, n)
    gate_ep = jnp.dot(onehot, mask_joined.reshape(n, 1),
                      preferred_element_type=f32)[:, 0]
    s_ep = jnp.dot(onehot, strengths.reshape(n, 1), precision=_HIGHEST,
                   preferred_element_type=f32)[:, 0]
    # An edge is live iff BOTH endpoints are: the partner of endpoint e
    # sits at e +/- k, a fixed permutation applied as one contraction.
    row2 = jax.lax.broadcasted_iota(jnp.int32, (two_k, two_k), 0)
    col2 = jax.lax.broadcasted_iota(jnp.int32, (two_k, two_k), 1)
    partner = (jnp.abs(row2 - col2) == (two_k // 2)).astype(f32)
    partner_gate = jnp.dot(partner, gate_ep.reshape(two_k, 1),
                           preferred_element_type=f32)[:, 0]
    valid = ep_mask * gate_ep * partner_gate         # (2k,) 0/1
    vals = ep_dw * valid                             # masked Δw/endpoint

    # -- 3. segment reduction over the 2k endpoints ---------------------
    ids_r = jax.lax.broadcast_in_dim(ep_ids, (two_k, two_k), (0,))
    ids_c = jax.lax.broadcast_in_dim(ep_ids, (two_k, two_k), (1,))
    v_r = jax.lax.broadcast_in_dim(valid, (two_k, two_k), (0,))
    v_c = jax.lax.broadcast_in_dim(valid, (two_k, two_k), (1,))
    same = (ids_r == ids_c).astype(f32) * v_r * v_c
    ds_here = jnp.dot(same, vals.reshape(two_k, 1), precision=_HIGHEST,
                      preferred_element_type=f32)[:, 0]
    cnt_before = jnp.sum(same * (col2 < row2).astype(f32), axis=1)
    head = jnp.logical_and(valid > 0.0, cnt_before == 0.0)

    # Every endpoint sum counts each edge exactly twice (both endpoints
    # carry the same payload and validity), hence the 0.5 edge factors.
    node_full = jnp.sum(jnp.where(
        head, 2.0 * s_ep * ds_here + ds_here * ds_here, 0.0))
    node_half = jnp.sum(jnp.where(
        head, s_ep * ds_here + 0.25 * ds_here * ds_here, 0.0))
    edge_full = 0.5 * jnp.sum(4.0 * ep_wold * vals + 2.0 * vals * vals)
    edge_half = 0.5 * jnp.sum(2.0 * ep_wold * vals + 0.5 * vals * vals)
    delta_s_full = jnp.sum(vals)            # = 2 Σ_ΔE Δw
    abs_moved_full = jnp.sum(jnp.abs(vals))  # = 2 Σ_ΔE |Δw|
    max_new_full = jnp.max(jnp.where(head, s_ep + ds_here, -jnp.inf))
    max_new_half = jnp.max(jnp.where(head, s_ep + 0.5 * ds_here,
                                     -jnp.inf))

    # Dense Δs carry-forward: transpose contraction against the one-hot
    # replaces the (n,) endpoint scatter.
    ds_dense = jnp.dot(vals.reshape(1, two_k), onehot, precision=_HIGHEST,
                       preferred_element_type=f32)[0, :]

    # -- 4. Theorem-2 scalar updates (ΔG/2 and ΔG from one reduction) ---
    q0 = sc_ref[0, 0]
    s0 = sc_ref[0, 1]
    smax0 = sc_ref[0, 2]
    c0 = jnp.where(s0 > 0, 1.0 / s0, 0.0)

    def theorem2(f, node_term, edge_term):
        d_s = f * delta_s_full
        dq = node_term + edge_term
        s_raw = s0 + d_s
        # delete-everything cancellation residue snaps to the empty state
        empty = s_raw <= 1e-6 * (f * abs_moved_full)
        denom = 1.0 + c0 * d_s
        denom = jnp.where(jnp.abs(denom) > 1e-30, denom, 1e-30)
        c_new = jnp.where(s_raw > 0, 1.0 / s_raw, 0.0)
        q_new = (q0 - 1.0) / (denom * denom) - c_new * c_new * dq + 1.0
        q_new = jnp.where(empty, 1.0, q_new)
        return q_new, jnp.where(empty, 0.0, s_raw), empty

    q_half, s_half, empty_half = theorem2(0.5, node_half, edge_half)
    q_full, s_full, empty_full = theorem2(1.0, node_full, edge_full)

    str_half = jnp.where(empty_half, 0.0,
                         strengths + 0.5 * ds_dense) * mask_joined
    str_full = jnp.where(empty_full, 0.0,
                         strengths + ds_dense) * mask_after
    if exact_smax:
        smax_half = jnp.max(str_half)
        smax_full = jnp.max(str_full)
    else:
        smax_half = jnp.where(
            empty_half, 0.0,
            smax0 + jnp.maximum(0.0, max_new_half - smax0))
        smax_full = jnp.where(
            empty_full, 0.0,
            smax0 + jnp.maximum(0.0, max_new_full - smax0))

    h_pre = _h_tilde(q0, s0, smax0)
    h_half = _h_tilde(q_half, s_half, smax_half)
    h_full = _h_tilde(q_full, s_full, smax_full)
    # Lemma-1 moments of the tick (`core.incremental.delta_moments`);
    # edge sums count each edge twice, as above.
    a1 = jnp.sum(jnp.where(head, 2.0 * s_ep * ds_here, 0.0)) \
        + 0.5 * jnp.sum(4.0 * ep_wold * vals)
    a2 = jnp.sum(jnp.where(head, ds_here * ds_here, 0.0)) \
        + 0.5 * jnp.sum(2.0 * vals * vals)
    div = divergence_from_increments(
        q0, s0, smax0, s_half, smax_half, s_full, smax_full,
        delta_s_full, a1, a2, h_half - 0.5 * (h_pre + h_full))

    sco_ref[...] = pack_lanes(sco_ref.shape, jnp.sqrt(jnp.maximum(div, 0.0)),
                              q_full, s_full, smax_full)
    stro_ref[0, :] = str_full
    masko_ref[0, :] = mask_after


@functools.partial(jax.jit, static_argnames=("exact_smax", "interpret"))
def stream_tick_pallas(
    scalars: jax.Array,    # (B, 1, 128) f32 lanes [q, S, s_max, 0...]
    strengths: jax.Array,  # (B, 1, n_pad) f32
    node_mask: jax.Array,  # (B, 1, n_pad) f32
    ep_ids: jax.Array,     # (B, 1, 2k) int32, [senders | receivers]
    ep_dw: jax.Array,      # (B, 1, 2k) f32, per-edge Δw tiled to endpoints
    ep_wold: jax.Array,    # (B, 1, 2k) f32, pre-change weights tiled
    ep_mask: jax.Array,    # (B, 1, 2k) f32, edge validity tiled
    nid: jax.Array,        # (B, 1, j_pad) int32 node slot ids
    nflag: jax.Array,      # (B, 1, j_pad) f32 +1/-1/0
    exact_smax: bool = False,
    interpret: bool = False,
):
    """Batched fused tick → (scalars', strengths', mask'), where
    ``scalars'`` holds lanes [dist, q', S', s_max']."""
    b, _, n = strengths.shape
    two_k = ep_ids.shape[-1]
    assert two_k % 256 == 0 and n % 128 == 0, (
        f"endpoint axis 2k={two_k} and node axis n={n} must be "
        "lane-aligned (ops.prepare pads them)")
    assert two_k <= MAX_ENDPOINTS, (
        f"2k={two_k} endpoints exceed the fused-tick VMEM ceiling; "
        "ops.py routes such tiles to the vmapped path")

    j = nid.shape[-1]
    widths_in = (SCALAR_LANES, n, n, two_k, two_k, two_k, two_k, j, j)
    widths_out = (SCALAR_LANES, n, n)
    return pl.pallas_call(
        functools.partial(_kernel, exact_smax=exact_smax),
        grid=(b,),
        in_specs=[row_spec(w) for w in widths_in],
        out_specs=[row_spec(w) for w in widths_out],
        out_shape=tuple(jax.ShapeDtypeStruct((b, 1, w), jnp.float32)
                        for w in widths_out),
        interpret=interpret,
        name="stream_tick",
    )(scalars, strengths, node_mask,
      ep_ids, ep_dw, ep_wold, ep_mask, nid, nflag)


@functools.partial(jax.jit, static_argnames=("exact_smax", "interpret"))
def stream_tick_pallas_stacked(
    scalars: jax.Array,    # (S, B, 1, 128) f32 lanes [q, S, s_max, 0...]
    strengths: jax.Array,  # (S, B, 1, n_pad) f32
    node_mask: jax.Array,  # (S, B, 1, n_pad) f32
    ep_ids: jax.Array,     # (S, B, 1, 2k) int32, [senders | receivers]
    ep_dw: jax.Array,      # (S, B, 1, 2k) f32
    ep_wold: jax.Array,    # (S, B, 1, 2k) f32
    ep_mask: jax.Array,    # (S, B, 1, 2k) f32
    nid: jax.Array,        # (S, B, 1, j_pad) int32
    nflag: jax.Array,      # (S, B, 1, j_pad) f32
    exact_smax: bool = False,
    interpret: bool = False,
):
    """Shard-stacked fused tick: a whole (S, B) layout-group as ONE
    `pallas_call`.

    The grid is extended to ``(S, B)`` and every BlockSpec squeezes the
    shard and stream axes (`stacked_row_spec`), so each grid step sees
    the exact same ``(1, w)`` refs as the per-batch entry point and the
    per-step kernel body — and its VMEM footprint — is reused verbatim. Semantically this is
    ``vmap(stream_tick_pallas)`` over the shard axis, spelled as one
    launch instead of S.
    """
    s, b, _, n = strengths.shape
    two_k = ep_ids.shape[-1]
    assert two_k % 256 == 0 and n % 128 == 0, (
        f"endpoint axis 2k={two_k} and node axis n={n} must be "
        "lane-aligned (ops.prepare pads them)")
    assert two_k <= MAX_ENDPOINTS, (
        f"2k={two_k} endpoints exceed the fused-tick VMEM ceiling; "
        "ops.py routes such tiles to the vmapped path")

    j = nid.shape[-1]
    widths_in = (SCALAR_LANES, n, n, two_k, two_k, two_k, two_k, j, j)
    widths_out = (SCALAR_LANES, n, n)
    return pl.pallas_call(
        functools.partial(_kernel, exact_smax=exact_smax),
        grid=(s, b),
        in_specs=[stacked_row_spec(w) for w in widths_in],
        out_specs=[stacked_row_spec(w) for w in widths_out],
        out_shape=tuple(jax.ShapeDtypeStruct((s, b, 1, w), jnp.float32)
                        for w in widths_out),
        interpret=interpret,
        name="stream_tick_stacked",
    )(scalars, strengths, node_mask,
      ep_ids, ep_dw, ep_wold, ep_mask, nid, nflag)
