"""Pallas TPU kernel: one full *sparse* serving tick per stream, in VMEM.

Grid: (B,) over the stream slots of a stacked `SparseStreamState`
batch. Structurally this is the `stream_tick` megakernel applied to the
**slot space**: every node-axis temporary is sized by ``n_slots`` (the
active-node capacity) instead of the virtual ``n_pad``, so the tick's
work and VMEM footprint are completely independent of how large the
virtual id space grows — the property the dense kernel's ``(2k, n_pad)``
one-hot fundamentally cannot have. A stream addressed in an n_pad of
10⁵ (or 10⁷) runs the exact same kernel as one addressed in 10³.

Per grid step, on one stream's row:

  1. node-slot mask join/leave updates ((j, n_slots) indicators);
  2. edge gating by the post-join mask + strength gather via the
     (2k, n_slots) endpoint one-hot — the `bsr_spmv`-style
     contraction-as-gather idiom, cheap because n_slots is the *active*
     capacity (hundreds), not the address space;
  3. same-endpoint (2k, 2k) segment sums → Theorem-2 statistics for
     both JSdist updates (ΔG/2 closed-form rescalings of the full-ΔG
     segments), exactly as `stream_tick`;
  4. the scalar Q'/S'/s_max' updates, empty-graph snap, slot-space
     strength carry, H̃/JSdist — plus the sparse path's extra output:
     the (m_pad,) **edge-store scatter**, a (k, m_pad) slot one-hot
     applying each gated lane's post-delta weight at its edge slot
     (padding/gated lanes ride the `EDGE_SLOT_SENTINEL` and match no
     slot).

Block layout, scalar slab and contraction precision are
`stream_tick`'s (see its module docstring): ``(B, 1, width)`` operands
with ``(None, 1, width)`` blocks, (q, S, s_max) in and (dist, q', S',
s_max') out through one ``(B, 1, 128)`` lane slab.

ops.py routes oversized (k_pad, n_slots, m_pad) tiles to the vmapped
XLA oracle (`ref.sparse_tick_ref`) before reaching this kernel's
asserts, and runs interpret mode off-TPU like every kernel package.
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

# Same endpoint-axis ceiling as stream_tick: the (2k, 2k) indicator
# temporaries dominate and are layout-independent.
MAX_ENDPOINTS = 2048

_HIGHEST = jax.lax.Precision.HIGHEST


def _h_tilde(q, s_total, s_max):
    """eq. (2) from the carried scalars, empty-graph convention H̃ = 0."""
    c = jnp.where(s_total > 0, 1.0 / s_total, 0.0)
    arg = jnp.maximum(2.0 * c * s_max, 1e-30)
    return jnp.where(s_total > 0, -q * jnp.log(arg), 0.0)


def _kernel(sc_ref, str_ref, mask_ref, ew_ref,
            ep_ids_ref, ep_dw_ref, ep_wold_ref, ep_mask_ref,
            eslot_ref, nid_ref, nflag_ref,
            sco_ref, stro_ref, masko_ref, ewo_ref, *, exact_smax: bool):
    f32 = jnp.float32
    strengths = str_ref[0, :]          # (n,) slot-space strengths
    node_mask = mask_ref[0, :]         # (n,) 0/1 allocated-and-active
    edge_w = ew_ref[0, :]              # (m,) slot-addressed edge store
    ep_ids = ep_ids_ref[0, :]          # (2k,) int32 [senders | receivers]
    ep_dw = ep_dw_ref[0, :]            # (2k,) f32 per-edge Δw, tiled
    ep_wold = ep_wold_ref[0, :]        # (2k,) f32 pre-change w, tiled
    ep_mask = ep_mask_ref[0, :]        # (2k,) f32 0/1 edge validity, tiled
    eslot = eslot_ref[0, :]            # (k,) int32 edge-store slots
    nid = nid_ref[0, :]                # (j,) int32 node join/leave slots
    nflag = nflag_ref[0, :]            # (j,) f32 +1 join / -1 leave / 0
    n = strengths.shape[0]
    m = edge_w.shape[0]
    two_k = ep_ids.shape[0]
    k = eslot.shape[0]
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

    # -- 2. endpoint one-hot over the SLOT axis (n = n_slots) -----------
    node_col = jax.lax.broadcasted_iota(jnp.int32, (two_k, n), 1)
    ep_b = jax.lax.broadcast_in_dim(ep_ids, (two_k, n), (0,))
    onehot = (ep_b == node_col).astype(f32)          # (2k, n_slots)
    gate_ep = jnp.dot(onehot, mask_joined.reshape(n, 1),
                      preferred_element_type=f32)[:, 0]
    s_ep = jnp.dot(onehot, strengths.reshape(n, 1), precision=_HIGHEST,
                   preferred_element_type=f32)[:, 0]
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

    node_full = jnp.sum(jnp.where(
        head, 2.0 * s_ep * ds_here + ds_here * ds_here, 0.0))
    node_half = jnp.sum(jnp.where(
        head, s_ep * ds_here + 0.25 * ds_here * ds_here, 0.0))
    edge_full = 0.5 * jnp.sum(4.0 * ep_wold * vals + 2.0 * vals * vals)
    edge_half = 0.5 * jnp.sum(2.0 * ep_wold * vals + 0.5 * vals * vals)
    delta_s_full = jnp.sum(vals)
    abs_moved_full = jnp.sum(jnp.abs(vals))
    max_new_full = jnp.max(jnp.where(head, s_ep + ds_here, -jnp.inf))
    max_new_half = jnp.max(jnp.where(head, s_ep + 0.5 * ds_here,
                                     -jnp.inf))

    ds_dense = jnp.dot(vals.reshape(1, two_k), onehot, precision=_HIGHEST,
                       preferred_element_type=f32)[0, :]

    # -- 4. Theorem-2 scalar updates (ΔG/2 and ΔG) ----------------------
    q0 = sc_ref[0, 0]
    s0 = sc_ref[0, 1]
    smax0 = sc_ref[0, 2]
    c0 = jnp.where(s0 > 0, 1.0 / s0, 0.0)

    def theorem2(f, node_term, edge_term):
        d_s = f * delta_s_full
        dq = node_term + edge_term
        s_raw = s0 + d_s
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

    # -- 5. edge-store scatter ((k, m_pad) slot one-hot) ----------------
    # Per-edge validity is the senders-half slice of the tiled endpoint
    # validity (both halves carry identical payloads). Sentinel slots
    # (padding / gated lanes) match no store column.
    gate_edge = valid[:k]                            # (k,) 0/1
    new_w = jnp.maximum(ep_wold[:k] + ep_dw[:k], 0.0) * gate_edge
    store_col = jax.lax.broadcasted_iota(jnp.int32, (k, m), 1)
    eslot_b = jax.lax.broadcast_in_dim(eslot, (k, m), (0,))
    gate_b = jax.lax.broadcast_in_dim(gate_edge, (k, m), (0,))
    oh_store = (eslot_b == store_col).astype(f32) * gate_b  # (k, m)
    touched = jnp.max(oh_store, axis=0)              # (m,) 0/1
    scattered = jnp.dot(new_w.reshape(1, k), oh_store, precision=_HIGHEST,
                        preferred_element_type=f32)[0, :]
    ew_full = edge_w * (1.0 - touched) + scattered
    ew_full = jnp.where(s_full > 0, ew_full, 0.0)

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
    ewo_ref[0, :] = ew_full


@functools.partial(jax.jit, static_argnames=("exact_smax", "interpret"))
def sparse_tick_pallas(
    scalars: jax.Array,     # (B, 1, 128) f32 lanes [q, S, s_max, 0...]
    strengths: jax.Array,   # (B, 1, n_slots) f32
    node_mask: jax.Array,   # (B, 1, n_slots) f32
    edge_weights: jax.Array,  # (B, 1, m_pad) f32
    ep_ids: jax.Array,      # (B, 1, 2k) int32, [senders | receivers]
    ep_dw: jax.Array,       # (B, 1, 2k) f32
    ep_wold: jax.Array,     # (B, 1, 2k) f32
    ep_mask: jax.Array,     # (B, 1, 2k) f32
    eslot: jax.Array,       # (B, 1, k) int32 edge-store slots
    nid: jax.Array,         # (B, 1, j_pad) int32 node slot ids
    nflag: jax.Array,       # (B, 1, j_pad) f32 +1/-1/0
    exact_smax: bool = False,
    interpret: bool = False,
):
    """Batched fused sparse tick → (scalars', strengths', mask',
    edge_weights'), where ``scalars'`` holds lanes [dist, q', S',
    s_max']."""
    b, _, n = strengths.shape
    m = edge_weights.shape[-1]
    two_k = ep_ids.shape[-1]
    assert two_k % 256 == 0 and n % 128 == 0 and m % 128 == 0, (
        f"endpoint axis 2k={two_k}, slot axis n={n} and store axis "
        f"m={m} must be lane-aligned (ops.prepare pads them)")
    assert eslot.shape[-1] == two_k // 2, (
        f"eslot axis {eslot.shape[-1]} must equal k={two_k // 2}")
    assert two_k <= MAX_ENDPOINTS, (
        f"2k={two_k} endpoints exceed the sparse-tick VMEM ceiling; "
        "ops.py routes such tiles to the vmapped path")

    j = nid.shape[-1]
    widths_in = (SCALAR_LANES, n, n, m, two_k, two_k, two_k, two_k,
                 two_k // 2, j, j)
    widths_out = (SCALAR_LANES, n, n, m)
    return pl.pallas_call(
        functools.partial(_kernel, exact_smax=exact_smax),
        grid=(b,),
        in_specs=[row_spec(w) for w in widths_in],
        out_specs=[row_spec(w) for w in widths_out],
        out_shape=tuple(jax.ShapeDtypeStruct((b, 1, w), jnp.float32)
                        for w in widths_out),
        interpret=interpret,
        name="sparse_tick",
    )(scalars, strengths, node_mask, edge_weights,
      ep_ids, ep_dw, ep_wold, ep_mask, eslot, nid, nflag)


@functools.partial(jax.jit, static_argnames=("exact_smax", "interpret"))
def sparse_tick_pallas_stacked(
    scalars: jax.Array,     # (S, B, 1, 128) f32 lanes [q, S, s_max, 0...]
    strengths: jax.Array,   # (S, B, 1, n_slots) f32
    node_mask: jax.Array,   # (S, B, 1, n_slots) f32
    edge_weights: jax.Array,  # (S, B, 1, m_pad) f32
    ep_ids: jax.Array,      # (S, B, 1, 2k) int32, [senders | receivers]
    ep_dw: jax.Array,       # (S, B, 1, 2k) f32
    ep_wold: jax.Array,     # (S, B, 1, 2k) f32
    ep_mask: jax.Array,     # (S, B, 1, 2k) f32
    eslot: jax.Array,       # (S, B, 1, k) int32 edge-store slots
    nid: jax.Array,         # (S, B, 1, j_pad) int32
    nflag: jax.Array,       # (S, B, 1, j_pad) f32
    exact_smax: bool = False,
    interpret: bool = False,
):
    """Shard-stacked fused sparse tick: a whole (S, B) layout-group as
    ONE `pallas_call`.

    Same spelling as `stream_tick.stream_tick_pallas_stacked`: the grid
    extends to ``(S, B)`` and every BlockSpec squeezes the shard and
    stream axes (`stacked_row_spec`), so each grid step sees the
    per-batch entry point's ``(1, w)`` refs and the per-step kernel
    body — and its VMEM footprint — is reused verbatim.
    """
    s, b, _, n = strengths.shape
    m = edge_weights.shape[-1]
    two_k = ep_ids.shape[-1]
    assert two_k % 256 == 0 and n % 128 == 0 and m % 128 == 0, (
        f"endpoint axis 2k={two_k}, slot axis n={n} and store axis "
        f"m={m} must be lane-aligned (ops.prepare pads them)")
    assert eslot.shape[-1] == two_k // 2, (
        f"eslot axis {eslot.shape[-1]} must equal k={two_k // 2}")
    assert two_k <= MAX_ENDPOINTS, (
        f"2k={two_k} endpoints exceed the sparse-tick VMEM ceiling; "
        "ops.py routes such tiles to the vmapped path")

    j = nid.shape[-1]
    widths_in = (SCALAR_LANES, n, n, m, two_k, two_k, two_k, two_k,
                 two_k // 2, j, j)
    widths_out = (SCALAR_LANES, n, n, m)
    return pl.pallas_call(
        functools.partial(_kernel, exact_smax=exact_smax),
        grid=(s, b),
        in_specs=[stacked_row_spec(w) for w in widths_in],
        out_specs=[stacked_row_spec(w) for w in widths_out],
        out_shape=tuple(jax.ShapeDtypeStruct((s, b, 1, w), jnp.float32)
                        for w in widths_out),
        interpret=interpret,
        name="sparse_tick_stacked",
    )(scalars, strengths, node_mask, edge_weights,
      ep_ids, ep_dw, ep_wold, ep_mask, eslot, nid, nflag)
