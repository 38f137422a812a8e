"""Pallas TPU kernel: fused Theorem-2 delta statistics in one VMEM pass.

Inputs are the *sorted endpoint* form of a GraphDelta (ops.py prepares
them in XLA: concatenate the 2Δm edge endpoints, map masked slots to a
sentinel node id that sorts last, argsort, gather the touched
strengths). The kernel then fuses everything Theorem 2 needs —

  ΔS        = 2 Σ_ΔE Δw
  ΔQ        = Σ_ΔV (2 s_i Δs_i + Δs_i²) + Σ_ΔE (4 w Δw + 2 Δw²)
  Δs_max in = max_ΔV (s_i + Δs_i)
  |ΔV|

— into a single pass over the (2Δm)-sized endpoint arrays: no (n,)
temporary, no second HBM trip. The per-node segment sum Δs_i uses the
sorted order: a same-node comparison matrix contracted against the
endpoint values on the MXU gives each slot its segment total, and the
strictly-lower-triangular occurrence count marks segment heads. The
(2Δm)² compare/contract is VPU/MXU work on a tile that already sits in
VMEM — HBM traffic stays O(Δm), which is what the pass is bound by for
streaming deltas.

The four statistics leave as lanes 0-3 of one ``(1, 128)`` row: Mosaic
cannot store a scalar to VMEM. The segment-sum contraction carries Δw
values and runs at ``Precision.HIGHEST`` (see `stream_tick.kernel`:
the default is about bf16 accurate).

Adaptation note: the CUDA analogue would be a sort + segmented-reduce
(CUB) pair of kernels; on TPU one fused kernel with an MXU segment
contraction replaces both.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import SCALAR_LANES, pack_lanes


def _kernel(sn_ref, sv_ref, ss_ref, ev_ref, dw_ref, wo_ref, mask_ref,
            out_ref):
    # Every per-endpoint vector stays a (1, 2k) lane row, so no value
    # ever changes between sublane and lane orientation.
    sn = sn_ref[...]           # (1, 2k) int32 sorted node ids, sentinel last
    sv = sv_ref[...]           # (1, 2k) f32 masked Δw per endpoint
    ss = ss_ref[...]           # (1, 2k) f32 gathered strengths
    ev = ev_ref[...]           # (1, 2k) f32 endpoint validity
    two_k = sn.shape[1]

    # Same-node matrix M[p, q] = [sn[p] == sn[q]] over the sorted run.
    sn_row = jax.lax.broadcast_in_dim(sn[0], (two_k, two_k), (0,))
    sn_col = jax.lax.broadcast_in_dim(sn[0], (two_k, two_k), (1,))
    same = (sn_row == sn_col).astype(jnp.float32)

    # Δs of each slot's segment: contract the endpoint values against
    # the (symmetric) segment indicator on the MXU; values are zero on
    # masked slots.
    ds_pos = jnp.dot(sv, same, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)

    # Segment head = first occurrence: no equal node id strictly before
    # (column sums of M over the rows above, M being symmetric).
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (two_k, two_k), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (two_k, two_k), 1)
    before = (row_ids < col_ids).astype(jnp.float32)
    cnt_before = jnp.sum(same * before, axis=0, keepdims=True)
    head = jnp.logical_and(cnt_before == 0.0, ev > 0.0)

    def total(x):
        return jnp.sum(x, axis=1, keepdims=True)

    node_term = total(jnp.where(
        head, 2.0 * ss * ds_pos + ds_pos * ds_pos, 0.0))
    max_new = jnp.max(jnp.where(head, ss + ds_pos, -jnp.inf), axis=1,
                      keepdims=True)
    n_touched = total(head.astype(jnp.float32))

    dwm = dw_ref[...] * mask_ref[...]
    edge_term = total(4.0 * wo_ref[...] * dwm + 2.0 * dwm * dwm)
    delta_s = 2.0 * total(dwm)

    out_ref[...] = pack_lanes(out_ref.shape, delta_s, node_term + edge_term,
                              max_new, n_touched)


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_stats_sorted_pallas(
    sorted_nodes: jax.Array,      # (1, 2k) int32
    sorted_vals: jax.Array,       # (1, 2k) f32
    sorted_strengths: jax.Array,  # (1, 2k) f32
    endpoint_valid: jax.Array,    # (1, 2k) f32
    dw: jax.Array,                # (1, k) f32
    w_old: jax.Array,             # (1, k) f32
    mask: jax.Array,              # (1, k) f32
    interpret: bool = False,
) -> jax.Array:
    """Sorted-endpoint delta arrays → (1, 128) row whose lanes 0-3 are
    [ΔS, ΔQ, max s', |ΔV|]."""
    two_k = sorted_nodes.shape[1]
    assert two_k % 128 == 0, (
        f"2·k_pad={two_k} must be lane-aligned (multiple of 128); "
        "pad the delta first (ops.prepare_sorted_delta does this)"
    )
    # The (2k, 2k) indicator temporaries must fit VMEM; ops.py routes
    # larger deltas to the XLA ref path before reaching this assert.
    assert two_k <= 2048, f"2·k_pad={two_k} too large for the fused kernel"
    vspec = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _kernel,
        in_specs=[vspec] * 7,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, SCALAR_LANES), jnp.float32),
        interpret=interpret,
        name="delta_stats",
    )(sorted_nodes, sorted_vals, sorted_strengths, endpoint_valid,
      dw, w_old, mask)
