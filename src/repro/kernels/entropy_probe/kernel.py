"""Pallas TPU kernels: attention-graph VNGE statistics without
materializing softmax(logits) in HBM.

Two kernels (flash-attention-style decomposition, DESIGN.md §4):

1. ``_row_stats_kernel`` — per row: max and exp-sum of the logits
   (softmax normalizers). Grid (BH, S/bs); block (bs, S). O(S) output.

2. ``_graph_stats_kernel`` — grid (BH, S/bs, S/bs) with the *row-tile*
   index innermost. For tile pair (jj fixed, ii sweeping) it loads the
   logits tile T[ii, jj] and its transpose partner T[jj, ii], rebuilds
   the two normalized attention tiles in VMEM from the row normalizers,
   and accumulates:
     · column sums of A into a (1, bs) block resident across the ii sweep
     · Σ A², Σ (A ∘ Aᵀ) into per-BH scalar accumulators
     · diag(A) when ii == jj
   Every logits tile is read twice (once as (ii,jj), once as its
   partner); read amplification 2× is the price for never writing the
   (S, S) attention matrix — still a ~4096× HBM-byte reduction vs.
   materializing A for S = 8k BH = 1.

Host-side (ops.py) closes the algebra: with row sums of softmax ≡ 1,
  r_i = 1 - diag_i, c_i = colsum_i - diag_i, s_i = (r_i + c_i)/2,
  Σ_E w² = ¼ (ΣA² - Σdiag²) + ¼ (ΣA∘Aᵀ - Σdiag²).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _row_stats_kernel(logits_ref, rowmax_ref, denom_ref):
    t = logits_ref[0].astype(jnp.float32)  # (bs, S)
    m = jnp.max(t, axis=1)
    rowmax_ref[0] = m
    denom_ref[0] = jnp.sum(jnp.exp(t - m[:, None]), axis=1)


def _graph_stats_kernel(
    t_ij_ref, t_ji_ref, rm_i_ref, dn_i_ref, rm_j_ref, dn_j_ref,
    scal_ref, colsum_ref, diag_ref, *, bs: int,
):
    ii = pl.program_id(2)  # innermost: row-tile sweep
    jj = pl.program_id(1)
    n_tiles = pl.num_programs(2)

    @pl.when(jnp.logical_and(jj == 0, ii == 0))
    def _init_scal():
        scal_ref[...] = jnp.zeros_like(scal_ref)

    @pl.when(ii == 0)
    def _init_cols():
        colsum_ref[...] = jnp.zeros_like(colsum_ref)

    # Normalized attention tiles rebuilt in VMEM.
    a_ij = jnp.exp(t_ij_ref[0].astype(jnp.float32)
                   - rm_i_ref[0][:, None]) / dn_i_ref[0][:, None]
    a_ji = jnp.exp(t_ji_ref[0].astype(jnp.float32)
                   - rm_j_ref[0][:, None]) / dn_j_ref[0][:, None]

    colsum_ref[0] += jnp.sum(a_ij, axis=0)
    scal_ref[0, 0] += jnp.sum(a_ij * a_ij)
    scal_ref[0, 1] += jnp.sum(a_ij * a_ji.T)

    @pl.when(ii == jj)
    def _diag():
        d = jnp.sum(a_ij * jnp.eye(bs, dtype=a_ij.dtype), axis=1)
        diag_ref[0] = d
        scal_ref[0, 2] += jnp.sum(d * d)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def attention_graph_stats_pallas(
    logits: jax.Array, bs: int = 128, interpret: bool = False,
):
    """logits (BH, S, S) → (scalars (BH, 3), colsums (BH, S), diag (BH, S)).

    scalars = [Σ A², Σ A∘Aᵀ, Σ diag²] (diag-inclusive; ops.py corrects).
    """
    bh, s, s2 = logits.shape
    assert s == s2 and s % bs == 0, f"S={s} must be a multiple of bs={bs}"
    nt = s // bs

    rowmax, denom = pl.pallas_call(
        _row_stats_kernel,
        grid=(bh, nt),
        in_specs=[pl.BlockSpec((1, bs, s), lambda b, i: (b, i, 0))],
        out_specs=[
            pl.BlockSpec((1, bs), lambda b, i: (b, i)),
            pl.BlockSpec((1, bs), lambda b, i: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s), jnp.float32),
            jax.ShapeDtypeStruct((bh, s), jnp.float32),
        ],
        interpret=interpret,
        name="entropy_probe_row_stats",
    )(logits)

    scal, colsum, diag = pl.pallas_call(
        functools.partial(_graph_stats_kernel, bs=bs),
        grid=(bh, nt, nt),  # ii (rows) innermost → colsum block resident
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda b, jj, ii: (b, ii, jj)),  # T[ii,jj]
            pl.BlockSpec((1, bs, bs), lambda b, jj, ii: (b, jj, ii)),  # T[jj,ii]
            pl.BlockSpec((1, bs), lambda b, jj, ii: (b, ii)),  # rowmax rows ii
            pl.BlockSpec((1, bs), lambda b, jj, ii: (b, ii)),  # denom rows ii
            pl.BlockSpec((1, bs), lambda b, jj, ii: (b, jj)),  # rowmax rows jj
            pl.BlockSpec((1, bs), lambda b, jj, ii: (b, jj)),  # denom rows jj
        ],
        out_specs=[
            pl.BlockSpec((1, 3), lambda b, jj, ii: (b, 0)),
            pl.BlockSpec((1, bs), lambda b, jj, ii: (b, jj)),
            pl.BlockSpec((1, bs), lambda b, jj, ii: (b, jj)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, 3), jnp.float32),
            jax.ShapeDtypeStruct((bh, s), jnp.float32),
            jax.ShapeDtypeStruct((bh, s), jnp.float32),
        ],
        interpret=interpret,
        name="entropy_probe_graph_stats",
    )(logits, logits, rowmax, denom, rowmax, denom)
    return scal, colsum, diag
