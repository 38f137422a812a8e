"""Pallas TPU kernel: fused one-HBM-pass Lemma-1 statistics over dense W.

Grid: (n/bm, n/bn), row-major with the column index innermost. For each
row-stripe i we stream its column tiles HBM→VMEM once, accumulating

  - partial row sums  (VMEM scratch, (bm, 1) f32)
  - Σ w² tile-locally (VMEM scratch, scalar accumulated across the stripe)

On the stripe's last column tile the row sums are finalized into the
global accumulators [S, Σs², Σw², s_max] held in a (4,)-shaped VMEM
output block shared by every grid step (TPU grid execution is sequential,
so cross-step accumulation into the same output block is sound).

Adaptation note (DESIGN.md §3): the CUDA analogue would be a two-kernel
row-sum + square-reduce with atomics; on TPU we exploit the sequential
grid and VMEM scratch instead — one pass over HBM, no atomics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(w_ref, out_ref, row_acc, w2_acc):
    i = pl.program_id(0)
    j = pl.program_id(1)
    ncols = pl.num_programs(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(j == 0)
    def _init_stripe():
        row_acc[...] = jnp.zeros_like(row_acc)
        w2_acc[...] = jnp.zeros_like(w2_acc)

    tile = w_ref[...].astype(jnp.float32)
    row_acc[...] += jnp.sum(tile, axis=1, keepdims=True)
    w2_acc[0, 0] += jnp.sum(tile * tile)

    @pl.when(j == ncols - 1)
    def _finalize_stripe():
        s = row_acc[...]  # (bm, 1) row sums of this stripe
        out_ref[0] += jnp.sum(s)
        out_ref[1] += jnp.sum(s * s)
        out_ref[2] += 0.5 * w2_acc[0, 0]
        out_ref[3] = jnp.maximum(out_ref[3], jnp.max(s))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def vnge_q_stats_pallas(
    w: jax.Array, bm: int = 128, bn: int = 128, interpret: bool = False,
) -> jax.Array:
    """(n, n) symmetric W → (4,) f32 [S, Σs², Σ_E w², s_max]."""
    n, n2 = w.shape
    assert n == n2, "W must be square"
    assert n % bm == 0 and n % bn == 0, (
        f"n={n} must be divisible by block sizes ({bm}, {bn}); pad W first"
    )
    grid = (n // bm, n // bn)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((4,), lambda i, j: (0,)),
        out_shape=jax.ShapeDtypeStruct((4,), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="vnge_q_stats",
    )(w)
