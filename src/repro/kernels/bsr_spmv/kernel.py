"""Pallas TPU kernel: block-sparse (ELL-of-blocks) SpMV y = W x.

The power-iteration matvec behind FINGER-Ĥ's λ_max. GPU implementations
use CSR + warp-per-row gathers; that idiom is latency-bound on TPU, so we
instead stream MXU-aligned (b × b) dense blocks HBM→VMEM and issue a
dense dot per block (DESIGN.md §3). x resides fully in VMEM — for the
paper's graph sizes (n up to a few hundred thousand) x is ≤ ~2 MB, far
under the ~16 MB VMEM budget; the block stream dominates HBM traffic and
arithmetic intensity is b/8 FLOP/byte (≈16 at b=128), comfortably above
the VPU roofline knee for this memory-bound op.

Grid: (n_rb,). Per row-stripe, a fori_loop over the stripe's block slots:
dynamic-slice x at col_id·b, dense (b, b) @ (b, 1) dot, accumulate in
VREGs, single VMEM write of the stripe's y block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(col_ids_ref, x_ref, values_ref, y_ref, *, max_bpr: int, b: int):
    def body(k, acc):
        col = col_ids_ref[0, k]
        xb = x_ref[pl.ds(col * b, b), :]  # (b, 1)
        blk = values_ref[0, k]  # (b, b)
        return acc + jnp.dot(blk, xb, preferred_element_type=jnp.float32)

    acc0 = jnp.zeros((b, 1), jnp.float32)
    y_ref[0] = jax.lax.fori_loop(0, max_bpr, body, acc0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_matvec_pallas(values, col_ids, x, interpret: bool = False):
    """values (n_rb, max_bpr, b, b), col_ids (n_rb, max_bpr), x (n,) → y (n,)."""
    n_rb, max_bpr, b, _ = values.shape
    n = n_rb * b
    x2 = x.reshape(n, 1).astype(jnp.float32)
    y = pl.pallas_call(
        functools.partial(_kernel, max_bpr=max_bpr, b=b),
        grid=(n_rb,),
        in_specs=[
            pl.BlockSpec((1, max_bpr), lambda i: (i, 0)),
            pl.BlockSpec((n, 1), lambda i: (0, 0)),  # x fully resident
            pl.BlockSpec((1, max_bpr, b, b), lambda i: (i, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, b, 1), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rb, b, 1), jnp.float32),
        interpret=interpret,
        name="bsr_matvec",
    )(col_ids, x2, values)
    return y.reshape(n)
