"""Pallas TPU kernels for the FINGER compute hot-spots.

- ``vnge_q``        : fused one-HBM-pass Lemma-1 statistics over dense W
- ``bsr_spmv``      : block-sparse Laplacian matvec (λ_max power iteration)
- ``delta_stats``   : fused Theorem-2 ΔS/ΔQ/Δs_max over sorted endpoints
- ``stream_tick``   : the single-pass batched serving tick — mask
  gating, node join/leave, delta statistics, state update and JSdist
  for B streams in one kernel launch (``method="fused_tick"``)

Each kernel ships kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper with CPU interpret fallback), ref.py (pure-jnp oracle) and
parity.py (interpret-vs-oracle check, auto-discovered by
`repro.kernels.parity` and run by the `repro.analysis.vmem` checker). Shared dispatch policy — backend
detection, interpret fallback, the configurable VMEM budget — lives in
`repro.kernels.dispatch`; the layout is enforced by the
``kernel-package-triple`` lint rule in `repro.analysis.lint`.
"""
from repro.kernels.bsr_spmv.ops import (
    BsrMatrix,
    bsr_matvec,
    dense_to_bsr,
    power_iteration_lmax_bsr,
)
from repro.kernels.delta_stats.ops import (
    delta_stats_fused,
    prepare_sorted_delta,
)
from repro.kernels.stream_tick.ops import (
    fits_fused_tick,
    stream_tick_fused,
)
from repro.kernels.vnge_q.ops import (
    quadratic_q_dense,
    vnge_q_stats,
    vnge_tilde_dense,
)
