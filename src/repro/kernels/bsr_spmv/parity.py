"""Interpret-vs-oracle parity for the ``bsr_spmv`` kernel."""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.graphs.generators import random_geometric_community
from repro.kernels.bsr_spmv.ops import bsr_matvec, dense_to_bsr
from repro.kernels.bsr_spmv.ref import bsr_matvec_ref
from repro.kernels.parity import assert_close


def check_parity() -> None:
    rng = np.random.default_rng(1)
    g = random_geometric_community(256, 4, 0.3, 0.01, seed=2)
    m = dense_to_bsr(np.asarray(g.weights), b=128)
    x = jnp.asarray(rng.random(m.n).astype(np.float32))
    assert_close("bsr_spmv", bsr_matvec(m, x, use_pallas=True),
                 bsr_matvec_ref(m, x), atol=1e-4)
