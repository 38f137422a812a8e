"""Interpret-vs-oracle parity for the ``vnge_q`` kernel."""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.kernels.parity import assert_close
from repro.kernels.vnge_q.ops import vnge_q_stats
from repro.kernels.vnge_q.ref import vnge_q_stats_ref


def check_parity() -> None:
    rng = np.random.default_rng(0)
    w = rng.random((256, 256)).astype(np.float32)
    w = np.triu(w, 1)
    w = jnp.asarray(w + w.T)
    assert_close("vnge_q", vnge_q_stats(w, use_pallas=True),
                 vnge_q_stats_ref(w), atol=1e-4)
