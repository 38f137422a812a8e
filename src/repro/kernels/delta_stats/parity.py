"""Interpret-vs-oracle parity for the ``delta_stats`` kernel."""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.core.state import finger_state
from repro.graphs.generators import erdos_renyi
from repro.graphs.types import GraphDelta
from repro.kernels.delta_stats.ops import delta_stats_fused
from repro.kernels.parity import assert_close


def check_parity() -> None:
    rng = np.random.default_rng(3)
    g = erdos_renyi(48, 0.2, seed=3, weighted=True).pad_to(64)
    state = finger_state(g)
    iu, ju = np.triu_indices(48, k=1)
    pick = rng.choice(len(iu), size=12, replace=False)
    ii, jj = iu[pick], ju[pick]
    w_old = np.asarray(g.weights)[ii, jj]
    dw = np.where(w_old > 0, -w_old, 0.6).astype(np.float32)
    delta = GraphDelta.from_arrays(ii, jj, dw, w_old, n_nodes=64,
                                   k_pad=16)
    got = jnp.stack(delta_stats_fused(state, delta, use_pallas=True))
    want = jnp.stack(delta_stats_fused(state, delta, use_pallas=False))
    assert_close("delta_stats", got, want, atol=1e-5)
