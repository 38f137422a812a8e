"""Interpret-vs-oracle parity for the ``stream_tick`` megakernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.engine import StreamEngine, stack_deltas
from repro.graphs.generators import erdos_renyi
from repro.graphs.types import GraphDelta
from repro.kernels.parity import assert_close
from repro.kernels.stream_tick.ops import (stream_tick_fused,
                                           stream_tick_fused_stacked)
from repro.kernels.stream_tick.ref import stream_tick_ref

N_PAD, K_PAD, B = 32, 8, 8


def _shard_fixture(seed):
    """One shard's (states, stacked_deltas): B streams of mixed-size
    graphs, each delta mixing edge updates, a deletion, and a join."""
    rng = np.random.default_rng(seed)
    ns = [int(n) for n in np.linspace(10, N_PAD, B).astype(int)]
    graphs = [erdos_renyi(n, 0.2, seed=seed * 64 + s, weighted=True)
              for s, n in enumerate(ns)]
    states = StreamEngine.init_states(graphs, n_pad=N_PAD)
    ds = []
    for g in graphs:
        n = g.n_nodes
        iu, ju = np.triu_indices(n, k=1)
        pick = rng.choice(len(iu), size=4, replace=False)
        ii, jj = iu[pick], ju[pick]
        # parity-fixture setup, not a serving hot path
        w_old = np.asarray(g.weights)[ii, jj]  # lint: disable=per-item-host-sync
        dw = np.where(w_old > 0, -w_old, 0.8).astype(np.float32)
        ds.append(GraphDelta.from_arrays(ii, jj, dw, w_old, n_nodes=n,
                                         n_pad=N_PAD, k_pad=K_PAD,
                                         join=[n - 1], j_pad=2))
    return states, stack_deltas(ds)


def check_parity() -> None:
    states, stacked = _shard_fixture(4)
    d_got, s_got = stream_tick_fused(states, stacked, exact_smax=True)
    d_want, s_want = stream_tick_ref(states, stacked, exact_smax=True)
    assert_close("stream_tick dist", d_got, d_want, atol=1e-5)
    for field in ("q", "s_total", "s_max", "strengths", "node_mask"):
        assert_close(f"stream_tick {field}", getattr(s_got, field),
                     getattr(s_want, field), atol=1e-5)

    # Shard-stacked megakernel: ONE (S, B)-gridded launch over a whole
    # fleet layout group must match the XLA oracle vmapped over the
    # shard axis, field by field, to 1e-5.
    shards = [_shard_fixture(s) for s in (4, 5, 6)]
    sstates = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                     *[st for st, _ in shards])
    sdeltas = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                     *[d for _, d in shards])
    d_got, s_got = stream_tick_fused_stacked(sstates, sdeltas,
                                             exact_smax=True)
    d_want, s_want = jax.vmap(
        lambda st, d: stream_tick_ref(st, d, exact_smax=True))(
            sstates, sdeltas)
    assert_close("stream_tick_stacked dist", d_got, d_want, atol=1e-5)
    for field in ("q", "s_total", "s_max", "strengths", "node_mask"):
        assert_close(f"stream_tick_stacked {field}",
                     getattr(s_got, field), getattr(s_want, field),
                     atol=1e-5)
