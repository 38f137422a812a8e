"""Interpret-vs-oracle parity for the ``sparse_tick`` kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sparse import SparseLayout, sparse_states_from_graphs
from repro.engine import stack_deltas
from repro.graphs.generators import erdos_renyi
from repro.graphs.types import GraphDelta
from repro.kernels.parity import assert_close
from repro.kernels.sparse_tick.ops import (sparse_tick_fused,
                                           sparse_tick_fused_stacked)
from repro.kernels.sparse_tick.ref import sparse_tick_ref

N_VIRTUAL, K_PAD, B = 4096, 8, 8
LAYOUT = SparseLayout(n_slots=64, m_pad=256)


def _shard_fixture(seed):
    """One shard's (states, stacked slot-space deltas): B streams of
    small graphs addressed in a huge virtual space, each delta mixing
    edge updates with a join deep inside the virtual space no dense
    n_pad=64 layout could address."""
    rng = np.random.default_rng(seed)
    ns = [int(n) for n in np.linspace(10, 30, B).astype(int)]
    graphs = [erdos_renyi(n, 0.2, seed=seed * 64 + s, weighted=True)
              for s, n in enumerate(ns)]
    states, slot_maps = sparse_states_from_graphs(
        graphs, LAYOUT, n_virtual=N_VIRTUAL)
    ds = []
    for g, sm in zip(graphs, slot_maps):
        n = g.n_nodes
        iu, ju = np.triu_indices(n, k=1)
        pick = rng.choice(len(iu), size=4, replace=False)
        ii, jj = iu[pick], ju[pick]
        # parity-fixture setup, not a serving hot path
        w_old = np.asarray(g.weights)[ii, jj]  # lint: disable=per-item-host-sync
        dw = np.where(w_old > 0, -w_old, 0.8).astype(np.float32)
        ii = np.concatenate([ii, [N_VIRTUAL - 1]])
        jj = np.concatenate([jj, [0]])
        dw = np.concatenate([dw, [0.6]]).astype(np.float32)
        w_old = np.concatenate([w_old, [0.0]]).astype(np.float32)
        virt = GraphDelta.from_arrays(
            ii, jj, dw, w_old, n_nodes=N_VIRTUAL, k_pad=K_PAD,
            join=[N_VIRTUAL - 1], j_pad=2)
        ds.append(sm.translate(virt))
    return states, stack_deltas(ds)


def check_parity() -> None:
    states, stacked = _shard_fixture(11)
    d_got, s_got = sparse_tick_fused(states, stacked, exact_smax=True)
    d_want, s_want = sparse_tick_ref(states, stacked, exact_smax=True)
    assert_close("sparse_tick dist", d_got, d_want, atol=1e-5)
    for field in ("q", "s_total", "s_max", "strengths", "node_mask",
                  "edge_weights"):
        assert_close(f"sparse_tick {field}", getattr(s_got, field),
                     getattr(s_want, field), atol=1e-5)

    # Shard-stacked scatter-tick: ONE (S, B)-gridded launch over a
    # whole same-capacity shard group must match the XLA oracle
    # vmapped over the shard axis, field by field, to 1e-5.
    shards = [_shard_fixture(s) for s in (11, 12, 13)]
    sstates = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                     *[st for st, _ in shards])
    sdeltas = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                     *[d for _, d in shards])
    d_got, s_got = sparse_tick_fused_stacked(sstates, sdeltas,
                                             exact_smax=True)
    d_want, s_want = jax.vmap(
        lambda st, d: sparse_tick_ref(st, d, exact_smax=True))(
            sstates, sdeltas)
    assert_close("sparse_tick_stacked dist", d_got, d_want, atol=1e-5)
    for field in ("q", "s_total", "s_max", "strengths", "node_mask",
                  "edge_weights"):
        assert_close(f"sparse_tick_stacked {field}",
                     getattr(s_got, field), getattr(s_want, field),
                     atol=1e-5)
