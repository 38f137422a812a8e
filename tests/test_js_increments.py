"""The served Jensen-Shannon divergence, computed from a tick's
increments, against float64 numpy on a graph large enough that a
float32 difference of whole entropies is rounding: a weighted graph
with S > 2**24 whose tick moves k/m ≈ 4e-4 of its edges."""
import jax
import numpy as np
import pytest

from repro.core import finger_state
from repro.core.incremental import delta_moments, update_state
from repro.core.jsdist import (
    _js_from_entropies,
    js_divergence_from_increments,
    jsdist_incremental,
)
from repro.core.sparse import (
    SparseLayout,
    sparse_jsdist_tick,
    sparse_state_from_graph,
)
from repro.graphs import EdgeList, GraphDelta

N, M, K = 20_000, 250_000, 100


def _graph_and_tick(seed, hub):
    """(graph, virtual delta, float64 divergence). ``hub`` links the
    heaviest node to a new neighbour, so s_max rises a little."""
    rng = np.random.default_rng(seed)
    lo = rng.integers(0, N, int(M * 1.1))
    hi = rng.integers(0, N, lo.size)
    keys = np.unique(np.minimum(lo, hi) * N + np.maximum(lo, hi))
    keys = rng.permutation(keys[keys // N != keys % N])[:M]
    lo, hi = keys // N, keys % N
    w = rng.uniform(50.0, 150.0, M).astype(np.float32)
    g = EdgeList.from_arrays(lo, hi, w, n_nodes=N)
    s = np.zeros(N)
    np.add.at(s, lo, w.astype(np.float64))
    np.add.at(s, hi, w.astype(np.float64))
    # the tick: 60 new edges, 20 re-weights, 20 deletions
    pick = rng.choice(M, 40, replace=False)
    a = rng.integers(0, N, 200)
    b = rng.integers(0, N, 200)
    if hub:
        a[0] = int(np.argmax(s))
    born = np.minimum(a, b) * N + np.maximum(a, b)
    born = born[(a != b) & ~np.isin(born, keys)]
    born = born[np.sort(np.unique(born, return_index=True)[1])][:60]
    d_lo = np.concatenate([lo[pick], born // N])
    d_hi = np.concatenate([hi[pick], born % N])
    w_old = np.concatenate([w[pick], np.zeros(born.size, np.float32)])
    dw = np.concatenate([
        rng.uniform(10.0, 40.0, 20).astype(np.float32), -w[pick[20:]],
        rng.uniform(50.0, 150.0, born.size).astype(np.float32)])
    if hub:
        dw[40] = 0.5  # the heaviest node's new edge
    delta = GraphDelta.from_arrays(d_lo, d_hi, dw, w_old, n_nodes=N,
                                   k_pad=128)

    def entropy(t):
        st = s.copy()
        step = dw.astype(np.float64) * t
        np.add.at(st, d_lo, step)
        np.add.at(st, d_hi, step)
        old = w_old.astype(np.float64)
        sum_w2 = np.sum(w.astype(np.float64) ** 2) \
            + np.sum((old + step) ** 2 - old ** 2)
        total = st.sum()
        s_max = max(s.max(), st[np.concatenate([d_lo, d_hi])].max())
        q = 1.0 - (np.sum(st * st) + 2.0 * sum_w2) / total ** 2
        return -q * np.log(2.0 * s_max / total)

    div = entropy(0.5) - 0.5 * (entropy(0.0) + entropy(1.0))
    assert g.weights.shape[0] == M and 2 * w.sum() > 2 ** 24
    return g, delta, div


@pytest.fixture(scope="module", params=[(3, False), (4, True)],
                ids=["spread", "hub"])
def case(request):
    return _graph_and_tick(*request.param)


@pytest.mark.parametrize("method", ["dense", "compact"])
def test_increments_match_float64(case, method):
    g, delta, want = case
    with jax.default_matmul_precision("highest"):
        state = finger_state(g.pad_to(N))
        half = update_state(state, delta.scaled(0.5), method=method)
        full = update_state(state, delta, method=method)
        got = float(js_divergence_from_increments(
            state, half, full, *delta_moments(state, delta, method)))
        dist, _ = jsdist_incremental(state, delta, method=method)
    assert want > 0
    assert abs(got - want) <= 1e-2 * want, (got, want)
    assert abs(float(dist) ** 2 - want) <= 1e-2 * want


def test_entropy_difference_fails_the_same_test(case):
    g, delta, want = case
    with jax.default_matmul_precision("highest"):
        state = finger_state(g.pad_to(N))
        half = update_state(state, delta.scaled(0.5), method="compact")
        full = update_state(state, delta, method="compact")
        old = float(_js_from_entropies(half.h_tilde(), state.h_tilde(),
                                       full.h_tilde())) ** 2
    assert abs(old - want) > 1e-2 * want, (old, want)


def test_sparse_tick_matches_float64(case):
    g, delta, want = case
    layout = SparseLayout(n_slots=N, m_pad=M + 1024)
    with jax.default_matmul_precision("highest"):
        state, slot_map = sparse_state_from_graph(g, layout)
        dist, _ = sparse_jsdist_tick(state, slot_map.translate(delta))
    assert abs(float(dist) ** 2 - want) <= 1e-2 * want
