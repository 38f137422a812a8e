"""Theorem 2 incremental updates: exactness vs batch recomputation,
streams, and hypothesis properties over random deltas."""
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import (
    finger_state,
    jsdist_incremental,
    jsdist_stream,
    jsdist_tilde,
    update_state,
)
from repro.graphs import DenseGraph, GraphDelta, apply_delta_dense
from repro.graphs.generators import erdos_renyi
from repro.graphs.layout import NodeLayout
from repro.graphs.streams import churn_stream


def _random_delta(g, rng, k=20, delete_frac=0.4):
    n = g.n_nodes
    w = np.asarray(g.weights)
    pairs = {}
    for _ in range(k):
        i, j = rng.integers(0, n, 2)
        if i == j:
            continue
        i, j = min(i, j), max(i, j)
        w_old = w[i, j]
        if w_old > 0 and rng.random() < delete_frac:
            dw = -w_old
        else:
            dw = float(rng.uniform(0.1, 2.0))
        pairs[(i, j)] = (dw, w_old)
    ii = np.array([p[0] for p in pairs], np.int32)
    jj = np.array([p[1] for p in pairs], np.int32)
    dw = np.array([v[0] for v in pairs.values()], np.float32)
    wo = np.array([v[1] for v in pairs.values()], np.float32)
    return GraphDelta.from_arrays(ii, jj, dw, wo, n_nodes=n)


class TestTheorem2:
    @pytest.mark.parametrize("seed", range(5))
    def test_incremental_q_exact(self, seed):
        rng = np.random.default_rng(seed)
        g = erdos_renyi(80, 0.1, seed=seed, weighted=True)
        st_ = finger_state(g)
        delta = _random_delta(g, rng)
        new = update_state(st_, delta, exact_smax=True)
        ref = finger_state(apply_delta_dense(g, delta))
        assert abs(float(new.q) - float(ref.q)) < 2e-5
        assert abs(float(new.s_total) - float(ref.s_total)) < 1e-3
        assert abs(float(new.s_max) - float(ref.s_max)) < 1e-4
        np.testing.assert_allclose(np.asarray(new.strengths),
                                   np.asarray(ref.strengths), atol=1e-4)

    def test_paper_smax_never_decreases(self):
        """eq. (3)'s Δs_max is clamped at 0 (paper-faithful mode)."""
        rng = np.random.default_rng(1)
        g = erdos_renyi(50, 0.2, seed=1, weighted=True)
        st_ = finger_state(g)
        delta = _random_delta(g, rng, k=40, delete_frac=1.0)
        new = update_state(st_, delta, exact_smax=False)
        assert float(new.s_max) >= float(st_.s_max) - 1e-6

    def test_chained_updates_stay_exact(self):
        rng = np.random.default_rng(2)
        g = erdos_renyi(60, 0.15, seed=2, weighted=True)
        st_ = finger_state(g)
        for _ in range(10):
            delta = _random_delta(g, rng)
            st_ = update_state(st_, delta, exact_smax=True)
            g = apply_delta_dense(g, delta)
        ref = finger_state(g)
        assert abs(float(st_.q) - float(ref.q)) < 1e-4


class TestStreams:
    def test_stream_scan_matches_loop(self):
        seq = churn_stream(n=100, steps=8, seed=4, k_pad=256)
        st0 = finger_state(seq.graphs[0])
        # python loop
        st_ = st0
        loop_d = []
        for d in seq.deltas:
            dist, st_ = jsdist_incremental(st_, d)
            loop_d.append(float(dist))
        # single lax.scan over the stacked deltas
        stacked = GraphDelta(
            senders=jnp.stack([d.senders for d in seq.deltas]),
            receivers=jnp.stack([d.receivers for d in seq.deltas]),
            dw=jnp.stack([d.dw for d in seq.deltas]),
            w_old=jnp.stack([d.w_old for d in seq.deltas]),
            mask=jnp.stack([d.mask for d in seq.deltas]),
            n_nodes=seq.graphs[0].n_nodes,
        )
        scan_d, _ = jsdist_stream(st0, stacked)
        np.testing.assert_allclose(np.asarray(scan_d), np.asarray(loop_d),
                                   rtol=1e-3, atol=1e-5)

    def test_incremental_close_to_batch_tilde(self):
        seq = churn_stream(n=100, steps=5, seed=5, k_pad=256)
        st_ = finger_state(seq.graphs[0])
        for t, d in enumerate(seq.deltas):
            dist, st_ = jsdist_incremental(st_, d, exact_smax=True)
            ref = float(jsdist_tilde(seq.graphs[t], seq.graphs[t + 1]))
            assert abs(float(dist) - ref) < 5e-3


class TestRegressions:
    def test_self_loops_dropped_with_warning(self):
        """i == j slots would double-count strengths and violate
        Lemma 1's zero-diagonal assumption — they must be dropped."""
        import warnings

        from repro.graphs import EdgeList

        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            el = EdgeList.from_arrays([0, 1, 2], [0, 2, 2],
                                      [1.0, 2.0, 3.0], n_nodes=4)
            d = GraphDelta.from_arrays([3, 0], [3, 1], [1.0, 1.0],
                                       [0.0, 0.0], n_nodes=4)
        assert any("self-loop" in str(w.message) for w in rec)
        assert float(jnp.sum(el.mask)) == 1.0  # only (1, 2) survives
        assert float(jnp.sum(d.mask)) == 1.0   # only (0, 1) survives
        np.testing.assert_allclose(np.asarray(el.strengths()),
                                   [0.0, 2.0, 2.0, 0.0])

    def test_empty_graph_entropy_is_zero(self):
        """trace(L) = 0 used to yield H̃ = -ln(1e-30) ≈ 69 nats."""
        from repro.core import vnge_hat, vnge_tilde

        g = DenseGraph.from_weights(jnp.zeros((12, 12)))
        assert float(vnge_tilde(g)) == 0.0
        assert float(vnge_hat(g)) == 0.0
        assert float(finger_state(g).h_tilde()) == 0.0
        # jit-safe: no host branch on traced values
        assert float(jax.jit(vnge_tilde)(g)) == 0.0

    @pytest.mark.parametrize("seed", range(8))
    @pytest.mark.parametrize("method", ["dense", "compact"])
    def test_delta_to_empty_graph(self, seed, method):
        """Deleting every edge snaps to the canonical empty state (Q=1,
        H̃=0) instead of nan-poisoning Q or exploding H̃ on float
        cancellation residue (seed-dependent before the fix)."""
        g = erdos_renyi(30, 0.3, seed=seed, weighted=True)
        w = np.asarray(g.weights)
        iu, ju = np.triu_indices(30, k=1)
        nz = w[iu, ju] > 0
        d = GraphDelta.from_arrays(iu[nz], ju[nz], -w[iu, ju][nz],
                                   w[iu, ju][nz], n_nodes=30)
        st_ = update_state(finger_state(g), d, exact_smax=True,
                           method=method)
        assert float(st_.s_total) == 0.0
        assert float(st_.q) == 1.0
        assert float(st_.s_max) == 0.0
        assert float(st_.h_tilde()) == 0.0

    def test_shrink_to_one_edge_is_not_empty(self):
        """A delta deleting all but one small edge must NOT snap to the
        empty state — the survivor graph's statistics stay exact."""
        n = 40
        w = np.zeros((n, n), np.float32)
        iu, ju = np.triu_indices(n, k=1)
        w[iu, ju] = 100.0  # heavy graph: S ≈ 1.56e5
        w = w + w.T
        g = DenseGraph.from_weights(jnp.asarray(w))
        keep = (0, 1)
        dw = np.full(len(iu), -100.0, np.float32)
        wo = np.full(len(iu), 100.0, np.float32)
        ki = np.where((iu == keep[0]) & (ju == keep[1]))[0][0]
        dw[ki] = -99.5  # survivor edge keeps weight 0.5
        for method in ("dense", "compact"):
            d = GraphDelta.from_arrays(iu, ju, dw, wo, n_nodes=n)
            st_ = update_state(finger_state(g), d, exact_smax=True,
                               method=method)
            ref = finger_state(apply_delta_dense(g, d))
            assert float(st_.s_total) > 0.5  # not snapped to empty
            assert abs(float(st_.s_total) - float(ref.s_total)) < 0.5
            assert abs(float(st_.h_tilde()) - float(ref.h_tilde())) < 1e-3

    def test_revive_from_empty_graph(self):
        """Adding edges to an empty state reproduces the from-scratch
        state exactly (c' = 1/ΔS path, beyond the paper's S > 0)."""
        empty = finger_state(DenseGraph.from_weights(jnp.zeros((12, 12))))
        d = GraphDelta.from_arrays([0, 1, 5], [1, 2, 9],
                                   [1.5, 0.5, 2.0], [0.0, 0.0, 0.0],
                                   n_nodes=12)
        for method in ("dense", "compact"):
            st_ = update_state(empty, d, exact_smax=True, method=method)
            ref = finger_state(apply_delta_dense(
                DenseGraph.from_weights(jnp.zeros((12, 12))), d))
            assert abs(float(st_.q) - float(ref.q)) < 1e-6
            assert abs(float(st_.h_tilde()) - float(ref.h_tilde())) < 1e-6

    def test_empty_then_continue_stream_stays_finite(self):
        """A stream that empties and refills keeps emitting finite
        scores (was nan-forever)."""
        g = erdos_renyi(25, 0.3, seed=2, weighted=True)
        st_ = finger_state(g)
        w = np.asarray(g.weights)
        iu, ju = np.triu_indices(25, k=1)
        nz = w[iu, ju] > 0
        kill = GraphDelta.from_arrays(iu[nz], ju[nz], -w[iu, ju][nz],
                                      w[iu, ju][nz], n_nodes=25)
        refill = GraphDelta.from_arrays([0, 3], [1, 4], [1.0, 2.0],
                                        [0.0, 0.0], n_nodes=25)
        d1, st_ = jsdist_incremental(st_, kill, exact_smax=True)
        d2, st_ = jsdist_incremental(st_, refill, exact_smax=True)
        assert np.isfinite(float(d1)) and np.isfinite(float(d2))
        assert np.isfinite(float(st_.q))

    def test_stream_synthesizers_shape_stable(self):
        """dos/hic sequences emit one common padded delta shape, so a
        jitted incremental step compiles exactly once."""
        from repro.graphs.streams import (
            dos_attack_sequence,
            hic_bifurcation_sequence,
        )

        seq, _ = dos_attack_sequence(n=100, n_graphs=5, seed=0)
        assert len({d.dw.shape for d in seq.deltas}) == 1
        seq2 = hic_bifurcation_sequence(n=50, n_samples=5,
                                        bifurcation_at=2, seed=0)
        assert len({d.dw.shape for d in seq2.deltas}) == 1
        # and the common shape survives an explicit k_pad
        seq3, _ = dos_attack_sequence(n=100, n_graphs=4, seed=1,
                                      k_pad=64)
        assert {d.dw.shape for d in seq3.deltas} == {(64,)}


# (kwargs of from_arrays, ValueError match or None). Lanes arrive
# unordered and padded short of k_pad; joins precede leaves.
_HOST_DELTA_CASES = {
    "lanes": (dict(senders=[3, 0, 5], receivers=[1, 2, 4],
                   dw=[0.5, -1.0, 2.0], w_old=[0.0, 1.0, 0.5],
                   n_nodes=6, k_pad=5), None),
    "self_loop_dropped": (dict(senders=[3, 2], receivers=[3, 1],
                               dw=[1.0, 0.5], w_old=[0.0, 0.0],
                               n_nodes=4, k_pad=3), None),
    "joins_and_leaves": (dict(senders=[0], receivers=[7], dw=[1.0],
                              w_old=[0.0], n_nodes=6, n_pad=8, k_pad=2,
                              join=[6, 7], leave=[2], j_pad=4), None),
    "layout_stamp": (dict(senders=[1], receivers=[0], dw=[1.0],
                          w_old=[0.0], n_nodes=4,
                          layout=NodeLayout(8, generation=3)), None),
    "k_over_k_pad": (dict(senders=[0, 1, 2], receivers=[1, 2, 3],
                          dw=[1.0] * 3, w_old=[0.0] * 3, n_nodes=4,
                          k_pad=2), "exceed k_pad=2"),
    "join_outside_n_pad": (dict(senders=[0], receivers=[1], dw=[1.0],
                                w_old=[0.0], n_nodes=4, n_pad=4,
                                join=[4]), "outside the n_pad=4"),
    "leave_outside_n_pad": (dict(senders=[0], receivers=[1], dw=[1.0],
                                 w_old=[0.0], n_nodes=4, leave=[-1]),
                            "outside the n_pad=4"),
    "j_over_j_pad": (dict(senders=[0], receivers=[1], dw=[1.0],
                          w_old=[0.0], n_nodes=8, join=[2, 3],
                          leave=[4], j_pad=2), "exceed j_pad=2"),
}


@pytest.mark.parametrize("case", sorted(_HOST_DELTA_CASES))
def test_host_from_arrays_is_from_arrays_on_the_host(case):
    """`GraphDelta.host_from_arrays` pads and validates exactly as
    `from_arrays` does, and differs only in where the leaves live:
    numpy on the host against device arrays."""
    import warnings

    kwargs, error = _HOST_DELTA_CASES[case]
    if error is not None:
        for build in (GraphDelta.host_from_arrays, GraphDelta.from_arrays):
            with pytest.raises(ValueError, match=error):
                build(**kwargs)
        return
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        host = GraphDelta.host_from_arrays(**kwargs)
        dev = GraphDelta.from_arrays(**kwargs)
    host_leaves, host_def = jax.tree_util.tree_flatten(host)
    dev_leaves, dev_def = jax.tree_util.tree_flatten(dev)
    assert host_def == dev_def  # same n_nodes, generation, None leaves
    for h, d in zip(host_leaves, dev_leaves):
        assert isinstance(h, np.ndarray) and not isinstance(h, jax.Array)
        assert isinstance(d, jax.Array)
        assert h.dtype == d.dtype and h.dtype in (np.int32, np.float32)
        np.testing.assert_array_equal(h, np.asarray(d))
    assert np.all(host.senders <= host.receivers)
    if "k_pad" in kwargs:
        assert host.senders.shape == (kwargs["k_pad"],)
    if case == "self_loop_dropped":
        assert sum("self-loop" in str(w.message) for w in rec) == 2
        assert host.mask.tolist() == [1.0, 0.0, 0.0]
        assert (host.senders[0], host.receivers[0]) == (1, 2)
    if case == "joins_and_leaves":
        assert host.node_ids.tolist() == [6, 7, 2, 0]
        assert host.node_flag.tolist() == [1.0, 1.0, -1.0, 0.0]
    if case == "layout_stamp":
        assert (host.n_nodes, host.layout_generation) == (8, 3)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.integers(1, 30))
def test_property_incremental_matches_batch(seed, k):
    rng = np.random.default_rng(seed)
    g = erdos_renyi(40, 0.2, seed=seed, weighted=True)
    st_ = finger_state(g)
    delta = _random_delta(g, rng, k=k)
    new = update_state(st_, delta, exact_smax=True)
    ref = finger_state(apply_delta_dense(g, delta))
    assert abs(float(new.q) - float(ref.q)) < 5e-5
