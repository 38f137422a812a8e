"""Sparse slot-space serving tick (`kernels.sparse_tick`) vs the
vmapped oracle and the dense `stream_tick` path.

Acceptance anchors (ISSUE 7):
- the fused sparse tick matches the vmapped slot-space oracle to 1e-5
  on every path — join/leave slots, edge-store allocate/free lanes,
  graph-emptying and reviving deltas, and empty (all-masked) ticks
  (property tests);
- relabeling invariance end to end: the same virtual delta sequence
  run through `SlotMap` translation + sparse ticks and through the
  dense `stream_tick` path yields the same FINGER statistics and
  JSdist scores to 1e-5;
- slot-space preconditions and capacity exhaustion fail by name
  (`SparseCapacityError`, named `ValueError`s) instead of silently
  mis-scattering;
- the `method="sparse_tick"` service lifecycle (ingest translation,
  virtual repad, `grow_capacity`) preserves score parity with a dense
  control service across migrations.
"""
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import SparseCapacityError, finger_state
from repro.core.sparse import (
    SlotMap,
    SparseLayout,
    sparse_state_from_graph,
    sparse_states_from_graphs,
)
from repro.engine import StreamEngine, stack_deltas
from repro.graphs import DenseGraph, EdgeList, GraphDelta
from repro.graphs.generators import erdos_renyi
from repro.kernels.sparse_tick.ops import (
    fits_sparse_tick,
    sparse_tick_fused,
)
from repro.kernels.sparse_tick.ref import sparse_tick_ref
from repro.kernels.stream_tick.ref import stream_tick_ref

from slotmap_reference import as_json
from repro.serving import (
    FingerService,
    IngestError,
    LayoutMigrationError,
    ServiceConfig,
    ServiceConfigError,
    TopKSpec,
)

_SPARSE_FIELDS = ("q", "s_total", "s_max", "strengths", "node_mask",
                  "edge_weights")


def _assert_sparse_tick_matches(states, stacked, exact_smax,
                                atol=1e-5, label=""):
    """Fused kernel vs the vmapped oracle on one tick; returns the
    fused result so test loops advance on the kernel's own output."""
    d_ref, s_ref = sparse_tick_ref(states, stacked,
                                   exact_smax=exact_smax)
    d_f, s_f = sparse_tick_fused(states, stacked,
                                 exact_smax=exact_smax)
    np.testing.assert_allclose(np.asarray(d_f), np.asarray(d_ref),
                               atol=atol, err_msg=f"{label}: dist")
    for field in _SPARSE_FIELDS:
        np.testing.assert_allclose(
            np.asarray(getattr(s_f, field)),
            np.asarray(getattr(s_ref, field)),
            atol=atol, err_msg=f"{label}: {field}")
    return d_f, s_f


class _VirtStream:
    """One tenant over its own virtual universe, emitting the same
    tick as a virtual-space delta (for `SlotMap` translation) and as a
    dense-layout delta (for the `stream_tick` control path)."""

    def __init__(self, n0, n_reserve, seed):
        self.n_total = n0 + n_reserve
        rng = np.random.default_rng(seed)
        w = np.zeros((self.n_total, self.n_total), np.float32)
        upper = np.triu(rng.random((n0, n0)) < 0.3, k=1)
        w[:n0, :n0] = upper * rng.uniform(0.5, 1.5, (n0, n0))
        w[:n0, :n0] += w[:n0, :n0].T
        self.w = w
        self.n0 = n0
        self.active = list(range(n0))
        self.reserve = list(range(n0, self.n_total))
        self.joined = []

    def base_graph(self):
        return DenseGraph.from_weights(
            jnp.asarray(self.w[:self.n0, :self.n0]))

    def dense_graph(self, n_pad):
        return DenseGraph.from_weights(
            jnp.asarray(self.w[:self.n0, :self.n0]), n_pad=n_pad)

    def random_tick(self, rng, k):
        """Mutate the mirror and return (ii, jj, dw, w_old, join,
        leave) in virtual ids."""
        join, leave, ii, jj = [], [], [], []
        if self.reserve and rng.random() < 0.4:
            v = self.reserve.pop(0)
            join.append(v)
            self.joined.append(v)
            self.active.append(v)
            for u in rng.choice(
                    [a for a in self.active if a != v],
                    size=min(2, len(self.active) - 1), replace=False):
                ii.append(min(v, int(u)))
                jj.append(max(v, int(u)))
        elif self.joined and rng.random() < 0.4:
            v = self.joined.pop(0)
            leave.append(v)
            self.active.remove(v)
            for u in np.flatnonzero(self.w[v]):
                ii.append(min(v, int(u)))
                jj.append(max(v, int(u)))
        pairs = {(a, b) for a, b in zip(ii, jj)}
        while len(pairs) < k and len(self.active) >= 2:
            a, b = rng.choice(self.active, size=2, replace=False)
            a, b = min(int(a), int(b)), max(int(a), int(b))
            if a != b:
                pairs.add((a, b))
        ii = np.array([p[0] for p in pairs], np.int32)
        jj = np.array([p[1] for p in pairs], np.int32)
        w_old = self.w[ii, jj]
        dw = np.where(
            np.isin(ii, leave) | np.isin(jj, leave) | (w_old > 0),
            -w_old, rng.uniform(0.2, 1.5, len(ii)).astype(np.float32))
        dw = dw.astype(np.float32)
        keep = np.abs(dw) > 1e-12
        ii, jj, dw, w_old = ii[keep], jj[keep], dw[keep], w_old[keep]
        self.w[ii, jj] += dw
        self.w[jj, ii] += dw
        return ii, jj, dw, w_old, join, leave


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 10_000), exact=st.booleans())
def test_property_sparse_matches_dense_join_leave(seed, exact):
    """Random delta sequences with joins/leaves: the fused sparse tick
    matches its oracle, and — by relabeling invariance — the dense
    `stream_tick` path on the same virtual sequence, to 1e-5."""
    rng = np.random.default_rng(seed)
    n_virtual, k_pad, j_pad, ticks, b = 48, 16, 2, 4, 3
    layout = SparseLayout(n_slots=24, m_pad=128)
    streams = [_VirtStream(n0=int(rng.integers(5, 12)), n_reserve=3,
                           seed=seed * 13 + i) for i in range(b)]
    sparse_states, slot_maps = sparse_states_from_graphs(
        [s.base_graph() for s in streams], layout,
        n_virtual=n_virtual)
    dense_states = StreamEngine.init_states(
        [s.dense_graph(n_virtual) for s in streams], n_pad=n_virtual)
    for t in range(ticks):
        virt_ds, dense_ds = [], []
        for s in streams:
            ii, jj, dw, w_old, join, leave = s.random_tick(rng, k=4)
            virt_ds.append(GraphDelta.from_arrays(
                ii, jj, dw, w_old, n_nodes=s.n_total, k_pad=k_pad,
                join=join, leave=leave, j_pad=j_pad))
            dense_ds.append(GraphDelta.from_arrays(
                ii, jj, dw, w_old, n_nodes=s.n_total,
                n_pad=n_virtual, k_pad=k_pad, join=join, leave=leave,
                j_pad=j_pad))
        stacked = stack_deltas(
            [sm.translate(d) for sm, d in zip(slot_maps, virt_ds)])
        d_sp, sparse_states = _assert_sparse_tick_matches(
            sparse_states, stacked, exact, label=f"tick {t}")
        d_dn, dense_states = stream_tick_ref(
            dense_states, stack_deltas(dense_ds), exact_smax=exact)
        np.testing.assert_allclose(
            np.asarray(d_sp), np.asarray(d_dn), atol=1e-5,
            err_msg=f"tick {t}: sparse vs dense dist")
        for field in ("q", "s_total", "s_max"):
            np.testing.assert_allclose(
                np.asarray(getattr(sparse_states, field)),
                np.asarray(getattr(dense_states, field)), atol=1e-5,
                err_msg=f"tick {t}: sparse vs dense {field}")
        # relabeling invariance: the nonzero strength multisets agree
        # (slot ids permute virtual ids; padding only adds zeros)
        n_slots = layout.n_slots
        np.testing.assert_allclose(
            np.sort(np.asarray(sparse_states.strengths), axis=-1),
            np.sort(np.asarray(dense_states.strengths),
                    axis=-1)[:, -n_slots:],
            atol=1e-5, err_msg=f"tick {t}: strength multiset")


class TestEdgeCases:
    N_VIRTUAL = 64

    def _dead_live(self):
        dead = DenseGraph.from_weights(
            jnp.zeros((4, 4)), node_mask=np.zeros(4, np.float32))
        live = erdos_renyi(12, 0.3, seed=0, weighted=True)
        layout = SparseLayout(n_slots=16, m_pad=32)
        return sparse_states_from_graphs(
            [dead, live], layout, n_virtual=self.N_VIRTUAL)

    def _empty_delta(self, k_pad=4):
        return GraphDelta.from_arrays(
            [], [], [], [], n_nodes=self.N_VIRTUAL, k_pad=k_pad,
            j_pad=2)

    def test_empty_delta_tick(self):
        states, maps = self._dead_live()
        stacked = stack_deltas(
            [sm.translate(self._empty_delta()) for sm in maps])
        d, out = _assert_sparse_tick_matches(states, stacked,
                                             exact_smax=True,
                                             label="empty")
        # the dead stream keeps emitting finite zero scores
        assert float(d[0]) == 0.0
        assert np.isfinite(np.asarray(d)).all()
        assert float(out.q[0]) == 1.0

    def test_graph_emptying_then_reviving(self):
        """Deleting every edge snaps to the canonical empty state and
        returns every edge slot to the free list; a join + first-edge
        delta revives the stream — all matching the oracle."""
        states, maps = self._dead_live()
        live = erdos_renyi(12, 0.3, seed=0, weighted=True)
        w = np.asarray(live.weights)
        iu, ju = np.nonzero(np.triu(w, 1))
        kill = GraphDelta.from_arrays(
            iu, ju, -w[iu, ju], w[iu, ju], n_nodes=12, k_pad=32,
            j_pad=2)
        stacked = stack_deltas([maps[0].translate(self._empty_delta(32)),
                                maps[1].translate(kill)])
        _, after = _assert_sparse_tick_matches(states, stacked,
                                               exact_smax=True,
                                               label="emptying")
        assert abs(float(after.s_total[1])) < 1e-6
        assert float(after.q[1]) == 1.0
        # every edge slot freed back to the SlotMap
        assert maps[1].n_free_edges == maps[1].layout.m_pad
        # revive deep inside the virtual space, past any dense
        # n_pad=16 layout's addressing
        revive = GraphDelta.from_arrays(
            [50], [60], [2.0], [0.0], n_nodes=self.N_VIRTUAL, k_pad=4,
            join=[50, 60], j_pad=2)
        stacked = stack_deltas([maps[0].translate(self._empty_delta()),
                                maps[1].translate(revive)])
        _, out = _assert_sparse_tick_matches(after, stacked,
                                             exact_smax=True,
                                             label="revive")
        # revive-from-empty is exact: H̃ matches a fresh two-node graph
        ref = finger_state(DenseGraph.from_weights(
            2.0 * jnp.eye(2)[::-1], n_pad=16))
        got = out.dense_view().h_tilde()
        assert abs(float(np.asarray(got)[1]) - float(ref.h_tilde())) \
            < 1e-6

    def test_untranslated_delta_rejected_by_name(self):
        states, _ = self._dead_live()
        virt = GraphDelta.from_arrays(
            [0], [1], [0.5], [0.0], n_nodes=self.N_VIRTUAL, k_pad=4)
        with pytest.raises(ValueError, match="edge_slots"):
            sparse_tick_fused(states, stack_deltas([virt, virt]))

    def test_wrong_slot_capacity_rejected_by_name(self):
        states, _ = self._dead_live()
        other = SlotMap(SparseLayout(n_slots=32, m_pad=32),
                        n_virtual=self.N_VIRTUAL)
        d = other.translate(GraphDelta.from_arrays(
            [0], [1], [0.5], [0.0], n_nodes=self.N_VIRTUAL, k_pad=4,
            join=[0, 1], j_pad=2))
        with pytest.raises(ValueError, match="n_slots"):
            sparse_tick_fused(states, stack_deltas([d, d]))

    def test_capacity_exhaustion_raises_by_name(self):
        sm = SlotMap(SparseLayout(n_slots=2, m_pad=1), n_virtual=100)
        with pytest.raises(SparseCapacityError, match="node slots"):
            sm.translate(GraphDelta.from_arrays(
                [], [], [], [], n_nodes=100, k_pad=4,
                join=[0, 1, 2], j_pad=4))
        with pytest.raises(SparseCapacityError):
            sm.translate(GraphDelta.from_arrays(
                [0, 0], [1, 2], [0.5, 0.5], [0.0, 0.0], n_nodes=100,
                k_pad=4, join=[0, 1, 2], j_pad=4))
        # rejection is atomic: the map stays untouched
        assert sm.n_free_nodes == 2
        assert sm.n_free_edges == 1

    def test_out_of_virtual_space_raises_by_name(self):
        sm = SlotMap(SparseLayout(n_slots=8, m_pad=8), n_virtual=16)
        with pytest.raises(ValueError, match="virtual space"):
            sm.translate(GraphDelta.from_arrays(
                [0], [99], [0.5], [0.0], n_nodes=100, k_pad=4))

    def test_edge_list_admission_matches_dense(self):
        """An `EdgeList` in a wide id space builds the same slot-space
        state and SlotMap as its dense form, without densifying."""
        g = erdos_renyi(12, 0.3, seed=4, weighted=True)
        ids = np.random.default_rng(4).choice(
            self.N_VIRTUAL, 12, replace=False)
        w = np.asarray(g.weights)
        iu, ju = np.nonzero(np.triu(w, 1))
        mask = np.zeros(self.N_VIRTUAL, np.float32)
        mask[ids] = 1.0
        el = EdgeList.from_arrays(ids[iu], ids[ju], w[iu, ju],
                                  n_nodes=self.N_VIRTUAL, node_mask=mask)
        layout = SparseLayout(n_slots=16, m_pad=32)
        got, got_map = sparse_state_from_graph(el, layout)
        want, want_map = sparse_state_from_graph(el.to_dense(), layout)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert as_json(got_map) == as_json(want_map)

    def test_translated_deltas_stack_on_host(self):
        """Slot-space deltas leave `SlotMap` as host arrays and stack
        there, so ingesting a sparse tick runs no device op."""
        _, maps = self._dead_live()
        stacked = stack_deltas(
            [sm.translate(self._empty_delta()) for sm in maps])
        leaves = jax.tree_util.tree_leaves(stacked)
        assert all(isinstance(x, np.ndarray) for x in leaves)
        assert stacked.edge_slots.shape == (2, 4)

    def test_vmem_guard(self):
        assert fits_sparse_tick(64, 256, 8, 2)
        assert not fits_sparse_tick(64, 256, 4096, 2)  # endpoint cap
        assert not fits_sparse_tick(500_000, 256, 8, 2)  # one-hot


class TestSparseServing:
    """`method="sparse_tick"` lifecycle parity vs a dense control."""

    N_VIRTUAL = 64

    def _open_pair(self, b=2, n=8):
        graphs = [erdos_renyi(n, 0.4, seed=s, weighted=True)
                  for s in range(b)]
        sparse = FingerService.open(ServiceConfig(
            batch_size=b, n_pad=self.N_VIRTUAL, k_pad=4, j_pad=2,
            method="sparse_tick", n_slots=12, m_pad=24,
            topk=TopKSpec(k=b)), graphs)
        dense = FingerService.open(ServiceConfig(
            batch_size=b, n_pad=self.N_VIRTUAL, k_pad=4, j_pad=2,
            method="fused_tick", topk=TopKSpec(k=b)), graphs)
        return sparse, dense, graphs

    def _tick_both(self, sparse, dense, virt_ds, label):
        sparse.ingest(virt_ds)
        dense.ingest([d for d in virt_ds])
        r_s, r_d = sparse.poll(), dense.poll()
        np.testing.assert_allclose(
            np.asarray(r_s.scores), np.asarray(r_d.scores), atol=1e-5,
            err_msg=label)
        return r_s

    def test_lifecycle_parity_across_migrations(self):
        sparse, dense, graphs = self._open_pair()
        rng = np.random.default_rng(3)
        mirrors = [np.asarray(g.weights).copy() for g in graphs]

        def toggles():
            ds = []
            for wm in mirrors:
                n = wm.shape[0]
                i, j = sorted(rng.choice(n, 2, replace=False).tolist())
                w_old = float(wm[i, j])
                ds.append(GraphDelta.from_arrays(
                    [i], [j], [0.5 if w_old == 0 else -w_old], [w_old],
                    n_nodes=self.N_VIRTUAL, k_pad=4, j_pad=2))
                wm[i, j] = wm[j, i] = 0.0 if w_old else 0.5
            return ds

        self._tick_both(sparse, dense, toggles(), "pre-migration")
        # virtual repad: a free host-side bump — the dense control
        # keeps its layout, so scores must be unchanged by it
        sparse.repad(4096)
        assert sparse.config.n_pad == 4096
        self._tick_both(sparse, dense, toggles(), "post-repad")
        # joins past the original virtual bound only the sparse side
        # renumbers; keep ids < 64 so the dense control can follow
        joins = [GraphDelta.from_arrays(
            [40 + s], [0], [0.7], [0.0], n_nodes=self.N_VIRTUAL,
            k_pad=4, join=[40 + s], j_pad=2) for s in range(2)]
        self._tick_both(sparse, dense, joins, "post-join")
        # capacity growth preserves slot ids and statistics
        sparse.grow_capacity(n_slots=24, m_pad=48)
        assert sparse.capacity.n_slots == 24
        self._tick_both(sparse, dense, toggles(), "post-grow")

    def test_prestacked_ingest_rejected_by_name(self):
        sparse, _, graphs = self._open_pair()
        stacked = stack_deltas([GraphDelta.from_arrays(
            [0], [1], [0.5], [0.0], n_nodes=self.N_VIRTUAL, k_pad=4)
            for _ in graphs])
        with pytest.raises(IngestError, match="per-stream"):
            sparse.ingest(stacked)

    def test_compact_shrink_rejected_by_name(self):
        sparse, _, _ = self._open_pair()
        with pytest.raises(ServiceConfigError, match="self-compacts"):
            sparse.compact()
        with pytest.raises(LayoutMigrationError, match="only grows"):
            sparse.repad(32)

    def test_sparse_checkpoint_round_trip(self, tmp_path):
        """Sparse services checkpoint: the per-stream `SlotMap`s ride
        in the manifest, so a restored service translates virtual ids
        (including joins into fresh slots) exactly like the original —
        pinned by score parity against an un-restored dense control."""
        sparse, dense, graphs = self._open_pair()
        rng = np.random.default_rng(7)
        mirrors = [np.asarray(g.weights).copy() for g in graphs]

        def toggles():
            ds = []
            for wm in mirrors:
                n = wm.shape[0]
                i, j = sorted(rng.choice(n, 2, replace=False).tolist())
                w_old = float(wm[i, j])
                ds.append(GraphDelta.from_arrays(
                    [i], [j], [0.5 if w_old == 0 else -w_old], [w_old],
                    n_nodes=self.N_VIRTUAL, k_pad=4, j_pad=2))
                wm[i, j] = wm[j, i] = 0.0 if w_old else 0.5
            return ds

        self._tick_both(sparse, dense, toggles(), "pre-save")
        cfg = sparse.config
        sparse.save(str(tmp_path))
        sparse.close()
        sparse = FingerService.restore(cfg, directory=str(tmp_path))
        assert sparse.capacity.n_slots == cfg.n_slots
        self._tick_both(sparse, dense, toggles(), "post-restore edges")
        # a join lands in a free slot chosen by the restored SlotMap's
        # free list — relabeling-invariant, so parity must still hold
        joins = [GraphDelta.from_arrays(
            [40 + s], [0], [0.7], [0.0], n_nodes=self.N_VIRTUAL,
            k_pad=4, join=[40 + s], j_pad=2)
            for s in range(len(graphs))]
        self._tick_both(sparse, dense, joins, "post-restore joins")
