"""FingerService: declarative config validation, bit-exact regression
against the pre-redesign StreamEngine path, ingestion queue semantics,
sharded top-k queries, and the repad state migration.

Acceptance anchors (ISSUE 3):
- the rewritten serving path produces *bit-exact* scores vs the
  pre-redesign `StreamEngine` loop for the same delta sequence;
- `top_anomalies` matches a full-gather oracle on a sharded mesh while
  only ever materializing the (num_shards · k) candidate row, never the
  (B,) score vector (8-device subprocess test).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.sanitize import compile_budget, no_transfers
from repro.distributed.sharding import auto_mesh
from repro.engine import StreamEngine, stack_deltas
from repro.graphs.generators import erdos_renyi
from repro.graphs.layout import NodeLayout
from repro.graphs.types import GraphDelta
from repro.serving import (
    CheckpointPolicy,
    FingerService,
    IngestError,
    LayoutMigrationError,
    ServiceConfig,
    ServiceConfigError,
    ServiceLifecycleError,
    TopKSpec,
    build_plan,
)


def _graphs(b, n, seed=0):
    return [erdos_renyi(n, 0.15, seed=seed + s, weighted=True)
            for s in range(b)]


def _tick_deltas(graphs, rng, k_pad, n_pad=None):
    ds = []
    for g in graphs:
        n = g.n_nodes
        i, j = sorted(rng.choice(n, 2, replace=False).tolist())
        w_old = float(np.asarray(g.weights)[i, j])
        ds.append(GraphDelta.from_arrays(
            [i], [j], [0.5 if w_old == 0 else -w_old], [w_old],
            n_nodes=n, n_pad=n_pad, k_pad=k_pad))
    return ds


class TestConfigValidation:
    def _base(self, **kw):
        kw.setdefault("batch_size", 8)
        kw.setdefault("n_pad", 16)
        kw.setdefault("k_pad", 4)
        return ServiceConfig(**kw)

    @pytest.mark.parametrize("field,value,match", [
        ("batch_size", 0, "batch_size"),
        ("n_pad", -1, "n_pad"),
        ("k_pad", 0, "k_pad"),
        ("j_pad", 0, "j_pad"),
        ("method", "sparse", "method"),
        ("placement", "galactic", "placement"),
        ("ingestion", "triple", "ingestion"),
        ("max_queue", 0, "max_queue"),
    ])
    def test_named_field_errors(self, field, value, match):
        with pytest.raises(ServiceConfigError, match=match):
            self._base(**{field: value}).validate()

    def test_multipod_needs_distinct_axes(self):
        with pytest.raises(ServiceConfigError, match="distinct"):
            self._base(placement="multipod", pod_axis="data").validate()

    def test_batch_must_divide_over_shards(self):
        with pytest.raises(ServiceConfigError, match="divide evenly"):
            self._base(batch_size=6).validate(num_shards=4)

    def test_topk_must_fit_per_shard(self):
        with pytest.raises(ServiceConfigError, match="per-shard"):
            self._base(batch_size=8, topk=TopKSpec(k=3)).validate(
                num_shards=4)

    def test_local_plan_rejects_mesh(self):
        mesh = auto_mesh((1,), ("data",))
        with pytest.raises(ServiceConfigError, match="takes no mesh"):
            build_plan(self._base(topk=TopKSpec(k=2)), mesh)

    def test_sharded_plan_rejects_missing_axis(self):
        mesh = auto_mesh((1,), ("model",))
        with pytest.raises(ServiceConfigError, match="no 'data' axis"):
            build_plan(self._base(placement="sharded",
                                  topk=TopKSpec(k=2)), mesh)

    def test_open_rejects_wrong_graph_count_and_oversize(self):
        cfg = self._base(topk=TopKSpec(k=2))
        with pytest.raises(ServiceConfigError, match="batch_size"):
            FingerService.open(cfg, _graphs(3, 8))
        with pytest.raises(ServiceConfigError, match="exceed config.n_pad"):
            FingerService.open(cfg, _graphs(8, 32))


class TestBitExactRegression:
    @pytest.mark.parametrize("method", ["dense", "compact"])
    def test_service_matches_stream_engine_bit_exact(self, method):
        """The acceptance criterion: the FingerService serving loop and
        the pre-redesign StreamEngine path produce *identical* score
        sequences for the same deltas (same compiled tick underneath)."""
        b, n_pad, k_pad, t = 16, 24, 4, 5
        graphs = _graphs(b, n_pad)
        rng = np.random.default_rng(1)
        ticks = [_tick_deltas(graphs, rng, k_pad) for _ in range(t)]

        engine = StreamEngine(method=method)
        st = StreamEngine.init_states(graphs)
        old = []
        for d in ticks:
            scores, st = engine.tick(st, stack_deltas(d))
            old.append(np.asarray(scores))

        cfg = ServiceConfig(batch_size=b, n_pad=n_pad, k_pad=k_pad,
                            method=method, topk=TopKSpec(k=4))
        with FingerService.open(cfg, graphs) as svc:
            for step, d in enumerate(ticks, start=1):
                svc.ingest(d)
                report = svc.poll()
                assert report.step == step
                np.testing.assert_array_equal(svc.scores(),
                                              old[step - 1])

    def test_double_buffered_matches_sync(self):
        b, n_pad, k_pad, t = 8, 16, 4, 4
        graphs = _graphs(b, n_pad, seed=5)
        rng = np.random.default_rng(5)
        ticks = [_tick_deltas(graphs, rng, k_pad) for _ in range(t)]
        outs = {}
        for mode in ("sync", "double_buffered"):
            cfg = ServiceConfig(batch_size=b, n_pad=n_pad, k_pad=k_pad,
                                ingestion=mode, topk=TopKSpec(k=2))
            with FingerService.open(cfg, graphs) as svc:
                for d in ticks:
                    svc.ingest(d)
                    svc.poll()
                outs[mode] = svc.scores()
        np.testing.assert_array_equal(outs["sync"],
                                      outs["double_buffered"])


class TestIngestionQueue:
    def _svc(self, **kw):
        kw.setdefault("batch_size", 4)
        kw.setdefault("n_pad", 12)
        kw.setdefault("k_pad", 3)
        kw.setdefault("topk", TopKSpec(k=2))
        cfg = ServiceConfig(**kw)
        return FingerService.open(cfg, _graphs(cfg.batch_size,
                                               cfg.n_pad)), cfg

    def test_poll_on_empty_queue_returns_none(self):
        svc, _ = self._svc()
        assert svc.poll() is None
        assert svc.scores() is None
        svc.close()

    def test_queue_depth_enforced(self):
        svc, cfg = self._svc(max_queue=2)
        rng = np.random.default_rng(0)
        g = _graphs(4, 12)
        svc.ingest(_tick_deltas(g, rng, 3))
        svc.ingest(_tick_deltas(g, rng, 3))
        assert svc.pending == 2
        with pytest.raises(IngestError, match="queue full"):
            svc.ingest(_tick_deltas(g, rng, 3))
        svc.poll()
        svc.poll()
        assert svc.pending == 0
        svc.close()

    @pytest.mark.parametrize("mutate,match", [
        (dict(k_pad=5), "k_pad"),
        (dict(n_pad=16), "n_pad"),
        (dict(j_pad=2), "node-slot"),
    ])
    def test_layout_mismatch_named_errors(self, mutate, match):
        svc, _ = self._svc()
        rng = np.random.default_rng(0)
        kw = dict(k_pad=3, n_pad=None, j_pad=None)
        kw.update(mutate)
        ds = []
        for g in _graphs(4, 12):
            extra = {}
            if kw["j_pad"]:
                extra = dict(join=[0], j_pad=kw["j_pad"])
            ds.append(GraphDelta.from_arrays(
                [0], [1], [0.5], [float(np.asarray(g.weights)[0, 1])],
                n_nodes=12, n_pad=kw["n_pad"], k_pad=kw["k_pad"],
                **extra))
        with pytest.raises(IngestError, match=match):
            svc.ingest(ds)
        svc.close()

    def test_wrong_batch_named_error(self):
        svc, _ = self._svc()
        rng = np.random.default_rng(0)
        with pytest.raises(IngestError, match="batch"):
            svc.ingest(_tick_deltas(_graphs(2, 12), rng, 3))
        svc.close()

    def test_unstacked_delta_named_error(self):
        svc, _ = self._svc()
        d = GraphDelta.from_arrays([0], [1], [0.5], [0.0], n_nodes=12,
                                   k_pad=3)
        with pytest.raises(IngestError, match="stacked"):
            svc.ingest(d)
        svc.close()


class TestTopAnomalies:
    def test_local_topk_matches_numpy_oracle(self):
        b = 12
        graphs = _graphs(b, 16, seed=2)
        rng = np.random.default_rng(2)
        cfg = ServiceConfig(batch_size=b, n_pad=16, k_pad=3,
                            topk=TopKSpec(k=4))
        with FingerService.open(cfg, graphs) as svc:
            with pytest.raises(ServiceLifecycleError,
                               match="before the first"):
                svc.top_anomalies()
            svc.ingest(_tick_deltas(graphs, rng, 3))
            svc.poll()
            scores = svc.scores()
            vals, ids = svc.top_anomalies(4)
            order = np.argsort(scores)[::-1][:4]
            np.testing.assert_array_equal(ids, order)
            np.testing.assert_allclose(vals, scores[order], rtol=0)
            with pytest.raises(ServiceConfigError, match="exceeds"):
                svc.top_anomalies(b + 1)
            with pytest.raises(ServiceConfigError, match="multipod"):
                svc.top_anomalies(2, per_pod=True)


class TestRepad:
    def test_repad_grows_layout_and_matches_oracle(self):
        from repro.core import finger_state, jsdist_incremental

        b, n0, n_pad = 3, 10, 12
        graphs = _graphs(b, n0, seed=4)
        rng = np.random.default_rng(4)
        cfg = ServiceConfig(batch_size=b, n_pad=n_pad, k_pad=3, j_pad=2,
                            topk=TopKSpec(k=2))
        svc = FingerService.open(cfg, graphs)
        # single-edge deltas carrying (empty) node slots to match j_pad
        d1 = []
        for g in graphs:
            i, j = sorted(rng.choice(n0, 2, replace=False).tolist())
            w_old = float(np.asarray(g.weights)[i, j])
            d1.append(GraphDelta.from_arrays(
                [i], [j], [0.5 if w_old == 0 else -w_old], [w_old],
                n_nodes=n0, n_pad=n_pad, k_pad=3, j_pad=2))
        svc.ingest(d1)
        svc.poll()
        s1 = svc.scores()

        # Acceptance: the growth is a device-side embed — no transfer
        # of the stacked state in either direction.
        with no_transfers():
            svc.repad(20)
        assert svc.config.n_pad == 20
        assert svc.layout == NodeLayout(20, generation=1)
        # join a node beyond the OLD layout — the previously-hard error
        d2 = [GraphDelta.from_arrays(
            [15], [0], [0.9], [0.0], n_nodes=n0, n_pad=20, k_pad=3,
            join=[15], j_pad=2) for _ in range(b)]
        svc.ingest(d2)
        svc.poll()
        s2 = svc.scores()
        assert np.isfinite(s2).all()

        # per-stream oracle over the larger layout from scratch
        for i in range(b):
            st = finger_state(graphs[i].pad_to(20))
            o1 = GraphDelta.from_arrays(
                np.asarray(d1[i].senders)[:1],
                np.asarray(d1[i].receivers)[:1],
                np.asarray(d1[i].dw)[:1], np.asarray(d1[i].w_old)[:1],
                n_nodes=n0, n_pad=20, k_pad=3, j_pad=2)
            r1, st_next = jsdist_incremental(st, o1)
            st = st_next
            r2, st = jsdist_incremental(st, d2[i])
            assert abs(float(r1) - s1[i]) < 1e-6
            assert abs(float(r2) - s2[i]) < 1e-6
        # old-layout deltas are now rejected by name
        stale = [GraphDelta.from_arrays([0], [1], [0.1], [0.0],
                                        n_nodes=n_pad, k_pad=3, j_pad=2)
                 for _ in range(b)]
        with pytest.raises(IngestError, match="repad"):
            svc.ingest(stale)
        svc.close()

    def test_repad_rejects_noop_and_lossy_shrink(self):
        b = 4
        graphs = _graphs(b, 12, seed=6)
        rng = np.random.default_rng(6)
        cfg = ServiceConfig(batch_size=b, n_pad=12, k_pad=3,
                            topk=TopKSpec(k=2))
        svc = FingerService.open(cfg, graphs)
        svc.ingest(_tick_deltas(graphs, rng, 3))
        with pytest.raises(ServiceConfigError, match="already at"):
            svc.repad(12)
        # every slot is live, so ANY shrink would truncate active state
        with pytest.raises(LayoutMigrationError, match="truncate"):
            svc.repad(8)
        # a refused migration must not have eaten the prefetched tick
        assert svc.pending == 1
        assert svc.poll() is not None
        svc.close()

    @pytest.mark.parametrize("ingestion", ["sync", "double_buffered"])
    def test_repad_relays_out_prefetched_queue(self, ingestion):
        """Satellite regression: a tick ingested *before* the migration
        (laid out for the old n_pad, possibly already transferred by the
        double-buffered ingestor) must be re-laid-out inside repad and
        produce the same scores as the drain-first ordering."""
        from repro.core import finger_state, jsdist_incremental

        b, n0 = 3, 10
        graphs = _graphs(b, n0, seed=8)
        rng = np.random.default_rng(8)
        cfg = ServiceConfig(batch_size=b, n_pad=n0, k_pad=3,
                            ingestion=ingestion, topk=TopKSpec(k=2))
        svc = FingerService.open(cfg, graphs)
        d1 = _tick_deltas(graphs, rng, 3)
        svc.ingest(d1)           # prefetched under n_pad=10 ...
        svc.repad(16)            # ... migrated to n_pad=16
        assert svc.pending == 1  # the queue survived the migration
        report = svc.poll()
        assert report is not None
        s1 = svc.scores()
        for i in range(b):
            st = finger_state(graphs[i].pad_to(16))
            ref, _ = jsdist_incremental(
                st, GraphDelta.from_arrays(
                    np.asarray(d1[i].senders)[:1],
                    np.asarray(d1[i].receivers)[:1],
                    np.asarray(d1[i].dw)[:1],
                    np.asarray(d1[i].w_old)[:1],
                    n_nodes=n0, n_pad=16, k_pad=3))
            assert abs(float(ref) - s1[i]) < 1e-6
        svc.close()

    def test_repad_truncates_inactive_tail(self):
        """Shrinking is legal exactly when the cut slots are inactive in
        every stream — grow to 24, then shrink back to 12 (slots 12..23
        were never activated)."""
        b = 3
        graphs = _graphs(b, 12, seed=9)
        rng = np.random.default_rng(9)
        cfg = ServiceConfig(batch_size=b, n_pad=12, k_pad=3,
                            topk=TopKSpec(k=2))
        svc = FingerService.open(cfg, graphs)
        svc.ingest(_tick_deltas(graphs, rng, 3))
        svc.poll()
        before = jax.device_get(svc.states())
        svc.repad(24)
        svc.repad(12)
        assert svc.layout == NodeLayout(12, generation=2)
        after = jax.device_get(svc.states())
        np.testing.assert_array_equal(np.asarray(before.strengths),
                                      np.asarray(after.strengths))
        np.testing.assert_array_equal(np.asarray(before.q),
                                      np.asarray(after.q))
        svc.ingest(_tick_deltas(graphs, rng, 3))
        assert svc.poll() is not None
        svc.close()


def _leave_delta(g, node, n_pad, k_pad, j_pad):
    """Delete every edge at `node`, then the node leaves — one delta
    honoring the isolated-leave contract."""
    w = np.asarray(g.weights)
    nb = np.nonzero(w[node])[0]
    return GraphDelta.from_arrays(
        np.full(len(nb), node), nb, -w[node, nb], w[node, nb],
        n_nodes=g.n_nodes, n_pad=n_pad, k_pad=k_pad,
        leave=[node], j_pad=j_pad)


class TestCompact:
    def _open(self, b=3, n0=12, n_pad=16, k_pad=12, j_pad=2, seed=11,
              **kw):
        graphs = _graphs(b, n0, seed=seed)
        # exact_smax: the oracle comparisons below rebuild fresh states,
        # whose s_max is exact — the eq. (3) never-decreasing bound
        # would differ after the leave deltas' deletions (by design).
        kw.setdefault("exact_smax", True)
        cfg = ServiceConfig(batch_size=b, n_pad=n_pad, k_pad=k_pad,
                            j_pad=j_pad, topk=TopKSpec(k=2), **kw)
        return FingerService.open(cfg, graphs), graphs

    def test_compact_reclaims_and_matches_unpadded_oracle(self):
        """Acceptance: after every stream's node 3 leaves and the layout
        compacts, the per-stream statistics equal a fresh unpadded
        FINGER state of the renumbered graph to 1e-5 — S, Σs², Σ_E w²
        and s_max are invariant under the renumbering."""
        from repro.core import finger_state

        svc, graphs = self._open()
        svc.ingest([_leave_delta(g, 3, 16, 12, 2) for g in graphs])
        svc.poll()
        report = svc.compact()
        assert report.old_n_pad == 16
        assert report.reclaimed == 16 - report.new_n_pad
        assert report.new_n_pad == 11  # 12 actives minus the left slot
        assert svc.layout.generation == 1
        assert np.array_equal(report.index_map[:4], [0, 1, 2, -1])

        states = jax.device_get(svc.states())
        keep = np.nonzero(report.index_map >= 0)[0]
        for i, g in enumerate(graphs):
            w = np.asarray(g.weights).copy()
            w[3, :] = 0.0
            w[:, 3] = 0.0
            renum = w[np.ix_(keep, keep)]  # the compacted addressing
            from repro.graphs.types import DenseGraph
            ref = finger_state(DenseGraph.from_weights(
                jnp.asarray(renum), n_pad=report.new_n_pad))
            np.testing.assert_allclose(
                np.asarray(states.strengths)[i],
                np.asarray(ref.strengths), atol=1e-5)
            for field in ("q", "s_total", "s_max"):
                assert abs(float(getattr(states, field)[i])
                           - float(getattr(ref, field))) < 1e-5, field
        svc.close()

    def test_ingestion_remaps_old_layout_deltas(self):
        """The layout-owned index map: after compact, producers still
        addressing the old 16-slot layout keep working (their ids are
        renumbered on ingest), and the scores match the oracle on the
        compacted layout."""
        from repro.core import finger_state, jsdist_incremental
        from repro.graphs.types import DenseGraph

        svc, graphs = self._open(seed=12)
        svc.ingest([_leave_delta(g, 2, 16, 12, 2) for g in graphs])
        svc.poll()
        report = svc.compact()
        keep = np.nonzero(report.index_map >= 0)[0]
        # delta still addressed in the OLD layout: edge (4, 7) -> the
        # compacted slots (index_map[4], index_map[7])
        old_i, old_j = 4, 7
        deltas = [GraphDelta.from_arrays(
            [old_i], [old_j], [0.7],
            [float(np.asarray(g.weights)[old_i, old_j])],
            n_nodes=12, n_pad=16, k_pad=12, j_pad=2) for g in graphs]
        svc.ingest(deltas)
        svc.poll()
        scores = svc.scores()
        for i, g in enumerate(graphs):
            w = np.asarray(g.weights).copy()
            w[2, :] = 0.0
            w[:, 2] = 0.0
            renum = w[np.ix_(keep, keep)]
            st = finger_state(DenseGraph.from_weights(
                jnp.asarray(renum), n_pad=report.new_n_pad))
            ni, nj = int(report.index_map[old_i]), \
                int(report.index_map[old_j])
            ref, _ = jsdist_incremental(st, GraphDelta.from_arrays(
                [ni], [nj], [0.7], [renum[ni, nj]],
                n_nodes=report.new_n_pad, n_pad=report.new_n_pad,
                k_pad=12, j_pad=2))
            assert abs(float(ref) - scores[i]) < 1e-5
        # a join addressing a DROPPED slot of the old layout is lossy
        stale_join = [GraphDelta.from_arrays(
            [0], [1], [0.1], [0.0], n_nodes=12, n_pad=16, k_pad=12,
            join=[2], j_pad=2) for _ in graphs]
        with pytest.raises(LayoutMigrationError, match="dropped"):
            svc.ingest(stale_join)
        svc.close()

    def test_compact_noop_and_lossy_named_errors(self):
        svc, graphs = self._open(b=2, n0=16, n_pad=16, seed=13)
        report = svc.compact()  # every slot live: nothing to reclaim
        assert report.reclaimed == 0
        assert svc.layout.generation == 0
        with pytest.raises(LayoutMigrationError, match="lossy"):
            svc.compact(new_n_pad=8)
        with pytest.raises(LayoutMigrationError, match="does not shrink"):
            svc.compact(new_n_pad=16)
        svc.close()

    def test_compact_aborts_cleanly_on_unmigratable_queued_tick(self, tmp_path):
        """A prefetched join addressing a slot the compaction would drop
        cannot be remapped — the migration must abort with the service
        (state, layout, queue, journal) exactly as it was, not
        half-migrated with the queue eaten."""
        from repro.serving import migrate

        svc, graphs = self._open(seed=15,
                                 checkpoint=CheckpointPolicy(
                                     str(tmp_path)))
        svc.ingest([_leave_delta(g, 4, 16, 12, 2) for g in graphs])
        svc.poll()
        # queue a join re-activating slot 4 — valid now, lossy to drop
        svc.ingest([GraphDelta.from_arrays(
            [0], [4], [0.3], [0.0], n_nodes=12, n_pad=16, k_pad=12,
            join=[4], j_pad=2) for g in graphs])
        before = jax.device_get(svc.states())
        with pytest.raises(LayoutMigrationError, match="dropped"):
            svc.compact()
        assert svc.layout.generation == 0
        assert svc.config.n_pad == 16
        assert svc.pending == 1
        assert migrate.load_layout_log(str(tmp_path)) == []
        after = jax.device_get(svc.states())
        for a, b in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(after)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the queued join still applies fine on the unmigrated layout
        assert svc.poll() is not None
        svc.close()

    def test_migrating_a_forked_journal_is_rejected(self, tmp_path):
        """Restoring an old-generation checkpoint into the same
        directory and migrating it again would fork the layout log
        (two records from one generation) — refused up front, before
        any state changes."""
        svc, graphs = self._open(seed=16,
                                 checkpoint=CheckpointPolicy(
                                     str(tmp_path)))
        svc.ingest([_leave_delta(g, 4, 16, 12, 2) for g in graphs])
        svc.poll()
        svc.save()
        svc.compact()  # journals generation 0 -> 1
        svc.close()
        forked = FingerService.restore(
            ServiceConfig(batch_size=3, n_pad=16, k_pad=12, j_pad=2,
                          topk=TopKSpec(k=2), exact_smax=True,
                          checkpoint=CheckpointPolicy(str(tmp_path))))
        assert forked.layout.generation == 0
        with pytest.raises(LayoutMigrationError, match="fork"):
            forked.compact()
        assert forked.layout.generation == 0  # untouched
        forked.close()

    def test_compact_relays_out_prefetched_queue(self):
        """A tick prefetched before compact() is remapped with the same
        index map ingestion applies — the queue survives the migration."""
        svc, graphs = self._open(seed=14, ingestion="double_buffered")
        svc.ingest([_leave_delta(g, 5, 16, 12, 2) for g in graphs])
        svc.poll()
        # prefetch a tick in the old layout, then migrate under it
        deltas = [GraphDelta.from_arrays(
            [0], [1], [0.4], [float(np.asarray(g.weights)[0, 1])],
            n_nodes=12, n_pad=16, k_pad=12, j_pad=2) for g in graphs]
        svc.ingest(deltas)
        report = svc.compact()
        assert report.reclaimed > 0
        assert svc.pending == 1
        assert svc.poll() is not None
        assert np.isfinite(svc.scores()).all()
        svc.close()


class TestLifecycle:
    def test_closed_service_raises_everywhere(self):
        graphs = _graphs(2, 8)
        cfg = ServiceConfig(batch_size=2, n_pad=8, k_pad=2,
                            topk=TopKSpec(k=1))
        svc = FingerService.open(cfg, graphs)
        svc.close()
        svc.close()  # idempotent
        for call in (lambda: svc.poll(), lambda: svc.scores(),
                     lambda: svc.ingest([]), lambda: svc.save(),
                     lambda: svc.repad(16)):
            with pytest.raises(ServiceLifecycleError, match="closed"):
                call()

    def test_save_without_directory_is_named_error(self):
        graphs = _graphs(2, 8)
        cfg = ServiceConfig(batch_size=2, n_pad=8, k_pad=2,
                            topk=TopKSpec(k=1))
        with FingerService.open(cfg, graphs) as svc:
            with pytest.raises(ServiceConfigError, match="directory"):
                svc.save()

    def test_restore_validates_layout_against_config(self, tmp_path):
        graphs = _graphs(4, 8, seed=7)
        cfg = ServiceConfig(batch_size=4, n_pad=8, k_pad=2,
                            topk=TopKSpec(k=1),
                            checkpoint=CheckpointPolicy(str(tmp_path)))
        with FingerService.open(cfg, graphs) as svc:
            svc.save()
        with pytest.raises(ServiceConfigError, match="batch_size"):
            FingerService.restore(cfg.with_(batch_size=8))
        with pytest.raises(ServiceConfigError, match="repad"):
            FingerService.restore(cfg.with_(n_pad=16))
        svc2 = FingerService.restore(cfg)
        assert svc2.step == 0
        svc2.close()


class TestDeviceCompaction:
    """The transfer-free compact(): occupancy + renumbering + gather on
    device (`migrate.compact_stacked_auto`), transfer-guard-tested like
    `grow_stacked`."""

    def _left_states(self, b=3, n0=12, n_pad=16):
        from repro.engine import StreamEngine

        graphs = _graphs(b, n0, seed=21)
        states = StreamEngine.init_states(graphs, n_pad=n_pad)
        # deactivate slots {3, 7} in every stream, zeroing strengths
        # (the compactable pattern: interior holes + inactive tail)
        mask = np.asarray(states.node_mask).copy()
        strengths = np.asarray(states.strengths).copy()
        mask[:, [3, 7]] = 0.0
        strengths[:, [3, 7]] = 0.0
        from repro.core.state import FingerState

        return FingerState(
            q=states.q, s_total=states.s_total, s_max=states.s_max,
            strengths=jnp.asarray(strengths),
            node_mask=jnp.asarray(mask), layout=states.layout)

    def test_transfer_guard_state_never_touches_host(self):
        from repro.serving import migrate

        states = self._left_states()
        new_layout = NodeLayout(10, generation=1)
        with no_transfers():
            out, imap_dev = migrate.compact_stacked_auto(states,
                                                         new_layout)
            jax.block_until_ready(out.strengths)
        # the small (n_pad,) index map transfers OUTSIDE the guard —
        # that is the journal/ingestion readback, not state movement
        imap = np.asarray(jax.device_get(imap_dev))
        assert imap.shape == (16,)

    def test_device_renumbering_matches_host_plan(self):
        """The on-device prefix-sum renumbering equals the host-side
        `plan_compaction` index map, and the gathered state equals the
        static-keep gather."""
        from repro.graphs.layout import plan_compaction
        from repro.serving import migrate

        states = self._left_states()
        occ = migrate.occupancy(states)
        host_plan = plan_compaction(occ, states.layout, new_n_pad=10)
        out, imap_dev = migrate.compact_stacked_auto(
            states, NodeLayout(10, generation=1))
        np.testing.assert_array_equal(np.asarray(imap_dev),
                                      host_plan.index_map)
        keep = host_plan.keep
        np.testing.assert_allclose(
            np.asarray(out.strengths),
            np.asarray(states.strengths)[:, keep], atol=0)
        np.testing.assert_allclose(
            np.asarray(out.node_mask),
            np.asarray(states.node_mask)[:, keep], atol=0)

    def test_compile_once_across_occupancy_patterns(self):
        """The dynamic renumbering compiles per (old, new) SHAPE pair,
        not per surviving-slot set — what makes a pending compaction
        pre-compilable before the final occupancy is known."""
        from repro.serving import migrate

        migrate._compact_auto_jit.cache_clear()
        base = self._left_states()
        mask2 = np.asarray(base.node_mask).copy()
        mask2[:, [3, 7]] = 1.0
        mask2[:, [1, 14]] = 0.0  # a different hole pattern
        from repro.core.state import FingerState

        other = FingerState(
            q=base.q, s_total=base.s_total, s_max=base.s_max,
            strengths=base.strengths * jnp.asarray(mask2 > 0,
                                                   jnp.float32),
            node_mask=jnp.asarray(mask2), layout=base.layout)
        new_layout = NodeLayout(14, generation=1)
        migrate.compact_stacked_auto(base, new_layout)
        with compile_budget(0, "compaction across occupancy patterns"):
            migrate.compact_stacked_auto(other, new_layout)

    def test_truncate_stacked_is_a_device_slice(self):
        from repro.serving import migrate

        states = self._left_states()
        # slots 12..15 are an inactive tail? no — _graphs fills n0=12,
        # so 12..15 are inactive by construction
        with no_transfers():
            out = migrate.truncate_stacked(states,
                                           NodeLayout(12, generation=1))
            jax.block_until_ready(out.strengths)
        np.testing.assert_allclose(np.asarray(out.strengths),
                                   np.asarray(states.strengths)[:, :12])


class TestPlanCache:
    def _open(self, b=3, n0=10, n_pad=12, **kw):
        graphs = _graphs(b, n0, seed=31)
        kw.setdefault("k_pad", 3)
        cfg = ServiceConfig(batch_size=b, n_pad=n_pad,
                            topk=TopKSpec(k=2), **kw)
        return FingerService.open(cfg, graphs), graphs

    def test_warm_then_repad_installs_the_warmed_plan(self):
        svc, graphs = self._open()
        rng = np.random.default_rng(31)
        svc.ingest(_tick_deltas(graphs, rng, 3, n_pad=12))
        svc.poll()
        warmed = svc.warm_next_layouts()  # growth_factor=2 -> 24
        assert 24 in warmed
        assert len(svc.plan_cache) >= 1
        assert NodeLayout(24, generation=1) in \
            svc.plan_cache.warmed_layouts
        warm_plans = {id(p) for p, _ in svc.plan_cache._plans.values()}
        svc.repad(24)
        assert id(svc.plan) in warm_plans, \
            "repad built a cold plan despite the warmed prediction"
        # the swapped-in plan serves correctly
        svc.ingest(_tick_deltas(graphs, rng, 3, n_pad=24))
        assert svc.poll() is not None
        assert np.isfinite(svc.scores()).all()
        svc.close()

    def test_warm_compact_prediction(self):
        svc, graphs = self._open(j_pad=2, exact_smax=True, k_pad=12)
        # node 4 leaves everywhere -> live-slot count drops to 9
        svc.ingest([_leave_delta(g, 4, 12, 12, 2) for g in graphs])
        svc.poll()
        warmed = svc.warm_next_layouts()
        assert 9 in warmed  # the pending compaction target
        warm_plans = {id(p) for p, _ in svc.plan_cache._plans.values()}
        report = svc.compact()
        assert report.new_n_pad == 9
        assert id(svc.plan) in warm_plans
        svc.close()

    def test_explicit_targets_and_mispredict_falls_back_cold(self):
        svc, graphs = self._open()
        assert svc.warm_next_layouts([20]) == [20]
        svc.repad(18)  # NOT the warmed target: cold path, still correct
        assert svc.config.n_pad == 18
        rng = np.random.default_rng(5)
        svc.ingest(_tick_deltas(graphs, rng, 3, n_pad=18))
        assert svc.poll() is not None
        svc.close()

    def test_disabled_policy_warms_nothing(self):
        from repro.serving import PlanCachePolicy

        svc, _ = self._open(plan_cache=PlanCachePolicy(enabled=False))
        assert svc.warm_next_layouts() == []
        assert len(svc.plan_cache) == 0
        svc.close()

    def test_policy_validation(self):
        from repro.serving import PlanCachePolicy

        with pytest.raises(ServiceConfigError, match="growth_factor"):
            ServiceConfig(batch_size=2, n_pad=8, k_pad=2,
                          plan_cache=PlanCachePolicy(growth_factor=0.5)
                          ).validate()


class TestGenerationGrace:
    """The `layout_generation` stamp on deltas: exact ingestion remap
    across size-reusing migration chains (keys are generations, so
    nothing shadows), grows included."""

    def _chain(self, tmp_path=None):
        """16 → compact(11) → repad(16): a size-reusing chain. Returns
        (svc, graphs, index_map of the compaction)."""
        b = 3
        graphs = _graphs(b, 12, seed=41)
        kw = {}
        if tmp_path is not None:
            kw["checkpoint"] = CheckpointPolicy(str(tmp_path))
        cfg = ServiceConfig(batch_size=b, n_pad=16, k_pad=12, j_pad=2,
                            exact_smax=True, topk=TopKSpec(k=2), **kw)
        svc = FingerService.open(cfg, graphs)
        svc.ingest([_leave_delta(g, 3, 16, 12, 2) for g in graphs])
        svc.poll()
        report = svc.compact()           # generation 0 -> 1, n_pad 11
        svc.repad(16)                    # generation 1 -> 2, n_pad 16
        assert svc.layout == NodeLayout(16, generation=2)
        return svc, graphs, report.index_map

    def test_gen0_delta_remaps_exactly_through_size_reuse(self):
        """A delta stamped with the ORIGINAL generation-0 layout of
        size 16 must renumber through the compaction map — the
        size-keyed legacy table cannot distinguish the two 16-slot
        layouts."""
        from repro.core import finger_state, jsdist_incremental
        from repro.graphs.types import DenseGraph

        svc, graphs, index_map = self._chain()
        gen0 = NodeLayout(16, generation=0)
        old_i, old_j = 4, 7
        deltas = [GraphDelta.from_arrays(
            [old_i], [old_j], [0.7],
            [float(np.asarray(g.weights)[old_i, old_j])],
            n_nodes=12, k_pad=12, j_pad=2, layout=gen0)
            for g in graphs]
        assert deltas[0].layout_generation == 0
        svc.ingest(deltas)
        svc.poll()
        scores = svc.scores()
        keep = np.nonzero(index_map >= 0)[0]
        ni, nj = int(index_map[old_i]), int(index_map[old_j])
        for i, g in enumerate(graphs):
            w = np.asarray(g.weights).copy()
            w[3, :] = 0.0
            w[:, 3] = 0.0
            renum = w[np.ix_(keep, keep)]
            st = finger_state(DenseGraph.from_weights(
                jnp.asarray(renum), n_pad=16))
            ref, _ = jsdist_incremental(
                st, GraphDelta.from_arrays(
                    [ni], [nj], [0.7], [renum[ni, nj]], n_nodes=16,
                    k_pad=12, j_pad=2), exact_smax=True)
            assert abs(float(ref) - scores[i]) < 1e-5, i

    def test_current_generation_passes_and_mis_stamp_raises(self):
        svc, graphs, _ = self._chain()
        cur = svc.layout  # generation 2, n_pad 16
        ok = [GraphDelta.from_arrays(
            [0], [1], [0.2], [0.0], n_nodes=16, k_pad=12, j_pad=2,
            layout=cur) for _ in graphs]
        svc.ingest(ok)
        assert svc.poll() is not None
        # current generation but wrong size: a mis-stamped delta
        bad = [GraphDelta.from_arrays(
            [0], [1], [0.2], [0.0], n_nodes=12, k_pad=12, j_pad=2,
            layout=NodeLayout(12, generation=2)) for _ in graphs]
        with pytest.raises(IngestError, match="mis-stamped"):
            svc.ingest(bad)
        # stale generation with the wrong size must also raise by name,
        # not escape as an IndexError from the remap gather (or worse,
        # silently renumber through the wrong-size map)
        bad0 = [GraphDelta.from_arrays(
            [0], [20], [0.2], [0.0], n_nodes=32, k_pad=12, j_pad=2,
            layout=NodeLayout(32, generation=0)) for _ in graphs]
        with pytest.raises(IngestError, match="mis-stamped"):
            svc.ingest(bad0)
        svc.close()

    def test_unknown_generation_rejected_by_name(self):
        svc, graphs, _ = self._chain()
        bad = [GraphDelta.from_arrays(
            [0], [1], [0.2], [0.0], n_nodes=16, k_pad=12, j_pad=2,
            layout=NodeLayout(16, generation=9)) for _ in graphs]
        with pytest.raises(IngestError, match="generation 9"):
            svc.ingest(bad)
        svc.close()

    def test_gen_stamped_delta_survives_a_pure_grow(self):
        """Grows contribute identity injections to the generation
        table, so a stamped old-layout delta keeps working where a raw
        old-size delta is rejected."""
        b = 3
        graphs = _graphs(b, 10, seed=43)
        cfg = ServiceConfig(batch_size=b, n_pad=10, k_pad=3,
                            topk=TopKSpec(k=2))
        svc = FingerService.open(cfg, graphs)
        svc.repad(20)
        stamped = [GraphDelta.from_arrays(
            [0], [1], [0.2], [float(np.asarray(g.weights)[0, 1])],
            n_nodes=10, k_pad=3, layout=NodeLayout(10, generation=0))
            for g in graphs]
        svc.ingest(stamped)
        assert svc.poll() is not None
        raw = [GraphDelta.from_arrays(
            [0], [1], [0.2], [0.0], n_nodes=10, k_pad=3)
            for _ in graphs]
        with pytest.raises(IngestError, match="repad"):
            svc.ingest(raw)
        svc.close()

    def test_restore_rebuilds_generation_table(self, tmp_path):
        """A restored service accepts the same generation-stamped
        old-layout deltas the live one did (table rebuilt from the
        journal)."""
        svc, graphs, index_map = self._chain(tmp_path)
        svc.save()
        cfg_now = svc.config
        svc.close()
        svc2 = FingerService.restore(cfg_now, directory=str(tmp_path))
        assert svc2.layout.generation == 2
        gen0 = NodeLayout(16, generation=0)
        deltas = [GraphDelta.from_arrays(
            [4], [7], [0.7],
            [float(np.asarray(g.weights)[4, 7])],
            n_nodes=12, k_pad=12, j_pad=2, layout=gen0)
            for g in graphs]
        svc2.ingest(deltas)
        assert svc2.poll() is not None
        assert np.isfinite(svc2.scores()).all()
        svc2.close()

    def test_stack_deltas_validates_generation_consistency(self):
        d1 = GraphDelta.from_arrays([0], [1], [1.0], [0.0], n_nodes=8,
                                    k_pad=4,
                                    layout=NodeLayout(8, generation=1))
        d2 = GraphDelta.from_arrays([0], [1], [1.0], [0.0], n_nodes=8,
                                    k_pad=4)
        with pytest.raises(ValueError, match="layout_generation"):
            stack_deltas([d1, d1, d2])


_SHARDED_TOPK_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax

from repro.distributed.sharding import auto_mesh
from repro.engine import StreamEngine, stack_deltas
from repro.graphs.generators import erdos_renyi
from repro.graphs.types import GraphDelta
from repro.serving import FingerService, ServiceConfig, TopKSpec

b, n, k_pad, k = 64, 24, 4, 3
graphs = [erdos_renyi(n, 0.15, seed=s, weighted=True) for s in range(b)]
rng = np.random.default_rng(0)

def tick_deltas():
    ds = []
    for g in graphs:
        i, j = sorted(rng.choice(n, 2, replace=False).tolist())
        w_old = float(np.asarray(g.weights)[i, j])
        ds.append(GraphDelta.from_arrays(
            [i], [j], [0.6 if w_old == 0 else -w_old], [w_old],
            n_nodes=n, k_pad=k_pad))
    return ds

ticks = [tick_deltas() for _ in range(3)]
engine = StreamEngine()
st = StreamEngine.init_states(graphs)
for t in ticks:
    ref, st = engine.tick(st, stack_deltas(t))
ref = np.asarray(ref)  # the full-gather oracle, host side only

out = {"n_devices": jax.device_count(), "cases": []}
meshes = {
    "sharded": auto_mesh((8,), ("data",)),
    "multipod": auto_mesh((2, 4), ("pod", "data")),
}
for placement, mesh in meshes.items():
    cfg = ServiceConfig(batch_size=b, n_pad=n, k_pad=k_pad,
                        placement=placement, ingestion="double_buffered",
                        topk=TopKSpec(k=k))
    svc = FingerService.open(cfg, graphs, mesh=mesh)
    for t in ticks:
        svc.ingest(t)
        svc.poll()
    scores = svc.scores()
    vals, ids = svc.top_anomalies(k)
    oracle_ids = np.argsort(ref)[::-1][:k]
    case = {
        "placement": placement,
        "scores_max_err": float(np.abs(scores - ref).max()),
        "topk_ids_match": bool(np.array_equal(ids, oracle_ids)),
        "topk_vals_max_err": float(np.abs(vals - ref[oracle_ids]).max()),
        # structural: the merge row is num_shards*k, never B
        "candidates": svc.plan.topk_candidate_count(k),
        "b": b,
    }
    if placement == "multipod":
        pv, pi = svc.top_anomalies(k, per_pod=True)
        ok = True
        per_pod = b // 2
        for p in range(2):
            blk = ref[p * per_pod:(p + 1) * per_pod]
            want = p * per_pod + np.argsort(blk)[::-1][:k]
            ok = ok and np.array_equal(pi[p], want)
            ok = ok and np.allclose(pv[p], blk[np.argsort(blk)[::-1][:k]])
        case["per_pod_match"] = bool(ok)
    svc.close()
    out["cases"].append(case)
print(json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_topk_matches_full_gather_oracle():
    """Acceptance: on an 8-device mesh, `top_anomalies` equals the
    full-gather oracle while the query only materializes the
    num_shards·k candidate row (structural check), for both the
    sharded and multipod placements — including per-pod reports."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    proc = subprocess.run([sys.executable, "-c", _SHARDED_TOPK_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 8
    assert len(out["cases"]) == 2
    for case in out["cases"]:
        assert case["scores_max_err"] < 1e-6, case
        assert case["topk_ids_match"], case
        assert case["topk_vals_max_err"] < 1e-6, case
        assert case["candidates"] < case["b"], case
    mp = out["cases"][1]
    assert mp["per_pod_match"], mp


_CACHE_SCRIPT = r"""
import json, sys
import jax, jax.numpy as jnp
from repro.serving.config import ServiceConfig
from repro.serving.service import _apply_compilation_cache

jnp.arange(3.0).block_until_ready()  # a compile before the cache is set
_apply_compilation_cache(ServiceConfig(
    batch_size=1, n_pad=8, k_pad=1, compilation_cache_dir=sys.argv[1]))
jax.jit(lambda x: x * 2.0 + 1.0)(jnp.arange(5.0)).block_until_ready()
print(json.dumps({"dir": jax.config.jax_compilation_cache_dir}))
"""


@pytest.mark.parametrize("env_set", [False, True])
def test_compilation_cache_placed_from_outside(tmp_path, env_set):
    """``JAX_COMPILATION_CACHE_DIR`` wins over
    `ServiceConfig.compilation_cache_dir` (neither overridden nor
    raised against); without it the configured directory is rooted,
    even after earlier compiles, and entries land there."""
    cfg_dir, env_dir = tmp_path / "configured", tmp_path / "from_env"
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    proc = subprocess.run([sys.executable, "-c", _CACHE_SCRIPT,
                           str(cfg_dir)], env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    used, unused = (env_dir, cfg_dir) if env_set else (cfg_dir, env_dir)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["dir"] == str(used)
    assert used.is_dir() and any(used.iterdir())
    assert not unused.exists()
