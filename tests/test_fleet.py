"""repro.fleet: the multi-tenant serving fleet (ISSUE 8).

Acceptance anchors:
- bucketed routing, live cross-shard promotion, in-flight compaction
  and shard-kill recovery all preserve every tenant's JSdist scores to
  1e-5 against a single oracle `FingerService` fed the same deltas —
  including a tenant whose shard compacts *between* ingest and poll
  (stamped old-generation deltas in flight);
- whole-fleet `save`/`restore` round-trips (per-shard serving
  checkpoints + the ``fleet.json`` manifest), and post-save recovery
  rebuilds tenants from the on-disk checkpoints;
- every public fleet error is importable by name from `repro.fleet`
  (discovery-guarded, mirroring the kernels parity guard).
"""
import pathlib
import re

import jax
import numpy as np
import pytest

from repro.fleet import (
    AdmissionError,
    FingerFleet,
    FleetConfig,
    FleetConfigError,
    FleetError,
    FleetIngestError,
    FleetLifecycleError,
    PoolGroupError,
    PoolSpec,
    RecoveryError,
    ShardUnavailableError,
    UnknownTenantError,
)
from repro.core.sparse import SlotMap
from repro.fleet.router import FleetRouter
from repro.graphs.generators import erdos_renyi
from repro.graphs.types import GraphDelta
from repro.serving import FingerService, ServiceConfig, TopKSpec
from repro.serving.migrate import embed_delta

from slotmap_reference import as_json

K_PAD, J_PAD = 3, 2


def _two_bucket_cfg(method="dense", **kw):
    return FleetConfig(pools=(
        PoolSpec(name="small", n_pad=8, shards=2, streams_per_shard=2,
                 k_pad=K_PAD, j_pad=J_PAD, method=method),
        PoolSpec(name="large", n_pad=32, shards=2, streams_per_shard=2,
                 k_pad=K_PAD, j_pad=J_PAD, method=method),
    ), **kw)


class Oracle:
    """A single `FingerService` fed every tenant's deltas, embedded
    into one shared layout — the fleet must match it to 1e-5 no matter
    how it shuffles tenants between shards underneath."""

    def __init__(self, names, graphs, n_pad=32):
        self.names = list(names)
        self.n_pad = n_pad
        self.svc = FingerService.open(
            ServiceConfig(batch_size=len(self.names), n_pad=n_pad,
                          k_pad=K_PAD, j_pad=J_PAD,
                          topk=TopKSpec(k=len(self.names))),
            [graphs[n] for n in self.names])
        z = np.zeros((0,), np.float32)
        self.empty = GraphDelta.from_arrays(
            z, z, z, z, n_nodes=0, n_pad=n_pad, k_pad=K_PAD,
            j_pad=J_PAD)

    def tick(self, ds):
        self.svc.ingest([embed_delta(ds[n], self.n_pad) if n in ds
                         else self.empty for n in self.names])
        self.svc.poll()
        vals = np.asarray(self.svc.scores()).ravel()
        return {n: float(vals[i]) for i, n in enumerate(self.names)}

    def close(self):
        self.svc.close()


def _graph(n, seed):
    return erdos_renyi(n, 0.4, seed=seed, weighted=True)


def _delta(n_nodes, seed, scale=2.0):
    r = np.random.default_rng(seed)
    i, j = sorted(r.choice(n_nodes, 2, replace=False).tolist())
    return GraphDelta.from_arrays(
        [i], [j], [float(r.uniform(0.5, scale))], [0.0],
        n_nodes=n_nodes, k_pad=K_PAD, j_pad=J_PAD)


def _assert_parity(got, ref, label, names=None):
    for n in (names or ref):
        assert abs(got[n] - ref[n]) < 1e-5, (label, n, got[n], ref[n])


class TestFleetConfig:
    def test_named_validation_errors(self):
        small = PoolSpec(name="s", n_pad=8, k_pad=2)
        with pytest.raises(FleetConfigError, match="at least one"):
            FleetConfig(pools=()).validate()
        with pytest.raises(FleetConfigError, match="unique"):
            FleetConfig(pools=(small, small)).validate()
        with pytest.raises(FleetConfigError, match="ascending"):
            FleetConfig(pools=(
                PoolSpec(name="a", n_pad=8, k_pad=2),
                PoolSpec(name="b", n_pad=8, k_pad=2))).validate()
        with pytest.raises(FleetConfigError, match="shards"):
            FleetConfig(pools=(
                PoolSpec(name="a", n_pad=8, shards=0,
                         k_pad=2),)).validate()
        # bad shard-level field fails through the serving layer's own
        # diagnostics, renamed to the fleet's config error
        with pytest.raises(FleetConfigError, match="'a'"):
            FleetConfig(pools=(
                PoolSpec(name="a", n_pad=8, k_pad=0),)).validate()
        with pytest.raises(FleetConfigError, match="compact_occupancy"):
            FleetConfig(pools=(small,),
                        compact_occupancy=0.0).validate()
        with pytest.raises(FleetConfigError, match="save_every"):
            FleetConfig(pools=(small,),
                        save_every_ticks=5).validate()
        # sparse pools persist too (SlotMaps serialize into the shard
        # checkpoint manifest) — a sparse + directory config is legal
        FleetConfig(pools=(
            PoolSpec(name="sp", n_pad=64, k_pad=2, j_pad=2,
                     method="sparse_tick", n_slots=8, m_pad=16),),
            directory="/tmp/never").validate()
        with pytest.raises(FleetConfigError, match="no pool named"):
            FleetConfig(pools=(small,)).pool_index("nope")
        assert _two_bucket_cfg().pool_index("large") == 1


class TestErrorExportDiscovery:
    """Every ``*Error`` class defined anywhere under `repro.fleet` must
    be importable by name from the package root (mirrors the kernels
    parity-discovery guard): a new fleet failure mode can never ship
    as an anonymous exception."""

    def test_every_fleet_error_is_exported(self):
        import repro.fleet as pkg

        root = pathlib.Path(list(pkg.__path__)[0])
        found = set()
        for py in root.glob("*.py"):
            found |= set(re.findall(r"^class (\w*Error)\b",
                                    py.read_text(), re.M))
        assert found, "discovery found no fleet error classes"
        for name in sorted(found):
            assert name in pkg.__all__, f"{name} missing from __all__"
            exc = getattr(pkg, name)
            assert issubclass(exc, FleetError), name
            assert issubclass(exc, Exception), name


class TestRoutingOracleParity:
    """The headline invariant: best-fit admission, within-bucket
    growth and cross-bucket auto-promotion are all invisible in the
    scores — every tick matches the single-service oracle to 1e-5."""

    def test_admission_growth_and_promotion_parity(self):
        names = ["a", "b", "c"]
        sizes = {"a": 5, "b": 7, "c": 20}
        graphs = {n: _graph(sizes[n], i + 1)
                  for i, n in enumerate(names)}
        fleet = FingerFleet.open(_two_bucket_cfg())
        oracle = Oracle(names, graphs)
        try:
            for n in names:
                fleet.admit(n, graphs[n])
            # best-fit bucket, least-loaded shard, smallest slot
            at = {n: (e.pool, e.shard, e.slot)
                  for n, e in ((n, fleet.directory.get(n))
                               for n in names)}
            assert at == {"a": (0, 0, 0), "b": (0, 1, 0),
                          "c": (1, 0, 0)}

            def tick(ds):
                fleet.ingest(ds)
                fleet.poll()
                got = fleet.scores()
                _assert_parity(got, oracle.tick(ds),
                               f"step {fleet.step}")
                return got

            for t in range(3):
                tick({n: _delta(sizes[n], 50 + 10 * t + k)
                      for k, n in enumerate(names)})

            # within-bucket growth: joins extend the tenant node space
            # but still fit the small bucket (positions 0..7)
            tick({"a": GraphDelta.from_arrays(
                [0], [6], [1.5], [0.0], n_nodes=7, k_pad=K_PAD,
                j_pad=J_PAD, join=[5, 6])})
            sizes["a"] = 7

            # outgrow the bucket: the capacity pre-pass promotes the
            # tenant to the large pool mid-stream, and the very tick
            # that triggered it still matches the oracle
            tick({"a": GraphDelta.from_arrays(
                [0], [8], [2.0], [0.0], n_nodes=9, k_pad=K_PAD,
                j_pad=J_PAD, join=[7, 8])})
            sizes["a"] = 9
            e = fleet.directory.get("a")
            assert e.pool == 1 and e.slot_of_node.shape[0] == 9

            for t in range(2):
                got = tick({n: _delta(sizes[n], 90 + 10 * t + k)
                            for k, n in enumerate(names)})

            # fleet top-k merge agrees with the oracle's ranking
            merged = fleet.top_anomalies(k=3)
            order = sorted(got, key=lambda n: -got[n])
            assert [n for n, _ in merged] == order
            for n, v in merged:
                assert abs(v - got[n]) < 1e-6

            # evict frees the slot for the next admission
            fleet.evict("b")
            assert "b" not in fleet.directory
            fleet.admit("b2", _graph(6, 77))
            assert fleet.directory.get("b2").pool == 0
        finally:
            fleet.close()
            oracle.close()


class TestAdmissionAndLifecycleErrors:
    def test_named_errors(self):
        cfg = FleetConfig(pools=(
            PoolSpec(name="tiny", n_pad=8, shards=1,
                     streams_per_shard=2, k_pad=K_PAD, j_pad=J_PAD),))
        with FingerFleet.open(cfg) as fleet:
            fleet.admit("a", _graph(4, 1))
            with pytest.raises(AdmissionError, match="already"):
                fleet.admit("a", _graph(4, 1))
            with pytest.raises(AdmissionError, match="node slot"):
                fleet.admit("big", _graph(9, 2))  # no bucket fits
            fleet.admit("b", _graph(4, 3))
            with pytest.raises(AdmissionError):  # every slot taken
                fleet.admit("c", _graph(4, 4))
            with pytest.raises(UnknownTenantError, match="ghost"):
                fleet.ingest({"ghost": _delta(4, 5)})
            # edges touching a node the tenant never joined
            with pytest.raises(FleetIngestError, match="never joined"):
                fleet.ingest({"a": GraphDelta.from_arrays(
                    [0], [6], [1.0], [0.0], n_nodes=7, k_pad=K_PAD,
                    j_pad=J_PAD)})
            with pytest.raises(ShardUnavailableError):
                fleet.shard_service(0, 5)
            # strict ingest/poll alternation
            fleet.ingest({"a": _delta(4, 6)})
            with pytest.raises(FleetLifecycleError, match="staged"):
                fleet.ingest({"a": _delta(4, 7)})
            with pytest.raises(FleetLifecycleError, match="staged"):
                fleet.promote("a")
            fleet.poll()
            with pytest.raises(AdmissionError):
                fleet.promote("a")  # no bigger bucket exists
        with pytest.raises(FleetLifecycleError, match="closed"):
            fleet.scores()


class TestInFlightCompaction:
    """A staged fleet tick survives its shard compacting underneath it:
    the queued deltas are stamped with the pre-compaction generation
    and remapped through the serving grace machinery, and the
    post-compaction scores still match the oracle."""

    def test_staged_tick_survives_compaction(self):
        cfg = FleetConfig(pools=(
            PoolSpec(name="only", n_pad=16, shards=1,
                     streams_per_shard=2, k_pad=K_PAD, j_pad=J_PAD),),
            compact_occupancy=0.95)
        names = ["x", "y"]
        sizes = {"x": 4, "y": 3}
        graphs = {n: _graph(sizes[n], i + 11)
                  for i, n in enumerate(names)}
        fleet = FingerFleet.open(cfg)
        oracle = Oracle(names, graphs, n_pad=16)
        try:
            for n in names:
                fleet.admit(n, graphs[n])
            for t in range(2):
                ds = {n: _delta(sizes[n], 300 + 10 * t + k)
                      for k, n in enumerate(names)}
                fleet.ingest(ds)
                fleet.poll()
                _assert_parity(fleet.scores(), oracle.tick(ds),
                               f"warm step {t}")

            # stage a tick, then compact the shard before polling it
            ds = {n: _delta(sizes[n], 400 + k)
                  for k, n in enumerate(names)}
            fleet.ingest(ds)
            actions = fleet.rebalance()
            assert [a["action"] for a in actions] == ["compact"]
            assert actions[0]["new_n_pad"] < 16
            fleet.poll()
            _assert_parity(fleet.scores(), oracle.tick(ds),
                           "tick across compaction")

            # the composed position maps keep routing correct, and a
            # later join repads the shard back up warm
            ds = {"x": GraphDelta.from_arrays(
                [0], [5], [1.2], [0.0], n_nodes=6, k_pad=K_PAD,
                j_pad=J_PAD, join=[4, 5])}
            fleet.ingest(ds)
            fleet.poll()
            svc = fleet.shard_service(0, 0)
            assert svc.layout.n_pad == 16  # repadded to pool bound
            _assert_parity(fleet.scores(), oracle.tick(ds),
                           "post-compaction join")
        finally:
            fleet.close()
            oracle.close()


class TestRecovery:
    """Shard death: WAL-only ticks while dead, then recovery rebuilds
    the tenant (base ⊕ replay) on a survivor — scores stay on the
    oracle trajectory throughout."""

    def test_kill_wal_recover_parity(self):
        names = ["a", "b", "c"]
        sizes = {"a": 5, "b": 7, "c": 20}
        graphs = {n: _graph(sizes[n], i + 21)
                  for i, n in enumerate(names)}
        fleet = FingerFleet.open(_two_bucket_cfg())
        oracle = Oracle(names, graphs)
        try:
            for n in names:
                fleet.admit(n, graphs[n])

            def tick(ds, live):
                fleet.ingest(ds)
                fleet.poll()
                got, ref = fleet.scores(), oracle.tick(ds)
                _assert_parity(got, ref, f"step {fleet.step}", live)
                return got, ref

            for t in range(2):
                tick({n: _delta(sizes[n], 500 + 10 * t + k)
                      for k, n in enumerate(names)}, names)

            dead = fleet.kill_shard("small", 0)  # tenant "a"
            assert dead.pool == 0 and fleet.live_shards()[0] == [1]
            with pytest.raises(ShardUnavailableError, match="dead"):
                fleet.shard_service(0, 0)
            stale = fleet.scores()["a"]

            # while dead: a's delta is WAL-only; others keep serving
            ds = {n: _delta(sizes[n], 600 + k)
                  for k, n in enumerate(names)}
            _, ref = tick(ds, ["b", "c"])
            assert fleet.scores()["a"] == stale  # last known score

            reports = fleet.recover()
            assert [r["tenant"] for r in reports] == ["a"]
            e = fleet.directory.get("a")
            assert (e.pool, e.shard) == (0, 1)  # surviving small shard
            # the replayed WAL tick lands exactly on the oracle score
            assert abs(fleet.scores()["a"] - ref["a"]) < 1e-5

            tick({n: _delta(sizes[n], 700 + k)
                  for k, n in enumerate(names)}, names)
        finally:
            fleet.close()
            oracle.close()

    def test_recovery_without_base_or_checkpoint_is_named(self):
        cfg = FleetConfig(pools=(
            PoolSpec(name="tiny", n_pad=8, shards=2,
                     streams_per_shard=2, k_pad=K_PAD, j_pad=J_PAD),))
        with FingerFleet.open(cfg) as fleet:
            fleet.admit("a", _graph(4, 1))
            fleet.directory.get("a").base_state = None  # simulate
            fleet.kill_shard("tiny", 0)
            with pytest.raises(RecoveryError, match="checkpoint"):
                fleet.recover()


class TestFleetPersistence:
    """Whole-fleet save/restore plus post-save recovery, which must go
    through the on-disk shard checkpoints (save truncates the
    in-memory bases)."""

    def test_save_restore_kill_recover_roundtrip(self, tmp_path):
        names = ["a", "b", "c"]
        sizes = {"a": 5, "b": 7, "c": 20}
        graphs = {n: _graph(sizes[n], i + 31)
                  for i, n in enumerate(names)}
        cfg = _two_bucket_cfg(directory=str(tmp_path))
        fleet = FingerFleet.open(cfg)
        oracle = Oracle(names, graphs)
        try:
            for n in names:
                fleet.admit(n, graphs[n])

            def tick(f, ds, live=names):
                f.ingest(ds)
                f.poll()
                got, ref = f.scores(), oracle.tick(ds)
                _assert_parity(got, ref, f"step {f.step}", live)
                return got, ref

            # scale=5: keep per-tick JSdists well off zero, so the
            # (float32) host-replay drift after the disk-based
            # recovery below is not sqrt-amplified past the bound
            def ds_at(seed):
                return {n: _delta(sizes[n], seed + k, scale=5.0)
                        for k, n in enumerate(names)}

            for t in range(2):
                tick(fleet, ds_at(800 + 10 * t))
            last = fleet.scores()
            path = fleet.save()
            assert path.endswith("fleet.json")
            assert all(e.base_state is None for e in fleet.directory)
            fleet.close()

            fleet = FingerFleet.restore(cfg)
            assert fleet.step == 2
            got = fleet.scores()  # last known, from the manifest
            _assert_parity(got, last, "restored scores")
            tick(fleet, ds_at(900))

            # post-save recovery: the restored entries carry no
            # in-memory base, so the dead shard's tenants rebuild from
            # its serving checkpoint + their post-restore WAL
            fleet.kill_shard("small", 0)
            _, ref = tick(fleet, ds_at(950), ["b", "c"])
            fleet.recover()
            assert abs(fleet.scores()["a"] - ref["a"]) < 1e-5
            tick(fleet, ds_at(990))
        finally:
            fleet.close()
            oracle.close()

    def test_save_preconditions_are_named(self, tmp_path):
        with FingerFleet.open(_two_bucket_cfg()) as fleet:
            with pytest.raises(FleetConfigError, match="directory"):
                fleet.save()
        cfg = _two_bucket_cfg(directory=str(tmp_path))
        with FingerFleet.open(cfg) as fleet:
            fleet.kill_shard("small", 1)
            with pytest.raises(FleetLifecycleError, match="recover"):
                fleet.save()
        with pytest.raises(FleetConfigError, match="manifest"):
            FingerFleet.restore(_two_bucket_cfg(
                directory=str(tmp_path / "empty")))


class TestSparsePool:
    """A sparse (slot-space) bucket serves virtual-id deltas at parity
    with a dense oracle, and a sparse tenant promotes *live* into a
    dense bucket (slot-map gather) without leaving the oracle
    trajectory."""

    N_VIRT = 64

    def test_sparse_bucket_parity(self):
        cfg = FleetConfig(pools=(
            PoolSpec(name="slots", n_pad=self.N_VIRT, shards=1,
                     streams_per_shard=2, k_pad=4, j_pad=2,
                     method="sparse_tick", n_slots=12, m_pad=24),
            PoolSpec(name="wide", n_pad=128, shards=1,
                     streams_per_shard=2, k_pad=4, j_pad=2),))
        names = ["u", "v"]
        graphs = {n: _graph(8, i + 41) for i, n in enumerate(names)}
        fleet = FingerFleet.open(cfg)
        oracle = FingerService.open(
            ServiceConfig(batch_size=2, n_pad=self.N_VIRT, k_pad=4,
                          j_pad=2, topk=TopKSpec(k=2)),
            [graphs[n] for n in names])
        try:
            for n in names:
                fleet.admit(n, graphs[n])
            assert fleet.directory.get("u").pool == 0  # best fit
            rng = np.random.default_rng(5)

            def tick(t):
                ds = {}
                for n in names:
                    i, j = sorted(rng.choice(8, 2,
                                             replace=False).tolist())
                    ds[n] = GraphDelta.from_arrays(
                        [i], [j], [float(rng.uniform(0.5, 2.0))],
                        [0.0], n_nodes=self.N_VIRT, k_pad=4, j_pad=2)
                fleet.ingest(ds)
                fleet.poll()
                oracle.ingest([ds[n] for n in names])
                oracle.poll()
                got = fleet.scores()
                ref = np.asarray(oracle.scores()).ravel()
                for i, n in enumerate(names):
                    assert abs(got[n] - float(ref[i])) < 1e-5, \
                        (t, n, got[n], float(ref[i]))

            for t in range(3):
                tick(t)
            # live sparse -> dense promotion: the tenant's FINGER row
            # leaves the slot universe through its SlotMap gather and
            # keeps serving from the dense bucket at exact parity
            report = fleet.promote("u")
            e = fleet.directory.get("u")
            assert e.pool == 1 and report["to"][0] == 1
            assert e.slot_of_node is not None
            for t in range(2):
                tick(10 + t)
        finally:
            fleet.close()
            oracle.close()


class TestSparseRouterHostLeaves:
    """A sparse pool's deltas stay on the host from the router to the
    shard's `SlotMap`s: `translate` and `empty_delta` give numpy leaves,
    the slot-space deltas equal those of the device-leaf re-pad the
    router used to make, and the fleet keeps the oracle's scores
    through adds, deletes, joins and leaves."""

    N_VIRT, N_NODES, K, J = 64, 8, 4, 2

    @staticmethod
    def _assert_host(delta):
        for leaf in jax.tree_util.tree_leaves(delta):
            assert isinstance(leaf, np.ndarray)
            assert not isinstance(leaf, jax.Array)

    @staticmethod
    def _device_repad(delta, pool):
        """The router's former re-pad: device leaves."""
        m = np.asarray(delta.mask) > 0
        join, leave = FleetRouter._split_node_slots(delta)
        return GraphDelta.from_arrays(
            np.asarray(delta.senders)[m], np.asarray(delta.receivers)[m],
            np.asarray(delta.dw)[m], np.asarray(delta.w_old)[m],
            n_nodes=delta.n_nodes, n_pad=pool.n_pad, k_pad=pool.k_pad,
            j_pad=pool.j_pad, join=join, leave=leave)

    def _tick_delta(self, w, t, rng):
        """Tick ``t`` of one tenant whose edge weights are ``w``
        (updated in place): one add and one delete among the base
        nodes, and node 8 joining with an edge (t=1), losing it (t=2)
        and leaving as node 9 joins (t=3), which then gains one."""
        lanes, join, leave = [], [], []

        def lane(i, j, dw):
            old = w.get((i, j), 0.0)
            lanes.append((i, j, dw, old))
            if old + dw > 0:
                w[(i, j)] = old + dw
            else:
                del w[(i, j)]

        base = [(i, j) for i in range(self.N_NODES)
                for j in range(i + 1, self.N_NODES)]
        absent = [p for p in base if p not in w]
        present = [p for p in base if p in w]
        lane(*absent[rng.integers(len(absent))],
             float(rng.uniform(0.5, 2.0)))
        gone = present[rng.integers(len(present))]
        lane(*gone, -w[gone])
        if t == 1:
            join.append(8)
            lane(0, 8, 1.5)
        elif t == 2:
            lane(0, 8, -w[(0, 8)])
        elif t == 3:
            leave.append(8)
            join.append(9)
        elif t == 4:
            lane(1, 9, 0.75)
        lo, hi, dw, w_old = zip(*lanes)
        return GraphDelta.from_arrays(
            lo, hi, dw, w_old, n_nodes=self.N_VIRT, k_pad=self.K,
            j_pad=self.J, join=join, leave=leave)

    def test_host_leaves_translate_as_device_leaves_did(self):
        cfg = FleetConfig(pools=(
            PoolSpec(name="slots", n_pad=self.N_VIRT, shards=1,
                     streams_per_shard=3, k_pad=self.K, j_pad=self.J,
                     method="sparse_tick", n_slots=12, m_pad=32),))
        pool = cfg.pools[0]
        names = ["u", "v"]
        graphs = {n: _graph(self.N_NODES, i + 71)
                  for i, n in enumerate(names)}
        fleet = FingerFleet.open(cfg)
        oracle = FingerService.open(
            ServiceConfig(batch_size=2, n_pad=self.N_VIRT, k_pad=self.K,
                          j_pad=self.J, topk=TopKSpec(k=2)),
            [graphs[n] for n in names])
        try:
            for n in names:
                fleet.admit(n, graphs[n])
            svc = fleet.shard_service(0, 0)
            self._assert_host(fleet.router.empty_delta(pool))
            slots = {n: fleet.directory.get(n).slot for n in names}
            # shadow maps: one fed the router's output, one the old
            # device-leaf re-pad, both from the shard's admitted maps
            host_maps, dev_maps = (
                {n: SlotMap.restore(svc.slot_maps[slots[n]].header(),
                                    svc.slot_maps[slots[n]].arrays())
                 for n in names} for _ in range(2))
            weights = {}
            for n in names:
                w = np.asarray(graphs[n].weights)
                weights[n] = {(i, j): float(w[i, j])
                              for i in range(self.N_NODES)
                              for j in range(i + 1, self.N_NODES)
                              if w[i, j] > 0}
            rng = np.random.default_rng(11)
            for t in range(6):
                ds = {n: self._tick_delta(weights[n], t, rng)
                      for n in names}
                for n in names:
                    entry = fleet.directory.get(n)
                    got = fleet.router.translate(entry, ds[n], pool)
                    self._assert_host(got)
                    new = host_maps[n].translate(got)
                    old = dev_maps[n].translate(
                        self._device_repad(ds[n], pool))
                    new_leaves, new_def = jax.tree_util.tree_flatten(new)
                    old_leaves, old_def = jax.tree_util.tree_flatten(old)
                    assert new_def == old_def, (t, n)
                    for a, b in zip(new_leaves, old_leaves):
                        assert a.dtype == b.dtype, (t, n)
                        np.testing.assert_array_equal(a, b)
                fleet.ingest(ds)
                fleet.poll()
                oracle.ingest([ds[n] for n in names])
                oracle.poll()
                got = fleet.scores()
                ref = np.asarray(oracle.scores()).ravel()
                for i, n in enumerate(names):
                    assert abs(got[n] - float(ref[i])) < 1e-5, \
                        (t, n, got[n], float(ref[i]))
                    assert as_json(svc.slot_maps[slots[n]]) \
                        == as_json(host_maps[n]), (t, n)
        finally:
            fleet.close()
            oracle.close()


class TestStackedSequentialParity:
    """The stacked pool-tick dispatch is a pure execution-plane
    optimization: the identical lifecycle — admit → ticks → cross-
    bucket promotion → staged-tick compaction → save/restore → shard
    kill + WAL tick + recovery — run with ``stacked_ticks`` on and off
    produces the same per-tenant scores to 1e-5 at every step. Holds
    for every tick method: the vmapped dense bodies AND the megakernel
    methods, whose stacked spelling is one (S, B)-gridded
    `pallas_call` per layout group."""

    NAMES = ["a", "b", "c"]
    SIZES = {"a": 5, "b": 6, "c": 18}

    def _lifecycle(self, stacked, tmp_path, method="dense"):
        sizes = dict(self.SIZES)
        graphs = {n: _graph(sizes[n], i + 61)
                  for i, n in enumerate(self.NAMES)}
        cfg = _two_bucket_cfg(method=method,
                              compact_occupancy=0.95,
                              stacked_ticks=stacked,
                              directory=str(tmp_path))
        trace = []
        fleet = FingerFleet.open(cfg)
        try:
            for n in self.NAMES:
                fleet.admit(n, graphs[n])

            def tick(seed):
                ds = {n: _delta(sizes[n], seed + k)
                      for k, n in enumerate(self.NAMES)}
                fleet.ingest(ds)
                fleet.poll()
                trace.append(fleet.scores())

            for t in range(3):
                tick(40 + 10 * t)
            fleet.promote("a")  # small -> large, live
            tick(80)
            # compact the vacated small shard under a staged tick
            fleet.ingest({n: _delta(sizes[n], 90 + k)
                          for k, n in enumerate(self.NAMES)})
            actions = fleet.rebalance()
            assert any(a["action"] == "compact" for a in actions)
            fleet.poll()
            trace.append(fleet.scores())
            # save / restore mid-stream, then keep serving
            fleet.save()
            fleet.close()
            fleet = FingerFleet.restore(cfg)
            tick(100)
            # kill b's shard: its tick goes WAL-only, then recovery
            # replays it on the survivor (from the saved checkpoint —
            # the restored entries carry no in-memory base)
            fleet.kill_shard("small", fleet.directory.get("b").shard)
            tick(110)
            fleet.recover()
            trace.append(fleet.scores())
            tick(120)
            trace.append(dict(fleet.top_anomalies(k=3)))
        finally:
            fleet.close()
        return trace

    @staticmethod
    def _assert_traces_match(stacked, sequential):
        assert len(stacked) == len(sequential)
        for i, (s, q) in enumerate(zip(stacked, sequential)):
            assert set(s) == set(q), i
            for n in s:
                assert abs(s[n] - q[n]) < 1e-5, (i, n, s[n], q[n])

    def test_lifecycle_scores_match_to_1e5(self, tmp_path):
        self._assert_traces_match(
            self._lifecycle(True, tmp_path / "on"),
            self._lifecycle(False, tmp_path / "off"))

    def test_fused_lifecycle_scores_match_to_1e5(self, tmp_path):
        """Megakernel pools through the same full lifecycle: the
        stacked (S, B)-gridded launch must be score-invisible against
        per-shard sequential fused ticks — including across the group
        splits promotion and compaction cause."""
        self._assert_traces_match(
            self._lifecycle(True, tmp_path / "on",
                            method="fused_tick"),
            self._lifecycle(False, tmp_path / "off",
                            method="fused_tick"))

    def _sparse_lifecycle(self, stacked, tmp_path):
        """Sparse lifecycle: sparse-pool ticks, live sparse → dense
        promotion, whole-fleet save/restore (SlotMaps through the
        checkpoint manifest), sparse shard kill + WAL tick + disk-
        base recovery."""
        cfg = FleetConfig(pools=(
            PoolSpec(name="slots", n_pad=24, shards=2,
                     streams_per_shard=2, k_pad=4, j_pad=2,
                     method="sparse_tick", n_slots=12, m_pad=24),
            PoolSpec(name="big", n_pad=64, shards=1,
                     streams_per_shard=2, k_pad=4, j_pad=2),
        ), stacked_ticks=stacked, directory=str(tmp_path))
        names = ["u", "v", "w"]
        graphs = {n: _graph(8, i + 71) for i, n in enumerate(names)}
        trace = []
        rng = np.random.default_rng(13)
        fleet = FingerFleet.open(cfg)
        try:
            for n in names:
                fleet.admit(n, graphs[n])
            assert all(fleet.directory.get(n).pool == 0
                       for n in names)

            def tick():
                ds = {}
                for n in names:
                    i, j = sorted(rng.choice(8, 2,
                                             replace=False).tolist())
                    ds[n] = GraphDelta.from_arrays(
                        [i], [j], [float(rng.uniform(0.5, 2.0))],
                        [0.0], n_nodes=24, k_pad=4, j_pad=2)
                fleet.ingest(ds)
                fleet.poll()
                trace.append(fleet.scores())

            for _ in range(3):
                tick()
            fleet.promote("u")  # sparse -> dense, live
            assert fleet.directory.get("u").pool == 1
            tick()
            # sparse shards persist: whole-fleet save/restore
            fleet.save()
            fleet.close()
            fleet = FingerFleet.restore(cfg)
            tick()
            # kill one sparse shard (its stacked group shrinks S=2→1),
            # WAL-only tick, then disk-base recovery through the
            # checkpoint's serialized SlotMaps
            fleet.kill_shard("slots", fleet.directory.get("v").shard)
            tick()
            fleet.recover()
            trace.append(fleet.scores())
            tick()
        finally:
            fleet.close()
        return trace

    def test_sparse_lifecycle_scores_match_to_1e5(self, tmp_path):
        self._assert_traces_match(
            self._sparse_lifecycle(True, tmp_path / "on"),
            self._sparse_lifecycle(False, tmp_path / "off"))


class TestPerShardReadout:
    """A shard that ticks on its own serves `scores` and `top_anomalies`
    from one host read of its (B,) scores: the same numbers as its
    one-slot device read and its device top-k."""

    def test_host_row_matches_the_device_reads(self, tmp_path):
        names = ["a", "b", "c"]
        cfg = _two_bucket_cfg(stacked_ticks=False, directory=str(tmp_path))
        fleet = FingerFleet.open(cfg)
        try:
            for i, n in enumerate(names):
                fleet.admit(n, _graph(6, i + 7))
            for t in range(3):
                fleet.ingest({n: _delta(6, 30 + 10 * t + k)
                              for k, n in enumerate(names)})
                fleet.poll()
                got, top = fleet.scores(), fleet.top_anomalies(k=2)
                dev = []
                for pool_i, shard_i in fleet.live_shard_ids():
                    svc = fleet.shard_service(pool_i, shard_i)
                    vals, slots = svc.top_anomalies(k=2)
                    for v, slot in zip(vals, slots):
                        entry = fleet.directory.tenant_at(
                            pool_i, shard_i, int(slot))
                        if entry is not None:
                            assert got[entry.name] == svc.score_at(
                                entry.slot)
                            dev.append((float(v), entry.name))
                dev.sort(key=lambda c: -c[0])
                assert len(dev) == len(names)
                assert top == [(n, v) for v, n in dev[:2]]
        finally:
            fleet.close()


class TestPoolTickGrouping:
    """`pooltick` group rules: one stacked launch covers one layout
    group of one method — mixed-method entry lists are a caller bug
    and raise by name instead of warming a plan no poll() will use."""

    def test_warm_pool_tick_rejects_mixed_methods(self):
        from repro.fleet import pooltick
        from repro.graphs.layout import NodeLayout

        dense = ServiceConfig(batch_size=2, n_pad=8, k_pad=3, j_pad=2)
        fused = dense.with_(method="fused_tick")
        lay = NodeLayout(8)
        with pytest.raises(PoolGroupError, match="mixed"):
            pooltick.warm_pool_tick([(dense, lay), (fused, lay)])

    def test_warm_covers_groups_over_the_vmem_guard(self):
        # A sparse pool whose tile the VMEM guard refuses ticks shard by
        # shard; warm() must have compiled those per-shard ticks too.
        from repro.analysis.sanitize import compile_budget
        from repro.kernels import dispatch

        cfg = FleetConfig(pools=(
            PoolSpec(name="slots", n_pad=24, shards=2,
                     streams_per_shard=2, k_pad=4, j_pad=2,
                     method="sparse_tick", n_slots=12, m_pad=24),))
        rng = np.random.default_rng(5)
        with dispatch.vmem_budget(1024), FingerFleet.open(cfg) as fleet:
            for i, n in enumerate("abc"):
                fleet.admit(n, _graph(8, i + 40))
            fleet.warm()
            with compile_budget(0, "polls of a guard-refused pool"):
                for _ in range(2):
                    ds = {}
                    for n in "abc":
                        i, j = sorted(rng.choice(8, 2, replace=False)
                                      .tolist())
                        ds[n] = GraphDelta.from_arrays(
                            [i], [j], [1.5], [0.0], n_nodes=24, k_pad=4,
                            j_pad=2)
                    fleet.ingest(ds)
                    fleet.poll()
                    fleet.scores()
                    fleet.top_anomalies(k=2)
            assert fleet.last_poll_launches == 2  # one per shard


class TestWalRetention:
    """`FleetConfig.wal_retention_ticks`: ingest prunes WAL entries
    older than the window, `wal_floor` records the pruned horizon, and
    recovery refuses a gapped log by name."""

    def _cfg(self, **kw):
        return FleetConfig(pools=(
            PoolSpec(name="tiny", n_pad=8, shards=2,
                     streams_per_shard=2, k_pad=K_PAD, j_pad=J_PAD),),
            wal_retention_ticks=2, **kw)

    def test_config_rejects_nonpositive_retention(self):
        with pytest.raises(FleetConfigError, match="wal_retention"):
            FleetConfig(pools=(
                PoolSpec(name="tiny", n_pad=8, k_pad=2),),
                wal_retention_ticks=0).validate()

    def test_prunes_and_raises_on_gapped_recovery(self):
        with FingerFleet.open(self._cfg()) as fleet:
            fleet.admit("a", _graph(4, 1))
            for t in range(5):
                fleet.ingest({"a": _delta(4, 100 + t)})
                fleet.poll()
            e = fleet.directory.get("a")
            assert [s for s, _ in e.wal] == [4, 5]
            assert e.wal_floor == 3
            # steps (0, 3] are gone and no durable base covers them
            fleet.kill_shard("tiny", e.shard)
            with pytest.raises(RecoveryError,
                               match="wal_retention_ticks"):
                fleet.recover()

    def test_save_keeps_recovery_within_window(self, tmp_path):
        with FingerFleet.open(
                self._cfg(directory=str(tmp_path))) as fleet:
            fleet.admit("a", _graph(4, 1))
            for t in range(3):
                fleet.ingest({"a": _delta(4, 200 + t)})
                fleet.poll()
            fleet.save()  # durable base at step 3 covers the pruning
            for t in range(2):
                fleet.ingest({"a": _delta(4, 300 + t)})
                fleet.poll()
            e = fleet.directory.get("a")
            assert e.base_step == 3 and e.wal_floor == 3
            before = fleet.scores()["a"]
            fleet.kill_shard("tiny", e.shard)
            fleet.recover()  # disk base + intact WAL: no gap
            assert abs(fleet.scores()["a"] - before) < 1e-5


class TestFleetHotPathBudgets:
    """The PR 9 dispatch/transfer regression gate, via the extended
    sentinel: warm fleet ticks run at zero compiles, `poll()` issues
    one launch per pool layout-group (not per shard), `ingest()` and
    the poll dispatch pull nothing to host, and `scores()` costs at
    most one device→host transfer per pool per tick."""

    def test_fleet_chain_budgets(self):
        from repro.analysis.sentinel import run_fleet_chain

        r = run_fleet_chain(ticks_per_phase=2)
        assert r["ok"]
        assert r["phases"] == {"ticks_promotion": 0,
                               "ticks_staged_compaction": 0}
        assert r["launches_steady"] == len(r["pools"])
        assert r["launches_post_compaction"] > len(r["pools"])
        assert r["transfer_budget_scores_per_tick"] == len(r["pools"])


class TestFleetProperty:
    """The ISSUE's end-to-end property: a randomized tick stream over
    ≥2 buckets × ≥2 shards in which a tenant is promoted across
    buckets mid-stream, a shard compacts under a staged tick, a shard
    is killed and its tenants restored onto survivors — and every
    tenant's score matches the single-service oracle to 1e-5 at every
    step."""

    def test_fleet_matches_oracle_through_all_events(self):
        names = ["a", "b", "c"]
        sizes = {"a": 5, "b": 6, "c": 18}
        graphs = {n: _graph(sizes[n], i + 61)
                  for i, n in enumerate(names)}
        cfg = _two_bucket_cfg(compact_occupancy=0.95)
        fleet = FingerFleet.open(cfg)
        oracle = Oracle(names, graphs)
        rng = np.random.default_rng(7)
        try:
            for n in names:
                fleet.admit(n, graphs[n])
            fleet.warm(background=True).wait(timeout=600)

            def rand_ds(grow=None):
                ds = {}
                for n in names:
                    if n == grow:
                        new = sizes[n] + 2
                        ds[n] = GraphDelta.from_arrays(
                            [0], [new - 1],
                            [float(rng.uniform(0.5, 2.0))], [0.0],
                            n_nodes=new, k_pad=K_PAD, j_pad=J_PAD,
                            join=[new - 2, new - 1])
                        sizes[n] = new
                    else:
                        ds[n] = _delta(sizes[n], int(rng.integers(1e6)))
                return ds

            for step in range(12):
                live = list(names)
                # a grows by 2 nodes on steps 2/4/6 — it crosses the
                # small bucket's n_pad=8 bound mid-stream and the
                # capacity pre-pass promotes it to the large pool
                ds = rand_ds(grow="a" if step in (2, 4, 6) else None)
                fleet.ingest(ds)
                if step == 5:
                    # compact under the staged tick (occupancy of the
                    # vacated small shards is now below 0.95)
                    fleet.rebalance()
                fleet.poll()
                got, ref = fleet.scores(), oracle.tick(ds)
                if step >= 8 and self._dead_holds(fleet, "b"):
                    live.remove("b")
                _assert_parity(got, ref, f"property step {step}", live)
                if step == 7:
                    fleet.kill_shard(
                        "small",
                        fleet.directory.get("b").shard)
                if step == 9:
                    fleet.recover()
                    assert abs(fleet.scores()["b"] - ref["b"]) < 1e-5
            assert fleet.directory.get("a").pool == 1
        finally:
            fleet.close()
            oracle.close()

    @staticmethod
    def _dead_holds(fleet, name):
        e = fleet.directory.get(name)
        return fleet._is_dead(e.pool, e.shard)
