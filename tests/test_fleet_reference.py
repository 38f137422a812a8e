"""The fleet against the plain per-stream reference.

Every other fleet test compares program code with program code (stacked
against sequential ticks, the fleet against an oracle `FingerService`).
Here each tenant's scores are held to core `jsdist_incremental` run on
that tenant's own deltas, one stream at a time, with no fleet, serving
or padding code in between: a two-pool fleet of three tenants ticks ten
times, in every tick path, under four kinds of traffic.
"""
import numpy as np
import pytest

from repro.core import finger_state, jsdist_incremental
from repro.fleet import FingerFleet, FleetConfig, PoolSpec
from repro.graphs.generators import erdos_renyi
from repro.graphs.types import GraphDelta

METHODS = ("dense", "compact", "fused_tick", "sparse_tick")
TRAFFIC = ("add", "churn", "reweight", "join_leave")
TICKS, K_PAD, J_PAD = 10, 4, 2
# Two tenants in the small pool, one in the large; a join_leave tenant
# takes a new node id every other tick, so the pools leave room for 5.
SIZES = {"a": 8, "b": 12, "c": 24}


def _config(method):
    sparse = method == "sparse_tick"

    def pool(name, n_pad, m_pad):
        return PoolSpec(name=name, n_pad=n_pad, shards=1,
                        streams_per_shard=2, k_pad=K_PAD, j_pad=J_PAD,
                        method=method,
                        n_slots=n_pad if sparse else None,
                        m_pad=m_pad if sparse else None)

    return FleetConfig(pools=(pool("small", 20, 64), pool("large", 32, 160)))


class Tenant:
    """One tenant's host-side truth: its live edge weights, and the
    reference state advanced by core `jsdist_incremental`."""

    def __init__(self, name, seed, traffic):
        self.name, self.traffic = name, traffic
        self.n = SIZES[name]
        self.graph = erdos_renyi(self.n, 0.3, seed=seed, weighted=True)
        w = np.asarray(self.graph.weights)
        self.w = {(i, j): float(w[i, j]) for i in range(self.n)
                  for j in range(i + 1, self.n) if w[i, j] > 0}
        self.rng = np.random.default_rng(seed + 1000)
        # Joins grow the node space, so the reference layout holds the
        # largest it reaches; other traffic runs on the graph as it is.
        self.n_ref = self.n + (TICKS + 1) // 2 \
            if traffic == "join_leave" else self.n
        g = self.graph if self.n_ref == self.n \
            else self.graph.pad_to(self.n_ref)
        self.ref = finger_state(g)
        self.joined = None  # (node, neighbour) of the last join

    def _absent(self):
        base = [(i, j) for i in range(self.n) for j in range(i + 1, self.n)
                if (i, j) not in self.w]
        return base[self.rng.integers(len(base))]

    def _present(self):
        live = sorted(self.w)
        return live[self.rng.integers(len(live))]

    def next_delta(self, t):
        """Tick ``t``'s lanes as (lo, hi, dw, w_old) plus joins and
        leaves; ``self.w`` and the node space move with it."""
        lanes, join, leave = [], [], []

        def lane(i, j, dw):
            old = self.w.get((i, j), 0.0)
            lanes.append((i, j, dw, old))
            if old + dw > 0:
                self.w[(i, j)] = old + dw
            else:
                del self.w[(i, j)]

        if self.traffic == "add":
            lane(*self._absent(), float(self.rng.uniform(0.5, 2.0)))
        elif self.traffic == "churn":
            gone = self._present()
            lane(*gone, -self.w[gone])
            lane(*self._absent(), float(self.rng.uniform(0.5, 2.0)))
        elif self.traffic == "reweight":
            p = self._present()
            lane(*p, float(self.rng.uniform(0.5, 2.0)) - self.w[p])
        elif t % 2 == 0:
            # a new node joins with its first edge
            v, u = self.n, int(self.rng.integers(SIZES[self.name]))
            self.n += 1
            join.append(v)
            lane(u, v, float(self.rng.uniform(0.5, 2.0)))
            self.joined = (u, v)
        else:
            # it loses that edge and leaves isolated
            u, v = self.joined
            lane(u, v, -self.w[(u, v)])
            leave.append(v)
        lo, hi, dw, w_old = (list(x) for x in zip(*lanes))
        return lo, hi, dw, w_old, join, leave

    def deltas(self, t):
        """(the fleet's tenant-space delta, the reference's delta)."""
        lo, hi, dw, w_old, join, leave = self.next_delta(t)
        kw = dict(k_pad=K_PAD, j_pad=J_PAD, join=join, leave=leave)
        fleet_d = GraphDelta.from_arrays(lo, hi, dw, w_old,
                                         n_nodes=self.n, **kw)
        if self.traffic != "join_leave":
            kw = dict(k_pad=K_PAD)  # the unpadded stream has no node lanes
        ref_d = GraphDelta.from_arrays(lo, hi, dw, w_old, n_nodes=self.n,
                                       n_pad=self.n_ref, **kw)
        return fleet_d, ref_d

    def active_mask(self):
        return np.ones(self.n) if self.ref.node_mask is None \
            else self.ref.node_mask

    def ref_tick(self, d):
        dist, self.ref = jsdist_incremental(self.ref, d)
        return float(dist)


def _active_nodes(fleet, name):
    e = fleet.directory.get(name)
    mask = fleet.shard_service(e.pool, e.shard).states().node_mask
    return int(np.sum(np.asarray(mask[e.slot])))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("traffic", TRAFFIC)
@pytest.mark.parametrize("method", METHODS)
def test_fleet_matches_per_stream_reference(method, traffic, seed):
    tenants = {n: Tenant(n, 10 * seed + i, traffic)
               for i, n in enumerate(SIZES)}
    fleet = FingerFleet.open(_config(method))
    try:
        for n, ten in tenants.items():
            fleet.admit(n, ten.graph)
        assert {n: fleet.directory.get(n).pool for n in tenants} == \
            {"a": 0, "b": 0, "c": 1}
        for t in range(TICKS):
            pairs = {n: ten.deltas(t) for n, ten in tenants.items()}
            fleet.ingest({n: p[0] for n, p in pairs.items()})
            fleet.poll()
            want = {n: tenants[n].ref_tick(p[1]) for n, p in pairs.items()}
            got = fleet.scores()
            for n, ten in tenants.items():
                assert abs(got[n] - want[n]) < 1e-5, (t, n, got[n], want[n])
                # scores cannot see an isolated leave; the node mask can
                assert _active_nodes(fleet, n) == \
                    int(np.sum(np.asarray(ten.active_mask()))), (t, n)
            # the two highest reference scores, each named by a tenant
            # that holds it (exact ties may come in either order)
            top = fleet.top_anomalies(k=2)
            best = sorted(want.values(), reverse=True)[:2]
            assert len(top) == 2, top
            for (n, v), w in zip(top, best):
                assert abs(v - w) < 1e-5 and abs(want[n] - w) < 1e-5, \
                    (t, top, want)
    finally:
        fleet.close()
