"""The fleet's profiler spans and counters (``finger.*``).

Three ticks of a small sparse fleet are traced on the CPU with
`jax.profiler` and read back with `ProfileData`, once with the pool
ticking as one stacked launch and once shard by shard. The spans must
nest as the serving loop runs, their counters must equal what was sent
and staged, every blocking device-to-host read of the read path must
have its own ``finger.d2h`` span, and the fleet-level spans of one tick
must share its fleet step.
"""
import glob
import os
from collections import Counter

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.fleet import FingerFleet, FleetConfig, PoolSpec
from repro.graphs.generators import erdos_renyi
from repro.graphs.types import GraphDelta
from repro.serving import FingerService, ServiceConfig, TopKSpec
from repro.serving.plans import dummy_tick_args

N_VIRT, N_NODES, B, K_PAD, TICKS, SAVE_EVERY = 32, 10, 3, 4, 3, 2
FLEET_SPANS = ("finger.ingest", "finger.poll", "finger.scores",
               "finger.top_anomalies")


def _deltas(rng, names):
    """Tenant ``i`` sends ``i + 1`` distinct edge lanes."""
    pairs = [(i, j) for i in range(N_NODES) for j in range(i + 1, N_NODES)]
    out = {}
    for t, name in enumerate(names):
        pick = rng.choice(len(pairs), t + 1, replace=False)
        lo, hi = zip(*(pairs[p] for p in pick))
        out[name] = GraphDelta.from_arrays(
            lo, hi, np.full(t + 1, 0.5), np.zeros(t + 1),
            n_nodes=N_VIRT, k_pad=K_PAD)
    return out


def _host_spans(trace_dir):
    """The ``finger.*`` host events as (name, start, end, stats)."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("finger."):
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.fixture(scope="module", params=["stacked", "per-shard"])
def traced(request, tmp_path_factory):
    """(mode, spans, lanes sent per tick, expected h2d bytes, fleet)."""
    cfg = FleetConfig(
        pools=(PoolSpec(name="slots", n_pad=N_VIRT, shards=1,
                        streams_per_shard=B, k_pad=K_PAD,
                        method="sparse_tick", n_slots=16, m_pad=64),),
        stacked_ticks=request.param == "stacked",
        directory=str(tmp_path_factory.mktemp("fleet")),
        save_every_ticks=SAVE_EVERY)
    fleet = FingerFleet.open(cfg)
    names = [f"t{i}" for i in range(B)]
    for i, name in enumerate(names):
        fleet.admit(name, erdos_renyi(N_NODES, 0.4, seed=i,
                                      weighted=True))
    rng = np.random.default_rng(7)
    # One warm tick outside the trace compiles what the ticks run.
    fleet.ingest(_deltas(rng, names))
    fleet.poll()
    fleet.scores()
    fleet.top_anomalies(k=2)
    svc = fleet.shard_service(0, 0)
    _, staged = dummy_tick_args(svc.config, svc.capacity)
    want_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(staged))
    sent = []
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(TICKS):
            deltas = _deltas(rng, names)
            sent.append(sum(d.lane_count() for d in deltas.values()))
            fleet.ingest(deltas)
            fleet.poll()
            fleet.scores()
            fleet.top_anomalies(k=2)
    finally:
        jax.profiler.stop_trace()
    yield request.param, _host_spans(trace_dir), sent, want_bytes, fleet
    fleet.close()


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _within(spans, name, outer):
    return [s for s in _named(spans, name) if _inside(s, outer)]


def test_ingest_spans_nest(traced):
    _, spans, _, _, _ = traced
    ingests = _named(spans, "finger.ingest")
    assert len(ingests) == TICKS
    for ingest in ingests:
        for name in ("finger.route", "finger.wal"):
            assert len(_within(spans, name, ingest)) == 1, name
        shard, = _within(spans, "finger.shard_ingest", ingest)
        for name in ("finger.slotmap", "finger.stack", "finger.h2d"):
            assert len(_within(spans, name, shard)) == 1, name
    for name in ("finger.route", "finger.wal", "finger.shard_ingest",
                 "finger.slotmap", "finger.stack", "finger.h2d"):
        for s in _named(spans, name):
            assert any(_inside(s, i) for i in ingests), name


def test_lane_counters_equal_the_lanes_sent(traced):
    _, spans, sent, _, _ = traced
    ingests = _named(spans, "finger.ingest")
    assert [s[3]["lanes"] for s in ingests] == sent
    slotmaps = _named(spans, "finger.slotmap")
    assert [s[3]["lanes"] for s in slotmaps] == sent
    for s in slotmaps:
        assert 0 < s[3]["kept"] <= s[3]["lanes"]


def test_slotmap_reads_nothing_back_from_the_fleet(traced):
    """The fleet router hands a sparse shard host leaves, so `SlotMap`
    reads no device array back."""
    _, spans, _, _, _ = traced
    slotmaps = _named(spans, "finger.slotmap")
    assert len(slotmaps) == TICKS
    assert [s[3]["device_reads"] for s in slotmaps] == [0] * TICKS


def test_slotmap_counts_device_leaves_read_back(tmp_path):
    """A direct sparse `FingerService` caller that passes `from_arrays`
    (device-leaf) deltas is counted: seven leaves a stream (five edge
    and two node leaves), none for a stream given host leaves."""
    graphs = [erdos_renyi(N_NODES, 0.4, seed=i, weighted=True)
              for i in range(B)]
    svc = FingerService.open(ServiceConfig(
        batch_size=B, n_pad=N_VIRT, k_pad=K_PAD, j_pad=2,
        method="sparse_tick", n_slots=16, m_pad=64,
        topk=TopKSpec(k=2)), graphs)
    try:
        deltas = [GraphDelta.from_arrays([0], [1 + i], [0.5], [0.0],
                                         n_nodes=N_VIRT, k_pad=K_PAD,
                                         j_pad=2)
                  for i in range(B - 1)]
        deltas.append(GraphDelta.host_from_arrays(
            [0], [B], [0.5], [0.0], n_nodes=N_VIRT, k_pad=K_PAD,
            j_pad=2))
        trace_dir = str(tmp_path)
        jax.profiler.start_trace(trace_dir)
        try:
            svc.ingest(deltas)
        finally:
            jax.profiler.stop_trace()
        svc.poll()
    finally:
        svc.close()
    slotmap, = _named(_host_spans(trace_dir), "finger.slotmap")
    assert slotmap[3]["device_reads"] == 7 * (B - 1)


def test_h2d_bytes_are_the_staged_deltas_leaves(traced):
    _, spans, _, want_bytes, _ = traced
    h2d = _named(spans, "finger.h2d")
    assert len(h2d) == TICKS
    assert want_bytes == B * K_PAD * 4 * 6  # six (B, k_pad) 4-byte leaves
    assert {s[3]["bytes"] for s in h2d} == {want_bytes}


def test_d2h_reads_per_tick_follow_the_read_path(traced):
    _, spans, _, _, _ = traced
    reads = Counter()
    for name in ("finger.scores", "finger.top_anomalies"):
        for outer in _named(spans, name):
            reads[outer[3]["step"]] += len(_within(spans, "finger.d2h",
                                                   outer))
    # one pull of the tick's scores, the pool's stacked plane or the
    # shard's own row, serves every tenant's score and the top-k
    assert list(reads.values()) == [1] * TICKS
    assert len(_named(spans, "finger.d2h")) == TICKS


def test_fleet_spans_carry_the_fleet_step(traced):
    _, spans, _, _, fleet = traced
    steps = {name: [s[3]["step"] for s in _named(spans, name)]
             for name in FLEET_SPANS}
    first = fleet.step - TICKS + 1
    for name in FLEET_SPANS:
        assert steps[name] == list(range(first, fleet.step + 1)), name


def test_poll_counts_its_launches_and_saves(traced):
    _, spans, _, _, fleet = traced
    for poll in _named(spans, "finger.poll"):
        assert poll[3]["launches"] == fleet.last_poll_launches == 1
        assert len(_within(spans, "finger.dispatch", poll)) == 1
        saves = _within(spans, "finger.save", poll)
        assert len(saves) == (poll[3]["step"] % SAVE_EVERY == 0)
    assert len(_named(spans, "finger.save")) \
        == sum(s % SAVE_EVERY == 0 for s in range(fleet.step - TICKS + 1,
                                                  fleet.step + 1))


def test_admit_and_new_edge_counters(tmp_path):
    """A sparse admission runs in a ``finger.admit`` span counting the
    nodes and edges it gave slots; ``finger.slotmap`` counts the edge
    slots a tick allocates (``new_edges``): its lanes less those whose
    edge is already live."""
    fleet = FingerFleet.open(FleetConfig(pools=(PoolSpec(
        name="slots", n_pad=N_VIRT, shards=1, streams_per_shard=B,
        k_pad=K_PAD, method="sparse_tick", n_slots=16, m_pad=64),)))
    try:
        graphs = [erdos_renyi(N_NODES, 0.4, seed=i, weighted=True)
                  for i in range(B)]
        rng = np.random.default_rng(3)
        deltas = _deltas(rng, [f"t{i}" for i in range(B)])
        fresh = 0
        for i, (name, d) in enumerate(deltas.items()):
            w = np.asarray(graphs[i].weights)
            fresh += sum(w[s, r] == 0 for s, r, m in zip(
                np.asarray(d.senders), np.asarray(d.receivers),
                np.asarray(d.mask)) if m > 0)
        jax.profiler.start_trace(str(tmp_path))
        try:
            for i, g in enumerate(graphs):
                fleet.admit(f"t{i}", g)
            fleet.ingest(deltas)
        finally:
            jax.profiler.stop_trace()
        fleet.poll()
    finally:
        fleet.close()
    spans = _host_spans(str(tmp_path))
    admits = _named(spans, "finger.admit")
    assert [s[3]["nodes"] for s in admits] == [N_NODES] * B
    assert [s[3]["edges"] for s in admits] == [
        int(np.count_nonzero(np.triu(np.asarray(g.weights), 1)))
        for g in graphs]
    slotmap, = _named(spans, "finger.slotmap")
    assert 0 < fresh < slotmap[3]["lanes"]
    assert slotmap[3]["new_edges"] == fresh


def _index_counters(spans):
    slotmaps = _named(spans, "finger.slotmap")
    counts = [(s[3]["index_rebuilds"], s[3]["probe_rounds"])
              for s in slotmaps]
    for count in counts:
        assert all(isinstance(c, int) and c >= 0 for c in count), count
    return counts


def test_an_add_only_fleet_never_rebuilds_its_edge_index(traced):
    """The fixture's deltas only add or re-weight edges, so no
    tombstone is left and the index is never rebuilt; every tick's
    lookups probe at least one round."""
    _, spans, _, _, _ = traced
    counts = _index_counters(spans)
    assert len(counts) == TICKS
    assert [r for r, _ in counts] == [0] * TICKS
    assert all(p > 0 for _, p in counts)


def test_a_churn_fleet_rebuilds_its_edge_index(tmp_path):
    """Each tick every tenant deletes five live edges and adds back the
    five it deleted the tick before: the tombstones pass a quarter of
    the live keys, so ``index_rebuilds`` counts rebuilds."""
    k_pad, cut = 12, 5
    fleet = FingerFleet.open(FleetConfig(pools=(PoolSpec(
        name="slots", n_pad=N_VIRT, shards=1, streams_per_shard=B,
        k_pad=k_pad, method="sparse_tick", n_slots=16, m_pad=64),)))
    try:
        live, gone = {}, {}
        for i in range(B):
            g = erdos_renyi(N_NODES, 0.4, seed=i, weighted=True)
            fleet.admit(f"t{i}", g)
            w = np.triu(np.asarray(g.weights), 1)
            live[f"t{i}"] = {(int(a), int(b)): float(w[a, b])
                             for a, b in zip(*np.nonzero(w))}
            gone[f"t{i}"] = {}
        rng = np.random.default_rng(5)
        jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(TICKS):
                deltas = {}
                for name, edges in live.items():
                    back = gone[name]
                    pick = rng.choice(len(edges), cut, replace=False)
                    cut_now = {k: edges[k] for k in
                               (sorted(edges)[p] for p in pick)}
                    lanes = [(a, b, -w, w) for (a, b), w in cut_now.items()]
                    lanes += [(a, b, w, 0.0) for (a, b), w in back.items()]
                    edges.update(back)
                    for k in cut_now:
                        del edges[k]
                    gone[name] = cut_now
                    deltas[name] = GraphDelta.host_from_arrays(
                        *zip(*lanes), n_nodes=N_VIRT, k_pad=k_pad)
                fleet.ingest(deltas)
                fleet.poll()
        finally:
            jax.profiler.stop_trace()
    finally:
        fleet.close()
    counts = _index_counters(_host_spans(str(tmp_path)))
    assert len(counts) == TICKS
    assert any(r > 0 for r, _ in counts)
