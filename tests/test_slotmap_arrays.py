"""The array-backed `SlotMap` against the dict-backed map it replaced
(`tests/slotmap_reference.py`): driven through the same seeded deltas,
both give the same slot-space deltas, the same assignments, the same
free lists in the same order and the same errors, across joins, leaves,
deletions, re-adds, duplicate lanes, capacity errors, capacity growth
and save/restore in both the array and the JSON form."""
import numpy as np
import pytest

from repro.core.sparse import (
    SlotMap,
    _EdgeIndex,
    SparseCapacityError,
    SparseLayout,
    sparse_state_from_graph,
)
from repro.graphs import EdgeList, GraphDelta

from slotmap_reference import DictSlotMap, as_json, dict_admit

N_VIRTUAL = 48


def _delta(rng, weights, k_pad=12, j_pad=4, dup=False):
    """A random virtual delta over the live ``weights`` {(lo, hi): w}:
    deletions, re-weights and new pairs (some touching inactive nodes),
    padding lanes holding junk, a self-loop, joins and leaves."""
    live = sorted(weights)
    lanes = []
    for _ in range(int(rng.integers(1, k_pad - 2))):
        if live and rng.random() < 0.5:
            lo, hi = live[int(rng.integers(len(live)))]
            w = weights[(lo, hi)]
            dw = -w if rng.random() < 0.5 else float(rng.uniform(0.1, 1))
            lanes.append((lo, hi, dw, w))
        else:
            lo, hi = (int(x) for x in rng.integers(0, N_VIRTUAL, 2))
            lanes.append((lo, hi, float(rng.uniform(0.1, 1)),
                          weights.get((min(lo, hi), max(lo, hi)), 0.0)))
    seen, uniq = set(), []
    for lane in lanes:
        key = (min(lane[:2]), max(lane[:2]))
        if key not in seen:
            seen.add(key)
            uniq.append(lane)
    if dup and uniq:
        uniq.append(uniq[int(rng.integers(len(uniq)))])
    snd = np.zeros(k_pad, np.int32)
    rcv = np.zeros(k_pad, np.int32)
    dw = np.zeros(k_pad, np.float32)
    w_old = np.zeros(k_pad, np.float32)
    mask = np.zeros(k_pad, np.float32)
    for i, (a, b, d, w) in enumerate(uniq[:k_pad - 1]):
        flip = rng.random() < 0.5
        snd[i], rcv[i] = (b, a) if flip else (a, b)
        dw[i], w_old[i], mask[i] = d, w, 1.0
    snd[k_pad - 1] = rcv[k_pad - 1] = int(rng.integers(N_VIRTUAL))
    mask[k_pad - 1] = 1.0  # a self-loop: dropped
    junk = mask == 0
    snd[junk] = rng.integers(-5, 3 * N_VIRTUAL, junk.sum())
    nid = np.zeros(j_pad, np.int32)
    flag = np.zeros(j_pad, np.float32)
    for i in range(int(rng.integers(0, j_pad + 1))):
        nid[i] = int(rng.integers(N_VIRTUAL))
        flag[i] = 1.0 if rng.random() < 0.6 else -1.0
        if flag[i] < 0 and nid[i] in nid[:i][flag[:i] < 0]:
            flag[i] = 1.0  # one leave lane per node
    return GraphDelta(senders=snd, receivers=rcv, dw=dw, w_old=w_old,
                      mask=mask, n_nodes=N_VIRTUAL, node_ids=nid,
                      node_flag=flag)


def _assert_same(got: SlotMap, want: DictSlotMap, label):
    assert got.n_free_nodes == len(want._free_nodes), label
    assert got.n_free_edges == len(want._free_edges), label
    assert as_json(got) == want.to_json(), label


def _assert_same_delta(got: GraphDelta, want: GraphDelta, label):
    for field in ("senders", "receivers", "dw", "w_old", "mask",
                  "edge_slots", "node_ids", "node_flag"):
        a, b = getattr(got, field), getattr(want, field)
        assert (a is None) == (b is None), (label, field)
        if a is not None:
            assert a.dtype == b.dtype, (label, field)
            np.testing.assert_array_equal(a, b, err_msg=f"{label} {field}")
    assert got.n_nodes == want.n_nodes, label


def _apply(weights, delta: GraphDelta, live):
    """The live weights {(lo, hi): w} after a translated virtual delta;
    ``live`` holds the map's edges after it."""
    for s, r, d, w, m in zip(delta.senders, delta.receivers, delta.dw,
                             delta.w_old, delta.mask):
        key = (int(min(s, r)), int(max(s, r)))
        if m > 0 and key in live:
            weights[key] = float(w) + float(d)
    return {k: w for k, w in weights.items() if k in live}


def _round_trip(sm: SlotMap, want: DictSlotMap, how: str) -> SlotMap:
    """``sm`` saved and restored: as its arrays, or from the JSON
    payload the dict map writes (older checkpoints' form)."""
    if how == "arrays":
        return SlotMap.restore(sm.header(), sm.arrays())
    return SlotMap.restore(want.to_json())


@pytest.mark.parametrize("seed", range(8))
def test_array_map_matches_dict_map(seed):
    rng = np.random.default_rng(seed)
    layout = SparseLayout(n_slots=16, m_pad=24)
    w0 = {}
    nodes = rng.choice(N_VIRTUAL, 10, replace=False)
    for _ in range(14):
        a, b = rng.choice(nodes, 2, replace=False)
        w0[(int(min(a, b)), int(max(a, b)))] = float(rng.uniform(0.2, 1))
    mask = np.zeros(N_VIRTUAL, np.float32)
    mask[nodes] = 1.0
    keys = sorted(w0)
    g = EdgeList.from_arrays([k[0] for k in keys], [k[1] for k in keys],
                             [w0[k] for k in keys], n_nodes=N_VIRTUAL,
                             node_mask=mask)
    _, got = sparse_state_from_graph(g, layout, stream=seed)
    want = dict_admit(g, layout, stream=seed)
    _assert_same(got, want, "admitted")
    weights = dict(w0)
    errors = {"dup": 0, "cap": 0}
    for t in range(60):
        label = f"seed {seed} tick {t}"
        roll = rng.random()
        if roll < 0.05:
            bigger = SparseLayout(
                got.layout.n_slots + int(rng.integers(0, 4)),
                got.layout.m_pad + int(rng.integers(1, 6)),
                generation=got.layout.generation + 1)
            got.grow(bigger)
            want.grow(bigger)
            _assert_same(got, want, label + " grow")
            continue
        if roll < 0.12:
            got = _round_trip(got, want, "arrays" if t % 2 else "json")
            want = DictSlotMap.from_json(want.to_json())
            _assert_same(got, want, label + " restore")
        d = _delta(rng, weights, dup=roll > 0.9)
        try:
            expect = want.translate(d)
        except (ValueError, SparseCapacityError) as e:
            with pytest.raises(type(e)) as caught:
                got.translate(d)
            assert str(caught.value) == str(e), label
            errors["cap" if isinstance(e, SparseCapacityError)
                   else "dup"] += 1
            _assert_same(got, want, label + " rejected")
            continue
        _assert_same_delta(got.translate(d), expect, label)
        _assert_same(got, want, label)
        weights = _apply(weights, d, want.edge_slot)
    assert errors["dup"] + errors["cap"] > 0


def test_leave_of_connected_node_frees_its_edges_in_key_order():
    layout = SparseLayout(n_slots=8, m_pad=8)
    g = EdgeList.from_arrays([0, 0, 1, 2], [3, 1, 3, 3],
                             [1.0, 1.0, 1.0, 1.0], n_nodes=6)
    _, got = sparse_state_from_graph(g, layout)
    want = dict_admit(g, layout)
    leave = GraphDelta.host_from_arrays([], [], [], [], n_nodes=6,
                                        k_pad=2, leave=[3], j_pad=2)
    _assert_same_delta(got.translate(leave), want.translate(leave),
                       "leave")
    _assert_same(got, want, "leave")
    # (0, 3), (1, 3), (2, 3) had slots 1, 2, 3: freed in that order
    assert list(got.arrays()["free_edges"][-3:]) == [1, 2, 3]


@pytest.mark.parametrize("seed", range(3))
def test_many_connected_leaves_in_one_delta(seed):
    """One delta in which 40 nodes with live edges leave, many linked to
    each other, beside joins and edge lanes: the edges go node by node
    in the delta's leave order, each node's in key order, as the dict
    map frees them."""
    rng = np.random.default_rng(100 + seed)
    n, active, m = 120, 112, 400
    lo = rng.integers(0, active, 3 * m)
    hi = rng.integers(0, active, 3 * m)
    keys = np.unique(np.minimum(lo, hi) * n + np.maximum(lo, hi))
    keys = rng.permutation(keys[keys // n != keys % n])[:m]
    mask = np.zeros(n, np.float32)
    mask[:active] = 1.0
    g = EdgeList.from_arrays(keys // n, keys % n, np.ones(keys.size),
                             n_nodes=n, node_mask=mask)
    layout = SparseLayout(n_slots=n, m_pad=m + 64)
    _, got = sparse_state_from_graph(g, layout)
    want = dict_admit(g, layout)
    live = sorted(want.edge_slot)
    pick = [live[i] for i in rng.choice(len(live), 6, replace=False)]
    d = GraphDelta.host_from_arrays(
        [a for a, _ in pick], [b for _, b in pick], np.full(6, 0.5),
        np.ones(6), n_nodes=n, k_pad=8, join=[113, 117, 115],
        leave=rng.permutation(active)[:40].tolist(), j_pad=48)
    _assert_same_delta(got.translate(d), want.translate(d), "leaves")
    _assert_same(got, want, "leaves")
    assert got.n_live_edges < m // 2  # most edges touched a leaver


def test_two_million_edges_fit_in_48_bytes_an_edge():
    rng = np.random.default_rng(5)
    n, m = 200_000, 2_000_000
    lo = rng.integers(0, n, int(m * 1.05))
    hi = rng.integers(0, n, lo.size)
    keys = np.unique(np.minimum(lo, hi) * n + np.maximum(lo, hi))
    keys = keys[keys // n != keys % n]
    keys = rng.permutation(keys)[:m]
    assert keys.size == m
    g = EdgeList(senders=(keys // n).astype(np.int32),
                 receivers=(keys % n).astype(np.int32),
                 weights=np.ones(m, np.float32),
                 mask=np.ones(m, np.float32), n_nodes=n)
    layout = SparseLayout(n_slots=n, m_pad=2 * m)
    state, sm = sparse_state_from_graph(g, layout)
    assert sm.n_live_edges == m
    assert sm.nbytes / m < 48, sm.nbytes / m
    assert float(state.s_total) == 2.0 * m
    # admitted edges take slots in (lo, hi) order; a delta finds them
    order = np.sort(keys)
    pick = rng.choice(m, 64, replace=False)
    d = GraphDelta.host_from_arrays(
        order[pick] // n, order[pick] % n, -np.ones(64), np.ones(64),
        n_nodes=n, k_pad=128)
    out = sm.translate(d)
    got = {(int(a), int(b)): int(s) for a, b, s, k in zip(
        d.senders, d.receivers, out.edge_slots, d.mask) if k > 0}
    assert got == {(int(k // n), int(k % n)): int(i)
                   for i, k in zip(pick, order[pick])}
    assert sm.n_live_edges == m - 64


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_edge_index_inserts_where_find_saw_room(seed):
    """Keys inserted at the free buckets a lookup reported, several of
    them meeting at one bucket and some at tombstones, are all found
    again, and the live and tombstone counts match the table."""
    rng = np.random.default_rng(seed)
    index = _EdgeIndex(48)  # 128 buckets: long clusters
    keys = rng.choice(1 << 40, 120, replace=False).astype(np.int64)
    slot_key = np.full(120, -1, np.int64)
    slot_key[:60] = keys[:60]
    index.insert(keys[:60], np.arange(60))
    _, buckets, _ = index.find(keys[:20], slot_key)
    index.release(buckets)
    slot_key[:20] = -1
    slots, _, free = index.find(keys[60:], slot_key)
    assert (slots < 0).all() and (free >= 0).all()
    assert np.unique(free).size < free.size  # some keys meet
    slot_key[60:] = keys[60:]
    index.insert(keys[60:], np.arange(60, 120), at=free)
    slots, _, free = index.find(keys, slot_key)
    assert (slots[:20] == -1).all()
    assert (slots[20:] == np.arange(20, 120)).all()
    assert (free[20:] == -1).all()
    assert index.live == int((index.table >= 0).sum()) == 100
    assert index.tombs == int((index.table == _EdgeIndex._TOMB).sum())


def _churn_delta(rng, live, n, count, k_pad):
    """Delete ``count`` random live unit-weight edges and add ``count``
    absent pairs in one host delta; ``live`` is updated in place."""
    gone = [live.pop(int(rng.integers(len(live)))) for _ in range(count)]
    new = []
    while len(new) < count:
        lo, hi = sorted(int(x) for x in rng.choice(n, 2, replace=False))
        if (lo, hi) not in live and (lo, hi) not in new \
                and (lo, hi) not in gone:
            new.append((lo, hi))
    live.extend(new)
    pairs = gone + new
    return gone, GraphDelta.host_from_arrays(
        [a for a, _ in pairs], [b for _, b in pairs],
        [-1.0] * count + [1.0] * count, [1.0] * count + [0.0] * count,
        n_nodes=n, k_pad=k_pad)


def _lookup(sm: SlotMap, pairs):
    keys = np.array([(lo << 32) | hi for lo, hi in pairs], np.int64)
    slots, _, _ = sm._index.find(keys, sm._key_of_slot)
    return slots


@pytest.mark.parametrize("seed", range(3))
def test_churn_keeps_tombstones_under_a_quarter_of_live_keys(seed):
    """300 ticks of as many deletes as adds: after every commit the
    index holds at most a quarter as many tombstones as live keys, the
    edge slots and free lists are the dict map's, every live key is
    found at its slot and no deleted key is found."""
    rng = np.random.default_rng(300 + seed)
    n, m = 160, 240
    pairs = set()
    while len(pairs) < m:
        lo, hi = sorted(int(x) for x in rng.choice(n, 2, replace=False))
        pairs.add((lo, hi))
    live = sorted(pairs)
    g = EdgeList.from_arrays([a for a, _ in live], [b for _, b in live],
                             np.ones(m), n_nodes=n)
    layout = SparseLayout(n_slots=n, m_pad=512)
    _, got = sparse_state_from_graph(g, layout)
    want = dict_admit(g, layout)
    deleted = set()
    for t in range(300):
        label = f"seed {seed} tick {t}"
        gone, d = _churn_delta(rng, live, n, int(rng.integers(4, 13)),
                               k_pad=32)
        _assert_same_delta(got.translate(d), want.translate(d), label)
        _assert_same(got, want, label)
        index = got._index
        assert index.tombs <= index.live // 4, label
        assert index.live == int((index.table >= 0).sum()), label
        assert index.tombs == int((index.table == _EdgeIndex._TOMB).sum())
        deleted = (deleted | set(gone)) - set(live)
        assert list(_lookup(got, want.edge_slot)) \
            == list(want.edge_slot.values()), label
        assert (_lookup(got, sorted(deleted)) == -1).all(), label
    assert got.index_counters[0] > 0  # the purge engaged


def test_a_delete_only_delta_purges_tombstones():
    """A delta that only deletes, past a quarter of the keys left,
    rebuilds the index: no tombstone is left, the remaining keys keep
    their slots and the deleted ones are gone."""
    rng = np.random.default_rng(11)
    n, m, cut = 40, 60, 16
    keys = rng.choice(n * n, 4 * m, replace=False)
    keys = keys[keys // n < keys % n][:m]
    pairs = [(int(k // n), int(k % n)) for k in keys]
    g = EdgeList.from_arrays([a for a, _ in pairs], [b for _, b in pairs],
                             np.ones(m), n_nodes=n)
    _, sm = sparse_state_from_graph(g, SparseLayout(n_slots=n, m_pad=128))
    slots = _lookup(sm, pairs)
    gone, kept = pairs[:cut], pairs[cut:]
    d = GraphDelta.host_from_arrays(
        [a for a, _ in gone], [b for _, b in gone], -np.ones(cut),
        np.ones(cut), n_nodes=n, k_pad=cut)
    staged = sm.stage(d)
    assert staged.edge_slots.size == 0 and staged.gone_slots.size == cut
    sm.commit(staged)
    assert sm.index_counters[0] == 1
    assert sm._index.tombs == 0
    assert sm._index.live == int((sm._index.table >= 0).sum()) == m - cut
    assert (_lookup(sm, kept) == slots[cut:]).all()
    assert (_lookup(sm, gone) == -1).all()
