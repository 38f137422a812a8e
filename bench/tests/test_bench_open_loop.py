"""The open loop: seeded arrivals with their count fixed, at most one
delta per tenant a tick, taken in order and never before it arrives,
latency from arrival; and `dos-as-fleet` under the `live` mix rehearsed
on the CPU at a tiny size through the real `FingerFleet`."""
import dataclasses
import json
import time

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny
from repro.fleet import errors  # noqa: F401 (imported by the first tick)

SEED = 2**31 + 777


@pytest.fixture(scope="module")
def open_loop():
    return harness.load_module(tiny.ROOT, "loops", "open")


def test_length_is_each_tenants_share(open_loop):
    assert open_loop.length({"deltas_per_s": 240, "tenants": 9}, 50) \
        == 1334
    assert open_loop.length({"deltas_per_s": 30, "tenants": 3}, 0.5) == 5


def test_arrivals_are_seeded_sorted_and_inside_the_window(open_loop):
    got = open_loop.arrivals(SEED, 4, 500, 2.0)
    assert got.shape == (4, 500)
    assert np.all(np.diff(got, axis=1) >= 0)
    assert got.min() >= 0.0 and got.max() < 2.0
    np.testing.assert_array_equal(got, open_loop.arrivals(SEED, 4, 500,
                                                          2.0))
    assert not np.array_equal(got, open_loop.arrivals(SEED + 1, 4, 500,
                                                      2.0))
    assert not np.array_equal(got[0], got[1])
    # uniform over the window: each half holds about half
    assert abs(np.mean(got < 1.0) - 0.5) < 0.05


class _Fleet:
    """Takes whatever it is given and spends ``tick_s`` a tick."""

    def __init__(self, names, tick_s):
        self.names, self.tick_s, self.batches = names, tick_s, []

    def ingest(self, batch):
        self.batches.append((time.perf_counter(), dict(batch)))

    def poll(self):
        time.sleep(self.tick_s)

    def scores(self):
        return {n: 0.0 for n in self.names}

    def top_anomalies(self, k):
        return [(n, 0.0) for n in self.names[:k]]


def _feed(tenants, per_tenant):
    names = [f"t{j}" for j in range(tenants)]
    return harness.Feed(
        names=names,
        deltas=[[(j, i) for i in range(per_tenant)] for j in range(tenants)],
        lanes=np.full((tenants, per_tenant), 3), seed=SEED)


def test_one_delta_per_tenant_a_tick_in_order(open_loop):
    feed = _feed(3, 40)
    fleet = _Fleet(feed.names, 0.01)
    traffic = {"deltas_per_s": 120, "tenants": 3}
    start = time.perf_counter()
    window = open_loop.run(fleet, feed, traffic, 1.0, 1,
                           harness.span(False))
    due = open_loop.arrivals(SEED, 3, 40, 1.0)
    taken = {j: [] for j in range(3)}
    for at, batch in fleet.batches:
        for name, (j, i) in batch.items():
            assert name == feed.names[j]
            assert at - start >= due[j, i] - 1e-3  # never early
            taken[j].append(i)
    for j in range(3):
        assert taken[j] == list(range(len(taken[j])))
    # the 10 ms ticks keep up with 120 deltas/s: nearly all scored
    assert sum(len(v) for v in taken.values()) >= 110
    assert window.schedule.shape == (len(fleet.batches), 3)
    for row, (_, batch) in zip(window.schedule, fleet.batches):
        assert sorted(feed.names[j] for j in np.nonzero(row >= 0)[0]) \
            == sorted(batch)
    assert window.lanes == 3 * window.latency_s.size
    # latency from arrival: at least a tick, at most the window
    assert window.latency_s.min() >= 0.01
    assert window.latency_s.max() < 1.0


def test_latency_holds_the_queue(open_loop):
    """Above capacity (50 ms ticks, 60 deltas/s for one tenant) the
    queue grows, and so does the wait."""
    feed = _feed(1, 60)
    window = open_loop.run(_Fleet(feed.names, 0.05), feed,
                           {"deltas_per_s": 60, "tenants": 1}, 1.0, 1,
                           harness.span(False))
    assert window.latency_s.size < 25
    assert window.latency_s[-1] > 3 * window.latency_s[0]


def test_a_mix_for_other_tenants_is_refused(open_loop):
    with pytest.raises(harness.SetupError, match="9 tenants"):
        open_loop.run(None, _feed(3, 5), {"deltas_per_s": 9, "tenants": 9},
                      1.0, 1, harness.span(False))


def test_dos_live_rehearsal_is_correct_and_well_formed():
    """`dos-as-fleet` under the `live` mix, the cell `dos.live` would
    be (BENCHMARK.json does not list it yet: PERF.md section 7)."""
    cell = tiny.tiny(harness.load_cell("dos.replay"))
    with open(f"{tiny.ROOT}/bench/traffic/live.json") as f:
        live = json.load(f)
    cell = dataclasses.replace(
        cell, name="dos.live",
        traffic=dict(live, deltas_per_s=60, tenants=3))
    out = tiny.run("dos.live", seconds=1.5, cell=cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    line = json.loads(json.dumps(harness.result(out, "cpu", "cpu", 1,
                                                False)))
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())
