"""The wiki-growth deployment: its generator makes what the
configuration states (counts, distinct pairs, seeded bursts), refuses a
program that would admit the graph edge by edge, and `wiki.replay`
rehearses on the CPU at a tiny size through the real `FingerFleet`."""
import copy
import json

import numpy as np
import pytest

from bench import harness
from bench.generators import wiki_growth
from bench.tests import tiny

SEED = 2**31 + 4242
TINY = dict(pages=3000, links=24000, admitted_links=12000,
            links_per_tick=128, burst_every=8, burst_lanes=32)


def _config():
    config = copy.deepcopy(harness.load_cell("wiki.replay").config)
    config.update(TINY)
    config["pools"][0].update(n_pad=3072, n_slots=3072, m_pad=24576,
                              k_pad=128)
    return config


@pytest.fixture(scope="module")
def tenant():
    ten, = wiki_growth.generate(_config(), 10_000, SEED)
    return ten


def test_published_sizes_are_stated():
    config = harness.load_cell("wiki.replay").config
    assert (config["pages"], config["links"]) == (1_870_709, 39_953_145)
    assert config["admitted_links"] == 19_976_572
    assert config["links"] - config["admitted_links"] == 19_976_573
    assert wiki_growth.ticks(config) == 1_220
    pool, = config["pools"]
    assert pool["n_slots"] >= config["pages"]
    assert pool["m_pad"] >= config["links"]
    assert pool["n_slots"] % 1024 == 0 and pool["m_pad"] % 1024 == 0
    assert config["reduced"] == []


def test_counts(tenant):
    config = _config()
    assert tenant.n_nodes == config["pages"]
    assert tenant.lo.size == config["admitted_links"]
    assert len(tenant.deltas) == wiki_growth.ticks(config) == 94
    lanes = [d.lanes for d in tenant.deltas]
    assert lanes[:-1] == [128] * 93
    assert sum(lanes) == config["links"] - config["admitted_links"]
    assert all(np.all(d.dw == 1.0) and np.all(d.w_old == 0.0)
               for d in tenant.deltas)


def test_links_are_distinct_pairs(tenant):
    n = tenant.n_nodes
    keys = np.concatenate([tenant.lo * n + tenant.hi]
                          + [d.lo * n + d.hi for d in tenant.deltas])
    assert np.unique(keys).size == keys.size == _config()["links"]
    lo, hi = keys // n, keys % n
    assert np.all(lo < hi) and np.all(hi < n)
    assert np.all(np.diff(tenant.lo * n + tenant.hi) > 0)  # in key order


def test_length_caps_the_stream():
    ten, = wiki_growth.generate(_config(), 7, SEED)
    assert len(ten.deltas) == 7


def test_seeded(tenant):
    again, = wiki_growth.generate(_config(), 10_000, SEED)
    other, = wiki_growth.generate(_config(), 10_000, SEED + 1)
    np.testing.assert_array_equal(again.lo, tenant.lo)
    for a, b in zip(again.deltas, tenant.deltas):
        np.testing.assert_array_equal(a.hi, b.hi)
    assert not np.array_equal(other.hi, tenant.hi)


def test_bursts_link_one_page(tenant):
    config = _config()
    bursts = []
    for t, d in enumerate(tenant.deltas):
        ends = np.bincount(np.concatenate([d.lo, d.hi]))
        if ends.max() >= config["burst_lanes"]:
            bursts.append(t)
    assert len(bursts) == len(range(bursts[0], 94, 8)) >= 11
    assert bursts == list(range(bursts[0], 94, 8))


def test_older_pages_gather_in_links(tenant):
    deg = np.bincount(np.concatenate([tenant.lo, tenant.hi]),
                      minlength=tenant.n_nodes)
    tenth = tenant.n_nodes // 10
    assert deg[:tenth].mean() > 3 * deg[-tenth:].mean()


def test_a_program_without_array_admission_is_refused(monkeypatch):
    from repro.core import sparse

    monkeypatch.delattr(sparse.SlotMap, "admit")
    with pytest.raises(harness.SetupError, match="SlotMap.admit"):
        wiki_growth.generate(_config(), 3, SEED)


def test_generation_without_the_program_skips_the_probe(monkeypatch):
    """`bench/control.py` and the reference run without the program on
    the path: the generator then draws the same graph."""
    import builtins

    real_import = builtins.__import__

    def no_program(name, *args, **kwargs):
        if name == "repro" or name.startswith("repro."):
            raise ImportError(f"no module named {name!r}")
        return real_import(name, *args, **kwargs)

    monkeypatch.setattr(builtins, "__import__", no_program)
    got = wiki_growth.generate(_config(), 3, SEED)
    monkeypatch.setattr(builtins, "__import__", real_import)
    want = wiki_growth.generate(_config(), 3, SEED)
    np.testing.assert_array_equal(got[0].lo, want[0].lo)
    np.testing.assert_array_equal(got[0].deltas[2].hi, want[0].deltas[2].hi)


def test_wiki_replay_rehearsal_is_correct_and_well_formed():
    cell = harness.load_cell("wiki.replay")
    cell.config = _config()
    cell.traffic = dict(cell.traffic, max_ticks_per_s=1000)
    out = tiny.run("wiki.replay", seconds=2.0, cell=cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    line = json.loads(json.dumps(harness.result(out, "cpu", "cpu", 1,
                                                False)))
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "checks"
