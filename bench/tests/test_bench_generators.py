"""The seeded generator makes what the configuration file states: sizes,
churn and planted fan-ins, and deltas that are valid on the graph they
apply to."""
import json
import os

import numpy as np
import pytest

from bench.generators import dos_fleet

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TICKS = 6
SEED = 2**31 + 12345


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _replay(tenant):
    """Apply every delta to a live edge set, checking each lane."""
    live = set(zip(tenant.lo.tolist(), tenant.hi.tolist()))
    sizes = []
    for d in tenant.deltas:
        assert np.all(d.lo < d.hi)
        pairs = list(zip(d.lo.tolist(), d.hi.tolist()))
        assert len(set(pairs)) == len(pairs)
        for pair, dw, w_old in zip(pairs, d.dw, d.w_old):
            if dw < 0:
                assert pair in live and w_old == 1.0
                live.remove(pair)
            else:
                assert pair not in live and w_old == 0.0
                live.add(pair)
        sizes.append(len(live))
    return sizes


@pytest.fixture(scope="module")
def dos():
    config = dict(_config("dos-as-fleet"), attack_within=TICKS)
    return config, dos_fleet.generate(config, TICKS, SEED)


def test_dos_tenants_have_the_snapshots_sizes_all_active(dos):
    config, tenants = dos
    assert len(tenants) == config["snapshots"] == 9
    widths = [t.n_nodes for t in tenants]
    edges = [len(t.lo) for t in tenants]
    assert widths[0] == config["min_ases"] == 10670
    assert widths[-1] == config["max_ases"] == 11174
    assert edges[0] == config["min_edges"] == 22002
    assert edges[-1] == config["max_edges"] == 23409
    assert widths == sorted(widths) and edges == sorted(edges)
    assert [(t.n_nodes, len(t.lo)) for t in tenants] == \
        dos_fleet.sizes(config)
    for t in tenants:
        assert np.all(t.lo < t.hi)
        keys = t.lo * t.n_nodes + t.hi
        assert np.unique(keys).size == keys.size
        degree = np.bincount(np.concatenate([t.lo, t.hi]),
                             minlength=t.n_nodes)
        assert degree.shape[0] == t.n_nodes and degree.min() >= 1


def test_dos_churn_and_one_planted_fan_in_per_tenant(dos):
    config, tenants = dos
    attack_ticks = []
    for t in tenants:
        m = len(t.lo)
        churn = max(1, int(config["churn_frac"] * m))
        n_bot = max(1, int(config["attack_frac"] * t.n_nodes))
        sizes = _replay(t)
        attacked = [i for i, d in enumerate(t.deltas)
                    if d.lanes != 2 * churn]
        assert len(attacked) == 1
        for i, d in enumerate(t.deltas):
            if i in attacked:
                born = d.dw > 0
                ends = np.concatenate([d.lo[born], d.hi[born]])
                hub = np.bincount(ends).max()
                assert n_bot - 2 * churn <= hub <= n_bot + churn
                assert d.lanes <= 2 * churn + n_bot
                pool, = config["pools"]
                assert d.lanes <= pool["k_pad"]
            else:
                assert (d.dw > 0).sum() == (d.dw < 0).sum() == churn
        # the edge count holds until the attack adds its fan-in
        assert sizes[:attacked[0]] == [m] * attacked[0]
        attack_ticks.append(attacked[0])
    # every tenant's attack lies somewhere in its stream, at a seeded tick
    assert len(set(attack_ticks)) > 1


def test_dos_seed_changes_order_not_sizes():
    config = _config("dos-as-fleet")
    config.update(snapshots=3, min_ases=200, max_ases=300, min_edges=420,
                  max_edges=640, attack_within=TICKS)
    a = dos_fleet.generate(config, TICKS, 1)
    b = dos_fleet.generate(config, TICKS, 1)
    c = dos_fleet.generate(config, TICKS, 2**40 + 2)
    for x, y, z in zip(a, b, c):
        assert x.n_nodes == z.n_nodes and len(x.lo) == len(z.lo)
        assert np.array_equal(x.lo, y.lo) and np.array_equal(x.hi, y.hi)
        assert [d.lanes for d in x.deltas] == [d.lanes for d in y.deltas]
        assert sorted(d.lanes for d in x.deltas) == \
            sorted(d.lanes for d in z.deltas)
    assert any(not np.array_equal(x.lo, z.lo) for x, z in zip(a, c))
