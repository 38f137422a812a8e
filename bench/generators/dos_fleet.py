"""The Oregon-1 AS peering graphs under churn, with planted DoS fan-ins
(the paper's section 4, Table 3), one tenant per snapshot.

Tenant i has the i-th of ``snapshots`` node and edge counts spread
evenly from (``min_ases``, ``min_edges``) to (``max_ases``,
``max_edges``); every AS is active. Its graph is BA(n, 2) topped up to
the edge count with pairs whose endpoints are drawn by degree. Every
tick removes ``churn_frac`` of the tenant's edges and adds as many
absent pairs, so the edge count holds between attacks. Once in every
stream, at a seeded delta among the first ``attack_within``,
``attack_frac`` of the tenant's ASes all peer with one target: every
seed carries the same work in another order.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from bench.generators.common import EdgeSet, Tenant, ba_edges, tenant_rng


def sizes(config: dict) -> List[Tuple[int, int]]:
    """(ASes, edges) of each tenant."""
    count = config["snapshots"]
    n = np.linspace(config["min_ases"], config["max_ases"], count)
    m = np.linspace(config["min_edges"], config["max_edges"], count)
    return [(int(a), int(b)) for a, b in zip(n.round(), m.round())]


def _graph(n: int, m: int, rng: np.random.Generator) -> EdgeSet:
    lo, hi = ba_edges(n, 2, rng)
    edges = EdgeSet(n, lo, hi)
    if m > len(edges):
        extra = edges.absent(m - len(edges), edges.by_degree, rng)
        edges.apply(np.zeros(0, np.int64), extra)
    return edges


def _tenant(name: str, n: int, m: int, config: dict, length: int,
            rng: np.random.Generator) -> Tenant:
    edges = _graph(n, m, rng)
    lo, hi = edges.pairs()
    churn = max(1, int(config["churn_frac"] * len(edges)))
    n_bot = max(1, int(config["attack_frac"] * n))
    attack_at = int(rng.integers(0, min(length, config["attack_within"])))

    def uniform(size, g):
        return g.integers(0, n, size)

    deltas = []
    for t in range(length):
        gone_at = rng.choice(len(edges), size=churn, replace=False)
        born = edges.absent(churn, uniform, rng)
        if t == attack_at:
            target = int(rng.integers(0, n))
            bots = rng.choice(n - 1, size=n_bot, replace=False)
            bots = np.where(bots >= target, bots + 1, bots)
            fan = edges.pair_keys(bots, np.full(n_bot, target))
            # a fan-in edge about to churn away stays; an absent one comes
            gone_at = gone_at[~np.isin(edges.keys[gone_at], fan)]
            fan = fan[~edges.contains(fan) & ~np.isin(fan, born)]
            born = np.concatenate([born, fan])
        deltas.append(edges.apply(gone_at, born))
    return Tenant(name=name, n_nodes=n, lo=lo, hi=hi,
                  weights=np.ones(lo.shape[0]), deltas=deltas)


def generate(config: dict, length: int, seed: int) -> List[Tenant]:
    """Every tenant with a stream of ``length`` deltas."""
    return [_tenant(f"oregon{i}-n{n}", n, m, config, length,
                    tenant_rng(seed, i))
            for i, (n, m) in enumerate(sizes(config))]
