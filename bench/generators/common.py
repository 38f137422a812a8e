"""Seeded numpy building blocks shared by the deployment generators.

Everything here is host numpy: no JAX array is made, so generating a
deployment compiles nothing. A tenant's stream is a list of
`Delta`s, each a set of distinct undirected edge lanes with their
signed weight change and pre-change weight.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Delta:
    """One tick's edge lanes for one tenant (lo < hi, each pair once)."""

    lo: np.ndarray     # (k,) int64
    hi: np.ndarray     # (k,) int64
    dw: np.ndarray     # (k,) float64, signed weight change
    w_old: np.ndarray  # (k,) float64, weight before the tick

    @property
    def lanes(self) -> int:
        return int(self.lo.shape[0])


@dataclasses.dataclass
class Tenant:
    """One tenant: its admitted graph and its stream."""

    name: str
    n_nodes: int
    lo: np.ndarray       # (m,) int64, admitted edges
    hi: np.ndarray
    weights: np.ndarray  # (m,) float64
    deltas: List[Delta]  # the stream, in order

    @property
    def max_lanes(self) -> int:
        return max(d.lanes for d in self.deltas)


def tenant_rng(seed: int, index: int) -> np.random.Generator:
    """Independent stream per (seed, tenant): any non-negative seed,
    however large."""
    return np.random.default_rng(np.random.SeedSequence(
        entropy=int(seed), spawn_key=(int(index),)))


def ba_edges(n: int, m_attach: int,
             rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    """Barabasi-Albert BA(n, m) as an edge list (lo < hi, each edge
    once): a seed clique of m + 1 nodes, then each new node attaches to
    m distinct targets drawn by degree."""
    m_attach = max(1, min(m_attach, n - 1))
    lo, hi = np.triu_indices(m_attach + 1, k=1)
    lo, hi = list(lo), list(hi)
    repeated = list(np.repeat(np.arange(m_attach + 1), m_attach))
    for v in range(m_attach + 1, n):
        targets: set = set()
        while len(targets) < m_attach:
            targets.add(int(repeated[rng.integers(0, len(repeated))]))
        for t in targets:
            lo.append(t)
            hi.append(v)
            repeated.append(t)
            repeated.append(v)
    return np.asarray(lo, np.int64), np.asarray(hi, np.int64)


class EdgeSet:
    """A tenant's live unit-weight edges, keyed lo * n + hi: an array of
    keys in no order, for uniform draws, beside a set of the same keys,
    for membership. A tick of churn overwrites the removed keys' places
    with the added ones, so it costs tens of microseconds whatever the
    graph's size."""

    def __init__(self, n: int, lo: np.ndarray, hi: np.ndarray):
        self.n = n
        self.keys = self.pair_keys(lo, hi)
        self.live = set(self.keys.tolist())
        if len(self.live) != self.keys.size:
            raise ValueError("EdgeSet: duplicate edges")

    def __len__(self) -> int:
        return int(self.keys.size)

    def pairs(self) -> Tuple[np.ndarray, np.ndarray]:
        return self.keys // self.n, self.keys % self.n

    def contains(self, keys: np.ndarray) -> np.ndarray:
        live = self.live
        return np.fromiter((k in live for k in keys.tolist()), bool,
                           count=keys.size)

    def pair_keys(self, i: np.ndarray, j: np.ndarray) -> np.ndarray:
        i, j = np.asarray(i, np.int64), np.asarray(j, np.int64)
        return np.minimum(i, j) * self.n + np.maximum(i, j)

    def absent(self, count: int, draw,
               rng: np.random.Generator) -> np.ndarray:
        """``count`` distinct pairs, in draw order, that are not live;
        ``draw(size, rng)`` gives candidate endpoints."""
        out = np.zeros(0, np.int64)
        while out.size < count:
            size = 2 * (count - out.size) + 8
            i, j = draw(size, rng), draw(size, rng)
            keys = self.pair_keys(i, j)[i != j]
            keys = keys[~self.contains(keys)]
            both = np.concatenate([out, keys])
            _, first = np.unique(both, return_index=True)
            out = both[np.sort(first)]
        return out[:count]

    def by_degree(self, size: int, rng: np.random.Generator) -> np.ndarray:
        """Nodes drawn with probability proportional to their degree:
        one end of each of ``size`` uniformly drawn live edges."""
        keys = self.keys[rng.integers(0, self.keys.size, size)]
        return np.where(rng.integers(0, 2, size) > 0, keys // self.n,
                        keys % self.n)

    def apply(self, gone_at: np.ndarray, born: np.ndarray) -> Delta:
        """Remove the live keys at positions ``gone_at`` and add the
        absent keys ``born`` (unit weights); return the delta, lanes
        sorted by (lo, hi)."""
        gone = self.keys[gone_at]
        both = min(gone_at.size, born.size)
        self.keys[gone_at[:both]] = born[:both]
        if both < gone_at.size:
            self.keys = np.delete(self.keys, gone_at[both:])
        if both < born.size:
            self.keys = np.concatenate([self.keys, born[both:]])
        self.live.difference_update(gone.tolist())
        self.live.update(born.tolist())
        keys = np.concatenate([gone, born])
        dw = np.concatenate([-np.ones(gone.size), np.ones(born.size)])
        order = np.argsort(keys, kind="stable")
        keys, dw = keys[order], dw[order]
        return Delta(keys // self.n, keys % self.n, dw,
                     (dw < 0).astype(np.float64))
