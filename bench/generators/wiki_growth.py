"""The English Wikipedia hyperlink graph growing link by link (the
paper's section 5; KONECT wikipedia-growth), one tenant at its
published size.

``pages`` pages, all admitted active, and ``links`` distinct undirected
unit pairs: draw e links a page drawn uniformly among the pages that
exist by then (pages arrive in id order, as many per draw as the
totals give) to one drawn with weight (id + 1) ** -``in_link_exponent``
among them, so older pages gather in-links: a preferential growth
model standing in for the real arrival order. Self-links and repeats
are dropped and made up by more draws, a surplus dropped at random. A
link arrives with the younger of its two pages (links in order of
their younger page, then of their older one). The first
``admitted_links`` links are the admitted graph; the rest stream
``links_per_tick`` a tick, the last tick shorter. One tick in
``burst_every``, at a seeded phase, gives its first ``burst_lanes``
lanes to a burst: that many pages link one existing page none of them
linked before, in place of the stream's links there (the paper's
anomalous months). The link count holds.

The probe: where the program is importable (`bench/run.py` puts it on
the path), generation first checks that it admits a graph with array
operations (`repro.core.sparse.SlotMap.admit`). A program without it
would take minutes and tens of GB of host memory to admit this graph
one edge at a time, so the cell fails at once with `SetupError`
instead. Callers that run without the program, `bench/control.py` and
the reference replay, skip the probe.
"""
from __future__ import annotations

import math
from typing import List

import numpy as np

from bench.generators.common import Delta, Tenant, tenant_rng
from bench.harness import SetupError


def require_array_admission() -> None:
    """`SetupError` for an importable program without array admission;
    nothing where the program is not on the path."""
    try:
        from repro.core import sparse
    except ImportError:
        return
    if not hasattr(sparse.SlotMap, "admit"):
        raise SetupError(
            "wiki-growth: this program's SlotMap has no array admission "
            "(SlotMap.admit), so it admits edges one by one in Python; "
            "at 19,976,572 links that takes minutes and tens of GB of "
            "host memory (if admission was renamed, update this probe)")


def ticks(config: dict) -> int:
    """The stream's length in ticks."""
    stream = config["links"] - config["admitted_links"]
    return int(math.ceil(stream / config["links_per_tick"]))


def _draw(n: int, total: int, first: int, count: int, exponent: float,
          rng: np.random.Generator) -> np.ndarray:
    """Keys hi * n + lo of ``count`` links drawn at arrival positions
    ``first`` on (self-links kept)."""
    alive = np.clip(np.ceil((first + 1 + np.arange(count))
                            * (n / total)), 2, n).astype(np.int64)
    src = np.minimum((rng.random(count) * alive).astype(np.int64),
                     alive - 1)
    a = 1.0 - exponent  # inverse CDF of weights (id + 1) ** -exponent
    span = ((np.arange(n + 1) + 1.0) ** a - 1.0) / a
    tgt = (rng.random(count) * span[alive] * a + 1.0) ** (1.0 / a) - 1.0
    tgt = np.minimum(tgt.astype(np.int64), alive - 1)
    return np.maximum(src, tgt) * n + np.minimum(src, tgt)


def links(config: dict, rng: np.random.Generator) -> np.ndarray:
    """``links`` distinct keys hi * n + lo (hi > lo), ascending: a link
    arrives with the younger of its pages, so the order of the keys is
    the order of arrival."""
    n, total = config["pages"], config["links"]
    exponent = config["in_link_exponent"]
    keys = np.zeros(0, np.int64)
    while keys.size < total:
        need = total - keys.size
        drawn = _draw(n, total, total - need, need + need // 16 + 1024,
                      exponent, rng)
        keys = np.unique(np.concatenate(
            [keys, drawn[drawn // n != drawn % n]]))
    if keys.size > total:  # drop the surplus at random
        keys = np.delete(keys, rng.choice(keys.size, keys.size - total,
                                          replace=False))
    return keys


def _bursts(config: dict, live: np.ndarray, count: int,
            rng: np.random.Generator) -> dict:
    """Tick -> burst keys, for the burst ticks among the first
    ``count``; ``live`` holds every link, ascending."""
    n, k = config["pages"], config["links_per_tick"]
    every, lanes = config["burst_every"], config["burst_lanes"]
    start = config["admitted_links"]
    taken = np.zeros(0, np.int64)
    out = {}
    for t in range(int(rng.integers(0, every)), count, every):
        # the pages that exist by then: those the links so far reach
        alive = int(live[start + t * k] // n)
        target = int(rng.integers(0, alive))
        got = np.zeros(0, np.int64)
        while got.size < lanes:
            src = rng.integers(0, alive, 2 * (lanes - got.size) + 64)
            cand = np.maximum(src, target) * n + np.minimum(src, target)
            cand = cand[src != target]
            at = np.minimum(np.searchsorted(live, cand), live.size - 1)
            cand = cand[(live[at] != cand) & ~np.isin(cand, taken)]
            both = np.concatenate([got, cand])
            got = both[np.sort(np.unique(both, return_index=True)[1])]
        out[t] = got[:lanes]
        taken = np.concatenate([taken, out[t]])
    return out


def generate(config: dict, length: int, seed: int) -> List[Tenant]:
    """The one tenant, with min(``length``, `ticks`) deltas."""
    require_array_admission()
    n, k = config["pages"], config["links_per_tick"]
    start = config["admitted_links"]
    rng = tenant_rng(seed, 0)
    keys = links(config, rng)
    count = min(int(length), ticks(config))
    bursts = _bursts(config, keys, count, rng)
    lo, hi = keys[:start] % n, keys[:start] // n
    graph = np.sort(lo * n + hi)  # in (lo, hi) order, as admission takes
    deltas = []
    for t in range(count):
        lanes = keys[start + t * k:start + (t + 1) * k].copy()
        if t in bursts:
            lanes[:bursts[t].size] = bursts[t]
        lanes = np.sort(lanes % n * n + lanes // n)
        ones = np.ones(lanes.size)
        deltas.append(Delta(lanes // n, lanes % n, ones, 0.0 * ones))
    return [Tenant(name="enwiki", n_nodes=n, lo=graph // n, hi=graph % n,
                   weights=np.ones(graph.size), deltas=deltas)]
