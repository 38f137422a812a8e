"""The open loop: deltas arrive on their own clock, whether or not the
fleet keeps up, as a live monitoring feed sends them. Each tenant's
deltas arrive at seeded times drawn uniformly over the window, sorted
(a Poisson stream with its count fixed), at the mix's
``deltas_per_s`` shared evenly by its ``tenants``. A tick carries at
most one delta per tenant, the oldest that has arrived; with none
waiting the loop sleeps until the next arrives. A delta's latency runs
from its arrival to the return of `top_anomalies` for its tick, so it
holds the wait in the queue. The window closes after ``seconds``; a
delta still waiting then is not scored."""
from __future__ import annotations

import math
import time

import numpy as np

from bench import harness
from bench.generators.common import tenant_rng


def length(traffic: dict, seconds: float) -> int:
    """Deltas per tenant: its share of the rate over the window."""
    return int(math.ceil(seconds * traffic["deltas_per_s"]
                         / traffic["tenants"]))


def arrivals(seed: int, tenants: int, count: int,
             seconds: float) -> np.ndarray:
    """(tenants, count) sorted arrival seconds from the window's
    start."""
    return np.stack([np.sort(tenant_rng(seed, 1000 + j).uniform(
        0.0, seconds, count)) for j in range(tenants)])


def run(fleet, feed: harness.Feed, traffic: dict, seconds: float,
        top_k: int, span) -> harness.Window:
    tenants = len(feed.names)
    if tenants != traffic["tenants"]:
        raise harness.SetupError(
            f"the mix is for {traffic['tenants']} tenants, the "
            f"configuration has {tenants}")
    count = min(length(traffic, seconds), feed.length)
    due = arrivals(feed.seed, tenants, count, seconds)
    nxt = np.zeros(tenants, np.int64)
    latency, carried, scores, tops = [], [], [], []
    lanes = attempted = failed = 0
    start = time.perf_counter()
    while True:
        now = time.perf_counter() - start
        if now >= seconds or (nxt >= count).all():
            break
        waiting = np.nonzero(nxt < count)[0]
        heads = due[waiting, nxt[waiting]]
        ready = waiting[heads <= now]
        if ready.size == 0:
            time.sleep(min(heads.min(), seconds) - now)
            continue
        batch = {feed.names[j]: feed.deltas[j][nxt[j]] for j in ready}
        got, top, took = harness.tick(fleet, batch, top_k, span)
        done = time.perf_counter() - start
        row = np.full(tenants, -1, np.int64)
        attempted += ready.size
        if took:
            row[ready] = nxt[ready]
            lanes += int(feed.lanes[ready, nxt[ready]].sum())
            latency.extend(done - due[ready, nxt[ready]])
        else:
            failed += ready.size
        nxt[ready] += 1
        carried.append(row)
        scores.append([got[n] for n in feed.names])
        tops.append(list(top))
    end = time.perf_counter()
    return harness.Window(
        seconds=end - start, latency_s=np.asarray(latency, np.float64),
        lanes=lanes,
        schedule=np.asarray(carried, np.int64).reshape(-1, tenants),
        scores=np.asarray(scores, np.float64).reshape(-1, tenants),
        tops=tops, attempted=attempted, failed=failed)
