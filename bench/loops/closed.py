"""The closed loop: every tenant's next delta is ready when the previous
tick returns, and ticks run back to back, as in a backfill after an
outage. Each tick carries one delta of every tenant, in stream order.
A delta is due when its tick's deltas are ready; its latency runs from
then to the return of `top_anomalies` for that tick.

The mix's ``max_ticks_per_s`` sizes the generated stream, so that a
window of ``seconds`` cannot run out of it below that rate. A program
that runs faster ends the window when the stream ends: the rate and
the tails are then those of a shorter window, and the log says so."""
from __future__ import annotations

import math
import time

import numpy as np

from bench import harness


def length(traffic: dict, seconds: float) -> int:
    return int(math.ceil(seconds * traffic["max_ticks_per_s"]))


def run(fleet, feed: harness.Feed, traffic: dict, seconds: float,
        top_k: int, span) -> harness.Window:
    tenants = len(feed.names)
    latency, carried, scores, tops = [], [], [], []
    lanes = attempted = failed = 0
    start = time.perf_counter()
    t = 0
    while time.perf_counter() - start < seconds and t < feed.length:
        batch = {n: feed.deltas[j][t] for j, n in enumerate(feed.names)}
        ready = time.perf_counter()
        got, top, took = harness.tick(fleet, batch, top_k, span)
        done = time.perf_counter()
        attempted += tenants
        carried.append(t if took else -1)
        if took:
            lanes += int(feed.lanes[:, t].sum())
            latency.append(done - ready)
        else:
            failed += tenants
        scores.append([got[n] for n in feed.names])
        tops.append(list(top))
        t += 1
    end = time.perf_counter()
    return harness.Window(
        seconds=end - start,
        latency_s=np.repeat(np.asarray(latency), tenants),
        lanes=lanes,
        schedule=np.repeat(np.asarray(carried, np.int64).reshape(-1, 1),
                           tenants, axis=1),
        scores=np.asarray(scores, np.float64), tops=tops,
        attempted=attempted, failed=failed)
