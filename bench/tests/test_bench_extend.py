"""A new configuration, traffic mix, client loop and per-layer metric
are new files and new entries in BENCHMARK.json: the harness finds them
by name, and no file the benchmark already has changes."""
import json
import os
import shutil

from bench import harness
from bench.tests import tiny

READER = '''
def read(ctx):
    """Ticks in the traced window."""
    return float(ctx.ticks) if ctx.ticks else None
'''

# An open loop at a fixed rate: tick i is due at start + i / ticks_per_s
# and carries one delta of every tenant; the loop sleeps until it is due.
PACED = '''
import time

import numpy as np

from bench import harness


def length(traffic, seconds):
    return int(seconds * traffic["ticks_per_s"]) + 1


def run(fleet, feed, traffic, seconds, top_k, span):
    period = 1.0 / traffic["ticks_per_s"]
    latency, scores, tops = [], [], []
    start = time.perf_counter()
    t = 0
    while t < feed.length and t * period < seconds:
        due = start + t * period
        time.sleep(max(0.0, due - time.perf_counter()))
        batch = {n: feed.deltas[j][t] for j, n in enumerate(feed.names)}
        got, top, _ = harness.tick(fleet, batch, top_k, span)
        latency.append(time.perf_counter() - due)
        scores.append([got[n] for n in feed.names])
        tops.append(list(top))
        t += 1
    tenants = len(feed.names)
    return harness.Window(
        seconds=time.perf_counter() - start,
        latency_s=np.repeat(np.asarray(latency), tenants),
        lanes=int(feed.lanes[:, :t].sum()),
        schedule=np.repeat(np.arange(t)[:, None], tenants, axis=1),
        scores=np.asarray(scores), tops=tops,
        attempted=t * tenants, failed=0)
'''


def _write(root, path, text):
    with open(os.path.join(root, path), "w") as f:
        f.write(text)


def test_new_files_and_entries_only(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(tiny.ROOT, "bench"),
                    os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(os.path.join(root, p), "rb").read()
              for p in ("bench/harness.py", "bench/run.py",
                        "bench/trace.py", "bench/loops/closed.py")}

    base = tiny.tiny(harness.load_cell("dos.replay", root))
    _write(root, "bench/configs/dos-small.json",
           json.dumps(dict(base.config, name="dos-small")))
    _write(root, "bench/traffic/paced.json",
           json.dumps({"loop": "paced", "ticks_per_s": 40}))
    _write(root, "bench/loops/paced.py", PACED)
    _write(root, "bench/traffic/short.json",
           json.dumps(dict(base.traffic, max_ticks_per_s=5)))
    _write(root, "bench/metrics/ticks_traced.py", READER)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "dos-small", "source": "a test", "reduced": [],
        "file": "bench/configs/dos-small.json", "why": "a test"})
    for mix in ("paced", "short"):
        manifest["workloads"].append({
            "name": f"dos-small.{mix}", "config": "dos-small",
            "traffic": mix, "chips": 1, "why": "a test"})
    manifest["per_layer"].append({
        "name": "ticks_traced", "unit": "ticks", "better": "higher",
        "source": "program_counter", "layer": "client loop",
        "moves": "edge_updates_per_s", "workloads": ["dos-small.paced"]})
    _write(root, "BENCHMARK.json", json.dumps(manifest))

    # a new loop: 40 ticks a second for one second, latency from each
    # tick's due time
    cell = harness.load_cell("dos-small.paced", root)
    assert "ticks_traced" in {m["name"] for m in cell.per_layer}
    out = tiny.run("dos-small.paced", root=root, cell=cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 40 * 3
    traced = tiny.run("dos-small.paced", trace=True, root=root, cell=cell)
    assert traced["correct"], traced["checks"]
    assert traced["metrics"]["ticks_traced"]["value"] == 40

    # a new mix of the closed loop, data only: a stream of 5 ticks a
    # second ends the one-second window after 5 ticks
    short = harness.load_cell("dos-small.short", root)
    out = tiny.run("dos-small.short", root=root, cell=short)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 5 * 3
    # the old cell does not report the new cell's metric
    old = harness.load_cell("dos.replay", root)
    assert "ticks_traced" not in {m["name"] for m in old.per_layer}
    for p, body in before.items():
        assert open(os.path.join(root, p), "rb").read() == body
