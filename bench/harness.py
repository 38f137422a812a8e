"""One run of one benchmark cell: generate, admit, warm, window, check.

Everything a cell needs is found by name. `BENCHMARK.json` names the
cell's configuration and traffic mix; the configuration's file names
its generator (``bench/generators/<generator>.py``) and its reference
(``bench/refs/<reference>.py``); the mix is a data file,
``bench/traffic/<mix>.json``, that names its client loop
(``bench/loops/<loop>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``. A new configuration, mix, loop or metric
is therefore new files and entries only.

A loop drives the client's calls `FingerFleet.ingest -> poll -> scores
-> top_anomalies` (`tick`) and records which delta of which tenant each
tick carried. Once the window has closed, the plain reference replays
the same deltas in the same ticks, and every score of every tick, each
tick's top-k and every tenant's final statistics are held to it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# The output checks, whose limits each configuration file states under
# "limits" (set from the readings PERF.md gives), and the window's
# compile count, which is always held to 0.
CHECKS = ("js_div_gap", "topk_div_gap", "stats_rel_gap")


class SetupError(RuntimeError):
    """The cell cannot be run as its configuration states."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """``<root>/bench/<kind>/<name>.py`` as a module."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SetupError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its
    configuration, traffic mix and the metrics it reports."""
    manifest = _load_json(os.path.join(root, "BENCHMARK.json"))
    found = [w for w in manifest["workloads"] if w["name"] == name]
    if len(found) != 1:
        raise SetupError(f"no workload named {name!r} in BENCHMARK.json")
    workload = found[0]
    entry, = [c for c in manifest["configs"]
              if c["name"] == workload["config"]]
    e2e = [m for m in manifest["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return Cell(
        name=name, chips=workload["chips"],
        config=_load_json(os.path.join(root, entry["file"])),
        traffic=_load_json(os.path.join(root, "bench", "traffic",
                                        workload["traffic"] + ".json")),
        end_to_end=e2e, per_layer=per_layer, root=root)


# -- the adapter between a configuration file and the program ----------------

def fleet_config(config: dict, cache_dir: Optional[str]):
    """The `FleetConfig` a configuration file describes."""
    from repro.fleet import FleetConfig, PoolSpec

    pools = tuple(
        PoolSpec(name=p["name"], n_pad=p["n_pad"], shards=p["shards"],
                 streams_per_shard=p["streams_per_shard"],
                 k_pad=p["k_pad"], method=p["method"],
                 n_slots=p.get("n_slots"), m_pad=p.get("m_pad"),
                 exact_smax=config["exact_smax"])
        for p in config["pools"])
    return FleetConfig(pools=pools, compilation_cache_dir=cache_dir)


def program_graph(tenant):
    """A generated tenant's admitted graph as the program's `EdgeList`
    (host arrays)."""
    from repro.graphs.types import EdgeList

    m = tenant.lo.shape[0]
    return EdgeList(senders=tenant.lo.astype(np.int32),
                    receivers=tenant.hi.astype(np.int32),
                    weights=tenant.weights.astype(np.float32),
                    mask=np.ones(m, np.float32), n_nodes=tenant.n_nodes)


def program_delta(delta, n_nodes: int):
    """A generated delta as the program's tenant-space `GraphDelta`."""
    from repro.graphs.types import GraphDelta

    return GraphDelta(senders=delta.lo.astype(np.int32),
                      receivers=delta.hi.astype(np.int32),
                      dw=delta.dw.astype(np.float32),
                      w_old=delta.w_old.astype(np.float32),
                      mask=np.ones(delta.lanes, np.float32),
                      n_nodes=n_nodes)


def tick_paths(fleet, config: dict) -> Dict[str, dict]:
    """Per pool: whether its tick, lowered from its live shards as
    `poll()` groups them, holds a Pallas TPU kernel, and whether the
    shards tick as one stacked launch or one launch each."""
    from repro.fleet import pooltick
    from repro.serving.plans import dummy_tick_args

    out = {}
    live = fleet.live_shards()
    for pool_i, pool in enumerate(config["pools"]):
        svcs = [fleet.shard_service(pool_i, s) for s in live[pool_i]]
        groups = pooltick.group_by_layout(svcs)
        if len(groups) != 1:
            raise SetupError(f"pool {pool['name']!r}: {len(groups)} "
                             "layout groups, expected one")
        group = groups[0]
        states = tuple(svc.states() for svc in group)
        deltas = tuple(dummy_tick_args(svc.config,
                                       svc.capacity or svc.layout)[1]
                       for svc in group)
        stacked = pooltick.group_fits([svc.config for svc in group])
        if stacked:
            cfg = group[0].config
            fn = pooltick.pool_tick_fn(cfg.exact_smax, cfg.method)
            text = fn.lower(states, deltas).as_text()
        else:
            text = group[0].plan.engine._tick.lower(
                states[0], deltas[0]).as_text()
        out[pool["name"]] = {
            "tick_path": "kernel" if "tpu_custom_call" in text else "xla",
            "launch": "stacked" if stacked else "per-shard",
            "launches": 1 if stacked else len(group)}
    return out


def check_tick_paths(paths: Dict[str, dict], config: dict,
                     platform: str) -> int:
    """Raise unless every pool ticks as its configuration claims
    (off the TPU a kernel runs interpreted, as plain XLA). Returns the
    launches one poll makes."""
    wrong = []
    for pool in config["pools"]:
        got = paths[pool["name"]]
        want_path = pool["tick_path"] if platform == "tpu" else "xla"
        if got["tick_path"] != want_path or got["launch"] != pool["launch"]:
            wrong.append(f"{pool['name']}: runs {got['tick_path']}, "
                         f"{got['launch']}; configuration claims "
                         f"{pool['tick_path']}, {pool['launch']}")
    if wrong:
        raise SetupError("tick paths differ from the configuration: "
                         + "; ".join(wrong))
    return sum(p["launches"] for p in paths.values())


# -- the run -------------------------------------------------------------------

@dataclasses.dataclass
class Feed:
    """The generated traffic, ready for the client: tenant ``j``'s
    ``i``-th delta is ``deltas[j][i]``, carrying ``lanes[j, i]`` edge
    lanes. ``seed`` is the run's, for a loop that draws its arrivals
    (as an open loop would)."""
    names: List[str]
    deltas: List[list]
    lanes: np.ndarray         # (tenants, length) int
    seed: int

    @property
    def length(self) -> int:
        return self.lanes.shape[1]


@dataclasses.dataclass
class Window:
    """What the client loop saw. ``schedule[t, j]`` is the index of
    tenant ``j``'s delta that tick ``t`` carried, or -1 for none."""
    seconds: float            # the window's length on the host clock
    latency_s: np.ndarray     # per delta scored: due -> top_anomalies
    lanes: int                # edge lanes scored
    schedule: np.ndarray      # (T, tenants) int
    scores: np.ndarray        # (T, tenants) fleet scores per tick
    tops: List[list]          # per tick, top_anomalies (name, score)
    attempted: int
    failed: int
    compiles: int = 0


def span(tracing: bool):
    """The benchmark's host span maker: a profiler annotation when the
    run is traced, nothing otherwise."""
    if not tracing:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def tick(fleet, batch: dict, top_k: int, span) -> tuple:
    """One tick through the client's calls: (scores, top-k, whether the
    fleet took the batch)."""
    from repro.fleet.errors import FleetIngestError

    with span("bench.tick"):
        with span("bench.ingest"):
            try:
                fleet.ingest(batch)
                took = True
            except FleetIngestError:
                took = False
        with span("bench.poll"):
            fleet.poll()
        with span("bench.readout"):
            got = fleet.scores()
            top = fleet.top_anomalies(k=top_k)
    return got, top, took


def fleet_stats(fleet, names: Sequence[str]) -> np.ndarray:
    """(tenants, 3) carried (q, S, s_max) read from each tenant's row."""
    out = []
    for name in names:
        entry = fleet.directory.get(name)
        st = fleet.shard_service(entry.pool, entry.shard).states()
        out.append([float(np.asarray(getattr(st, f))[entry.slot])
                    for f in ("q", "s_total", "s_max")])
    return np.asarray(out, np.float64)


def reference_replay(ref_module, tenants, schedule: np.ndarray,
                     dtype=np.float64):
    """The reference's (ticks, tenants) scores and (tenants, 3) final
    statistics over the deltas the window sent, tick by tick as
    ``schedule`` says. A tenant with no delta in a tick scores 0, the
    distance of its graph from itself."""
    refs = [ref_module.FingerJS(t.n_nodes, t.lo, t.hi, t.weights, dtype)
            for t in tenants]
    scores = np.zeros(schedule.shape)
    for tick_i, row in enumerate(schedule):
        for j, (t, r) in enumerate(zip(tenants, refs)):
            if row[j] >= 0:
                d = t.deltas[row[j]]
                scores[tick_i, j] = r.step(d.lo, d.hi, d.dw, d.w_old)
    stats = np.asarray([r.stats() for r in refs], np.float64)
    return scores, stats


def compare(scores: np.ndarray, tops: Sequence[Sequence[tuple]],
            names: Sequence[str], ref_scores: np.ndarray,
            got_stats: np.ndarray, ref_stats: np.ndarray,
            top_k: int) -> Dict[str, float]:
    """The output numbers held to the configuration's limits.

    A served score is the square root of a Jensen-Shannon divergence,
    and a root near zero magnifies rounding: float32 scores of a graph
    that barely changed read about 1e-3 from float64 whatever their
    size. So scores are compared as divergences (their squares):

    - ``js_div_gap``: the widest gap over every tenant and tick;
    - ``topk_div_gap``: over the ticks, the larger of how much the
      reference divergence of a tenant left out of ``top_anomalies``
      exceeds that of one listed (0 when the listing is the
      reference's own, ties aside) and the widest gap between a listed
      tenant's divergence as ``top_anomalies`` reports it and the
      reference's;
    - ``stats_rel_gap``: the widest relative gap of every tenant's
      final carried (q, S, s_max).
    """
    ticks = len(tops)
    div, ref_div = np.square(scores), np.square(ref_scores)
    js_div_gap = float(np.max(np.abs(div - ref_div))) \
        if ticks else float("inf")
    col = {n: j for j, n in enumerate(names)}
    topk_div_gap = 0.0
    for tick, top in enumerate(tops):
        listed = [n for n, _ in top]
        if len(listed) != min(top_k, len(names)) \
                or len(set(listed)) != len(listed):
            topk_div_gap = float("inf")
            break
        inside = np.zeros(len(names), bool)
        inside[[col[n] for n in listed]] = True
        if not inside.all():
            topk_div_gap = max(topk_div_gap,
                               float(ref_div[tick][~inside].max()
                                     - ref_div[tick][inside].min()))
        topk_div_gap = max(topk_div_gap, max(
            abs(float(v) ** 2 - ref_div[tick][col[n]]) for n, v in top))
    denom = np.maximum(np.abs(ref_stats), 1e-30)
    stats_rel_gap = float(np.max(np.abs(got_stats - ref_stats) / denom))
    return {"js_div_gap": js_div_gap, "topk_div_gap": topk_div_gap,
            "stats_rel_gap": stats_rel_gap}


def limits_of(config: dict) -> Dict[str, float]:
    """Each check's limit: the configuration's, and 0 compiles."""
    limits = {k: float(config["limits"][k]) for k in CHECKS}
    limits["window_compiles"] = 0.0
    return limits


def worst(scores, ref_scores, got_stats, ref_stats,
          tenants) -> List[str]:
    """Where the widest gaps lie, for the log."""
    if not len(scores):
        return []
    gap = np.abs(np.square(scores) - np.square(ref_scores))
    tick, j = np.unravel_index(int(np.argmax(gap)), gap.shape)
    rel = np.abs(got_stats - ref_stats) / np.maximum(np.abs(ref_stats),
                                                     1e-30)
    k, f = np.unravel_index(int(np.argmax(rel)), rel.shape)
    return [
        f"widest js_div gap: {tenants[j].name} tick {tick}: score "
        f"{float(scores[tick, j])!r} vs reference "
        f"{float(ref_scores[tick, j])!r}",
        f"widest stats gap: {tenants[k].name} {('q', 'S', 's_max')[f]} "
        f"{float(got_stats[k, f])!r} vs reference "
        f"{float(ref_stats[k, f])!r}"]


def percentile(values: np.ndarray, q: float) -> float:
    return float(np.percentile(values, q)) if values.size else float("nan")


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             log: Callable[[str], None], cache_dir: Optional[str],
             t_start: float, platform: str,
             faults: Sequence[Callable] = ()) -> dict:
    """One run; returns the result's fields (without ``device``'s
    identity). Each of ``faults`` is called with the warm fleet and the
    tenants before the window, to break the timed path (tests only)."""
    import jax

    from repro.analysis.sanitize import compile_budget
    from repro.fleet import FingerFleet

    config, traffic = cell.config, cell.traffic
    loop = load_module(cell.root, "loops", traffic["loop"])
    top_k = config["top_k"]
    phases = {}

    t0 = time.perf_counter()
    generator = load_module(cell.root, "generators", config["generator"])
    tenants = generator.generate(config, loop.length(traffic, seconds),
                                 seed)
    feed = Feed(names=[t.name for t in tenants],
                deltas=[[program_delta(d, t.n_nodes) for d in t.deltas]
                        for t in tenants],
                lanes=np.asarray([[d.lanes for d in t.deltas]
                                  for t in tenants], np.int64),
                seed=seed)
    phases["generate"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    fleet = FingerFleet.open(fleet_config(config, cache_dir))
    try:
        for t in tenants:
            entry = fleet.admit(t.name, program_graph(t))
            pool = config["pools"][entry.pool]
            if t.max_lanes > pool["k_pad"]:
                raise SetupError(
                    f"{t.name}: {t.max_lanes} lanes in one tick exceed "
                    f"pool {pool['name']!r}'s k_pad={pool['k_pad']}")
        phases["admit"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        # Warm-up: empty ticks through the client's own calls compile
        # exactly the programs the window drives, and change no state.
        for _ in range(2):
            fleet.ingest({})
            fleet.poll()
            fleet.scores()
            fleet.top_anomalies(k=top_k)
        launches = check_tick_paths(tick_paths(fleet, config), config,
                                    platform)
        if fleet.last_poll_launches != launches:
            raise SetupError(f"a poll made {fleet.last_poll_launches} "
                             f"launches, the pools claim {launches}")
        jax.effects_barrier()
        # The generated traffic lives as long as the run: keep the
        # collector from walking it again in the window.
        gc.collect()
        gc.freeze()
        phases["warm"] = time.perf_counter() - t0
        setup_s = time.perf_counter() - t_start
        log("setup_s " + f"{setup_s:.3f}: " + ", ".join(
            f"{k} {v:.3f} s" for k, v in phases.items())
            + f", imports and device {setup_s - sum(phases.values()):.3f} s")

        for fault in faults:
            fault(fleet, tenants)
        if trace:
            import tempfile

            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with compile_budget(None, "measured window") as compiles, \
                    span(trace)("bench.window"):
                window = loop.run(fleet, feed, traffic, seconds, top_k,
                                  span(trace))
        finally:
            if trace:
                jax.profiler.stop_trace()
        window.compiles = compiles.count
        if window.seconds < seconds:
            log(f"the stream ran out after {window.seconds:.3f} s of "
                f"{seconds} s: the window ended with it")
        log(f"window: {len(window.tops)} ticks, {window.latency_s.size} "
            f"deltas, {window.lanes} lanes in {window.seconds:.3f} s, "
            f"{window.compiles} compiles")
        memory = jax.devices()[0].memory_stats() or {}
        got_stats = fleet_stats(fleet, feed.names)
    finally:
        fleet.close()
    del fleet, feed
    gc.unfreeze()
    reference = load_module(cell.root, "refs", config["reference"])
    t0 = time.perf_counter()
    ref_scores, ref_stats = reference_replay(reference, tenants,
                                             window.schedule)
    checks = compare(window.scores, window.tops, [t.name for t in tenants],
                     ref_scores, got_stats, ref_stats, top_k)
    checks["window_compiles"] = float(window.compiles)
    log(f"reference replay {time.perf_counter() - t0:.3f} s")
    for line in worst(window.scores, ref_scores, got_stats, ref_stats,
                      tenants):
        log(line)
    limits = limits_of(config)
    result = {
        "correct": all(checks[k] <= limits[k] for k in limits),
        "attempted": window.attempted,
        "failed": window.failed,
        "memory_peak_bytes": int(memory.get("peak_bytes_in_use", 0)),
        "checks": {k: {"value": checks[k], "limit": limits[k]}
                   for k in limits},
    }
    if trace:
        result.update(read_trace(cell, trace_dir, len(window.tops)))
    else:
        lat_ms = window.latency_s * 1e3
        values = {
            "edge_updates_per_s": window.lanes / window.seconds,
            "score_latency_p50_ms": percentile(lat_ms, 50),
            "score_latency_p95_ms": percentile(lat_ms, 95),
            "setup_s": setup_s,
        }
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
    return result


@dataclasses.dataclass
class TraceContext:
    """What a per-layer metric's reader is given."""
    events: list
    lo_ns: float
    hi_ns: float
    ticks: int
    config: dict
    device_kind: str
    root: str

    def peaks(self) -> dict:
        """The chip's published peaks (``bench/peaks.json``); a device
        kind missing there is an error, never a default."""
        table = _load_json(os.path.join(self.root, "bench",
                                        "peaks.json"))["devices"]
        if self.device_kind not in table:
            raise SetupError(f"no peaks for device kind "
                             f"{self.device_kind!r} in bench/peaks.json")
        return table[self.device_kind]


def read_trace(cell: Cell, trace_dir: str, ticks: int) -> dict:
    """Per-layer metrics, busy and window seconds and the breakdown,
    from the traced window; the trace is deleted afterwards."""
    import shutil

    import jax

    from bench import trace as tr

    try:
        events = tr.load(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    lo, hi = tr.window(events)
    ctx = TraceContext(events=events, lo_ns=lo, hi_ns=hi, ticks=ticks,
                       config=cell.config,
                       device_kind=jax.devices()[0].device_kind,
                       root=cell.root)
    metrics = {}
    for m in cell.per_layer:
        value = load_module(cell.root, "metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    ops = tr.device_ops(events)
    busy = [tr.busy_ns(plane_ops, lo, hi) for plane_ops in ops.values()]
    all_ops = [e for plane_ops in ops.values() for e in plane_ops]
    spans = tr.spans(events)
    return {
        "metrics": metrics,
        "busy_s": (sum(busy) / len(busy) * 1e-9) if busy else 0.0,
        "window_s": (hi - lo) * 1e-9,
        "breakdown": {
            "device_ops": [[n, s] for n, s in
                           tr.op_seconds(all_ops, lo, hi)[:10]],
            "idle_gaps": [[n, s] for n, s in
                          tr.idle_by_span(all_ops, [
                              s for s in spans if s.name != "bench.window"],
                              lo, hi)[:10]],
        },
    }


def result(out: dict, platform: str, kind: str, count: int,
           traced: bool) -> dict:
    """The result line of a run, ``checks`` last."""
    device = {"platform": platform, "kind": kind, "count": count,
              "memory_peak_bytes": out["memory_peak_bytes"]}
    if traced:
        device["busy_s"] = out["busy_s"]
        device["window_s"] = out["window_s"]
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": device}
    if traced:
        line["breakdown"] = out["breakdown"]
    line["checks"] = out["checks"]
    return line


def stderr_lines(checks: Dict[str, dict]) -> List[str]:
    """One line per number compared, beside its limit."""
    return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
            for k, v in checks.items()]


def add_src_path(root: str) -> None:
    src = os.path.join(root, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
