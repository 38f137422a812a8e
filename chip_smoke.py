#!/usr/bin/env python3
"""Chip smoke test: the FINGER fleet's served path on a TPU.

Drives `FingerFleet` → `FingerService` → `ExecutionPlan` → tick kernels
once, through the entry points a user calls, on a DoS-monitoring
deployment modelled on the paper's §4 Table 3 (Oregon-1 AS peering
snapshots, 11,174 ASes, one planted fan-in attack). Every tenant is
generated from ``--seed``:

- the planted tenant is a full Oregon-1-width AS graph: all 11,174
  nodes active, ~22k edges (`graphs.streams.dos_attack_edge_sequence`,
  built as edge lists);
- 63 churn-only tenants (`dos_attack_sequence`) have node-id widths on
  a Zipf law from 64 up, each with at most ``active_cap`` active ASes;
- 4 weighted Hi-C contact-map tenants (`hic_bifurcation_sequence`), so
  the parity check also sees non-integer weights and f32 rounding.

They land in four pools by width (the fleet router's best fit):

- ``dense``  — small tenants, the vmapped XLA tick;
- ``fused``  — mid-size tenants, the `stream_tick` megakernel;
- ``sparse`` — wide id spaces with few active ASes, the `sparse_tick`
  slot-space kernel;
- ``oregon`` — the full-width tenant. Its tick tile (11,264 node slots,
  24,576 edge slots, a 780-edge burst) is far over the kernels' VMEM
  guard, which routes it to the XLA sparse tick: the path a user at
  that size gets.

Set-up is `admit` + `fleet.warm()`; then ``--ticks`` steady ticks of
``ingest → poll → scores / top_anomalies`` run under a zero-compile
budget. The run fails (exit 1, no ``"ok": true``) when the platform is
not ``tpu``, a DoS tenant's score differs by more than 1e-5 from a
per-tenant f32 `jsdist_incremental` reference on the same deltas and
chip, any tenant's carried (q, S, s_max) differ by more than 1e-5
relative from a float64 reference on the host, the
planted tenant is missing from `top_anomalies` at its burst tick, the
steady window compiles anything or a poll is not one launch per pool,
or a pool's tick — compiled from its live shard group, as `poll()`
groups them — is not the path the deployment claims for it:
``tpu_custom_call`` in each kernel pool, none in the others.

``--four-chips`` instead runs only `FingerService(placement="sharded")`
over a 4-device mesh against ``placement="local"`` on the same deltas
(scores and `top_anomalies`). Everything runs in this one process.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py [--seed 0] [--ticks 20]
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro.analysis.sanitize import compile_budget  # noqa: E402
from repro.core.jsdist import jsdist_incremental  # noqa: E402
from repro.core.state import host_device, host_finger_state  # noqa: E402
from repro.distributed.sharding import auto_mesh  # noqa: E402
from repro.fleet import FingerFleet, FleetConfig, PoolSpec  # noqa: E402
from repro.fleet import pooltick  # noqa: E402
from repro.graphs.streams import (dos_attack_edge_sequence,  # noqa: E402
                                  dos_attack_sequence,
                                  hic_bifurcation_sequence)
from repro.graphs.types import EdgeList, GraphDelta  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.kernels.sparse_tick.ops import sparse_tick_vmem_bytes  # noqa: E402
from repro.kernels.stream_tick.ops import fused_tick_vmem_bytes  # noqa: E402
from repro.serving import FingerService, ServiceConfig, TopKSpec  # noqa: E402
from repro.serving.plans import dummy_tick_args  # noqa: E402

# Oregon-1 AS peering snapshots: 10,670-11,174 nodes (paper §4 Table 3).
OREGON1_NODES = 11_174
TOL = 1e-5
TOP_K = 8
TPU_KERNEL = 'custom_call_target="tpu_custom_call"'


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


@dataclasses.dataclass(frozen=True)
class Deployment:
    """The DoS-monitoring fleet: tenant population and pool layout.

    ``active_cap`` bounds the ASes a churn tenant observes: its node-id
    space is its full width, but at most ``active_cap`` of its ids carry
    edges, which keeps the ``sparse`` pool's tile under the kernels'
    VMEM guard. The planted tenant has no cap: its whole ``max_width``
    id space is active. ``kernel_pools`` names the pools whose tick
    must be a Pallas kernel; every other pool must tick in XLA.
    """

    tenants: int = 64
    ticks: int = 20
    min_width: int = 64
    max_width: int = OREGON1_NODES
    active_cap: int = 448
    attack_frac: float = 0.05  # X = 5% of the nodes fan into one target
    hic_widths: Tuple[int, ...] = (384, 384, 3000, 3000)
    hic_loci: int = 16
    pools: Tuple[PoolSpec, ...] = (
        PoolSpec(name="dense", n_pad=128, shards=2, streams_per_shard=16,
                 k_pad=16),
        PoolSpec(name="fused", n_pad=512, shards=2, streams_per_shard=16,
                 k_pad=160, method="fused_tick"),
        PoolSpec(name="sparse", n_pad=4096, shards=2,
                 streams_per_shard=8, k_pad=128, method="sparse_tick",
                 n_slots=512, m_pad=4096),
        PoolSpec(name="oregon", n_pad=OREGON1_NODES, shards=1,
                 streams_per_shard=1, k_pad=896, method="sparse_tick",
                 n_slots=11_264, m_pad=24_576),
    )
    kernel_pools: Tuple[str, ...] = ("fused", "sparse")


@dataclasses.dataclass
class Tenant:
    name: str
    width: int
    pool: int
    graph: EdgeList               # admitted graph, tenant id space
    deltas: List[GraphDelta]      # per tick, tenant id space, host arrays
    ref_deltas: List[GraphDelta]  # the same deltas addressed at pool n_pad
    attack_at: Optional[int]      # 0-based transition of the attack
    weighted: bool = False


def tenant_widths(dep: Deployment) -> List[int]:
    """Zipf (Pareto, exponent 1) quantiles of the node-id-space width:
    half the tenants within 2x the smallest width, one in eight past 8x;
    the widest tenant is exactly ``max_width``."""
    p = (np.arange(dep.tenants) + 0.5) / dep.tenants
    w = np.minimum(dep.min_width / (1.0 - p), dep.max_width).astype(int)
    w[-1] = dep.max_width
    return [int(x) for x in w]


def pool_for(dep: Deployment, width: int) -> int:
    """Best-fit bucket: the smallest pool whose n_pad covers ``width``
    (the fleet router's rule; every pool is sized to hold its tenants)."""
    for i, pool in enumerate(dep.pools):
        if width <= pool.n_pad:
            return i
    raise SmokeFailure(f"no pool holds a tenant of width {width}")


def _relabeled_delta(d: GraphDelta, ids: np.ndarray, n_nodes: int,
                     k_pad: int) -> GraphDelta:
    m = np.asarray(d.mask) > 0
    out = GraphDelta.from_arrays(
        ids[np.asarray(d.senders)[m]], ids[np.asarray(d.receivers)[m]],
        np.asarray(d.dw)[m], np.asarray(d.w_old)[m],
        n_nodes=n_nodes, k_pad=k_pad)
    return jax.tree_util.tree_map(np.asarray, out)


def _tenant(dep: Deployment, name: str, width: int, first: EdgeList,
            deltas: List[GraphDelta], ids: np.ndarray,
            attack_at: Optional[int] = None,
            weighted: bool = False) -> Tenant:
    """A tenant whose graph lives on ``ids`` of a ``width``-wide id
    space: ``first`` and ``deltas`` are addressed in ``len(ids)``
    local ids."""
    pool_i = pool_for(dep, width)
    pool = dep.pools[pool_i]
    src = np.asarray(first.senders)[np.asarray(first.mask) > 0]
    dst = np.asarray(first.receivers)[np.asarray(first.mask) > 0]
    w = np.asarray(first.weights)[np.asarray(first.mask) > 0]
    mask = np.zeros(width, np.float32)
    mask[ids] = 1.0
    graph = EdgeList.from_arrays(ids[src], ids[dst], w, n_nodes=width,
                                 node_mask=mask)
    return Tenant(
        name=f"{name}-w{width}", width=width, pool=pool_i,
        graph=jax.tree_util.tree_map(np.asarray, graph),
        deltas=[_relabeled_delta(d, ids, width, pool.k_pad)
                for d in deltas],
        ref_deltas=[_relabeled_delta(d, ids, pool.n_pad, pool.k_pad)
                    for d in deltas],
        attack_at=attack_at, weighted=weighted)


def _edge_list(weights: np.ndarray) -> EdgeList:
    iu, ju = np.nonzero(np.triu(weights, 1))
    return EdgeList.from_arrays(iu, ju, weights[iu, ju],
                                n_nodes=weights.shape[0])


def make_tenants(dep: Deployment, seed: int) -> List[Tenant]:
    """Every tenant's admitted graph and per-tick deltas, from ``seed``.

    The widest tenant carries the planted attack with its whole id
    space active; the other DoS tenants churn only, each on
    ``min(width, active_cap)`` random distinct ids of its id space.
    The Hi-C tenants are weighted contact maps of ``hic_loci`` loci."""
    widths = tenant_widths(dep)
    out = []
    for i, width in enumerate(widths):
        tseed = seed * 100_003 + i
        if i == len(widths) - 1:
            first, deltas, attack_at = dos_attack_edge_sequence(
                n=width, n_graphs=dep.ticks + 1,
                attack_frac=dep.attack_frac, seed=tseed,
                k_pad=dep.pools[pool_for(dep, width)].k_pad)
            out.append(_tenant(dep, f"attacked{i:02d}", width, first,
                               deltas, np.arange(width), attack_at))
            continue
        n_act = min(width, dep.active_cap)
        seq, _ = dos_attack_sequence(
            n=n_act, n_graphs=dep.ticks + 1, attack_frac=0.0, seed=tseed,
            k_pad=dep.pools[pool_for(dep, width)].k_pad)
        ids = np.random.default_rng(tseed).choice(width, n_act,
                                                  replace=False)
        out.append(_tenant(dep, f"as{i:02d}", width,
                           _edge_list(np.asarray(seq.graphs[0].weights)),
                           seq.deltas, ids))
    for h, width in enumerate(dep.hic_widths):
        tseed = seed * 100_003 + dep.tenants + h
        seq = hic_bifurcation_sequence(
            n=dep.hic_loci, n_samples=dep.ticks + 1,
            bifurcation_at=dep.ticks // 2, seed=tseed,
            k_pad=dep.pools[pool_for(dep, width)].k_pad)
        ids = np.random.default_rng(tseed).choice(width, dep.hic_loci,
                                                  replace=False)
        out.append(_tenant(dep, f"hic{h}", width,
                           _edge_list(np.asarray(seq.graphs[0].weights)),
                           seq.deltas, ids, weighted=True))
    return out


STATS = ("q", "s_total", "s_max")  # the carried FINGER statistics


def reference_run(tenants: List[Tenant], pools, x64: bool = False
                  ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """Per-tenant `jsdist_incremental` on the same deltas: the plain
    reference the fleet is held to (padded to the pool width, which
    every FINGER statistic is invariant to, so each pool compiles one
    reference step). Each tenant's initial state is computed on the
    host, as the fleet computes it at admission; the steps run on the
    default device in f32, the fleet's dtype, or with ``x64`` in
    float64. Returns each tenant's per-tick scores and its final
    (q, S, s_max)."""
    dtype = np.float64 if x64 else np.float32

    def cast(tree):
        return jax.tree_util.tree_map(
            lambda x: np.asarray(x, dtype) if x.dtype.kind == "f" else x,
            tree)

    scores, finals = {}, {}
    with jax.enable_x64(x64):
        step = jax.jit(functools.partial(jsdist_incremental,
                                         exact_smax=False))
        for t in tenants:
            with jax.default_device(host_device()):
                padded = t.graph.pad_to(pools[t.pool].n_pad)
            st = cast(host_finger_state(padded))
            dists = []
            for d in t.ref_deltas:
                dist, st = step(st, cast(d))
                dists.append(dist)
            scores[t.name] = np.asarray(jax.device_get(dists), np.float64)
            finals[t.name] = np.asarray(
                [jax.device_get(getattr(st, f)) for f in STATS], np.float64)
    return scores, finals


def fleet_stats(fleet: FingerFleet, entries: Dict[str, object]
                ) -> Dict[str, np.ndarray]:
    """Each named tenant's carried (q, S, s_max), read from its shard's
    live state row."""
    out = {}
    for name, entry in entries.items():
        st = fleet.shard_service(entry.pool, entry.shard).states()
        out[name] = np.asarray(
            [jax.device_get(getattr(st, f))[entry.slot] for f in STATS],
            np.float64)
    return out


class PhaseClock:
    """Wall time per named phase, printed as it completes."""

    def __init__(self, log: Callable[[str], None]):
        self.log = log
        self.seconds: Dict[str, float] = {}

    def run(self, name: str, fn, *args, **kwargs):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        self.seconds[name] = time.perf_counter() - t0
        self.log(f"phase {name}: {self.seconds[name]:.3f} s")
        return out


def tile_estimate_bytes(pool: PoolSpec) -> Optional[int]:
    """The kernel guard's VMEM estimate of one grid step of ``pool``'s
    tick (None for the XLA-only methods)."""
    if pool.method == "fused_tick":
        return fused_tick_vmem_bytes(pool.n_pad, pool.k_pad, pool.j_pad)
    if pool.method == "sparse_tick":
        return sparse_tick_vmem_bytes(pool.n_slots, pool.m_pad,
                                      pool.k_pad, pool.j_pad)
    return None


def pool_tick_programs(fleet: FingerFleet, pool_i: int
                       ) -> List[Tuple[str, str]]:
    """(launch, compiled HLO) of each launch ``poll()`` makes for one
    pool: its live shards grouped by layout as `poll()` groups them, a
    group that passes `pooltick.group_fits` as one stacked launch and
    any other shard on its own. Each program is lowered from the live
    states of its shards (deltas are the plan's zero dummies, of the
    shapes the queued deltas have)."""
    svcs = [fleet.shard_service(pool_i, s)
            for s in fleet.live_shards()[pool_i]]
    out = []
    for group in pooltick.group_by_layout(svcs):
        states = tuple(svc.states() for svc in group)
        deltas = tuple(dummy_tick_args(svc.config,
                                       svc.capacity or svc.layout)[1]
                       for svc in group)
        if pooltick.group_fits([svc.config for svc in group]):
            cfg = group[0].config
            fn = pooltick.pool_tick_fn(cfg.exact_smax, cfg.method)
            out.append((f"stacked launch of {len(group)} shard(s)",
                        fn.lower(states, deltas).compile().as_text()))
            continue
        for svc, st, d in zip(group, states, deltas):
            out.append(("per-shard launch", svc.plan.engine._tick
                        .lower(st, d).compile().as_text()))
    return out


def check_tick_paths(dep: Deployment, fleet: FingerFleet,
                     log: Callable[[str], None]) -> Dict[str, str]:
    """Each pool's tick is one launch, on the path ``dep`` claims."""
    paths, wrong = {}, []
    budget = dispatch.vmem_budget_bytes()
    for pool_i, pool in enumerate(dep.pools):
        programs = pool_tick_programs(fleet, pool_i)
        n_calls = [hlo.count(TPU_KERNEL) for _, hlo in programs]
        claim = "Pallas" if pool.name in dep.kernel_pools else "XLA"
        est = tile_estimate_bytes(pool)
        guard = "" if est is None else (
            f", tile estimate {est / 2**20:.2f} MiB vs VMEM guard "
            f"{budget / 2**20:.2f} MiB")
        paths[pool.name] = (
            f"{pool.method}, claimed {claim}{guard}: "
            + "; ".join(f"{launch}, {n} tpu_custom_call"
                        for (launch, _), n in zip(programs, n_calls)))
        log(f"tick path {pool.name}: {paths[pool.name]}")
        ok = len(programs) == 1 and (
            all(n_calls) if claim == "Pallas" else not any(n_calls))
        if not ok:
            wrong.append(pool.name)
    if wrong:
        raise SmokeFailure(
            f"pools {wrong} do not tick as claimed (one launch each; "
            "tpu_custom_call in every kernel pool and none elsewhere)")
    return paths


def run_fleet(dep: Deployment, seed: int,
              log: Callable[[str], None] = print,
              cache_dir: Optional[str] = None) -> Dict[str, object]:
    """The fleet phases; raises `SmokeFailure` on a failed check."""
    clock = PhaseClock(log)
    host = host_device() or jax.devices()[0]
    with jax.default_device(host):
        tenants = clock.run("generate", make_tenants, dep, seed)
    for i, pool in enumerate(dep.pools):
        members = [t for t in tenants if t.pool == i]
        if len(members) > pool.capacity:
            raise SmokeFailure(
                f"pool {pool.name!r} holds {pool.capacity} tenants, "
                f"{len(members)} need it")
        active = [int(np.asarray(t.graph.node_mask).sum())
                  for t in members]
        edges = [int(np.asarray(t.graph.mask).sum()) for t in members]
        log(f"pool {pool.name}: method={pool.method} n_pad={pool.n_pad} "
            f"k_pad={pool.k_pad} shards={pool.shards}x"
            f"{pool.streams_per_shard} tenants={len(members)} widths="
            f"{min(t.width for t in members)}-"
            f"{max(t.width for t in members)} active nodes="
            f"{min(active)}-{max(active)} edges={min(edges)}-{max(edges)}")
    planted, = [t for t in tenants if t.attack_at is not None]
    burst_tick = planted.attack_at + 1
    log(f"planted attack: tenant {planted.name} at tick {burst_tick}")

    weighted = [t for t in tenants if t.weighted]
    ref, _ = clock.run("reference", reference_run, tenants, dep.pools)
    with jax.default_device(host):
        _, final64 = clock.run("reference_f64", reference_run, tenants,
                               dep.pools, x64=True)
    log(f"reference devices: f32 {jax.devices()[0].platform}, "
        f"float64 {host.platform}")
    config = FleetConfig(pools=dep.pools, compilation_cache_dir=cache_dir)
    fleet = FingerFleet.open(config)
    try:
        def admit_all():
            entries = {}
            for t in tenants:
                entries[t.name] = entry = fleet.admit(t.name, t.graph)
                if entry.pool != t.pool:
                    raise SmokeFailure(
                        f"{t.name} admitted to pool {entry.pool}, "
                        f"expected {t.pool}")
            return entries
        entries = clock.run("admit", admit_all)
        handle = fleet.warm(background=True)
        clock.run("warm_compile", handle.wait)

        # DoS graphs are integer-weighted: every sum is exact in f32, so
        # on one device the fleet's scores are the f32 reference's to
        # the bit. A weighted graph's score sqrt(H̃_half − (H̃ + H̃')/2)
        # is a small difference of O(1) entropies, moved ~1e-5 by the
        # order of f32 sums alone, so the Hi-C scores are reported and
        # every tenant is also held on its carried (q, S, s_max), which
        # are well conditioned, against a float64 reference.
        err_dos = err_w = 0.0
        detected = None
        launches = set()
        steady = {"ingest": 0.0, "poll": 0.0, "scores": 0.0,
                  "top_anomalies": 0.0}
        t_window = time.perf_counter()
        with compile_budget(None, "steady fleet ticks") as compiles:
            for tick in range(dep.ticks):
                t0 = time.perf_counter()
                fleet.ingest({t.name: t.deltas[tick] for t in tenants})
                t1 = time.perf_counter()
                fleet.poll()
                t2 = time.perf_counter()
                got = fleet.scores()
                t3 = time.perf_counter()
                top = fleet.top_anomalies(k=TOP_K)
                t4 = time.perf_counter()
                launches.add(fleet.last_poll_launches)
                for k, v in zip(steady, (t1 - t0, t2 - t1, t3 - t2, t4 - t3)):
                    steady[k] += v
                for t in tenants:
                    e = abs(got[t.name] - ref[t.name][tick])
                    if t.weighted:
                        err_w = max(err_w, e)
                    else:
                        err_dos = max(err_dos, e)
                if tick + 1 == burst_tick:
                    detected = planted.name in [n for n, _ in top]
                    log(f"tick {tick + 1} top_anomalies: "
                        + ", ".join(f"{n}={v:.4f}" for n, v in top)
                        + f"; planted scored {got[planted.name]:.4f}")
        window_s = time.perf_counter() - t_window
        log(f"steady window: {dep.ticks} ticks in {window_s:.3f} s, "
            f"{compiles.count} compiles, launches per poll "
            f"{sorted(launches)} for {len(dep.pools)} pools; per-phase "
            "totals " + ", ".join(f"{k}={v:.3f} s"
                                  for k, v in steady.items()))
        got_stats = fleet_stats(fleet, entries)
        rel = max(float(np.max(np.abs(got_stats[n] - final64[n])
                               / np.maximum(np.abs(final64[n]), 1e-30)))
                  for n in got_stats)
        rel_w = max(float(np.max(np.abs(got_stats[t.name]
                                        - final64[t.name])
                                 / np.abs(final64[t.name])))
                    for t in weighted) if weighted else 0.0
        log(f"parity scores: max |fleet - f32 reference| = {err_dos:.3e} "
            f"over {len(tenants) - len(weighted)} DoS tenants x "
            f"{dep.ticks} ticks (tolerance {TOL}); weighted Hi-C "
            f"({len(weighted)} tenants, reported) {err_w:.3e}")
        log(f"parity statistics: final (q, S, s_max) max relative "
            f"|fleet - float64 reference| = {rel:.3e} over "
            f"{len(tenants)} tenants (tolerance {TOL}); weighted Hi-C "
            f"{rel_w:.3e}")
        log(f"detection: planted tenant in top_anomalies({TOP_K}) at "
            f"burst tick {burst_tick}: {detected}")
        if compiles.count:
            raise SmokeFailure(
                f"{compiles.count} compiles in the steady window")
        if launches != {len(dep.pools)}:
            raise SmokeFailure(
                f"polls made {sorted(launches)} launches, not one per "
                f"pool ({len(dep.pools)})")
        if err_dos > TOL:
            raise SmokeFailure(f"DoS score parity {err_dos:.3e} > {TOL}")
        if rel > TOL:
            raise SmokeFailure(f"statistics parity {rel:.3e} > {TOL} "
                               "(relative)")
        if not detected:
            raise SmokeFailure("planted DoS tenant missing from "
                               "top_anomalies at its burst tick")
        paths = check_tick_paths(dep, fleet, log)
    finally:
        fleet.close()
    return {"max_abs_err": err_dos, "stats_rel_err": rel,
            "detected": detected,
            "steady_compiles": compiles.count, "window_s": window_s,
            "phases_s": clock.seconds, "tick_paths": paths}


def four_chip_deployment(ticks: int) -> Deployment:
    """The sharded-placement check: the fleet's mid-size tenants (the
    `stream_tick` kernel pool) as one 64-stream service."""
    return Deployment(
        tenants=64, ticks=ticks, max_width=512, hic_widths=(),
        pools=(PoolSpec(name="fused", n_pad=512, shards=1,
                        streams_per_shard=64, k_pad=160,
                        method="fused_tick"),),
        kernel_pools=("fused",))


def run_four_chips(dep: Deployment, seed: int,
                   log: Callable[[str], None] = print,
                   cache_dir: Optional[str] = None,
                   n_devices: int = 4) -> Dict[str, object]:
    """`placement="sharded"` over an ``n_devices`` mesh against
    ``placement="local"`` on the same deltas: scores and top-k. ``dep``
    has one pool, whose method and widths both services use."""
    pool, ticks = dep.pools[0], dep.ticks
    with jax.default_device(host_device()):
        tenants = make_tenants(dep, seed)
        graphs = [jax.tree_util.tree_map(np.asarray,
                                         t.graph.pad_to(pool.n_pad))
                  for t in tenants]
    top_k = min(TOP_K, len(tenants) // n_devices)
    base = ServiceConfig(batch_size=len(tenants), n_pad=pool.n_pad,
                         k_pad=pool.k_pad, method=pool.method,
                         topk=TopKSpec(k=top_k),
                         compilation_cache_dir=cache_dir)
    mesh = auto_mesh((n_devices,), ("data",),
                     devices=jax.devices()[:n_devices])
    local = FingerService.open(base, graphs)
    sharded = FingerService.open(base.with_(placement="sharded"), graphs,
                                 mesh=mesh)
    worst, top_equal = 0.0, True
    try:
        for tick in range(ticks):
            for svc in (local, sharded):
                svc.ingest([t.ref_deltas[tick] for t in tenants])
                svc.poll()
            worst = max(worst, float(np.max(np.abs(
                local.scores() - sharded.scores()))))
            lv, li = local.top_anomalies(top_k)
            sv, si = sharded.top_anomalies(top_k)
            top_equal &= bool(np.array_equal(li, si)
                              and np.allclose(lv, sv, atol=TOL))
    finally:
        local.close()
        sharded.close()
    log(f"four-chip: sharded over {n_devices} devices vs local, "
        f"{len(tenants)} streams x {ticks} ticks: max |Δscore| = "
        f"{worst:.3e}, top_anomalies({top_k}) equal every tick: "
        f"{top_equal}")
    if worst > TOL or not top_equal:
        raise SmokeFailure("sharded placement disagrees with local")
    return {"max_abs_err": worst, "top_equal": top_equal}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ticks", type=int, default=20)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-local placement "
                         "comparison over a 4-chip mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: {device}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if dispatch.default_interpret(None):
        print("chip_smoke: kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    cache_dir = os.path.join(ROOT, ".jax_cache")
    print(f"compilation cache: "
          f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or cache_dir}",
          flush=True)

    def log(msg: str) -> None:
        print(msg, flush=True)

    try:
        if args.four_chips:
            if len(devices) < 4:
                print(f"chip_smoke: --four-chips needs 4 devices, found "
                      f"{len(devices)}", file=sys.stderr)
                return 1
            run_four_chips(four_chip_deployment(args.ticks), args.seed,
                           log=log, cache_dir=cache_dir)
        else:
            run_fleet(Deployment(ticks=args.ticks), args.seed, log=log,
                      cache_dir=cache_dir)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
