"""`FingerFleet`: the multi-tenant serving fleet facade.

One fleet = ordered buckets (pools) of `FingerService` shards + a
tenant directory. Tenants are admitted with a host graph, stream
tenant-space deltas through `ingest`/`poll` (strict alternation; every
live shard ticks every poll, so shard step == fleet step always), are
promoted across buckets when they outgrow one, survive shard death
(`kill_shard`/`recover`), and persist as a whole
(`save`/`restore` — per-shard serving checkpoints + one ``fleet.json``
tenant manifest).

Queries read a tick's scores once: a stacked launch leaves its pool an
(S, B) score plane and a shard that ticked on its own its (B,) row, each
pulled to the host with one transfer when `scores` or `top_anomalies`
first needs it; `top_anomalies` merges per-shard top-k candidates. A
shard without a row (before its first tick) is read through the jitted
one-slot index and the device top-k.

The serving loop writes profiler spans (`jax.profiler.TraceAnnotation`,
free when no profiler runs), all named ``finger.*``: ``finger.ingest``
(counters ``step``, ``lanes``) split into ``finger.route`` and
``finger.wal`` beside each shard's ``finger.shard_ingest``;
``finger.poll`` (``step``, ``launches``) around each
``finger.dispatch`` and the periodic ``finger.save``;
``finger.scores`` and ``finger.top_anomalies`` (``step``) around every
blocking device-to-host read, ``finger.d2h``. The fleet-level spans of
one tick carry the same ``step``.
"""
from __future__ import annotations

import json
import os
import tempfile
from typing import Dict, List, Optional, Tuple, Union

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.sparse import SparseCapacityError, sparse_state_from_graph
from repro.core.state import FingerState, host_finger_state
from repro.fleet import pooltick
from repro.fleet.config import FleetConfig
from repro.fleet.directory import TenantDirectory, TenantEntry
from repro.fleet.errors import (AdmissionError, FleetConfigError,
                                FleetLifecycleError, ShardUnavailableError)
from repro.fleet.rebalance import Rebalancer
from repro.fleet.recovery import DeadShard, recover_shard
from repro.fleet.router import FleetRouter
from repro.graphs.types import DenseGraph, GraphDelta
from repro.serving import FingerService
from repro.serving.service import ServiceLifecycleError, WarmupHandle

_MANIFEST = "fleet.json"


class FingerFleet:
    """Build with `open` (fresh) or `restore` (from a fleet
    directory); never construct directly."""

    def __init__(self, config: FleetConfig,
                 shards: List[List[Optional[FingerService]]],
                 directory: TenantDirectory, step: int = 0):
        self._config = config
        self._shards = shards
        self._directory = directory
        self._router = FleetRouter(config, directory)
        self._rebalancer = Rebalancer(self)
        self._step = step
        self._staged = False
        self._closed = False
        self._dead: Dict[Tuple[int, int], DeadShard] = {}
        # The per-pool score plane: pool -> [(shard_ids, (S, B) device
        # score matrix)] per stacked launch of the latest tick, plus
        # its lazily-materialized host mirror (one transfer per group
        # per tick, shared by every scores()/top_anomalies() read).
        self._pool_scores_dev: Dict[int, list] = {}
        self._pool_scores_host: Dict[int, Dict[int, np.ndarray]] = {}
        self._last_poll_launches = 0

    # -- construction -----------------------------------------------------
    @staticmethod
    def _seed_graph() -> DenseGraph:
        """The free-slot placeholder every stream opens with: one
        inactive node, zero weight — all statistics exactly zero."""
        return DenseGraph.from_weights(
            np.zeros((1, 1), np.float32),
            node_mask=np.zeros((1,), np.float32))

    @classmethod
    def open(cls, config: FleetConfig) -> "FingerFleet":
        config.validate()
        shards: List[List[Optional[FingerService]]] = []
        for pool in config.pools:
            row: List[Optional[FingerService]] = []
            plan = None
            for i in range(pool.shards):
                scfg = pool.service_config(
                    config.directory, i,
                    compilation_cache_dir=config.compilation_cache_dir)
                svc = FingerService.open(
                    scfg,
                    [cls._seed_graph()] * pool.streams_per_shard,
                    plan=plan)
                if plan is None:
                    plan = svc.plan  # one compiled tick per pool
                row.append(svc)
            shards.append(row)
        return cls(config, shards, TenantDirectory())

    # -- introspection ----------------------------------------------------
    @property
    def config(self) -> FleetConfig:
        return self._config

    @property
    def step(self) -> int:
        return self._step

    @property
    def directory(self) -> TenantDirectory:
        return self._directory

    @property
    def router(self) -> FleetRouter:
        return self._router

    @property
    def rebalancer(self) -> Rebalancer:
        return self._rebalancer

    def shard_service(self, pool_i: int, shard_i: int) -> FingerService:
        pools = self._config.pools
        if not (0 <= pool_i < len(pools)
                and 0 <= shard_i < pools[pool_i].shards):
            raise ShardUnavailableError(
                f"no shard ({pool_i}, {shard_i}) in this fleet")
        svc = self._shards[pool_i][shard_i]
        if svc is None:
            raise ShardUnavailableError(
                f"shard ({self._config.pools[pool_i].name!r}, "
                f"{shard_i}) is dead (killed and not reopened)")
        return svc

    def live_shard_ids(self) -> List[Tuple[int, int]]:
        return [(p, s)
                for p in range(len(self._config.pools))
                for s in range(self._config.pools[p].shards)
                if self._shards[p][s] is not None]

    def live_shards(self) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for p, s in self.live_shard_ids():
            out.setdefault(p, []).append(s)
        return out

    def _is_dead(self, pool_i: int, shard_i: int) -> bool:
        return self._shards[pool_i][shard_i] is None

    def _check_open(self, what: str) -> None:
        if self._closed:
            raise FleetLifecycleError(f"{what} on a closed FingerFleet")

    def _require_unstaged(self, what: str) -> None:
        if self._staged:
            raise FleetLifecycleError(
                f"{what} with a staged tick pending; poll() it first")

    # -- admission --------------------------------------------------------
    def admit(self, name: str, graph) -> TenantEntry:
        """Admit a tenant with its current graph (tenant node space =
        the graph's). Best-fit bucket, least-loaded shard; the stream
        row is installed live (`install_stream`). A sparse tenant's
        admission runs in a ``finger.admit`` span counting the nodes
        and edges it allocated slots for (``nodes``, ``edges``)."""
        self._check_open("admit")
        self._require_unstaged("admit")
        if name in self._directory:
            raise AdmissionError(f"tenant {name!r} already admitted")
        n_t = int(graph.n_nodes)
        pool_i, shard_i, slot = self._router.place(
            n_t, self.live_shards())
        pool = self._config.pools[pool_i]
        svc = self.shard_service(pool_i, shard_i)
        if pool.method == "sparse_tick":
            with TraceAnnotation("finger.admit") as span:
                try:
                    row, slot_map = sparse_state_from_graph(
                        graph, svc.capacity, n_virtual=svc.config.n_pad,
                        stream=slot)
                except SparseCapacityError as e:
                    raise AdmissionError(
                        f"tenant {name!r}: {e}") from e
                svc.install_stream(slot, row, slot_map=slot_map)
                span.set_metadata(
                    nodes=slot_map.layout.n_slots - slot_map.n_free_nodes,
                    edges=slot_map.n_live_edges)
            # the tenant-space base is the slot row read through the map
            base = {
                "q": float(row.q), "s_total": float(row.s_total),
                "s_max": float(row.s_max),
                "strengths": slot_map.to_virtual(
                    np.asarray(row.strengths, np.float32), n_t),
                "node_mask": slot_map.to_virtual(
                    np.asarray(row.node_mask, np.float32), n_t),
            }
            slot_of_node = None
        else:
            # Same O(n + m) init pass `StreamEngine.init_states` runs on
            # the unpadded graph (zero-padding into the shard layout
            # commutes with every FINGER statistic), computed on the
            # host: the values are host bookkeeping.
            st = host_finger_state(graph)
            base = {
                "q": float(st.q), "s_total": float(st.s_total),
                "s_max": float(st.s_max),
                "strengths": np.asarray(st.strengths, np.float32).copy(),
                "node_mask":
                    np.ones((n_t,), np.float32) if st.node_mask is None
                    else np.asarray(st.node_mask, np.float32).copy(),
            }
            self._install_row(svc, pool_i, slot, base)
            slot_of_node = np.arange(n_t, dtype=np.int32)
        entry = TenantEntry(
            name=name, pool=pool_i, shard=shard_i, slot=slot,
            n_nodes=n_t, slot_of_node=slot_of_node,
            base_step=self._step, base_state=base,
            installed_step=self._step, wal_floor=self._step)
        self._directory.add(entry)
        return entry

    def evict(self, name: str) -> None:
        """Remove a tenant and free its stream slot."""
        self._check_open("evict")
        self._require_unstaged("evict")
        entry = self._directory.get(name)
        if not self._is_dead(entry.pool, entry.shard):
            self.shard_service(entry.pool,
                               entry.shard).clear_stream(entry.slot)
        self._directory.remove(name)

    def install_dense(self, pool_i: int, shard_i: int, slot: int,
                      base: dict) -> None:
        """Install a tenant-space snapshot at identity positions into
        one dense stream row (shared by promotion and recovery);
        repads the shard back to its pool bound first if it was
        compacted below the tenant's size."""
        svc = self.shard_service(pool_i, shard_i)
        if int(base["strengths"].shape[0]) > svc.layout.n_pad:
            svc.repad(self._config.pools[pool_i].n_pad)
        self._install_row(svc, pool_i, slot, base)

    def _install_row(self, svc: FingerService, pool_i: int, slot: int,
                     base: dict) -> None:
        n_t = int(base["strengths"].shape[0])
        n_pad = svc.layout.n_pad
        strengths = np.zeros((n_pad,), np.float32)
        strengths[:n_t] = base["strengths"]
        mask = np.zeros((n_pad,), np.float32)
        mask[:n_t] = base["node_mask"]
        row = FingerState(
            q=np.float32(base["q"]),
            s_total=np.float32(base["s_total"]),
            s_max=np.float32(base["s_max"]),
            strengths=strengths, node_mask=mask,
            layout=svc.states().layout)
        svc.install_stream(slot, row)

    # -- the serving loop -------------------------------------------------
    def ingest(self, deltas: Dict[str, GraphDelta]) -> None:
        """Stage one fleet tick: tenant-space deltas keyed by tenant
        name (absent tenants tick an empty delta). Runs the capacity
        pre-pass (warm repad / promotion) first, appends every delta
        to its tenant's WAL, then fans the translated per-slot deltas
        to the owning shards. Deltas for tenants on a dead shard are
        WAL-only — they replay at `recover`."""
        self._check_open("ingest")
        self._require_unstaged("ingest")
        step_next = self._step + 1
        with TraceAnnotation(
                "finger.ingest", step=step_next,
                lanes=sum(d.lane_count() for d in deltas.values())):
            with TraceAnnotation("finger.route"):
                stages, sparse_slots, wal_pending = self._route(deltas)
            with TraceAnnotation("finger.wal"):
                self._append_wal(wal_pending, step_next)
            for pool_i, shard_i in self.live_shard_ids():
                pool = self._config.pools[pool_i]
                svc = self.shard_service(pool_i, shard_i)
                key = (pool_i, shard_i)
                if pool.method == "sparse_tick":
                    slots = sparse_slots.get(key, {})
                    empty = None
                    if len(slots) < pool.streams_per_shard:
                        empty = self._router.empty_delta(pool)
                    svc.ingest([slots.get(s, empty)
                                for s in range(pool.streams_per_shard)])
                else:
                    stage = stages.get(key)
                    if stage is None:  # no tenant delta: all-zero rows
                        stage = self._router.stage_for(key, pool)
                    svc.ingest(stage.finish(svc))
        self._staged = True

    def _route(self, deltas: Dict[str, GraphDelta]):
        """The capacity pre-pass (warm repad / promotion), then every
        delta translated for its shard: ``(stages, sparse_slots,
        wal_pending)``."""
        for name in deltas:
            self._directory.get(name)  # fail fast, by name
        for name, d in deltas.items():
            entry = self._directory.get(name)
            if self._is_dead(entry.pool, entry.shard):
                continue
            self._rebalancer.ensure_capacity(name, d)
        # Translation: dense tenants stage numpy-vectorized rows
        # straight into their shard's preallocated (B, k_pad) buffers
        # (one stacked GraphDelta per shard, no per-tenant allocation);
        # sparse tenants keep the per-tenant path — their SlotMap
        # translation is stateful inside the service.
        stages: Dict[Tuple[int, int], object] = {}
        sparse_slots: Dict[Tuple[int, int], Dict[int, GraphDelta]] = {}
        wal_pending: List[Tuple[TenantEntry, GraphDelta]] = []
        for name, d in deltas.items():
            entry = self._directory.get(name)
            wal_pending.append((entry, d))
            if self._is_dead(entry.pool, entry.shard):
                continue
            svc = self.shard_service(entry.pool, entry.shard)
            pool = self._config.pools[entry.pool]
            key = (entry.pool, entry.shard)
            if pool.method == "sparse_tick":
                t = self._router.translate(entry, d, pool)
                sparse_slots.setdefault(key, {})[entry.slot] = t
            else:
                stage = stages.get(key)
                if stage is None:
                    stage = self._router.stage_for(key, pool)
                    stages[key] = stage
                self._router.stage_dense(entry, d, svc, pool, stage)
        return stages, sparse_slots, wal_pending

    def _append_wal(self, wal_pending: List[Tuple[TenantEntry,
                                                   GraphDelta]],
                    step_next: int) -> None:
        """WAL: one buffered commit per tick, after every translation
        succeeded — a rejected tick leaves no partial WAL — with the
        retention policy applied as part of the same pass."""
        retention = self._config.wal_retention_ticks
        for entry, d in wal_pending:
            entry.wal.append((step_next, d))
            if retention is not None:
                cutoff = step_next - retention
                if entry.wal[0][0] <= cutoff:
                    pruned_to = max(s for s, _ in entry.wal
                                    if s <= cutoff)
                    entry.wal = [w for w in entry.wal
                                 if w[0] > cutoff]
                    entry.wal_floor = max(entry.wal_floor, pruned_to)

    def poll(self) -> int:
        """Advance the whole fleet one tick (all live shards — shard
        step stays == fleet step). Ticks an all-empty delta when
        nothing was staged. Returns the new fleet step.

        Steady-state dispatch (``config.stacked_ticks``): each pool's
        live shards — every method, megakernel pools included —
        advance as ONE stacked launch per layout group
        (`fleet.pooltick`), leaving the (S, B) score matrix on device
        as the tick's score plane. A group whose S-stacked operands
        exceed the device-residency budget (`pooltick.group_fits`)
        falls back to sequential per-shard `poll()` for that group
        only. A due periodic save runs AFTER every pool's tick has
        been dispatched — the checkpoint never serializes ahead of
        device work — in its own ``finger.save`` span.
        """
        self._check_open("poll")
        with TraceAnnotation("finger.poll", step=self._step + 1) as span:
            launches = self._poll()
            span.set_metadata(launches=launches)
        return self._step

    def _poll(self) -> int:
        """`poll`'s body; returns the launches it dispatched."""
        if not self._staged:
            self.ingest({})
        self._pool_scores_dev = {}
        self._pool_scores_host = {}
        launches = 0
        live = self.live_shards()
        for pool_i in sorted(live):
            pool = self._config.pools[pool_i]
            # (shard ids, scores): an (S, B) plane of a stacked launch,
            # or one shard's (B,) row where it ticked on its own
            planes = []
            self._pool_scores_dev[pool_i] = planes
            if not (self._config.stacked_ticks
                    and pooltick.stackable(pool.method)):
                for shard_i in live[pool_i]:
                    self._poll_alone(pool_i, shard_i, planes)
                    launches += 1
                continue
            # Group live shards by live layout: shards of one pool
            # share a config, but a compacted shard has a private
            # (smaller, regenerated) layout and ticks in its own
            # group; sparse shards additionally key on their live
            # SparseLayout capacity (grow_capacity re-keys a shard).
            groups: Dict[tuple, list] = {}
            for shard_i in live[pool_i]:
                svc = self.shard_service(pool_i, shard_i)
                gkey = (svc.layout.n_pad, svc.layout.generation,
                        svc.capacity)
                groups.setdefault(gkey, []).append((shard_i, svc))
            for members in groups.values():
                group = [svc for _, svc in members]
                if not pooltick.group_fits(
                        [svc.config for svc in group]):
                    # S-stacked operands would blow the residency
                    # budget: this group ticks sequentially.
                    for shard_i, _ in members:
                        self._poll_alone(pool_i, shard_i, planes)
                        launches += 1
                    continue
                dists = pooltick.tick_pool(group)
                launches += 1
                planes.append(([s for s, _ in members], dists))
        self._step += 1
        self._staged = False
        self._last_poll_launches = launches
        every = self._config.save_every_ticks
        if every is not None and self._step % every == 0:
            with TraceAnnotation("finger.save"):
                self.save()
        return launches

    def _poll_alone(self, pool_i: int, shard_i: int, planes: list) -> None:
        """Tick one shard on its own; its (B,) scores join the pool's
        planes, so one read serves `scores` and `top_anomalies`."""
        report = self.shard_service(pool_i, shard_i).poll()
        if report is not None:
            planes.append(([shard_i], report.scores))

    @property
    def last_poll_launches(self) -> int:
        """Device launches the latest `poll()` dispatched — one per
        pool layout-group when stacked, one per shard sequentially
        (the sentinel's dispatch-budget probe)."""
        return self._last_poll_launches

    # -- queries ----------------------------------------------------------
    def _host_score_row(self, pool_i: int,
                        shard_i: int) -> Optional[np.ndarray]:
        """One shard's (B,) host score row of the latest tick —
        materialized lazily with ONE device→host transfer per stacked
        layout-group, or per shard that ticked on its own, per tick,
        then indexed for free by every per-tenant read and top-k merge.
        None when the shard did not tick in the latest poll."""
        rows = self._pool_scores_host.get(pool_i)
        if rows is None:
            planes = self._pool_scores_dev.get(pool_i)
            if planes is None:
                return None
            rows = {}
            for shard_ids, mat in planes:
                with TraceAnnotation("finger.d2h"):
                    host = np.asarray(mat)  # the plane's one transfer
                if host.ndim == 1:  # a shard that ticked on its own
                    host = host[None]
                for j, s in enumerate(shard_ids):
                    rows[s] = host[j]
            self._pool_scores_host[pool_i] = rows
        return rows.get(shard_i)

    def scores(self, names: Optional[List[str]] = None
               ) -> Dict[str, float]:
        """Latest per-tenant JSdist scores, read from the cached host
        score rows (one device→host transfer per stacked group or
        per shard that ticked alone, per tick, amortized over every
        tenant); a shard without a row keeps the jitted one-slot read.
        Tenants stranded on a dead shard — or (re)installed since the
        shard last ticked — report their last known score."""
        self._check_open("scores")
        with TraceAnnotation("finger.scores", step=self._step):
            out: Dict[str, float] = {}
            for name in (self._directory.names() if names is None
                         else names):
                entry = self._directory.get(name)
                if (self._is_dead(entry.pool, entry.shard)
                        or entry.installed_step >= self._step):
                    # dead shard, or row (re)installed since the shard
                    # last ticked: the slot's device score is stale
                    out[name] = entry.last_score
                    continue
                row = self._host_score_row(entry.pool, entry.shard)
                if row is not None:
                    entry.last_score = float(row[entry.slot])
                else:
                    svc = self.shard_service(entry.pool, entry.shard)
                    v = svc.score_at(entry.slot)
                    if v is not None:
                        entry.last_score = float(v)
                out[name] = entry.last_score
            return out

    def top_anomalies(self, k: int = 8) -> List[Tuple[str, float]]:
        """The k highest-scoring tenants of the latest tick: per-shard
        candidate rows (k capped at each shard's stream count), mapped
        slot→tenant, merged and cut to k. Shards with a host score row
        take their candidates from it (free once `scores` pulled it);
        a shard without one runs the device-side `top_anomalies`
        query."""
        self._check_open("top_anomalies")
        with TraceAnnotation("finger.top_anomalies", step=self._step):
            cands: List[Tuple[float, str]] = []
            for pool_i, shard_i in self.live_shard_ids():
                pool = self._config.pools[pool_i]
                kk = min(k, pool.streams_per_shard)
                row = self._host_score_row(pool_i, shard_i)
                if row is not None:
                    # Stable sort on the negated row matches lax.top_k's
                    # tie-breaking (lowest slot wins among equal scores).
                    slots = np.argsort(-row, kind="stable")[:kk]
                    vals = row[slots]
                else:
                    svc = self.shard_service(pool_i, shard_i)
                    try:
                        vals, slots = svc.top_anomalies(k=kk)
                    except ServiceLifecycleError:
                        continue  # shard has not ticked yet
                for v, s in zip(np.ravel(vals), np.ravel(slots)):
                    entry = self._directory.tenant_at(pool_i, shard_i,
                                                      int(s))
                    if entry is not None:
                        cands.append((float(v), entry.name))
            cands.sort(key=lambda t: -t[0])
            return [(name, v) for v, name in cands[:k]]

    # -- rebalancing ------------------------------------------------------
    def promote(self, name: str,
                to_pool: Optional[str] = None) -> dict:
        """Move a tenant to a bigger bucket, live (checkpoint-through
        row migration; see `Rebalancer.promote`)."""
        self._check_open("promote")
        self._require_unstaged("promote")
        return self._rebalancer.promote(name, to_pool=to_pool)

    def rebalance(self) -> List[dict]:
        """One occupancy-driven upkeep sweep (auto-compaction). Legal
        with a staged tick: queued deltas are remapped through the
        serving grace machinery."""
        self._check_open("rebalance")
        return self._rebalancer.auto_rebalance()

    def warm(self, background: bool = False
             ) -> Union[list, WarmupHandle]:
        """Pre-compile the whole steady-state rebalance surface (see
        `Rebalancer.warm`)."""
        self._check_open("warm")
        return self._rebalancer.warm(background=background)

    # -- failure + recovery -----------------------------------------------
    def kill_shard(self, pool_name: str, shard_i: int) -> DeadShard:
        """Take one shard out of service (simulated failure: its
        device state is dropped). Its tenants keep accumulating WAL
        until `recover` rebuilds them on survivors."""
        self._check_open("kill_shard")
        self._require_unstaged("kill_shard")
        pool_i = self._config.pool_index(pool_name)
        svc = self.shard_service(pool_i, shard_i)
        dead = DeadShard(
            pool=pool_i, shard=shard_i, layout=svc.layout,
            step=self._step,
            ckpt_dir=svc.config.checkpoint.directory,
            method=svc.config.method)
        svc.close()
        self._shards[pool_i][shard_i] = None
        self._dead[(pool_i, shard_i)] = dead
        return dead

    def recover(self) -> List[dict]:
        """Rebuild every dead shard's tenants on surviving shards (see
        `repro.fleet.recovery`). The dead slots stay out of rotation;
        returns one report per recovered tenant."""
        self._check_open("recover")
        self._require_unstaged("recover")
        reports = []
        for key in sorted(self._dead):
            reports.extend(recover_shard(self, self._dead[key]))
        self._dead.clear()
        return reports

    # -- persistence ------------------------------------------------------
    def save(self) -> str:
        """Checkpoint the whole fleet: every shard's serving
        checkpoint plus the ``fleet.json`` manifest (step, per-shard
        layouts, tenant directory). After a save, tenants' in-memory
        recovery bases are truncated — recovery past this point goes
        through the on-disk checkpoints. Returns the manifest path."""
        self._check_open("save")
        self._require_unstaged("save")
        if self._config.directory is None:
            raise FleetConfigError(
                "save: FleetConfig.directory is None — declare a "
                "fleet directory to persist")
        if self._dead:
            raise FleetLifecycleError(
                f"save with dead shard(s) {sorted(self._dead)}; "
                "recover() first so the manifest captures a "
                "fully-live fleet")
        pools_manifest: Dict[str, list] = {}
        for pool_i, pool in enumerate(self._config.pools):
            recs = []
            for shard_i in range(pool.shards):
                svc = self.shard_service(pool_i, shard_i)
                svc.save()
                rec = {"n_pad": svc.layout.n_pad,
                       "generation": svc.layout.generation}
                if svc.capacity is not None:
                    # Sparse shards: live slot capacities can outgrow
                    # the PoolSpec values (grow_capacity), so the
                    # manifest records them per shard.
                    rec["n_slots"] = int(svc.capacity.n_slots)
                    rec["m_pad"] = int(svc.capacity.m_pad)
                recs.append(rec)
            pools_manifest[pool.name] = recs
        # Truncate recovery material first so the manifest records the
        # post-save base steps.
        for entry in self._directory:
            entry.base_step = self._step
            entry.base_state = None
            entry.wal = [w for w in entry.wal if w[0] > self._step]
            # Everything at/under the new durable base is covered by
            # the on-disk checkpoints — pruning it never gaps recovery.
            entry.wal_floor = max(entry.wal_floor, self._step)
        manifest = {"step": self._step, "pools": pools_manifest,
                    "tenants": self._directory.to_json()}
        os.makedirs(self._config.directory, exist_ok=True)
        path = os.path.join(self._config.directory, _MANIFEST)
        fd, tmp = tempfile.mkstemp(dir=self._config.directory,
                                   suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(manifest, f, indent=1)
        os.replace(tmp, path)
        return path

    @classmethod
    def restore(cls, config: FleetConfig) -> "FingerFleet":
        """Resume a whole fleet from its directory: each shard through
        `FingerService.restore` (layout-log aware), the tenant
        directory from the manifest."""
        config.validate()
        if config.directory is None:
            raise FleetConfigError(
                "restore: FleetConfig.directory is None")
        path = os.path.join(config.directory, _MANIFEST)
        if not os.path.exists(path):
            raise FleetConfigError(
                f"restore: no fleet manifest at {path!r}")
        with open(path) as f:
            manifest = json.load(f)
        step = int(manifest["step"])
        shards: List[List[Optional[FingerService]]] = []
        for pool_i, pool in enumerate(config.pools):
            recs = manifest["pools"].get(pool.name)
            if recs is None or len(recs) != pool.shards:
                raise FleetConfigError(
                    f"restore: manifest pool {pool.name!r} has "
                    f"{None if recs is None else len(recs)} shard "
                    f"record(s), config declares {pool.shards}")
            row: List[Optional[FingerService]] = []
            plans: Dict[int, object] = {}
            for shard_i, rec in enumerate(recs):
                scfg = pool.service_config(
                    config.directory, shard_i,
                    compilation_cache_dir=config.compilation_cache_dir
                ).with_(n_pad=int(rec["n_pad"]))
                if "n_slots" in rec:
                    scfg = scfg.with_(n_slots=int(rec["n_slots"]),
                                      m_pad=int(rec["m_pad"]))
                pkey = (scfg.n_pad, scfg.n_slots, scfg.m_pad)
                svc = FingerService.restore(scfg, plan=plans.get(pkey))
                plans.setdefault(pkey, svc.plan)
                row.append(svc)
            shards.append(row)
        directory = TenantDirectory.from_json(manifest["tenants"])
        return cls(config, shards, directory, step=step)

    # -- teardown ---------------------------------------------------------
    def close(self) -> None:
        if self._closed:
            return
        for pool_i, shard_i in self.live_shard_ids():
            self._shards[pool_i][shard_i].close()
        self._closed = True

    def __enter__(self) -> "FingerFleet":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
