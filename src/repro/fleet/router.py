"""Tenant routing: bucket admission and per-tenant delta translation.

The router owns three pure-host jobs:

- `place`: best-fit admission — the smallest bucket (pool) whose
  ``n_pad`` covers the tenant's node space and still has a free stream
  slot on a live shard, spilling upward through the bucket ladder;
  `AdmissionError` by name when nothing fits.
- `stage_dense`: one dense-pool tenant's *tenant-space* `GraphDelta`
  (node ids in the tenant's private zero-based space) → its row of the
  shard's stacked delta — virtual ids mapped through the tenant's
  ``slot_of_node`` position map (joins allocate fresh positions), lanes
  re-padded to the pool's static ``k_pad``/``j_pad``, and the result
  stamped with the shard's live `NodeLayout` generation so a migration
  racing an in-flight tick is remapped by the serving grace machinery
  instead of scattering into stale slots.
- `translate`: one sparse-pool tenant's delta re-padded to the pool's
  static sizes, on the host; the shard's own `SlotMap`s map its
  virtual ids to slots.

Positions are per-stream: each stream row has its own (n_pad,) state,
so two tenants on one shard both use low positions — only the shared
static layout (and its migrations) couples them.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.fleet.config import FleetConfig, PoolSpec
from repro.fleet.directory import TenantDirectory, TenantEntry
from repro.fleet.errors import AdmissionError, FleetIngestError
from repro.graphs.types import GraphDelta, _drop_self_loops


class ShardStage:
    """Preallocated (B, k_pad)/(B, j_pad) staging buffers for one
    shard's tick worth of translated tenant deltas.

    `stage_dense` writes each tenant's shard-space lanes straight into
    its slot's row; untouched rows stay all-zero — exactly the
    free-slot no-op delta. `finish` turns the buffers into ONE stacked
    `GraphDelta` (already (B, k_pad) — `FingerService.ingest` skips the
    per-slot `stack_deltas` entirely), so a shard's ingest is one
    numpy-vectorized handoff instead of B per-slot `from_arrays` calls.

    The buffers are reused across ticks (reset() zero-fills in place);
    `finish` hands off COPIES because `jax.device_put` of a numpy array
    may alias the host buffer on CPU — the next tick's reset would race
    the in-flight async transfer (the PR-1 host-buffer aliasing class,
    see the `numpy-handoff-no-copy` lint rule).
    """

    def __init__(self, batch: int, k_pad: int, j_pad: Optional[int]):
        self.batch, self.k_pad, self.j_pad = batch, k_pad, j_pad
        self.senders = np.zeros((batch, k_pad), np.int32)
        self.receivers = np.zeros((batch, k_pad), np.int32)
        self.dw = np.zeros((batch, k_pad), np.float32)
        self.w_old = np.zeros((batch, k_pad), np.float32)
        self.mask = np.zeros((batch, k_pad), np.float32)
        if j_pad is None:
            self.node_ids = self.node_flag = None
        else:
            self.node_ids = np.zeros((batch, j_pad), np.int32)
            self.node_flag = np.zeros((batch, j_pad), np.float32)

    def reset(self) -> None:
        for buf in (self.senders, self.receivers, self.dw, self.w_old,
                    self.mask, self.node_ids, self.node_flag):
            if buf is not None:
                buf.fill(0)

    def write_row(self, slot: int, lo: np.ndarray, hi: np.ndarray,
                  dw: np.ndarray, w_old: np.ndarray,
                  join_pos: np.ndarray, leave_pos: np.ndarray) -> None:
        k = lo.shape[0]
        self.senders[slot, :k] = lo
        self.receivers[slot, :k] = hi
        self.dw[slot, :k] = dw
        self.w_old[slot, :k] = w_old
        self.mask[slot, :k] = 1.0
        if self.node_ids is not None and (join_pos.size
                                          or leave_pos.size):
            j, l = join_pos.size, leave_pos.size
            self.node_ids[slot, :j] = join_pos
            self.node_ids[slot, j:j + l] = leave_pos
            self.node_flag[slot, :j] = 1.0
            self.node_flag[slot, j:j + l] = -1.0

    def finish(self, svc) -> GraphDelta:
        """The tick's stacked (B, k_pad) shard-space GraphDelta, stamped
        with the shard's live layout generation (the serving grace
        machinery remaps it if the shard migrates before the tick)."""
        return GraphDelta(
            senders=self.senders.copy(),
            receivers=self.receivers.copy(),
            dw=self.dw.copy(), w_old=self.w_old.copy(),
            mask=self.mask.copy(), n_nodes=svc.layout.n_pad,
            node_ids=None if self.node_ids is None
            else self.node_ids.copy(),
            node_flag=None if self.node_flag is None
            else self.node_flag.copy(),
            layout_generation=svc.layout.generation)


class FleetRouter:
    def __init__(self, config: FleetConfig,
                 directory: TenantDirectory):
        self._config = config
        self._directory = directory
        self._stages: Dict[Tuple[int, int], ShardStage] = {}

    # -- admission --------------------------------------------------------
    def place(self, n_required: int,
              live_shards: Dict[int, List[int]],
              min_pool: int = 0, max_pool: Optional[int] = None,
              dense_only: bool = False) -> Tuple[int, int, int]:
        """Best-fit (pool, shard, slot) for a tenant of ``n_required``
        node slots: ascending buckets from ``min_pool``, least-loaded
        live shard within the bucket, smallest free slot within the
        shard. ``dense_only`` restricts to dense pools (migrations and
        recovery install dense rows — a sparse edge store cannot be
        rebuilt from FINGER statistics)."""
        pools = self._config.pools
        hi = len(pools) if max_pool is None else max_pool + 1
        for pool_i in range(min_pool, hi):
            pool = pools[pool_i]
            if dense_only and pool.method == "sparse_tick":
                continue
            if n_required > pool.n_pad:
                continue
            best = None
            for shard_i in live_shards.get(pool_i, []):
                load = len(self._directory.slots_in_use(pool_i,
                                                        shard_i))
                if load >= pool.streams_per_shard:
                    continue
                if best is None or load < best[1]:
                    best = (shard_i, load)
            if best is not None:
                shard_i = best[0]
                used = self._directory.slots_in_use(pool_i, shard_i)
                slot = min(set(range(pool.streams_per_shard)) - used)
                return pool_i, shard_i, slot
        raise AdmissionError(
            f"no pool can host a tenant of {n_required} node slot(s) "
            f"(buckets {[(p.name, p.n_pad) for p in pools]}, "
            f"searched pools [{min_pool}, {hi}), "
            f"dense_only={dense_only}) — every fitting bucket is full "
            "or too small")

    # -- delta translation ------------------------------------------------
    @staticmethod
    def _split_node_slots(delta: GraphDelta
                          ) -> Tuple[np.ndarray, np.ndarray]:
        """Tenant-space (join_ids, leave_ids) from the delta's node
        lanes (deduplicated, order-preserving)."""
        if delta.node_ids is None:
            z = np.zeros((0,), np.int32)
            return z, z
        ids = np.asarray(delta.node_ids, np.int64)
        flag = np.asarray(delta.node_flag)
        join = ids[flag > 0]
        leave = ids[flag < 0]
        _, ji = np.unique(join, return_index=True)
        _, li = np.unique(leave, return_index=True)
        return (join[np.sort(ji)].astype(np.int32),
                leave[np.sort(li)].astype(np.int32))

    def required_positions(self, entry: TenantEntry,
                           delta: GraphDelta) -> int:
        """Stream-row positions the tenant needs *after* this delta:
        its placed high-water count plus the delta's first-time joins.
        Positions are never freed on leave (a rejoining node reuses
        its slot), so this is monotone — the promotion trigger."""
        if entry.slot_of_node is None:
            return entry.n_nodes  # sparse: virtual bound governs
        join, _ = self._split_node_slots(delta)
        som = entry.slot_of_node
        placed = int(np.count_nonzero(som >= 0))
        new = sum(1 for v in join.tolist()
                  if v >= som.shape[0] or som[v] < 0)
        return placed + new

    def translate(self, entry: TenantEntry, delta: GraphDelta,
                  pool: PoolSpec) -> GraphDelta:
        """A sparse-pool tenant's virtual-id delta re-padded to the
        pool's static sizes, with host (numpy) leaves: sparse shards
        translate virtual ids themselves (per-stream `SlotMap`s inside
        the service, host code), and the stacked tick goes to the
        device once after them. Dense pools stage through
        `stage_dense`."""
        join, leave = self._split_node_slots(delta)
        if (join.size or leave.size) and pool.j_pad is None:
            raise FleetIngestError(
                f"tenant {entry.name!r}: delta carries node "
                f"join/leave slots but pool {pool.name!r} has "
                "j_pad=None (no node lanes); use a pool with join "
                "slots")
        m = np.asarray(delta.mask) > 0
        if delta.n_nodes > pool.n_pad:
            raise FleetIngestError(
                f"tenant {entry.name!r}: delta addresses "
                f"{delta.n_nodes} virtual node(s), beyond pool "
                f"{pool.name!r}'s virtual bound n_pad={pool.n_pad}")
        try:
            return GraphDelta.host_from_arrays(
                np.asarray(delta.senders)[m],
                np.asarray(delta.receivers)[m],
                np.asarray(delta.dw)[m], np.asarray(delta.w_old)[m],
                n_nodes=delta.n_nodes, n_pad=pool.n_pad,
                k_pad=pool.k_pad, j_pad=pool.j_pad,
                join=join, leave=leave)
        except ValueError as e:
            raise FleetIngestError(
                f"tenant {entry.name!r}: {e}") from e

    # -- vectorized staging (the dense fleet ingest hot path) -------------
    def stage_for(self, key: Tuple[int, int],
                  pool: PoolSpec) -> ShardStage:
        """The (zeroed) staging buffers of one dense shard's tick,
        reused across ticks — allocation happens once per shard, not
        once per tick."""
        stage = self._stages.get(key)
        if stage is None or (stage.batch, stage.k_pad, stage.j_pad) != \
                (pool.streams_per_shard, pool.k_pad, pool.j_pad):
            stage = ShardStage(pool.streams_per_shard, pool.k_pad,
                               pool.j_pad)
            self._stages[key] = stage
        else:
            stage.reset()
        return stage

    def stage_dense(self, entry: TenantEntry, delta: GraphDelta,
                    svc, pool: PoolSpec, stage: ShardStage) -> None:
        """One dense-pool tenant's delta into ``stage``'s row
        ``entry.slot`` (see module docstring), numpy-vectorized with
        named rejections (`FleetIngestError`). Mutates
        ``entry.slot_of_node`` (join placement) — call once per
        (tenant, tick)."""
        join, leave = self._split_node_slots(delta)
        if (join.size or leave.size) and pool.j_pad is None:
            raise FleetIngestError(
                f"tenant {entry.name!r}: delta carries node "
                f"join/leave slots but pool {pool.name!r} has "
                "j_pad=None (no node lanes); use a pool with join "
                "slots")
        som = entry.slot_of_node
        if delta.n_nodes > som.shape[0]:
            som = np.concatenate([
                som, np.full((delta.n_nodes - som.shape[0],), -1,
                             np.int32)])
            entry.slot_of_node = som
            entry.n_nodes = int(delta.n_nodes)
        n_pad = svc.layout.n_pad
        new = [v for v in join.tolist() if som[v] < 0]
        if new:
            used = set(som[som >= 0].tolist())
            pos = 0
            for v in new:
                while pos in used:
                    pos += 1
                if pos >= n_pad:
                    raise FleetIngestError(
                        f"tenant {entry.name!r}: join of node {v} "
                        f"overflows the shard layout n_pad={n_pad}; "
                        "the rebalancer must repad or promote first")
                som[v] = pos
                used.add(pos)
        m = np.asarray(delta.mask) > 0
        snd = som[np.asarray(delta.senders, np.int64)[m]]
        rcv = som[np.asarray(delta.receivers, np.int64)[m]]
        if (snd < 0).any() or (rcv < 0).any():
            bad = sorted(set(
                np.asarray(delta.senders)[m][snd < 0].tolist()
                + np.asarray(delta.receivers)[m][rcv < 0].tolist()))
            raise FleetIngestError(
                f"tenant {entry.name!r}: delta edge(s) touch node(s) "
                f"{bad} the tenant never joined")
        leave_pos = som[leave.astype(np.int64)] if leave.size \
            else np.zeros((0,), np.int32)
        if leave.size and (leave_pos < 0).any():
            bad = sorted(leave[leave_pos < 0].tolist())
            raise FleetIngestError(
                f"tenant {entry.name!r}: leave of never-joined "
                f"node(s) {bad}")
        dw = np.asarray(delta.dw, np.float32)[m]
        w_old = np.asarray(delta.w_old, np.float32)[m]
        snd, rcv, dw, w_old = _drop_self_loops(
            snd.astype(np.int32), rcv.astype(np.int32), dw, w_old,
            kind="FleetRouter.stage_dense")
        if snd.shape[0] > pool.k_pad:
            raise FleetIngestError(
                f"tenant {entry.name!r}: k={snd.shape[0]} delta edges "
                f"exceed k_pad={pool.k_pad}")
        j = int(join.size + leave.size)
        if pool.j_pad is not None and j > pool.j_pad:
            raise FleetIngestError(
                f"tenant {entry.name!r}: {j} node join/leave slots "
                f"exceed j_pad={pool.j_pad}")
        stage.write_row(
            entry.slot, np.minimum(snd, rcv), np.maximum(snd, rcv),
            dw, w_old,
            som[join.astype(np.int64)].astype(np.int32) if join.size
            else np.zeros((0,), np.int32),
            leave_pos.astype(np.int32))

    @staticmethod
    def empty_delta(pool: PoolSpec) -> GraphDelta:
        """The free-slot no-op delta of one sparse shard tick, with host
        leaves like `translate`'s (dense shards' free slots are the
        all-zero rows of their `ShardStage`)."""
        z = np.zeros((0,), np.float32)
        return GraphDelta.host_from_arrays(
            z, z, z, z, n_nodes=0, n_pad=pool.n_pad, k_pad=pool.k_pad,
            j_pad=pool.j_pad)
