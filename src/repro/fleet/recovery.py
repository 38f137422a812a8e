"""Shard-failure recovery: rebuild a dead shard's tenants on survivors.

Promotes the repo's train-side fault-tolerance pattern (checkpoint +
resume, `train.fault_tolerance`) into serving: each tenant is rebuilt
as ``base ⊕ replay(wal)`` where

- ``base`` is the tenant-space snapshot in its directory entry, or —
  after a fleet save truncated it — the dead shard's *on-disk serving
  checkpoint* (the shared `train.checkpoint` format), walked forward
  through the shard's journaled layout migrations
  (`migrate.migrate_host_arrays`) to the layout at death so the
  directory's position maps index it correctly, then gathered to
  tenant space; and
- ``replay(wal)`` re-applies the tenant's own deltas since the base,
  host-side through the exact incremental update
  (`core.jsdist.jsdist_incremental`) — including any tick that was
  in flight when the shard died (the WAL is appended at ingest, before
  the device ever sees the delta).

The rebuilt tenant is then placed on a surviving *dense* shard (same
bucket first, spilling up) and installed at identity positions —
sparse slot-space tenants also land on dense pools, since their edge
store cannot be reconstructed from FINGER statistics. A dead sparse
shard's disk base is gathered to tenant space through the per-stream
`SlotMap`s its checkpoint stores (virtual id → slot), in place of a
dense position map.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.jsdist import jsdist_incremental
from repro.core.state import FingerState
from repro.engine.stream import restore_stacked_state
from repro.fleet.errors import AdmissionError, RecoveryError
from repro.graphs.layout import NodeLayout
from repro.graphs.types import GraphDelta
from repro.serving import migrate


@dataclasses.dataclass(frozen=True)
class DeadShard:
    """What the fleet remembers about a killed shard: enough to read
    its last checkpoint and interpret the directory's position maps
    (which are addressed in the layout at death)."""

    pool: int
    shard: int
    layout: NodeLayout
    step: int
    ckpt_dir: Optional[str]
    method: str


def replay_tenant(base: dict, wal: List[Tuple[int, GraphDelta]],
                  base_step: int, exact_smax: bool
                  ) -> Tuple[dict, Optional[float]]:
    """``base ⊕ replay(wal entries past base_step)`` in tenant space.

    Returns the rebuilt tenant-space snapshot (its node space grown to
    cover every replayed delta) and the last replayed JSdist score
    (None when nothing replayed). Host-side and method-exact: the
    dense incremental update is the reference the device paths are
    tested against, so the rebuilt state matches the lost shard's to
    float tolerance.
    """
    strengths = np.asarray(base["strengths"], np.float32).copy()
    mask = np.asarray(base["node_mask"], np.float32).copy()
    n = int(strengths.shape[0])
    state = FingerState(
        q=jnp.float32(base["q"]), s_total=jnp.float32(base["s_total"]),
        s_max=jnp.float32(base["s_max"]),
        strengths=jnp.asarray(strengths),
        node_mask=jnp.asarray(mask), layout=NodeLayout(n))
    last = None
    for step_no, d in wal:
        if step_no <= base_step:
            continue
        if d.n_nodes > n:
            grown = NodeLayout(d.n_nodes,
                               generation=state.layout.generation)
            # Host-side replay: these per-delta materializations ARE
            # the recovery path's work, not a serving-loop hazard.
            state = FingerState(
                q=state.q, s_total=state.s_total, s_max=state.s_max,
                strengths=jnp.asarray(np.pad(
                    np.asarray(state.strengths),  # lint: disable=per-item-host-sync
                    (0, d.n_nodes - n))),
                node_mask=jnp.asarray(np.pad(
                    np.asarray(state.node_mask),  # lint: disable=per-item-host-sync
                    (0, d.n_nodes - n))),
                layout=grown)
            n = d.n_nodes
        dd = migrate.embed_delta(d, n) if d.n_nodes < n else d
        dist, state = jsdist_incremental(state, dd,
                                         exact_smax=exact_smax,
                                         method="dense")
        last = float(dist)
    out = {"q": float(state.q), "s_total": float(state.s_total),
           "s_max": float(state.s_max),
           "strengths": np.asarray(state.strengths, np.float32),
           "node_mask": np.asarray(state.node_mask, np.float32)}
    return out, last


def _load_dead_checkpoint(dead: DeadShard, exact_smax: bool):
    """The dead shard's last checkpoint, walked to the layout at death
    (so directory position maps index it): per-stream scalars plus the
    (B, n_pad_death) strengths/mask. Sparse checkpoints skip the
    layout walk — slot ids survive capacity growth unchanged — and
    surface the checkpoint's per-stream `SlotMap`s instead (the gather
    table sparse tenants are read through)."""
    states, step_saved, meta = restore_stacked_state(
        dead.ckpt_dir, exact_smax=exact_smax, method=dead.method)
    strengths = np.asarray(states.strengths, np.float32)
    mask = np.ones_like(strengths) if states.node_mask is None \
        else np.asarray(states.node_mask, np.float32)
    slot_maps = None
    if dead.method == "sparse_tick":
        slot_maps = meta.get("slot_maps")
    else:
        gen = int(meta.get("layout_generation", 0))
        if (strengths.shape[-1] != dead.layout.n_pad
                or gen != dead.layout.generation):
            log = migrate.load_layout_log(dead.ckpt_dir)
            strengths, mask, gen, _ = migrate.migrate_host_arrays(
                strengths, mask, log, gen, dead.layout.n_pad)
    return {
        "strengths": strengths, "node_mask": mask,
        "q": np.asarray(states.q, np.float32),
        "s_total": np.asarray(states.s_total, np.float32),
        "s_max": np.asarray(states.s_max, np.float32),
        "step": int(step_saved),
        "slot_maps": slot_maps,
    }


def recover_shard(fleet, dead: DeadShard) -> List[dict]:
    """Restore every tenant of one dead shard onto survivors (see
    module docstring). Returns one report dict per tenant."""
    pool = fleet.config.pools[dead.pool]
    tenants = fleet.directory.tenants_on(dead.pool, dead.shard)
    disk = None
    reports = []
    for entry in tenants:
        if entry.wal_floor > entry.base_step:
            # The retention policy pruned WAL entries the durable base
            # does not cover: steps (base_step, wal_floor] are gone,
            # so base ⊕ replay(wal) would silently skip them.
            raise RecoveryError(
                f"tenant {entry.name!r}: WAL steps "
                f"({entry.base_step}, {entry.wal_floor}] were "
                f"truncated by the retention policy "
                f"(wal_retention_ticks) before a durable base covered "
                "them — recovery cannot replay a gapped log; lower "
                "the retention window or save() the fleet more often")
        if entry.base_state is not None:
            base, base_step = entry.base_state, entry.base_step
        else:
            if dead.ckpt_dir is None:
                raise RecoveryError(
                    f"tenant {entry.name!r}: no in-memory base and "
                    f"shard ({pool.name!r}, {dead.shard}) has no "
                    "checkpoint directory")
            if disk is None:
                try:
                    disk = _load_dead_checkpoint(dead,
                                                 pool.exact_smax)
                except FileNotFoundError as e:
                    raise RecoveryError(
                        f"tenant {entry.name!r}: {e}") from e
            row_s = disk["strengths"][entry.slot]
            row_m = disk["node_mask"][entry.slot]
            strengths = np.zeros((entry.n_nodes,), np.float32)
            mask = np.zeros((entry.n_nodes,), np.float32)
            if pool.method == "sparse_tick":
                # Sparse tenants carry no dense position map; gather
                # through the checkpoint's SlotMap.
                if not disk["slot_maps"]:
                    raise RecoveryError(
                        f"tenant {entry.name!r}: sparse shard "
                        f"({pool.name!r}, {dead.shard})'s checkpoint "
                        "carries no SlotMap payloads (it predates "
                        "sparse persistence) — its slot assignments "
                        "are unrecoverable")
                slot_map = disk["slot_maps"][entry.slot]
                strengths = slot_map.to_virtual(row_s, entry.n_nodes)
                mask = slot_map.to_virtual(row_m, entry.n_nodes)
            else:
                som = entry.slot_of_node
                valid = np.nonzero(som >= 0)[0]
                strengths[valid] = row_s[som[valid]]
                mask[valid] = row_m[som[valid]]
            base = {"q": float(disk["q"][entry.slot]),
                    "s_total": float(disk["s_total"][entry.slot]),
                    "s_max": float(disk["s_max"][entry.slot]),
                    "strengths": strengths, "node_mask": mask}
            base_step = disk["step"]
        new_base, last = replay_tenant(base, entry.wal, base_step,
                                       pool.exact_smax)
        n_t = int(new_base["strengths"].shape[0])
        try:
            tgt_pool, tgt_shard, tgt_slot = fleet.router.place(
                n_t, fleet.live_shards(),
                min_pool=dead.pool if pool.method != "sparse_tick"
                else 0,
                dense_only=True)
        except AdmissionError as e:
            raise RecoveryError(
                f"tenant {entry.name!r}: no surviving dense shard "
                f"fits its {n_t} node slot(s): {e}") from e
        fleet.install_dense(tgt_pool, tgt_shard, tgt_slot, new_base)
        entry.pool, entry.shard, entry.slot = (tgt_pool, tgt_shard,
                                               tgt_slot)
        entry.n_nodes = n_t
        entry.slot_of_node = np.arange(n_t, dtype=np.int32)
        entry.base_state = new_base
        entry.base_step = fleet.step
        entry.wal = []
        entry.wal_floor = fleet.step
        entry.installed_step = fleet.step
        if last is not None:
            entry.last_score = last
        reports.append({"tenant": entry.name,
                        "to": (tgt_pool, tgt_shard, tgt_slot),
                        "replayed": last is not None})
    return reports
