"""Sparse large-n stream state: FINGER over an active-slot universe.

The dense serving layout sizes every per-stream array by ``n_pad`` — the
padded worst case of the *virtual* node-id space — so a stream whose
graph lives inside a huge id space (the paper's Wikipedia experiments,
Table 2: n in the millions) pays O(n_pad) memory and O(k · n_pad) tick
work even when only a few hundred nodes are ever active. This module
decouples the two sizes:

- the **virtual space** (``n_virtual``, the serving config's ``n_pad``)
  is a host-side addressing bound only — no device array is ever sized
  by it;
- the **slot space** (`SparseLayout`: ``n_slots`` active-node slots and
  an ``m_pad``-capacity edge-weight store) sizes every device array, so
  per-stream memory is O(n_slots + m_pad) and a tick costs
  O(Δm² + n_slots) — independent of ``n_virtual``.

VNGE is invariant under node relabeling (the Laplacian spectrum does
not see id names), so a `SparseStreamState` over slot ids carries
*exactly* the FINGER statistics of the virtual graph: the Theorem-2 /
Algorithm-2 math is the proven dense math of `core.incremental` and
`core.jsdist`, applied to a slot-universe view of the state. The only
new moving parts are

- `SlotMap` — the host-side translator from virtual node ids to slots
  (allocating node slots on join, edge slots on new edges, freeing them
  on deletion/leave), which also owns the ingest-time validation the
  jit scatters cannot do: an out-of-capacity edge raises a named
  `SparseCapacityError` instead of being silently dropped by a
  ``mode="drop"`` scatter;
- the ``(m_pad,)`` ``edge_weights`` store carried so the state remains
  self-describing (the FINGER statistics themselves never read it —
  ``w_old`` rides in the delta, same contract as the dense path).

`repro.kernels.sparse_tick` fuses the batched slot-space tick into one
Pallas launch (``ServiceConfig.method="sparse_tick"``); `sparse_jsdist
_tick` below is its single-stream oracle.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.jsdist import _js_from_entropies
from repro.core.incremental import update_state
from repro.core.state import FingerState, host_finger_state
from repro.graphs.types import (
    DenseGraph,
    EdgeList,
    GraphDelta,
    _pytree_dataclass,
    node_mask_after_joins,
)

__all__ = [
    "EDGE_SLOT_SENTINEL",
    "SparseCapacityError",
    "SparseLayout",
    "SparseStreamState",
    "SlotMap",
    "sparse_jsdist_tick",
    "sparse_state_from_graph",
    "sparse_states_from_graphs",
]

# Out-of-store slot id for padding/gated delta lanes: every
# ``mode="drop"`` scatter ignores it, and unlike ``m_pad`` itself it
# stays out of range across any future capacity growth.
EDGE_SLOT_SENTINEL = np.int32(2**31 - 1)

# A post-delta edge weight at/below this fraction of the moved mass is
# a deletion: the edge's slot is returned to the free list.
_DELETED_EDGE_TOL = 1e-9


class SparseCapacityError(RuntimeError):
    """A sparse stream ran out of node/edge slots (or addressed past
    its virtual space). Grow the capacity (`FingerService.grow_capacity`
    / `SparseLayout.grown`) instead of letting a jit scatter drop the
    update silently."""


@dataclasses.dataclass(frozen=True)
class SparseLayout:
    """Static device-capacity layout of one sparse stream batch.

    ``n_slots`` active-node slots and ``m_pad`` edge-store slots;
    ``generation`` counts capacity migrations exactly like
    `NodeLayout.generation` counts dense layout migrations. Hashable
    and frozen so it rides as the static aux field of the state pytree
    and as a jit static argument of the capacity-grow transform.
    """

    n_slots: int
    m_pad: int
    generation: int = 0

    def __post_init__(self):
        if self.n_slots <= 0:
            raise ValueError(
                f"SparseLayout: n_slots must be positive, got "
                f"{self.n_slots}")
        if self.m_pad <= 0:
            raise ValueError(
                f"SparseLayout: m_pad must be positive, got {self.m_pad}")
        if self.generation < 0:
            raise ValueError(
                f"SparseLayout: generation must be >= 0, got "
                f"{self.generation}")

    def grown(self, n_slots: Optional[int] = None,
              m_pad: Optional[int] = None) -> "SparseLayout":
        """The next layout after a capacity bump (either axis may stay).

        Slot ids are preserved — growth only appends free slots — so
        unlike a dense repad no state renumbering or delta remap is
        needed; the generation bump still marks the migration for plan
        cache keys and journaling.
        """
        n_new = self.n_slots if n_slots is None else int(n_slots)
        m_new = self.m_pad if m_pad is None else int(m_pad)
        if n_new < self.n_slots or m_new < self.m_pad:
            raise ValueError(
                f"SparseLayout.grown: ({n_new}, {m_new}) shrinks the "
                f"current capacity ({self.n_slots}, {self.m_pad}); "
                "sparse capacity only grows")
        if (n_new, m_new) == (self.n_slots, self.m_pad):
            raise ValueError(
                "SparseLayout.grown: new capacity equals the current "
                f"({self.n_slots}, {self.m_pad})")
        return SparseLayout(n_new, m_new, generation=self.generation + 1)


@_pytree_dataclass(static_fields=("layout",))
class SparseStreamState:
    """FINGER sufficient statistics over the slot universe.

    Identical statistics to a `FingerState` of the virtual graph
    (relabeling invariance), with every array sized by the
    `SparseLayout` capacities instead of the virtual ``n_pad``.
    """

    q: jax.Array                # Lemma-1 quadratic proxy Q
    s_total: jax.Array          # S = trace(L) = 1/c
    s_max: jax.Array            # largest nodal strength
    strengths: jax.Array        # (n_slots,) per-slot strengths
    node_mask: jax.Array        # (n_slots,) 0/1 allocated-and-active
    edge_weights: jax.Array     # (m_pad,) slot-addressed edge store
    layout: SparseLayout        # static capacities + generation

    @property
    def n_slots(self) -> int:
        return int(self.strengths.shape[-1])

    @property
    def m_pad(self) -> int:
        return int(self.edge_weights.shape[-1])

    def n_active(self) -> jax.Array:
        return jnp.sum(self.node_mask).astype(jnp.int32)

    def dense_view(self) -> FingerState:
        """The slot-universe `FingerState` carrying the same statistics.

        ``layout=None`` (the legacy unmasked spelling would lose the
        mask; the view keeps it) — slot-space deltas carry
        ``n_nodes == n_slots`` so the dense layout check is moot.
        """
        return FingerState(
            q=self.q, s_total=self.s_total, s_max=self.s_max,
            strengths=self.strengths, node_mask=self.node_mask,
            layout=None)

    def h_tilde(self) -> jax.Array:
        return self.dense_view().h_tilde()


def _require_slot_delta(state: SparseStreamState, delta: GraphDelta,
                        where: str) -> None:
    if delta.edge_slots is None:
        raise ValueError(
            f"{where}: delta carries no edge_slots — sparse ticks need "
            "slot-space deltas; translate virtual deltas through the "
            "stream's SlotMap first (FingerService does this at ingest)")
    if delta.n_nodes != state.layout.n_slots:
        raise ValueError(
            f"{where}: delta is addressed in an n_slots={delta.n_nodes} "
            f"slot space but the state's layout has n_slots="
            f"{state.layout.n_slots} (generation "
            f"{state.layout.generation}); grow the capacity first "
            "(FingerService.grow_capacity)")


def _advance_edge_store(state: SparseStreamState, delta: GraphDelta,
                        s_total_after: jax.Array) -> jax.Array:
    """Carry the (m_pad,) edge store through the *full* ΔG update.

    Post-gate lanes write their new weight (``w_old + dw``, clamped at
    zero) at their slot; padding/gated lanes sit on the sentinel and
    are dropped. An emptying delta snaps the whole store to zero, same
    as the strengths snap in `update_state`.
    """
    mask_joined = state.node_mask
    if delta.node_ids is not None:
        mask_joined = node_mask_after_joins(mask_joined, delta)
    gate = delta.mask * mask_joined[delta.senders] \
        * mask_joined[delta.receivers]
    slots = jnp.where(gate > 0, delta.edge_slots,
                      jnp.int32(EDGE_SLOT_SENTINEL))
    new_w = jnp.maximum(delta.w_old + delta.dw, 0.0)
    ew = state.edge_weights.at[slots].set(new_w, mode="drop")
    return jnp.where(s_total_after > 0, ew, jnp.zeros_like(ew))


def sparse_jsdist_tick(
    state: SparseStreamState,
    delta: GraphDelta,
    exact_smax: bool = False,
    method: str = "compact",
) -> Tuple[jax.Array, SparseStreamState]:
    """Algorithm 2 on one sparse stream: (JSdist, updated state).

    Two Theorem-2 updates (ΔG/2 and ΔG) through the dense math on the
    slot-universe view — O(Δm) statistics under ``method="compact"``
    plus the O(n_slots) strength carry — then the edge-store scatter.
    The single-stream oracle of `repro.kernels.sparse_tick`.
    """
    _require_slot_delta(state, delta, "sparse_jsdist_tick")
    view = state.dense_view()
    half = update_state(view, delta.scaled(0.5), exact_smax=exact_smax,
                        method=method)
    full = update_state(view, delta, exact_smax=exact_smax,
                        method=method)
    dist = _js_from_entropies(half.h_tilde(), view.h_tilde(),
                              full.h_tilde())
    ew = _advance_edge_store(state, delta, full.s_total)
    return dist, SparseStreamState(
        q=full.q, s_total=full.s_total, s_max=full.s_max,
        strengths=full.strengths, node_mask=full.node_mask,
        edge_weights=ew, layout=state.layout)


# ---------------------------------------------------------------------------
# Host-side virtual-id -> slot translation
# ---------------------------------------------------------------------------


class SlotMap:
    """Per-stream host translator from virtual node ids to device slots.

    Owns the allocation discipline of one stream's slot space: node
    slots are allocated on join and freed on leave, edge slots are
    allocated the first time an edge appears and freed when a delta
    deletes it (post-delta weight ≈ 0) or its endpoint leaves. All
    frees/allocations commit only after the whole delta validates, so a
    rejected delta never corrupts the map — and freed slots are not
    reused within the same delta (a single tick's scatter must never
    write one slot twice).

    ``translate`` is stateful: call it exactly once per applied delta,
    in tick order (serving ingestion does; the queue holds translated
    deltas). For multi-stream atomicity, ``stage`` / ``commit`` split
    the two halves: serving ingestion stages every stream of a tick
    first (pure — a rejection leaves every map untouched) and commits
    only once the whole batch validated.
    """

    def __init__(self, layout: SparseLayout, n_virtual: int,
                 stream: Optional[int] = None):
        if int(n_virtual) <= 0:
            raise ValueError(
                f"SlotMap: n_virtual must be positive, got {n_virtual}")
        self.layout = layout
        self.n_virtual = int(n_virtual)
        self.stream = stream
        self.node_slot: Dict[int, int] = {}
        self.edge_slot: Dict[Tuple[int, int], int] = {}
        # stacks: allocation pops from the end, frees push back
        self._free_nodes: List[int] = list(range(layout.n_slots - 1,
                                                 -1, -1))
        self._free_edges: List[int] = list(range(layout.m_pad - 1,
                                                 -1, -1))
        self._node_edges: Dict[int, Set[Tuple[int, int]]] = {}

    def _where(self) -> str:
        tag = "" if self.stream is None else f"[stream {self.stream}] "
        return f"SlotMap.translate: {tag}"

    @property
    def n_free_nodes(self) -> int:
        return len(self._free_nodes)

    @property
    def n_free_edges(self) -> int:
        return len(self._free_edges)

    def grow(self, new_layout: SparseLayout) -> None:
        """Adopt a grown layout: append the new slots to the free lists
        (existing assignments keep their ids)."""
        if new_layout.n_slots < self.layout.n_slots \
                or new_layout.m_pad < self.layout.m_pad:
            raise ValueError(
                f"SlotMap.grow: ({new_layout.n_slots}, "
                f"{new_layout.m_pad}) shrinks the current capacity "
                f"({self.layout.n_slots}, {self.layout.m_pad})")
        self._free_nodes = list(
            range(new_layout.n_slots - 1, self.layout.n_slots - 1, -1)
        ) + self._free_nodes
        self._free_edges = list(
            range(new_layout.m_pad - 1, self.layout.m_pad - 1, -1)
        ) + self._free_edges
        self.layout = new_layout

    def grow_virtual(self, n_virtual: int) -> None:
        """Raise the virtual addressing bound (a host-only 'repad')."""
        if int(n_virtual) < self.n_virtual:
            raise ValueError(
                f"SlotMap.grow_virtual: n_virtual={n_virtual} shrinks "
                f"the current bound {self.n_virtual}")
        self.n_virtual = int(n_virtual)

    # -- persistence -----------------------------------------------------
    def to_json(self) -> dict:
        """The map as a JSON-serializable dict: capacities, the two
        assignment tables, and the free lists *in stack order* —
        allocation order is part of the translation contract (the
        next join must take the same slot after a round trip), so the
        free lists persist verbatim rather than being re-derived."""
        return {
            "n_slots": int(self.layout.n_slots),
            "m_pad": int(self.layout.m_pad),
            "generation": int(self.layout.generation),
            "n_virtual": int(self.n_virtual),
            "stream": self.stream,
            "node_slot": [[int(v), int(s)]
                          for v, s in sorted(self.node_slot.items())],
            "edge_slot": [[int(lo), int(hi), int(s)]
                          for (lo, hi), s
                          in sorted(self.edge_slot.items())],
            "free_nodes": [int(s) for s in self._free_nodes],
            "free_edges": [int(s) for s in self._free_edges],
        }

    @classmethod
    def from_json(cls, payload: dict) -> "SlotMap":
        """Rebuild a map serialized by `to_json` — assignments, free
        lists (exact order), and the per-node edge index (re-derived
        from the edge table)."""
        layout = SparseLayout(n_slots=int(payload["n_slots"]),
                              m_pad=int(payload["m_pad"]),
                              generation=int(payload["generation"]))
        sm = cls(layout, int(payload["n_virtual"]),
                 stream=payload.get("stream"))
        sm.node_slot = {int(v): int(s)
                        for v, s in payload["node_slot"]}
        sm.edge_slot = {(int(lo), int(hi)): int(s)
                        for lo, hi, s in payload["edge_slot"]}
        sm._free_nodes = [int(s) for s in payload["free_nodes"]]
        sm._free_edges = [int(s) for s in payload["free_edges"]]
        sm._node_edges = {int(v): set() for v in sm.node_slot}
        for key in sm.edge_slot:
            sm._node_edges.setdefault(key[0], set()).add(key)
            sm._node_edges.setdefault(key[1], set()).add(key)
        return sm

    def translate(self, delta: GraphDelta) -> GraphDelta:
        """Virtual-space `GraphDelta` → slot-space delta with edge slots.

        Mirrors the dense gating semantics exactly: joins allocate
        before the edge lanes are resolved, lanes touching an inactive
        (unallocated) node are dropped (they would be gated to zero by
        the dense node mask), leaves free after them. Raises
        `SparseCapacityError` when the node/edge capacity is exhausted
        and `ValueError` for out-of-virtual-space addressing or
        duplicate edge lanes. Equivalent to ``commit(stage(delta))``.
        """
        return self.commit(self.stage(delta))

    def stage(self, delta: GraphDelta) -> "_StagedTranslation":
        """The pure half of `translate`: validate + resolve slots
        without mutating the map. Apply with `commit` (exactly once,
        before any further stage on this map)."""
        where = self._where()
        if delta.edge_slots is not None:
            raise ValueError(
                where + "delta already carries edge_slots; a delta is "
                "translated exactly once")
        if delta.n_nodes > self.n_virtual:
            raise ValueError(
                where + f"delta is addressed in an n_pad="
                f"{delta.n_nodes} virtual space but this stream's bound "
                f"is n_pad={self.n_virtual}; repad the service first")
        senders = np.asarray(delta.senders, np.int64)
        receivers = np.asarray(delta.receivers, np.int64)
        dw = np.asarray(delta.dw, np.float32)
        w_old = np.asarray(delta.w_old, np.float32)
        mask = np.asarray(delta.mask, np.float32)
        k_pad = senders.shape[0]

        valid = mask > 0
        bad = valid & ((np.minimum(senders, receivers) < 0)
                       | (np.maximum(senders, receivers)
                          >= self.n_virtual))
        if bad.any():
            ids = np.unique(np.concatenate(
                [senders[bad], receivers[bad]]))
            ids = [int(i) for i in ids
                   if i < 0 or i >= self.n_virtual]
            raise ValueError(
                where + f"edge endpoint id(s) {ids[:8]} outside the "
                f"n_pad={self.n_virtual} virtual space; re-pad the "
                "stream to a larger n_pad to grow past it")

        joins: List[int] = []
        leaves: List[int] = []
        if delta.node_ids is not None:
            nid = np.asarray(delta.node_ids, np.int64)
            nflag = np.asarray(delta.node_flag, np.float32)
            oob = (nflag != 0) & ((nid < 0) | (nid >= self.n_virtual))
            if oob.any():
                raise ValueError(
                    where + f"join/leave node id(s) "
                    f"{sorted(set(int(i) for i in nid[oob]))} outside "
                    f"the n_pad={self.n_virtual} virtual space")
            joins = [int(i) for i in nid[nflag > 0]]
            leaves = [int(i) for i in nid[nflag < 0]]

        # -- stage (no mutation until everything validates) --------------
        staged_nodes: Dict[int, int] = {}
        for vid in joins:
            if vid in self.node_slot or vid in staged_nodes:
                continue  # re-join of an active node: mask no-op
            idx = len(staged_nodes)
            if idx >= len(self._free_nodes):
                raise SparseCapacityError(
                    where + f"node slots exhausted (n_slots="
                    f"{self.layout.n_slots}, all allocated) while "
                    f"joining node {vid}; grow the capacity "
                    "(FingerService.grow_capacity)")
            staged_nodes[vid] = self._free_nodes[-(1 + idx)]

        def slot_of(vid: int) -> Optional[int]:
            if vid in self.node_slot:
                return self.node_slot[vid]
            return staged_nodes.get(vid)

        out_snd = np.zeros(k_pad, np.int32)
        out_rcv = np.zeros(k_pad, np.int32)
        out_dw = np.zeros(k_pad, np.float32)
        out_wold = np.zeros(k_pad, np.float32)
        out_mask = np.zeros(k_pad, np.float32)
        out_slot = np.full(k_pad, EDGE_SLOT_SENTINEL, np.int32)

        staged_edges: Dict[Tuple[int, int], int] = {}
        deleted: List[Tuple[int, int]] = []
        seen: Set[Tuple[int, int]] = set()
        for lane in range(k_pad):
            if not valid[lane]:
                continue
            lo = int(min(senders[lane], receivers[lane]))
            hi = int(max(senders[lane], receivers[lane]))
            if lo == hi:
                continue  # self-loop: from_arrays drops these already
            s_lo, s_hi = slot_of(lo), slot_of(hi)
            if s_lo is None or s_hi is None:
                # dense semantics: an edge touching an inactive node is
                # gated to exactly zero — drop the lane host-side
                continue
            key = (lo, hi)
            if key in seen:
                raise ValueError(
                    where + f"duplicate edge lane for ({lo}, {hi}) in "
                    "one delta; the slot-addressed edge store cannot "
                    "scatter one slot twice per tick — merge the "
                    "lanes' dw host-side")
            seen.add(key)
            if key in self.edge_slot:
                slot = self.edge_slot[key]
            else:
                idx = len(staged_edges)
                if idx >= len(self._free_edges):
                    raise SparseCapacityError(
                        where + f"edge slots exhausted (m_pad="
                        f"{self.layout.m_pad}, "
                        f"{len(self.edge_slot) + idx} live) while "
                        f"adding edge ({lo}, {hi}); grow the capacity "
                        "(FingerService.grow_capacity)")
                slot = self._free_edges[-(1 + idx)]
                staged_edges[key] = slot
            new_w = float(w_old[lane]) + float(dw[lane])
            if key in self.edge_slot and new_w <= _DELETED_EDGE_TOL * (
                    abs(float(w_old[lane])) + abs(float(dw[lane]))):
                deleted.append(key)
            out_snd[lane] = min(s_lo, s_hi)
            out_rcv[lane] = max(s_lo, s_hi)
            out_dw[lane] = dw[lane]
            out_wold[lane] = w_old[lane]
            out_mask[lane] = 1.0
            out_slot[lane] = slot

        out_nid = out_nflag = None
        if delta.node_ids is not None:
            j_pad = nid.shape[0]
            out_nid = np.zeros(j_pad, np.int32)
            out_nflag = np.zeros(j_pad, np.float32)
            freed_nodes: List[int] = []
            for lane in range(j_pad):
                if nflag[lane] > 0:
                    slot = slot_of(int(nid[lane]))
                    out_nid[lane] = slot
                    out_nflag[lane] = 1.0
                elif nflag[lane] < 0:
                    vid = int(nid[lane])
                    slot = slot_of(vid)
                    if slot is None:
                        continue  # leave of an inactive node: no-op
                    out_nid[lane] = slot
                    out_nflag[lane] = -1.0
                    freed_nodes.append(vid)
        else:
            freed_nodes = []

        # Host (numpy) leaves: the service stacks the B per-stream
        # deltas on the host and moves the tick to the device once.
        slot_delta = GraphDelta(
            senders=out_snd, receivers=out_rcv, dw=out_dw,
            w_old=out_wold, mask=out_mask,
            n_nodes=self.layout.n_slots,
            node_ids=out_nid, node_flag=out_nflag,
            layout_generation=None,
            edge_slots=out_slot,
        )
        return _StagedTranslation(
            delta=slot_delta, staged_nodes=staged_nodes,
            staged_edges=staged_edges, deleted=deleted,
            freed_nodes=freed_nodes)

    def commit(self, staged: "_StagedTranslation") -> GraphDelta:
        """Apply a staged translation to the map and return its
        slot-space delta. The staged slot assignments index this map's
        free lists, so nothing may stage or commit on this map in
        between."""
        staged_nodes = staged.staged_nodes
        staged_edges = staged.staged_edges
        if staged_nodes:
            del self._free_nodes[-len(staged_nodes):]
            for vid, slot in staged_nodes.items():
                self.node_slot[vid] = slot
                self._node_edges.setdefault(vid, set())
        if staged_edges:
            del self._free_edges[-len(staged_edges):]
            for key, slot in staged_edges.items():
                self.edge_slot[key] = slot
                self._node_edges.setdefault(key[0], set()).add(key)
                self._node_edges.setdefault(key[1], set()).add(key)
        for key in staged.deleted:
            self._release_edge(key)
        for vid in staged.freed_nodes:
            for key in list(self._node_edges.get(vid, ())):
                # isolated-leave contract: normally already deleted
                self._release_edge(key)
            self._node_edges.pop(vid, None)
            self._free_nodes.append(self.node_slot.pop(vid))
        return staged.delta

    def _release_edge(self, key: Tuple[int, int]) -> None:
        slot = self.edge_slot.pop(key, None)
        if slot is None:
            return
        self._free_edges.append(slot)
        for vid in key:
            edges = self._node_edges.get(vid)
            if edges is not None:
                edges.discard(key)


@dataclasses.dataclass
class _StagedTranslation:
    """One `SlotMap.stage` result awaiting `commit` (see SlotMap)."""

    delta: GraphDelta
    staged_nodes: Dict[int, int]
    staged_edges: Dict[Tuple[int, int], int]
    deleted: List[Tuple[int, int]]
    freed_nodes: List[int]


# ---------------------------------------------------------------------------
# Construction from host graphs
# ---------------------------------------------------------------------------

Graph = Union[DenseGraph, EdgeList]


def sparse_state_from_graph(
    g: Graph,
    layout: SparseLayout,
    n_virtual: Optional[int] = None,
    stream: Optional[int] = None,
) -> Tuple[SparseStreamState, SlotMap]:
    """Host graph → (slot-space state, its `SlotMap`), one O(n + m) pass.

    Active nodes get slots in ascending virtual-id order, edges in
    (i, j) lexicographic order; the FINGER statistics are computed on
    the slot-space graph directly (relabeling invariance makes them
    exactly the virtual graph's).
    """
    n_virtual = g.n_nodes if n_virtual is None else int(n_virtual)
    if g.n_nodes > n_virtual:
        raise ValueError(
            f"sparse_state_from_graph: graph n_nodes={g.n_nodes} "
            f"exceeds the virtual bound n_virtual={n_virtual}")
    if g.node_mask is None:
        active = np.arange(g.n_nodes, dtype=np.int64)
    else:
        active = np.nonzero(np.asarray(g.node_mask) > 0)[0]
    if active.size > layout.n_slots:
        raise SparseCapacityError(
            f"sparse_state_from_graph: {active.size} active node(s) "
            f"exceed n_slots={layout.n_slots}; use a larger capacity")
    if isinstance(g, EdgeList):
        # Straight from the edge arrays (one entry per undirected edge,
        # senders < receivers): no n x n densification, so a wide
        # virtual id space costs O(n + m) host work.
        vals = np.asarray(g.masked_weights(), np.float32)
        nz = vals != 0.0
        iu = np.asarray(g.senders, np.int64)[nz]
        ju = np.asarray(g.receivers, np.int64)[nz]
        order = np.lexsort((ju, iu))
        iu, ju, vals = iu[order], ju[order], vals[nz][order]
    else:
        w = np.asarray(g.masked_weights(), np.float32)
        iu, ju = np.triu_indices(g.n_nodes, k=1)
        vals = w[iu, ju]
        nz = vals != 0.0
        iu, ju, vals = iu[nz], ju[nz], vals[nz]
    if iu.size > layout.m_pad:
        raise SparseCapacityError(
            f"sparse_state_from_graph: {iu.size} edge(s) exceed "
            f"m_pad={layout.m_pad}; use a larger capacity")

    slot_map = SlotMap(layout, n_virtual, stream=stream)
    for vid in active:
        slot_map.node_slot[int(vid)] = slot_map._free_nodes.pop()
        slot_map._node_edges.setdefault(int(vid), set())
    snd = np.zeros(iu.size, np.int32)
    rcv = np.zeros(iu.size, np.int32)
    ew = np.zeros(layout.m_pad, np.float32)
    for lane in range(iu.size):
        key = (int(iu[lane]), int(ju[lane]))
        slot = slot_map._free_edges.pop()
        slot_map.edge_slot[key] = slot
        slot_map._node_edges[key[0]].add(key)
        slot_map._node_edges[key[1]].add(key)
        a, b = slot_map.node_slot[key[0]], slot_map.node_slot[key[1]]
        snd[lane], rcv[lane] = min(a, b), max(a, b)
        ew[slot] = vals[lane]

    slot_mask = np.zeros(layout.n_slots, np.float32)
    for vid in active:
        slot_mask[slot_map.node_slot[int(vid)]] = 1.0
    el = EdgeList.from_arrays(
        snd, rcv, vals, n_nodes=layout.n_slots,
        m_pad=max(int(iu.size), 1), n_pad=layout.n_slots,
        node_mask=slot_mask)
    fs = host_finger_state(el)
    state = SparseStreamState(
        q=jnp.asarray(fs.q), s_total=jnp.asarray(fs.s_total),
        s_max=jnp.asarray(fs.s_max), strengths=jnp.asarray(fs.strengths),
        node_mask=jnp.asarray(slot_mask), edge_weights=jnp.asarray(ew),
        layout=layout)
    return state, slot_map


def sparse_states_from_graphs(
    graphs: Sequence[Graph],
    layout: SparseLayout,
    n_virtual: int,
) -> Tuple[SparseStreamState, List[SlotMap]]:
    """B host graphs → stacked (B, …) sparse state + per-stream maps."""
    pairs = [sparse_state_from_graph(g, layout, n_virtual=n_virtual,
                                     stream=i)
             for i, g in enumerate(graphs)]
    if not pairs:
        raise ValueError("sparse_states_from_graphs: empty stream list")
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[s for s, _ in pairs])
    return stacked, [m for _, m in pairs]
