"""The dict-backed `SlotMap` the array-backed one in `repro.core.sparse`
replaced, kept as the plain reference its equivalence tests drive.

Per-edge Python dicts and sets and Python-list free stacks: the same
allocation contract, spelled one lane at a time. One change from the
served original: a leaving node's remaining edges are released in
ascending (lo, hi) order (the original walked a set, in hash order), the
order the array map documents.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from repro.core.sparse import (
    EDGE_SLOT_SENTINEL,
    SparseCapacityError,
    SparseLayout,
    _DELETED_EDGE_TOL,
)
from repro.graphs.types import EdgeList, GraphDelta

class DictSlotMap:
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
    def from_json(cls, payload: dict) -> "DictSlotMap":
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
            for key in sorted(self._node_edges.get(vid, ())):
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


def dict_admit(g, layout: SparseLayout, n_virtual: Optional[int] = None,
               stream: Optional[int] = None) -> DictSlotMap:
    """The map the original admission built for host graph ``g``:
    active nodes get slots in ascending virtual-id order, edges in
    (i, j) lexicographic order, one Python step each."""
    n_virtual = g.n_nodes if n_virtual is None else int(n_virtual)
    if g.node_mask is None:
        active = np.arange(g.n_nodes, dtype=np.int64)
    else:
        active = np.nonzero(np.asarray(g.node_mask) > 0)[0]
    if isinstance(g, EdgeList):
        vals = np.asarray(g.masked_weights(), np.float32)
        nz = vals != 0.0
        iu = np.asarray(g.senders, np.int64)[nz]
        ju = np.asarray(g.receivers, np.int64)[nz]
        order = np.lexsort((ju, iu))
        iu, ju = iu[order], ju[order]
    else:
        w = np.asarray(g.masked_weights(), np.float32)
        iu, ju = np.triu_indices(g.n_nodes, k=1)
        nz = w[iu, ju] != 0.0
        iu, ju = iu[nz], ju[nz]
    slot_map = DictSlotMap(layout, n_virtual, stream=stream)
    for vid in active:
        slot_map.node_slot[int(vid)] = slot_map._free_nodes.pop()
        slot_map._node_edges.setdefault(int(vid), set())
    for lane in range(iu.size):
        key = (int(iu[lane]), int(ju[lane]))
        slot_map.edge_slot[key] = slot_map._free_edges.pop()
        slot_map._node_edges[key[0]].add(key)
        slot_map._node_edges[key[1]].add(key)
    return slot_map


def as_json(sm) -> dict:
    """An array-backed map in the JSON form `DictSlotMap.to_json` writes
    (the single payload older checkpoints hold), read from its `header`
    and `arrays`: what the two maps are compared by."""
    arrays = sm.arrays()
    out = dict(sm.header())
    vids = arrays["vid_of_slot"]
    slots = np.nonzero(vids >= 0)[0]
    out["node_slot"] = sorted([int(v), int(s)]
                              for v, s in zip(vids[slots], slots))
    keys = arrays["key_of_slot"]
    slots = np.nonzero(keys >= 0)[0]
    out["edge_slot"] = sorted([int(k >> 32), int(k & 0xFFFFFFFF), int(s)]
                              for k, s in zip(keys[slots], slots))
    out["free_nodes"] = arrays["free_nodes"].tolist()
    out["free_edges"] = arrays["free_edges"].tolist()
    return out

