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
from typing import Dict, List, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.jsdist import js_from_increments
from repro.core.incremental import delta_moments, update_state
from repro.core.state import FingerState
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

# An undirected edge (lo, hi) of the virtual space is keyed
# ``lo << 32 | hi`` (node ids are int32).
_KEY_SHIFT = 32
_KEY_MASK = (1 << _KEY_SHIFT) - 1


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
    plus the O(n_slots) strength carry, scored from the tick's
    increments (`core.jsdist.js_divergence_from_increments`) — then the
    edge-store scatter. The single-stream oracle of
    `repro.kernels.sparse_tick`.
    """
    _require_slot_delta(state, delta, "sparse_jsdist_tick")
    view = state.dense_view()
    half = update_state(view, delta.scaled(0.5), exact_smax=exact_smax,
                        method=method)
    full = update_state(view, delta, exact_smax=exact_smax,
                        method=method)
    dist = js_from_increments(view, half, full,
                              *delta_moments(view, delta, method))
    ew = _advance_edge_store(state, delta, full.s_total)
    return dist, SparseStreamState(
        q=full.q, s_total=full.s_total, s_max=full.s_max,
        strengths=full.strengths, node_mask=full.node_mask,
        edge_weights=ew, layout=state.layout)


# ---------------------------------------------------------------------------
# Host-side virtual-id -> slot translation
# ---------------------------------------------------------------------------


class _EdgeIndex:
    """Open-addressed index from edge key to edge slot (linear probing).

    The buckets hold edge slots (int32); a bucket's key is read from the
    map's slot-to-key array, so the index costs 4 bytes a bucket. It is
    sized for the edge capacity (``m_pad`` at most 70% full) and rebuilt
    in place when the capacity grows, when live and released buckets
    (tombstones) fill it past 85%, or when tombstones outnumber a quarter
    of the live keys: a probe walks past every tombstone on its path, so
    under steady deletes and adds they would lengthen every lookup.
    Every operation works on a whole batch of keys at once, one probe
    round per numpy pass; a lookup also notes where each absent key
    would go, so its insertion need not probe again. ``rebuilds`` and
    ``probe_rounds`` count the rebuilds and `find`'s probe rounds so far.
    """

    _EMPTY = -1
    _TOMB = -2
    _MAX_LOAD = 0.7
    _CROWDED = 0.85
    _LIVE_PER_TOMB = 4
    _MULT = np.uint64(0x9E3779B97F4A7C15)

    def __init__(self, capacity: int):
        self.rebuilds = 0
        self.probe_rounds = 0
        self._clear(capacity)

    def _clear(self, capacity: int) -> None:
        bits = max(4, int(np.ceil(np.log2(max(capacity, 1)
                                          / self._MAX_LOAD))))
        self.bits = bits
        self.table = np.full(1 << bits, self._EMPTY, np.int32)
        self.live = 0
        self.tombs = 0

    def rebuild(self, capacity: int, keys: np.ndarray,
                slots: np.ndarray) -> None:
        """Empty the index, sized for ``capacity``, and insert the live
        ``keys`` at ``slots``: no tombstones are left."""
        self._clear(capacity)
        self.insert(keys, slots)
        self.rebuilds += 1

    @property
    def nbytes(self) -> int:
        return int(self.table.nbytes)

    def _home(self, keys: np.ndarray) -> np.ndarray:
        h = keys.astype(np.uint64) * self._MULT
        return (h >> np.uint64(64 - self.bits)).astype(np.int64)

    def find(self, keys: np.ndarray, slot_key: np.ndarray):
        """(slots, buckets, free) of ``keys``: a present key's slot and
        bucket, -1 for an absent one; an absent key's first free bucket
        on its probe path, where `insert` may put it, -1 for a present
        one."""
        n = keys.shape[0]
        slots = np.full(n, -1, np.int32)
        where = np.full(n, -1, np.int64)
        free = np.full(n, -1, np.int64)
        todo = np.arange(n)
        pos = self._home(keys)
        want = keys
        mask = (1 << self.bits) - 1
        while todo.size:
            self.probe_rounds += 1
            b = self.table[pos]
            live = b >= 0
            hit = live & (slot_key[np.where(live, b, 0)] == want)
            slots[todo[hit]] = b[hit]
            where[todo[hit]] = pos[hit]
            opened = ~live
            if self.tombs:  # the first tombstone, else the closing empty
                opened &= free[todo] < 0
            free[todo[opened]] = pos[opened]
            go = ~hit & (b != self._EMPTY)
            todo, pos, want = todo[go], (pos[go] + 1) & mask, want[go]
        free[slots >= 0] = -1
        return slots, where, free

    def insert(self, keys: np.ndarray, slots: np.ndarray,
               at: Optional[np.ndarray] = None) -> None:
        """Add distinct ``keys`` (absent from the index) at ``slots``:
        each at the first free bucket from its home on, or from its
        bucket in ``at`` on (`find`'s free buckets, with nothing
        inserted since; keys of one batch that meet there go in turn)."""
        mask = (1 << self.bits) - 1
        pos = self._home(keys) if at is None else at
        slots = slots.astype(np.int32)
        while pos.size:
            b = self.table[pos]
            free = b < 0
            cand = pos[free]
            self.table[cand] = slots[free]
            won = np.zeros(pos.size, bool)
            won[free] = self.table[cand] == slots[free]
            self.tombs -= int(np.count_nonzero(b[won] == self._TOMB))
            go = ~won
            pos, slots = (pos[go] + 1) & mask, slots[go]
        self.live += int(keys.shape[0])

    def release(self, buckets: np.ndarray) -> None:
        self.table[buckets] = self._TOMB
        self.live -= int(buckets.shape[0])
        self.tombs += int(buckets.shape[0])

    def crowded(self, adding: int) -> bool:
        return (self.live + self.tombs + adding) \
            > self._CROWDED * self.table.shape[0]

    def tombstoned(self) -> bool:
        return self.tombs > self.live // self._LIVE_PER_TOMB


class _SlotStack:
    """A free list as an int32 stack: allocation takes from the top,
    frees push on top, a capacity grow slides the new slots in at the
    bottom (they are used last)."""

    def __init__(self, capacity: int):
        self.items = np.arange(capacity - 1, -1, -1, dtype=np.int32)
        self.top = capacity

    @classmethod
    def of(cls, capacity: int, items) -> "_SlotStack":
        st = cls.__new__(cls)
        st.items = np.zeros(capacity, np.int32)
        items = np.asarray(items, np.int32)
        st.items[:items.size] = items
        st.top = int(items.size)
        return st

    def peek(self, count: int) -> np.ndarray:
        """The next ``count`` slots, in the order they are taken."""
        return self.items[self.top - count:self.top][::-1].copy()

    def take(self, count: int) -> None:
        self.top -= count

    def push(self, slots: np.ndarray) -> None:
        self.items[self.top:self.top + slots.size] = slots
        self.top += int(slots.size)

    def grow(self, old: int, new: int) -> None:
        items = np.zeros(new, np.int32)
        added = new - old
        items[:added] = np.arange(new - 1, old - 1, -1, dtype=np.int32)
        items[added:added + self.top] = self.items[:self.top]
        self.items, self.top = items, self.top + added

    def as_array(self) -> np.ndarray:
        return self.items[:self.top].copy()


def _edge_keys(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """One int64 key per undirected edge, ordered as (lo, hi) is."""
    return (lo.astype(np.int64) << _KEY_SHIFT) | hi.astype(np.int64)


class SlotMap:
    """Per-stream host translator from virtual node ids to device slots.

    Owns the allocation discipline of one stream's slot space: node
    slots are allocated on join and freed on leave, edge slots are
    allocated the first time an edge appears and freed when a delta
    deletes it (post-delta weight ≈ 0) or its endpoint leaves (a
    leaving node's remaining edges go in ascending (lo, hi) order). All
    frees/allocations commit only after the whole delta validates, so a
    rejected delta never corrupts the map — and freed slots are not
    reused within the same delta (a single tick's scatter must never
    write one slot twice).

    Array-backed, so a delta's lanes resolve in a few numpy passes and
    a graph of tens of millions of edges fits: node id → slot is an
    int32 array over ``n_virtual`` (beside its inverse and each node
    slot's live-edge count), edge slot → key ``lo << 32 | hi`` an int64
    array over ``m_pad``, key → edge slot a `_EdgeIndex`, and the
    two free lists int32 stacks. About 39 bytes of host memory an edge
    when half the edge capacity is live.

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
        self._slot_of_vid = np.full(self.n_virtual, -1, np.int32)
        self._vid_of_slot = np.full(layout.n_slots, -1, np.int32)
        self._degree = np.zeros(layout.n_slots, np.int32)
        self._key_of_slot = np.full(layout.m_pad, -1, np.int64)
        self._free_nodes = _SlotStack(layout.n_slots)
        self._free_edges = _SlotStack(layout.m_pad)
        self._index = _EdgeIndex(layout.m_pad)

    def _where(self) -> str:
        tag = "" if self.stream is None else f"[stream {self.stream}] "
        return f"SlotMap.translate: {tag}"

    @property
    def n_free_nodes(self) -> int:
        return self._free_nodes.top

    @property
    def n_free_edges(self) -> int:
        return self._free_edges.top

    @property
    def n_live_edges(self) -> int:
        return self.layout.m_pad - self._free_edges.top

    @property
    def nbytes(self) -> int:
        """Host bytes the map's arrays hold."""
        return int(self._slot_of_vid.nbytes + self._vid_of_slot.nbytes
                   + self._degree.nbytes + self._key_of_slot.nbytes
                   + self._free_nodes.items.nbytes
                   + self._free_edges.items.nbytes + self._index.nbytes)

    def grow(self, new_layout: SparseLayout) -> None:
        """Adopt a grown layout: the new slots go to the bottom of the
        free lists (existing assignments keep their ids)."""
        old = self.layout
        if new_layout.n_slots < old.n_slots \
                or new_layout.m_pad < old.m_pad:
            raise ValueError(
                f"SlotMap.grow: ({new_layout.n_slots}, "
                f"{new_layout.m_pad}) shrinks the current capacity "
                f"({old.n_slots}, {old.m_pad})")
        self._free_nodes.grow(old.n_slots, new_layout.n_slots)
        self._free_edges.grow(old.m_pad, new_layout.m_pad)
        self._vid_of_slot = _extend(self._vid_of_slot, new_layout.n_slots,
                                    -1)
        self._degree = _extend(self._degree, new_layout.n_slots, 0)
        self._key_of_slot = _extend(self._key_of_slot, new_layout.m_pad,
                                    -1)
        self.layout = new_layout
        if new_layout.m_pad != old.m_pad:
            self._rebuild_index()

    def grow_virtual(self, n_virtual: int) -> None:
        """Raise the virtual addressing bound (a host-only 'repad')."""
        if int(n_virtual) < self.n_virtual:
            raise ValueError(
                f"SlotMap.grow_virtual: n_virtual={n_virtual} shrinks "
                f"the current bound {self.n_virtual}")
        self.n_virtual = int(n_virtual)
        self._slot_of_vid = _extend(self._slot_of_vid, self.n_virtual, -1)

    @property
    def index_counters(self) -> Tuple[int, int]:
        """The edge index's (rebuilds, probe rounds) since the map was
        made or restored."""
        return self._index.rebuilds, self._index.probe_rounds

    def _rebuild_index(self) -> None:
        slots = np.nonzero(self._key_of_slot >= 0)[0]
        self._index.rebuild(self.layout.m_pad, self._key_of_slot[slots],
                            slots)

    def admit(self, vids: np.ndarray, lo: np.ndarray,
              hi: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Allocate slots for an admitted graph, in the order given:
        its active nodes ``vids`` (inactive so far), then its distinct
        edges (``lo`` < ``hi``, both ends among ``vids``). Returns
        (node slots, edge slots); the caller checks capacity."""
        nodes = self._free_nodes.peek(vids.size)
        self._free_nodes.take(vids.size)
        self._slot_of_vid[vids] = nodes
        self._vid_of_slot[nodes] = vids
        edges = self._free_edges.peek(lo.size)
        self._free_edges.take(lo.size)
        keys = _edge_keys(lo, hi)
        self._key_of_slot[edges] = keys
        for ends in (lo, hi):
            self._degree += np.bincount(
                self._slot_of_vid[ends],
                minlength=self.layout.n_slots).astype(np.int32)
        self._index.insert(keys, edges)
        return nodes, edges

    # -- persistence -----------------------------------------------------
    def header(self) -> dict:
        """The map's sizes, JSON-serializable; `arrays` holds the rest."""
        return {"n_slots": int(self.layout.n_slots),
                "m_pad": int(self.layout.m_pad),
                "generation": int(self.layout.generation),
                "n_virtual": int(self.n_virtual),
                "stream": self.stream}

    def arrays(self) -> Dict[str, np.ndarray]:
        """The map's state as arrays: each node slot's virtual id and
        each edge slot's key (-1 where free), and the free lists in
        stack order — allocation order is part of the translation
        contract (the next join must take the same slot after a round
        trip), so the free lists persist verbatim."""
        return {"vid_of_slot": self._vid_of_slot.copy(),
                "key_of_slot": self._key_of_slot.copy(),
                "free_nodes": self._free_nodes.as_array(),
                "free_edges": self._free_edges.as_array()}

    @classmethod
    def from_arrays(cls, header: dict,
                    arrays: Mapping[str, np.ndarray]) -> "SlotMap":
        """Rebuild a map saved as `header` and `arrays`."""
        layout = SparseLayout(n_slots=int(header["n_slots"]),
                              m_pad=int(header["m_pad"]),
                              generation=int(header["generation"]))
        sm = cls.__new__(cls)
        sm.layout = layout
        sm.n_virtual = int(header["n_virtual"])
        sm.stream = header.get("stream")
        sm._vid_of_slot = np.asarray(arrays["vid_of_slot"], np.int32).copy()
        sm._key_of_slot = np.asarray(arrays["key_of_slot"], np.int64).copy()
        sm._free_nodes = _SlotStack.of(layout.n_slots,
                                       arrays["free_nodes"])
        sm._free_edges = _SlotStack.of(layout.m_pad, arrays["free_edges"])
        sm._slot_of_vid = np.full(sm.n_virtual, -1, np.int32)
        slots = np.nonzero(sm._vid_of_slot >= 0)[0]
        sm._slot_of_vid[sm._vid_of_slot[slots]] = slots
        sm._degree = np.zeros(layout.n_slots, np.int32)
        edges = np.nonzero(sm._key_of_slot >= 0)[0]
        keys = sm._key_of_slot[edges]
        for ends in (keys >> _KEY_SHIFT, keys & _KEY_MASK):
            sm._degree += np.bincount(
                sm._slot_of_vid[ends],
                minlength=layout.n_slots).astype(np.int32)
        sm._index = _EdgeIndex(layout.m_pad)
        sm._index.insert(keys, edges)
        return sm

    @classmethod
    def from_json(cls, payload: dict) -> "SlotMap":
        """Rebuild a map from the single JSON payload older checkpoints
        hold: its sizes, ``node_slot`` as [vid, slot] and ``edge_slot``
        as [lo, hi, slot] entries, and the free lists in stack order."""
        n_slots, m_pad = int(payload["n_slots"]), int(payload["m_pad"])
        vid_of_slot = np.full(n_slots, -1, np.int32)
        nodes = np.asarray(payload["node_slot"], np.int64).reshape(-1, 2)
        vid_of_slot[nodes[:, 1]] = nodes[:, 0]
        key_of_slot = np.full(m_pad, -1, np.int64)
        edges = np.asarray(payload["edge_slot"], np.int64).reshape(-1, 3)
        key_of_slot[edges[:, 2]] = _edge_keys(edges[:, 0], edges[:, 1])
        return cls.from_arrays(payload, {
            "vid_of_slot": vid_of_slot, "key_of_slot": key_of_slot,
            "free_nodes": payload["free_nodes"],
            "free_edges": payload["free_edges"]})

    def to_virtual(self, row: np.ndarray, n_nodes: int) -> np.ndarray:
        """A per-slot ``row`` gathered into virtual ids ``0 .. n_nodes
        - 1``: each allocated node's value at its id, 0 elsewhere."""
        out = np.zeros((n_nodes,), np.asarray(row).dtype)
        slots = np.nonzero((self._vid_of_slot >= 0)
                           & (self._vid_of_slot < n_nodes))[0]
        out[self._vid_of_slot[slots]] = np.asarray(row)[slots]
        return out

    ARRAYS = ("vid_of_slot", "key_of_slot", "free_nodes", "free_edges")

    @classmethod
    def restore(cls, payload: dict,
                arrays: Optional[Mapping[str, np.ndarray]] = None
                ) -> "SlotMap":
        """A map from a checkpoint: its `header` and `arrays`, or the
        single JSON payload older checkpoints hold."""
        if "node_slot" in payload:
            return cls.from_json(payload)
        if arrays is None:
            raise ValueError("SlotMap.restore: the payload holds no "
                             "assignments and no arrays were given")
        return cls.from_arrays(payload, arrays)

    # -- translation -----------------------------------------------------
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

    def _slots_of(self, vids: np.ndarray, new_vids: np.ndarray,
                  new_slots: np.ndarray) -> np.ndarray:
        """Node slots of in-range ``vids``, counting staged joins;
        -1 for an inactive node."""
        slots = self._slot_of_vid[vids]
        if new_vids.size:
            miss = np.nonzero(slots < 0)[0]
            order = np.argsort(new_vids)
            idx = np.minimum(np.searchsorted(new_vids[order], vids[miss]),
                             new_vids.size - 1)
            hit = new_vids[order][idx] == vids[miss]
            slots[miss[hit]] = new_slots[order][idx[hit]]
        return slots

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

        new_vids = np.zeros(0, np.int64)
        if delta.node_ids is not None:
            nid = np.asarray(delta.node_ids, np.int64)
            nflag = np.asarray(delta.node_flag, np.float32)
            oob = (nflag != 0) & ((nid < 0) | (nid >= self.n_virtual))
            if oob.any():
                raise ValueError(
                    where + f"join/leave node id(s) "
                    f"{sorted(set(int(i) for i in nid[oob]))} outside "
                    f"the n_pad={self.n_virtual} virtual space")
            joins = nid[nflag > 0]
            # a re-join of an active node is a mask no-op
            first = np.zeros(joins.size, bool)
            first[np.unique(joins, return_index=True)[1]] = True
            new_vids = joins[first & (self._slot_of_vid[joins] < 0)]
            if new_vids.size > self.n_free_nodes:
                raise SparseCapacityError(
                    where + f"node slots exhausted (n_slots="
                    f"{self.layout.n_slots}, all allocated) while "
                    f"joining node {int(new_vids[self.n_free_nodes])}; "
                    "grow the capacity (FingerService.grow_capacity)")
        new_nodes = self._free_nodes.peek(new_vids.size)

        # -- edge lanes: drop padding, self-loops and lanes touching an
        # inactive node (the dense node mask gates them to exactly zero)
        lanes = np.nonzero(valid)[0]
        lo = np.minimum(senders[lanes], receivers[lanes])
        hi = np.maximum(senders[lanes], receivers[lanes])
        s_lo = self._slots_of(lo, new_vids, new_nodes)
        s_hi = self._slots_of(hi, new_vids, new_nodes)
        keep = (lo != hi) & (s_lo >= 0) & (s_hi >= 0)
        lanes, lo, hi, s_lo, s_hi = (x[keep] for x in
                                     (lanes, lo, hi, s_lo, s_hi))
        keys = _edge_keys(lo, hi)
        order = np.argsort(keys, kind="stable")
        later = np.zeros(keys.size, bool)
        later[order[1:][keys[order[1:]] == keys[order[:-1]]]] = True
        slots, buckets, free = self._index.find(keys, self._key_of_slot)
        fresh = (slots < 0) & ~later
        over = np.nonzero(fresh & (np.cumsum(fresh)
                                   > self.n_free_edges))[0]
        first_over = int(over[0]) if over.size else keys.size
        first_dup = int(np.argmax(later)) if later.any() else keys.size
        if first_dup < first_over:
            raise ValueError(
                where + f"duplicate edge lane for ({int(lo[first_dup])}, "
                f"{int(hi[first_dup])}) in one delta; the slot-addressed "
                "edge store cannot scatter one slot twice per tick — "
                "merge the lanes' dw host-side")
        if first_over < keys.size:
            raise SparseCapacityError(
                where + f"edge slots exhausted (m_pad="
                f"{self.layout.m_pad}, "
                f"{self.n_live_edges + self.n_free_edges} live) while "
                f"adding edge ({int(lo[first_over])}, "
                f"{int(hi[first_over])}); grow the capacity "
                "(FingerService.grow_capacity)")
        new_edges = self._free_edges.peek(int(np.count_nonzero(fresh)))
        slots[fresh] = new_edges
        w0 = w_old[lanes].astype(np.float64)
        d = dw[lanes].astype(np.float64)
        gone = (~fresh) & (w0 + d <= _DELETED_EDGE_TOL
                           * (np.abs(w0) + np.abs(d)))

        out_snd = np.zeros(k_pad, np.int32)
        out_rcv = np.zeros(k_pad, np.int32)
        out_dw = np.zeros(k_pad, np.float32)
        out_wold = np.zeros(k_pad, np.float32)
        out_mask = np.zeros(k_pad, np.float32)
        out_slot = np.full(k_pad, EDGE_SLOT_SENTINEL, np.int32)
        out_snd[lanes] = np.minimum(s_lo, s_hi)
        out_rcv[lanes] = np.maximum(s_lo, s_hi)
        out_dw[lanes] = dw[lanes]
        out_wold[lanes] = w_old[lanes]
        out_mask[lanes] = 1.0
        out_slot[lanes] = slots

        out_nid = out_nflag = None
        freed = np.zeros(0, np.int64)
        if delta.node_ids is not None:
            out_nid = np.zeros(nid.shape[0], np.int32)
            out_nflag = np.zeros(nid.shape[0], np.float32)
            at = np.nonzero(nflag > 0)[0]
            out_nid[at] = self._slots_of(nid[at], new_vids, new_nodes)
            out_nflag[at] = 1.0
            at = np.nonzero(nflag < 0)[0]
            left = self._slots_of(nid[at], new_vids, new_nodes)
            at, left = at[left >= 0], left[left >= 0]  # inactive: no-op
            out_nid[at] = left
            out_nflag[at] = -1.0
            freed = nid[at]
            freed = freed[np.sort(np.unique(freed, return_index=True)[1])]

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
            delta=slot_delta, node_vids=new_vids, node_slots=new_nodes,
            edge_keys=keys[fresh], edge_slots=new_edges,
            edge_buckets=free[fresh],
            edge_ends=np.concatenate([s_lo[fresh], s_hi[fresh]]),
            gone_slots=slots[gone], gone_buckets=buckets[gone],
            gone_ends=np.concatenate([s_lo[gone], s_hi[gone]]),
            freed_vids=freed)

    def commit(self, staged: "_StagedTranslation") -> GraphDelta:
        """Apply a staged translation to the map and return its
        slot-space delta. The staged slot assignments index this map's
        free lists, so nothing may stage or commit on this map in
        between."""
        if staged.node_vids.size:
            self._free_nodes.take(staged.node_vids.size)
            self._slot_of_vid[staged.node_vids] = staged.node_slots
            self._vid_of_slot[staged.node_slots] = staged.node_vids
        if staged.edge_slots.size:
            self._free_edges.take(staged.edge_slots.size)
            self._key_of_slot[staged.edge_slots] = staged.edge_keys
            np.add.at(self._degree, staged.edge_ends, np.int32(1))
        if staged.gone_slots.size:
            self._index.release(staged.gone_buckets)
            self._drop_edges(staged.gone_slots, staged.gone_ends)
        if staged.edge_slots.size:
            if self._index.crowded(staged.edge_slots.size):
                self._rebuild_index()
            else:
                self._index.insert(staged.edge_keys, staged.edge_slots,
                                   at=staged.edge_buckets)
        freed = staged.freed_vids
        if freed.size:
            slots = self._slot_of_vid[freed]
            if self._degree[slots].any():
                self._release_edges_of(freed)
            self._slot_of_vid[freed] = -1
            self._vid_of_slot[slots] = -1
            self._free_nodes.push(slots)
        if self._index.tombstoned():
            self._rebuild_index()
        return staged.delta

    def _drop_edges(self, slots: np.ndarray, ends: np.ndarray) -> None:
        """Free edge ``slots`` (pushed in the order given); ``ends``
        are their endpoints' node slots."""
        self._key_of_slot[slots] = -1
        np.subtract.at(self._degree, ends, np.int32(1))
        self._free_edges.push(slots.astype(np.int32))

    def _release_edges_of(self, vids: np.ndarray) -> None:
        """Free every live edge of the distinct leaving nodes ``vids``
        (isolated-leave contract: normally already deleted): node by
        node in the order given, each node's edges in ascending key
        order, an edge between two of them with the first. One pass
        over the edge slots for the whole delta."""
        slots = np.nonzero(self._key_of_slot >= 0)[0]
        keys = self._key_of_slot[slots]
        lo, hi = keys >> _KEY_SHIFT, keys & _KEY_MASK
        rank = np.full(self.n_virtual, vids.size, np.int32)
        rank[vids] = np.arange(vids.size, dtype=np.int32)
        owner = np.minimum(rank[lo], rank[hi])
        mine = np.nonzero(owner < vids.size)[0]
        mine = mine[np.lexsort((keys[mine], owner[mine]))]
        slots, keys, lo, hi = slots[mine], keys[mine], lo[mine], hi[mine]
        _, buckets, _ = self._index.find(keys, self._key_of_slot)
        self._index.release(buckets)
        self._drop_edges(slots, np.concatenate(
            [self._slot_of_vid[lo], self._slot_of_vid[hi]]))


def _extend(a: np.ndarray, size: int, fill) -> np.ndarray:
    if size == a.shape[0]:
        return a
    out = np.full(size, fill, a.dtype)
    out[:a.shape[0]] = a
    return out


@dataclasses.dataclass
class _StagedTranslation:
    """One `SlotMap.stage` result awaiting `commit` (see SlotMap): the
    joining nodes and new edges with the slots they take (and the index
    buckets the edges go to), the deleted edges' slots and index
    buckets, the leaving nodes; ``*_ends`` are
    node slots of the edges' two endpoints (lo ends, then hi ends)."""

    delta: GraphDelta
    node_vids: np.ndarray
    node_slots: np.ndarray
    edge_keys: np.ndarray
    edge_slots: np.ndarray
    edge_buckets: np.ndarray
    edge_ends: np.ndarray
    gone_slots: np.ndarray
    gone_buckets: np.ndarray
    gone_ends: np.ndarray
    freed_vids: np.ndarray


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
    (i, j) lexicographic order, each set in a few numpy passes; the
    FINGER statistics are computed on the slot-space graph directly
    (relabeling invariance makes them exactly the virtual graph's), in
    float64 and rounded to the state's float32.
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
        vals = vals[nz]
        keys = _edge_keys(iu, ju)
        if np.any(keys[1:] <= keys[:-1]):  # already in order: no sort
            order = np.argsort(keys, kind="stable")
            iu, ju, vals = iu[order], ju[order], vals[order]
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
    node_slots, edge_slots = slot_map.admit(active, iu, ju)
    a = slot_map._slot_of_vid[iu]
    b = slot_map._slot_of_vid[ju]
    ew = np.zeros(layout.m_pad, np.float32)
    ew[edge_slots] = vals
    slot_mask = np.zeros(layout.n_slots, np.float32)
    slot_mask[node_slots] = 1.0
    # Lemma 1 in float64 numpy, one pass over the edges, then rounded
    w = vals.astype(np.float64)
    strengths = np.bincount(a, w, layout.n_slots) \
        + np.bincount(b, w, layout.n_slots)
    s_total = strengths.sum()
    c = 1.0 / s_total if s_total > 0 else 0.0
    q = 1.0 - c * c * (strengths @ strengths + 2.0 * (w @ w))
    state = SparseStreamState(
        q=jnp.asarray(q, jnp.float32),
        s_total=jnp.asarray(s_total, jnp.float32),
        s_max=jnp.asarray(strengths.max(), jnp.float32),
        strengths=jnp.asarray(strengths.astype(np.float32)),
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
