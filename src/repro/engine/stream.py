"""StreamEngine: B independent FINGER streams advanced in lockstep.

.. deprecated::
    `StreamEngine` is now the *plan-internal executor* of
    `repro.serving.FingerService`, which states placement, ingestion,
    checkpointing, and top-k query policy once in a declarative
    `ServiceConfig` instead of per call site. The class stays fully
    API-compatible for existing callers; new serving code should open a
    `FingerService` (migration note in `examples/README.md`).

The ROADMAP serving target is millions of users, each with their own
evolving graph (session interaction graph, per-tenant topology, …). The
per-stream state of Algorithm 2 is tiny — (Q, S, s_max) plus the (n,)
strengths and node mask — so thousands of streams fit on one device as a
stacked `FingerState` with a leading batch axis. Each serving tick
applies one `GraphDelta` per stream:

  tick      : vmapped `jsdist_incremental` over the B axis — one fused
              XLA computation instead of B Python-loop dispatches;
  run       : `lax.scan` of the vmapped tick over a (T, B, …) delta
              sequence — the whole online loop in one XLA program;
  tick_sharded : the same tick under `shard_map`, streams sharded over
              the mesh "data" axis. Streams are independent, so the body
              needs zero collectives — scaling to a pod is embarrassing.

Variable-topology batches: streams do NOT need to share a true node
count. `init_states` embeds every host graph into one shared static
layout size `n_pad` and gives each stream a dynamic (n_pad,) node mask;
inactive slots contribute exactly zero to every statistic, so each
stream's H̃/JSdist equals its own unpadded FINGER value while the whole
heterogeneous batch runs one compiled (B, n_pad, k_pad) program. Node
joins/leaves are per-stream `GraphDelta` node slots, so tenants can grow
and shrink mid-stream without recompilation.

Restartable serving: `save`/`restore` persist the stacked state through
`train.checkpoint` (atomic tmp-dir + rename writes; restore gathers to
host and re-shards onto whatever mesh the new job runs), so a serving
restart resumes scores exactly instead of replaying every stream.

All entry points are jit-compiled once per (B, n_pad, k_pad) shape; the
stream synthesizers' common `k_pad` keeps that a single compilation.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.jsdist import jsdist_incremental
from repro.core.state import FingerState, finger_state
from repro.graphs.layout import NodeLayout
from repro.graphs.types import GraphDelta
from repro.train.checkpoint import (
    latest_checkpoint,
    load_arrays,
    load_manifest,
    restore_checkpoint,
    save_checkpoint,
)


def _check_consistent(label: str, kind: str, values) -> None:
    """Raise naming the offending streams when a static field disagrees.

    Without this, `jnp.stack`/`tree_map` dies with an opaque pytree
    structure error that names no stream at all.
    """
    values = list(values)
    if not values:
        raise ValueError(f"{label}: empty stream list")
    majority = max(set(values), key=values.count)
    bad = [i for i, v in enumerate(values) if v != majority]
    if bad:
        raise ValueError(
            f"{label} needs a common {kind}, got {majority!r} for most "
            f"streams but {[values[i] for i in bad]!r} for stream(s) "
            f"{bad}; pad every stream to one shared layout "
            f"(thread n_pad/k_pad through the constructors)")


def slot_map_checkpoint(slot_maps) -> Tuple[List[dict], dict]:
    """Per-stream `SlotMap`s as checkpoint metadata (one header each)
    and the arrays to store beside the state (`_restore_slot_maps`)."""
    arrays = {f"slot_maps/{i}/{k}": v
              for i, sm in enumerate(slot_maps)
              for k, v in sm.arrays().items()}
    return [sm.header() for sm in slot_maps], arrays


def _restore_slot_maps(path: str, payloads: list) -> list:
    """The checkpoint's `SlotMap`s: headers with their stored arrays,
    or the single JSON payloads older checkpoints hold."""
    from repro.core.sparse import SlotMap

    names = [f"slot_maps/{i}/{k}" for i, p in enumerate(payloads)
             if "node_slot" not in p for k in SlotMap.ARRAYS]
    stored = load_arrays(path, names)
    return [SlotMap.restore(p, {k: stored.get(f"slot_maps/{i}/{k}")
                                for k in SlotMap.ARRAYS})
            for i, p in enumerate(payloads)]


def restore_stacked_state(ckpt_dir: str, *, exact_smax: bool,
                          method: str) -> Tuple[FingerState, int, dict]:
    """Latest checkpoint → (host stacked FingerState, step, metadata).

    The manifest's layout fields rebuild the pytree without a template,
    and the saved engine config is validated against the restoring one
    (mismatches break the identical-scores guarantee). Shared by
    `StreamEngine.restore` and `serving.FingerService.restore` — one
    on-disk format, so checkpoints migrate freely between the two APIs.
    """
    path = latest_checkpoint(ckpt_dir)
    if path is None:
        raise FileNotFoundError(
            f"restore: no checkpoint under {ckpt_dir!r}")
    manifest = load_manifest(path)
    meta = manifest["metadata"]
    if meta.get("kind") != "stream_engine_state":
        raise ValueError(
            f"restore: {path!r} is not a FINGER serving checkpoint "
            f"(kind={meta.get('kind')!r})")
    for key, want in (("exact_smax", exact_smax), ("method", method)):
        if key in meta and meta[key] != want:
            raise ValueError(
                f"restore: checkpoint was saved with {key}="
                f"{meta[key]!r} but this engine uses {want!r}; "
                "resuming across configs breaks the identical-"
                "scores guarantee — construct the engine with the "
                "saved config")
    b, n_pad = int(meta["b"]), int(meta["n_pad"])
    zb = jnp.zeros((b,), jnp.float32)
    sp = meta.get("sparse")
    if sp is not None:
        # Slot-space checkpoint: rebuild the SparseStreamState pytree
        # from the recorded capacities; the host SlotMaps (headers in
        # the metadata, arrays beside the state) come back as
        # ``meta["slot_maps"]``.
        from repro.core.sparse import SparseLayout, SparseStreamState

        slayout = SparseLayout(int(sp["n_slots"]), int(sp["m_pad"]),
                               generation=int(sp["generation"]))
        zbs = jnp.zeros((b, slayout.n_slots), jnp.float32)
        template = SparseStreamState(
            q=zb, s_total=zb, s_max=zb, strengths=zbs, node_mask=zbs,
            edge_weights=jnp.zeros((b, slayout.m_pad), jnp.float32),
            layout=slayout)
        states, manifest = restore_checkpoint(path, template,
                                              manifest=manifest)
        states = jax.tree_util.tree_map(jnp.asarray, states)
        if meta.get("slot_maps") is not None:
            meta = dict(meta, slot_maps=_restore_slot_maps(
                path, meta["slot_maps"]))
        return states, int(manifest["step"]), meta
    zbn = jnp.zeros((b, n_pad), jnp.float32)
    has_mask = bool(meta.get("has_node_mask"))
    # Mask-aware checkpoints carry their layout generation (older
    # manifests predate migrations: generation 0).
    layout = NodeLayout(
        n_pad, generation=int(meta.get("layout_generation", 0))) \
        if has_mask else None
    template = FingerState(
        q=zb, s_total=zb, s_max=zb, strengths=zbn,
        node_mask=zbn if has_mask else None, layout=layout)
    states, manifest = restore_checkpoint(path, template,
                                          manifest=manifest)
    states = jax.tree_util.tree_map(jnp.asarray, states)
    return states, int(manifest["step"]), meta


def stack_states(states: Sequence[FingerState]) -> FingerState:
    """[state_b] → stacked FingerState with a leading (B,) batch axis.

    Every stream must share one node layout: equal strengths shape
    (n_pad) and agreeing node-mask presence. Validated up front so the
    error names the offending streams instead of an opaque pytree
    mismatch.
    """
    _check_consistent("stack_states", "n_pad (strengths shape)",
                      (tuple(s.strengths.shape) for s in states))
    _check_consistent("stack_states", "node_mask presence",
                      (s.node_mask is not None for s in states))
    _check_consistent("stack_states", "NodeLayout",
                      (s.layout for s in states))
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def unstack_states(states: FingerState) -> List[FingerState]:
    """Stacked (B, …) FingerState → list of B per-stream states."""
    b = states.q.shape[0]
    return [jax.tree_util.tree_map(lambda x: x[i], states)
            for i in range(b)]


def stack_deltas(deltas: Sequence[GraphDelta]) -> GraphDelta:
    """[delta_b] → stacked (B, k_pad) GraphDelta.

    Streams must share every static/layout dimension — k_pad, n_pad
    (the static `n_nodes`), node-slot presence and j_pad. Each is
    validated up front with an error naming the offending streams.
    Host (numpy) deltas stack on the host: no device op and no compile,
    and the ingestion path moves the stacked tick to the device once.
    """
    _check_consistent("stack_deltas", "k_pad",
                      (d.dw.shape[-1] for d in deltas))
    _check_consistent("stack_deltas", "n_pad (static n_nodes)",
                      (d.n_nodes for d in deltas))
    _check_consistent("stack_deltas", "node-slot presence",
                      (d.node_ids is not None for d in deltas))
    _check_consistent("stack_deltas", "layout_generation",
                      (d.layout_generation for d in deltas))
    _check_consistent("stack_deltas", "edge_slots presence",
                      (d.edge_slots is not None for d in deltas))
    if deltas[0].node_ids is not None:
        _check_consistent("stack_deltas", "j_pad",
                          (d.node_ids.shape[-1] for d in deltas))
    on_host = all(isinstance(x, np.ndarray)
                  for x in jax.tree_util.tree_leaves(list(deltas)))
    stack = np.stack if on_host else jnp.stack
    return jax.tree_util.tree_map(lambda *xs: stack(xs), *deltas)


class StreamEngine:
    """Batched Algorithm-2 engine for B concurrent graph streams.

    Parameters
    ----------
    exact_smax : recompute s_max exactly after deletions (O(n) per
        stream; the paper's eq. (3) never decreases s_max).
    method : Δ-statistics path, ``"dense"``, ``"compact"``, or
        ``"fused_tick"`` (see `core.incremental`). Under
        ``"fused_tick"`` the whole batched tick — mask gating, node
        join/leave updates, delta statistics, state update, JSdist —
        runs as ONE Pallas kernel launch gridded over the B stream
        slots (`repro.kernels.stream_tick`; interpret mode off TPU,
        with the VMEM size guard routing oversized (k_pad, n_pad)
        tiles back to this class's vmapped op chain).
    """

    def __init__(self, exact_smax: bool = False, method: str = "dense"):
        self.exact_smax = exact_smax
        self.method = method

        # The per-stream step keeps a non-batched spelling for scan /
        # compatibility callers; the megakernels are whole-tick fusions,
        # so their closest single-stream analog is the compact path.
        step_method = "compact" if method in ("fused_tick",
                                              "sparse_tick") else method

        if method == "sparse_tick":
            # Slot-space streams: the state is a SparseStreamState and
            # deltas are SlotMap-translated (see `repro.core.sparse`).
            from repro.core.sparse import sparse_jsdist_tick

            def step(state, delta: GraphDelta):
                return sparse_jsdist_tick(state, delta,
                                          exact_smax=exact_smax,
                                          method="compact")
        else:
            def step(state: FingerState, delta: GraphDelta):
                return jsdist_incremental(state, delta,
                                          exact_smax=exact_smax,
                                          method=step_method)

        self._step = step
        self._vstep = jax.vmap(step)
        if method == "fused_tick":
            from repro.kernels.stream_tick.ops import stream_tick_fused

            def tick_body(states: FingerState, deltas: GraphDelta):
                return stream_tick_fused(states, deltas,
                                         exact_smax=exact_smax)
        elif method == "sparse_tick":
            from repro.kernels.sparse_tick.ops import sparse_tick_fused

            def tick_body(states, deltas: GraphDelta):
                return sparse_tick_fused(states, deltas,
                                         exact_smax=exact_smax)
        else:
            tick_body = self._vstep

        def scoped_tick_body(states, deltas: GraphDelta):
            # The tick's device operations carry this name in the
            # profiler's trace, whatever the jitted function is called.
            with jax.named_scope("finger.tick"):
                return tick_body(states, deltas)

        # The one batched-tick computation every entry point executes:
        # `tick` jits it, `run` scans it, and the serving plans wrap it
        # in shard_map (each shard runs it on its resident streams).
        self._tick_body = scoped_tick_body
        # Donate the stacked state: the engine owns it and a serving tick
        # should update the (B, n) strengths in place, not copy them.
        self._tick = jax.jit(self._tick_body, donate_argnums=(0,))
        self._run = jax.jit(self._scan_run, donate_argnums=(0,))

    # -- construction ----------------------------------------------------
    @staticmethod
    def init_states(graphs, n_pad: Optional[int] = None,
                    layout: Optional[NodeLayout] = None) -> FingerState:
        """Initial stacked state from B host graphs (one O(n + m) pass
        per stream, host-side; the online loop never does this again).

        Heterogeneous node counts are welcome: every graph is embedded
        into a shared `NodeLayout` (pass one, or an ``n_pad``; default:
        the largest layout in the batch) with a per-stream node mask, so
        a batch of tenants with n ∈ {32, 57, 96, 128} runs as one
        (B, n_pad) program. Uniform batches get an all-ones mask — the
        compiled tick is identical either way, so mixed-`n` serving
        costs nothing extra.

        The state is computed on the *unpadded* graph and only the
        node-space arrays (strengths, mask) are embedded into the
        layout: padding commutes with the FINGER statistics (padded
        slots carry zero strength, contributing nothing to S, Q or
        s_max), and padding the graph itself would materialize an
        (n_pad, n_pad) weights matrix — 40 GB per stream at the sparse
        path's n_pad = 1e5 virtual bound.
        """
        graphs = list(graphs)
        if layout is None:
            layout = NodeLayout(max(g.n_nodes for g in graphs)
                                if n_pad is None else int(n_pad))
        elif n_pad is not None and int(n_pad) != layout.n_pad:
            raise ValueError(
                f"init_states: n_pad={n_pad} conflicts with "
                f"layout.n_pad={layout.n_pad}; pass one or the other")
        too_big = [i for i, g in enumerate(graphs)
                   if g.n_nodes > layout.n_pad]
        if too_big:
            raise ValueError(
                f"init_states: stream(s) {too_big} have n_nodes > "
                f"n_pad={layout.n_pad}")

        def embed(g) -> FingerState:
            st = finger_state(g)
            n = g.n_nodes
            strengths = jnp.pad(st.strengths, (0, layout.n_pad - n))
            mask = layout.embed_mask(g.node_mask, n,
                                     dtype=strengths.dtype)
            return FingerState(q=st.q, s_total=st.s_total,
                               s_max=st.s_max, strengths=strengths,
                               node_mask=mask, layout=layout)

        return stack_states([embed(g) for g in graphs])

    @staticmethod
    def init_sparse_states(graphs, layout, n_virtual: int):
        """Initial stacked `SparseStreamState` + per-stream `SlotMap`s.

        The slot-space counterpart of `init_states` for
        ``method="sparse_tick"``: every graph's active nodes/edges are
        assigned device slots in a shared `SparseLayout` capacity, and
        the returned host-side slot maps own all future virtual-id →
        slot translation (serving ingestion calls them per delta).
        """
        from repro.core.sparse import sparse_states_from_graphs

        return sparse_states_from_graphs(list(graphs), layout,
                                         n_virtual=int(n_virtual))

    # -- persistence -----------------------------------------------------
    def save(self, ckpt_dir: str, states: FingerState, step: int = 0,
             metadata: Optional[dict] = None,
             keep_last: Optional[int] = None,
             prune_policy=None) -> str:
        """Persist the stacked serving state (atomic write).

        Goes through `train.checkpoint`: arrays are gathered to host and
        published with a tmp-dir + rename, so a crash mid-save can never
        corrupt the latest checkpoint. The manifest records the stacked
        layout so `restore` can rebuild the pytree without a template.
        ``prune_policy`` takes any `train.checkpoint` policy form
        (int / ``("keep_every_n", n, k)`` / callable); ``keep_last`` is
        the legacy int spelling.
        """
        from repro.core.sparse import SparseStreamState

        # Reserved keys win over caller metadata: restore() depends on
        # them to rebuild the pytree and validate the engine config.
        meta = dict(metadata or {})
        meta.update({
            "kind": "stream_engine_state",
            "b": int(states.q.shape[0]),
            "n_pad": int(states.strengths.shape[-1]),
            "has_node_mask": states.node_mask is not None,
            "layout_generation": (states.layout.generation
                                  if states.layout is not None else 0),
            "exact_smax": self.exact_smax,
            "method": self.method,
        })
        if isinstance(states, SparseStreamState):
            # Slot-space checkpoints record their capacities (n_pad
            # above is the slot width, not the virtual bound); the
            # host SlotMap payloads ride in the caller's metadata
            # (`FingerService.save` puts them under "slot_maps").
            meta["sparse"] = {
                "n_slots": int(states.layout.n_slots),
                "m_pad": int(states.layout.m_pad),
                "generation": int(states.layout.generation),
            }
        return save_checkpoint(ckpt_dir, step, states, metadata=meta,
                               keep_last=keep_last,
                               prune_policy=prune_policy)

    def restore(self, ckpt_dir: str, mesh: Optional[Mesh] = None,
                axis: str = "data") -> Tuple[FingerState, int]:
        """Resume the stacked state from the latest checkpoint.

        Returns ``(states, step)``. Mesh-agnostic: arrays come back on
        host and are re-sharded onto `mesh[axis]` when a mesh is given —
        the saving job's device layout is irrelevant, so an elastic
        restart can change pod shape and keep serving.
        """
        states, step, _ = restore_stacked_state(
            ckpt_dir, exact_smax=self.exact_smax, method=self.method)
        if mesh is not None:
            states = self.shard_states(states, mesh, axis)
        return states, step

    # -- serving ---------------------------------------------------------
    def tick(self, states: FingerState,
             deltas: GraphDelta) -> Tuple[jax.Array, FingerState]:
        """One serving tick: (B,) JSdist scores + updated stacked state.

        `states` is donated — pass the engine-owned state and rebind it
        to the returned one.
        """
        dists, new_states = self._tick(states, deltas)
        return dists, new_states

    def _scan_run(self, states: FingerState, delta_seq: GraphDelta):
        def body(carry, delta_t):
            dists, new_carry = self._tick_body(carry, delta_t)
            return new_carry, dists

        final, dists = jax.lax.scan(body, states, delta_seq)
        return dists, final

    def run(self, states: FingerState,
            delta_seq: GraphDelta) -> Tuple[jax.Array, FingerState]:
        """Scan T ticks over a stacked (T, B, k_pad) delta sequence.

        Returns the (T, B) distance matrix and the final stacked state —
        the whole T×B online loop is one XLA while-scan.
        """
        return self._run(states, delta_seq)

    # -- multi-device ----------------------------------------------------
    def make_sharded_tick(self, mesh: Mesh, axis: str = "data"):
        """Compile a tick with streams sharded over `mesh[axis]`.

        Each device owns B/p streams; the body is the plain vmapped step
        (independent streams ⇒ no collectives). Returns a jitted
        callable with the same (states, deltas) → (dists, states)
        contract as `tick`.
        """
        spec = P(axis)
        sharded = jax.shard_map(
            self._tick_body, mesh=mesh,
            in_specs=(spec, spec), out_specs=(spec, spec),
        )
        return jax.jit(sharded, donate_argnums=(0,))

    def shard_states(self, states: FingerState, mesh: Mesh,
                     axis: str = "data") -> FingerState:
        """device_put the stacked state sharded over its stream axis."""
        sharding = NamedSharding(mesh, P(axis))
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), states)
