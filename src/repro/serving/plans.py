"""ExecutionPlan: one compiled serving tick per ServiceConfig.

A plan owns everything placement-shaped: the compiled batched tick
(vmapped Algorithm 2, optionally under `shard_map`), how stacked state
and delta pytrees are laid out on devices, and how `top_anomalies`
queries run. `FingerService` chooses a plan once at `open` time from
``config.placement``:

- ``LocalPlan``    : single-device jit(vmap(step)) — the plain
  `StreamEngine` tick.
- ``ShardedPlan``  : streams sharded over ``(data_axis,)``. Independent
  streams ⇒ the tick body needs zero collectives.
- ``MultiPodPlan`` : streams sharded over ``(pod_axis, data_axis)``;
  adds per-pod top-k queries merged over the data axis only.

Sharded top-k without the full gather: each shard computes a local
`lax.top_k` over its B/p resident scores, emits (k,) candidate values
plus *global* stream ids (shard offset from `lax.axis_index`), and the
final merge runs `top_k` over the (p·k,) candidate row — the (B,) score
vector itself is never materialized on one device. Per-pod queries
all-gather candidates over the data axis only (n_data·k values per
pod).

`StreamEngine` is the plan-internal executor: plans reuse its batched
tick body (the vmapped step chain, or the `kernels.stream_tick` fused
megakernel under ``method="fused_tick"``) and state sharding helpers
rather than re-deriving them — all three placements run the same body
inside their `shard_map`.

`PlanCache` is the warm pool behind pause-free migrations: it holds
plans pre-compiled (`ExecutionPlan.warm_tick`) for *predicted next
layouts* — the repad growth schedule plus the pending compaction
target — so `FingerService.repad`/`compact` swap to an
already-compiled tick instead of paying a fresh trace+compile while
serving is stalled.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.state import FingerState
from repro.distributed.sharding import auto_mesh
from repro.engine.stream import StreamEngine
from repro.graphs.layout import NodeLayout
from repro.graphs.types import GraphDelta
from repro.serving.config import ServiceConfig, ServiceConfigError


def dummy_tick_args(config: ServiceConfig, layout):
    """Zero-filled (states, deltas) of exactly the shapes/statics the
    serving tick compiles for under ``config`` at ``layout``.

    The single source of dummy-argument truth shared by
    `ExecutionPlan.warm_tick` and the static-analysis gate
    (`analysis.hlo_audit`) — both must populate/audit the *same* jit
    cache entry the real tick hits. ``layout`` is a `NodeLayout` for
    the dense methods and a `core.sparse.SparseLayout` for
    ``method="sparse_tick"`` (sparse dummies are slot-space: deltas
    carry ``edge_slots`` and are addressed in n_slots, never n_pad).
    """
    c = config
    b, k, j = c.batch_size, c.k_pad, c.j_pad
    f32, i32 = jnp.float32, jnp.int32
    if c.method == "sparse_tick":
        from repro.core.sparse import (EDGE_SLOT_SENTINEL, SparseLayout,
                                       SparseStreamState)
        if not isinstance(layout, SparseLayout):
            raise ServiceConfigError(
                f"method='sparse_tick' ticks over a SparseLayout, got "
                f"{type(layout).__name__}")
        if layout.n_slots != c.n_slots or layout.m_pad != c.m_pad:
            raise ServiceConfigError(
                f"layout capacities (n_slots={layout.n_slots}, "
                f"m_pad={layout.m_pad}) disagree with the config "
                f"(n_slots={c.n_slots}, m_pad={c.m_pad})")
        n, m = layout.n_slots, layout.m_pad
        states = SparseStreamState(
            q=jnp.zeros((b,), f32), s_total=jnp.zeros((b,), f32),
            s_max=jnp.zeros((b,), f32),
            strengths=jnp.zeros((b, n), f32),
            node_mask=jnp.zeros((b, n), f32),
            edge_weights=jnp.zeros((b, m), f32), layout=layout)
        deltas = GraphDelta(
            senders=jnp.zeros((b, k), i32),
            receivers=jnp.zeros((b, k), i32),
            dw=jnp.zeros((b, k), f32), w_old=jnp.zeros((b, k), f32),
            mask=jnp.zeros((b, k), f32), n_nodes=n,
            node_ids=None if j is None else jnp.zeros((b, j), i32),
            node_flag=None if j is None else jnp.zeros((b, j), f32),
            edge_slots=jnp.full((b, k), int(EDGE_SLOT_SENTINEL), i32))
        return states, deltas
    if layout.n_pad != c.n_pad:
        raise ServiceConfigError(
            f"warm_tick: layout n_pad={layout.n_pad} != this "
            f"plan's config.n_pad={c.n_pad}")
    n = layout.n_pad
    states = FingerState(
        q=jnp.zeros((b,), f32), s_total=jnp.zeros((b,), f32),
        s_max=jnp.zeros((b,), f32),
        strengths=jnp.zeros((b, n), f32),
        node_mask=jnp.zeros((b, n), f32), layout=layout)
    deltas = GraphDelta(
        senders=jnp.zeros((b, k), i32),
        receivers=jnp.zeros((b, k), i32),
        dw=jnp.zeros((b, k), f32), w_old=jnp.zeros((b, k), f32),
        mask=jnp.zeros((b, k), f32), n_nodes=n,
        node_ids=None if j is None else jnp.zeros((b, j), i32),
        node_flag=None if j is None else jnp.zeros((b, j), f32))
    return states, deltas


def _mesh_axis_size(mesh: Mesh, axis: str) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis not in sizes:
        raise ServiceConfigError(
            f"mesh axes {tuple(mesh.axis_names)} carry no {axis!r} axis "
            f"required by the placement")
    return sizes[axis]


class ExecutionPlan:
    """Compiled tick + placement policy for one ServiceConfig.

    Subclasses fill in ``axes`` (the mesh axis names the stream axis is
    sharded over; empty for local) and ``mesh``. All compilation happens
    in ``__init__`` / first call — a running service never recompiles
    unless `FingerService.repad` swaps the plan for a larger layout.
    """

    axes: Tuple[str, ...] = ()
    mesh: Optional[Mesh] = None

    def __init__(self, config: ServiceConfig):
        self.config = config
        self.engine = StreamEngine(exact_smax=config.exact_smax,
                                   method=config.method)
        self._topk_cache = {}

    # -- placement geometry ---------------------------------------------
    @property
    def num_shards(self) -> int:
        out = 1
        for ax in self.axes:
            out *= _mesh_axis_size(self.mesh, ax)
        return out

    @property
    def streams_per_shard(self) -> int:
        return self.config.batch_size // self.num_shards

    def topk_candidate_count(self, k: int) -> int:
        """Size of the merge row a global top-k query materializes —
        num_shards·k, never the full (B,) score vector."""
        return self.num_shards * k

    def _spec(self) -> P:
        return P(self.axes if len(self.axes) > 1 else self.axes[0])

    # -- data movement ---------------------------------------------------
    def state_sharding(self) -> Optional[NamedSharding]:
        """How this plan lays the stacked state out (stream axis over
        ``axes``); None for the single-device plan. Device-side layout
        migrations pass it as ``out_shardings`` to reshard in place."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self._spec())

    def shard_states(self, states: FingerState) -> FingerState:
        sharding = self.state_sharding()
        if sharding is None:
            return states
        return jax.tree_util.tree_map(
            lambda x: jax.device_put(x, sharding), states)

    def put_deltas(self, deltas: GraphDelta) -> GraphDelta:
        """Start the host→device transfer of one tick's stacked deltas.

        Returns immediately with the transfer in flight (jax transfers
        are asynchronous) — the double-buffered ingestor leans on this
        to overlap tick T+1's transfer with tick T's compute. Every
        tick's transfer passes here, in a ``finger.h2d`` span counting
        the delta's ``bytes``.
        """
        nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(deltas))
        with TraceAnnotation("finger.h2d", bytes=nbytes):
            if self.mesh is None:
                return jax.device_put(deltas)
            sharding = NamedSharding(self.mesh, self._spec())
            return jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sharding), deltas)

    # -- the tick --------------------------------------------------------
    def tick(self, states: FingerState,
             deltas: GraphDelta) -> Tuple[jax.Array, FingerState]:
        """(B,) JSdist scores + updated stacked state. `states` is
        donated — rebind to the returned one."""
        raise NotImplementedError

    def warm_tick(self, layout: NodeLayout) -> None:
        """Compile this plan's tick (and default top-k query) ahead of
        serving by running them once on zero-filled dummy state/deltas
        of the declared shapes.

        The dummy tick populates exactly the jit cache entry the real
        tick will hit — same shapes, same static layout (generation
        included; a `NodeLayout` for dense methods, a `SparseLayout`
        under ``method="sparse_tick"``), same shardings (the dummies
        go through `shard_states`/`put_deltas`) — so a migration that
        installs this plan pays no compile pause. Called by
        `PlanCache.warm` with the *predicted* post-migration layout.
        """
        c = self.config
        states, deltas = dummy_tick_args(c, layout)
        states = self.shard_states(states)
        deltas = self.put_deltas(deltas)
        dists, _ = self.tick(states, deltas)
        self.topk(dists, c.topk.k)
        jax.block_until_ready(dists)

    # -- queries ---------------------------------------------------------
    def _validate_k(self, k: int) -> None:
        if k <= 0:
            raise ServiceConfigError(f"top_anomalies k={k} must be "
                                     f"positive")
        if k > self.streams_per_shard:
            raise ServiceConfigError(
                f"top_anomalies k={k} exceeds the per-shard stream "
                f"count {self.streams_per_shard} "
                f"(batch_size={self.config.batch_size} over "
                f"{self.num_shards} shard(s)); shrink k or re-open with "
                f"a coarser placement")

    def topk(self, scores: jax.Array,
             k: int) -> Tuple[jax.Array, jax.Array]:
        """Global top-k: ((k,) values, (k,) stream ids), descending."""
        self._validate_k(k)
        fn = self._topk_cache.get(k)
        if fn is None:
            fn = self._compile_topk(k)
            self._topk_cache[k] = fn
        return fn(scores)

    def _compile_topk(self, k: int):
        raise NotImplementedError


class LocalPlan(ExecutionPlan):
    """Single-device vmapped tick — `StreamEngine.tick` verbatim, so
    scores are bit-exact with the pre-redesign engine path."""

    axes = ()
    mesh = None

    def tick(self, states, deltas):
        return self.engine.tick(states, deltas)

    def _compile_topk(self, k: int):
        def topk(scores):
            vals, ids = jax.lax.top_k(scores, k)
            return vals, ids.astype(jnp.int32)

        return jax.jit(topk)


class _ShardedPlanBase(ExecutionPlan):
    """Common shard_map machinery for the sharded/multipod placements."""

    def __init__(self, config: ServiceConfig, mesh: Mesh):
        super().__init__(config)
        self.mesh = mesh
        for ax in self.axes:
            _mesh_axis_size(mesh, ax)  # named error before any compile
        config.validate(num_shards=self.num_shards)
        spec = self._spec()
        # The engine's batched tick body: the vmapped step chain, or the
        # fused stream_tick megakernel (each shard launches it over its
        # resident B/p streams) under method="fused_tick".
        body = self.engine._tick_body
        self._tick = jax.jit(
            jax.shard_map(body, mesh=mesh, in_specs=(spec, spec),
                          out_specs=(spec, spec), check_vma=False),
            donate_argnums=(0,))

    def tick(self, states, deltas):
        return self._tick(states, deltas)

    def _shard_offset_ids(self, local_idx: jax.Array) -> jax.Array:
        """Local top-k indices → global stream ids for this shard.

        Shard order under P(axes) partitions the stream axis first by
        the leading axis, so the linear shard index is the mixed-radix
        number over ``axes`` — matching the unsharded host-side order.
        """
        shard = jnp.asarray(0, jnp.int32)
        for ax in self.axes:
            shard = shard * _mesh_axis_size(self.mesh, ax) \
                + jax.lax.axis_index(ax)
        return local_idx.astype(jnp.int32) \
            + shard * self.streams_per_shard

    def _compile_topk(self, k: int):
        spec = self._spec()

        def body(scores):  # (B/p,) resident scores of one shard
            vals, idx = jax.lax.top_k(scores, k)
            return vals, self._shard_offset_ids(idx)

        cand = jax.shard_map(body, mesh=self.mesh, in_specs=(spec,),
                             out_specs=(spec, spec), check_vma=False)

        def topk(scores):
            # (p·k,) candidates — the only cross-shard materialization.
            cand_vals, cand_ids = cand(scores)
            vals, pos = jax.lax.top_k(cand_vals, k)
            return vals, cand_ids[pos]

        return jax.jit(topk)


class ShardedPlan(_ShardedPlanBase):
    """Streams sharded over ``(data_axis,)`` of a single-pod mesh."""

    def __init__(self, config: ServiceConfig, mesh: Mesh):
        self.axes = (config.data_axis,)
        super().__init__(config, mesh)


class MultiPodPlan(_ShardedPlanBase):
    """Streams sharded over ``(pod_axis, data_axis)``; per-pod top-k
    queries merge candidates over the data axis only."""

    def __init__(self, config: ServiceConfig, mesh: Mesh):
        self.axes = (config.pod_axis, config.data_axis)
        super().__init__(config, mesh)
        self._pod_topk_cache = {}

    @property
    def n_pods(self) -> int:
        return _mesh_axis_size(self.mesh, self.config.pod_axis)

    def pod_topk(self, scores: jax.Array,
                 k: int) -> Tuple[jax.Array, jax.Array]:
        """Per-pod top-k: ((n_pods, k) values, (n_pods, k) stream ids).

        Each pod's anomaly report is computed inside the pod — the
        merge all-gathers n_data·k candidates over the data axis and
        never crosses the pod axis.
        """
        self._validate_k(k)
        fn = self._pod_topk_cache.get(k)
        if fn is None:
            fn = self._compile_pod_topk(k)
            self._pod_topk_cache[k] = fn
        return fn(scores)

    def _compile_pod_topk(self, k: int):
        spec = self._spec()
        data_axis = self.config.data_axis
        pod_axis = self.config.pod_axis

        def body(scores):  # (B/p,) resident scores of one shard
            vals, idx = jax.lax.top_k(scores, k)
            gids = self._shard_offset_ids(idx)
            cv = jax.lax.all_gather(vals, data_axis).reshape(-1)
            ci = jax.lax.all_gather(gids, data_axis).reshape(-1)
            pv, pos = jax.lax.top_k(cv, k)
            return pv[None], ci[pos][None]  # (1, k) per pod, data-repl.

        out_spec = P(pod_axis, None)
        fn = jax.shard_map(body, mesh=self.mesh, in_specs=(spec,),
                           out_specs=(out_spec, out_spec), check_vma=False)
        return jax.jit(fn)


class PlanCache:
    """Warm pool of pre-compiled `ExecutionPlan`s for layout migrations.

    Keyed by the compilation-relevant `ServiceConfig` fields plus the
    mesh identity. ``warm`` builds a plan for a *predicted* next config
    and compiles its tick for the predicted post-migration
    `NodeLayout` (generation included — the layout is a static part of
    the compiled program); ``get`` is what `FingerService` swaps
    through: a cache hit returns the already-compiled plan (popped —
    one migration consumes one warm plan), a miss falls back to the
    cold `build_plan` path.

    Thread-safe on the cache dict: `FingerService.warm_next_layouts`
    (and the fleet rebalancer's bulk pre-warm) may insert from a
    background warming thread while the serving thread pops — the lock
    covers only the dict, never a compile (jit compilation is itself
    thread-safe and runs outside the lock).
    """

    def __init__(self):
        self._plans: Dict[tuple, Tuple[ExecutionPlan, NodeLayout]] = {}
        self._lock = threading.Lock()

    @staticmethod
    def _key(config: ServiceConfig, mesh: Optional[Mesh]) -> tuple:
        # Under the sparse method n_pad is the *virtual* addressing
        # bound — a host-side number no compiled program depends on —
        # so a free virtual repad between warm() and get() must not
        # invalidate a warm plan. Key on None instead.
        n_pad = None if config.method == "sparse_tick" else config.n_pad
        return (config.batch_size, n_pad, config.k_pad,
                config.j_pad, config.n_slots, config.m_pad,
                config.method, config.exact_smax,
                config.placement, config.data_axis, config.pod_axis,
                None if mesh is None else id(mesh))

    def __len__(self) -> int:
        with self._lock:
            return len(self._plans)

    @property
    def warmed_layouts(self) -> Tuple[NodeLayout, ...]:
        """The layouts currently held warm (introspection/tests)."""
        with self._lock:
            return tuple(layout for _, layout in self._plans.values())

    def warm(self, config: ServiceConfig, mesh: Optional[Mesh],
             layout: NodeLayout) -> ExecutionPlan:
        """Build + fully compile a plan for ``config`` at ``layout``."""
        plan = build_plan(config, mesh)
        plan.warm_tick(layout)
        with self._lock:
            self._plans[self._key(config, mesh)] = (plan, layout)
        return plan

    def get(self, config: ServiceConfig, mesh: Optional[Mesh],
            layout: NodeLayout) -> ExecutionPlan:
        """The plan to install for ``config``: warm if predicted
        correctly, freshly built (cold) otherwise. A warm plan whose
        predicted layout generation disagrees is still *valid* for the
        config (compilation correctness only depends on the config);
        its first tick just compiles cold."""
        with self._lock:
            hit = self._plans.pop(self._key(config, mesh), None)
        if hit is not None:
            cached = hit[0].config
            if config.method == "sparse_tick":
                # Accept a plan warmed before a virtual repad: n_pad is
                # host-side only, so align it instead of recompiling.
                cached = cached.with_(n_pad=config.n_pad)
            if cached == config:
                hit[0].config = cached
                return hit[0]
        return build_plan(config, mesh)


def build_plan(config: ServiceConfig,
               mesh: Optional[Mesh] = None) -> ExecutionPlan:
    """config.placement → the matching compiled plan (named errors for
    placement/mesh mismatches; a default host mesh is built when the
    sharded placements get none)."""
    if config.placement == "local":
        if mesh is not None:
            raise ServiceConfigError(
                "placement='local' takes no mesh; use 'sharded' or "
                "'multipod' to place streams on a mesh")
        config.validate(num_shards=1)
        return LocalPlan(config)
    if config.placement == "sharded":
        if mesh is None:
            mesh = auto_mesh((jax.device_count(),), (config.data_axis,))
        return ShardedPlan(config, mesh)
    if config.placement == "multipod":
        if mesh is None:
            mesh = auto_mesh((1, jax.device_count()),
                             (config.pod_axis, config.data_axis))
        return MultiPodPlan(config, mesh)
    raise ServiceConfigError(f"unknown placement {config.placement!r}")
