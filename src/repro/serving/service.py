"""FingerService: the declarative serving facade over FINGER streams.

One object owns the whole serving lifecycle that callers used to
hand-assemble from `StreamEngine` pieces:

    config = ServiceConfig(batch_size=256, n_pad=128, k_pad=32,
                           placement="sharded",
                           ingestion="double_buffered",
                           checkpoint=CheckpointPolicy("/ckpts"))
    with FingerService.open(config, graphs) as svc:
        for tick_deltas in feed:
            svc.ingest(tick_deltas)      # transfer overlaps compute
            svc.poll()                   # advance one tick (async)
        worst = svc.top_anomalies(8)     # sharded top-k, no full gather
        svc.save()

Lifecycle: `open` (or `restore`) → `ingest`/`poll` in any interleaving
the queue depth allows → `scores`/`top_anomalies` queries → `save` →
`close` (also via context manager). Two live layout migrations:

- `repad(new_n_pad)` grows (or losslessly truncates) the shared
  `NodeLayout`. Growth is a jitted device-side embed — the stacked
  state never round-trips through host, and under the sharded/multipod
  placements it reshards in place. A shrink that would cut an active
  slot raises `LayoutMigrationError` instead of truncating.
- `compact()` drops permanently-left node slots (inactive in every
  stream), renumbering the survivors; the resulting old→new index map
  stays installed so ingestion keeps accepting deltas addressed in the
  pre-compaction layout for a grace period.

Both migrations re-lay-out any prefetched ticks still in the ingestion
queue (a double-buffered tick laid out for the old `n_pad` would
otherwise be applied against the wrong layout), bump the layout
generation, and journal themselves into the checkpoint directory so
`restore` can walk an old-generation checkpoint forward. They swap
through the warm `PlanCache` when the target layout was predicted
(`warm_next_layouts` — the repad growth schedule plus the pending
compaction target, knobs in `ServiceConfig.plan_cache`), installing an
already-compiled plan with no compile pause.

All placement/ingestion/query policy lives in the `ServiceConfig`; the
compiled execution comes from `plans.build_plan`. `StreamEngine` remains
underneath as the plan-internal executor.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from typing import (Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from repro.core.state import FingerState
from repro.engine.stream import (StreamEngine, restore_stacked_state,
                                 slot_map_checkpoint)
from repro.graphs.layout import (
    NodeLayout,
    compose_index_maps,
    identity_index_map,
)
from repro.graphs.types import GraphDelta
from repro.serving import migrate
from repro.serving.config import ServiceConfig, ServiceConfigError
from repro.serving.ingest import make_ingestor
from repro.serving.migrate import CompactionReport, LayoutMigrationError
from repro.serving.plans import (
    ExecutionPlan,
    MultiPodPlan,
    PlanCache,
    build_plan,
)
from repro.train.checkpoint import save_checkpoint

# One on-disk format with StreamEngine.save: a FingerService checkpoint
# restores into a bare StreamEngine and vice versa (the migration path).
_CKPT_KIND = "stream_engine_state"

# The leaves of a virtual delta that `SlotMap.stage` reads on the host.
_SLOTMAP_READS = ("senders", "receivers", "dw", "w_old", "mask",
                  "node_ids", "node_flag")


class ServiceLifecycleError(RuntimeError):
    """An operation was called in a state that cannot honor it (closed
    service, empty queue where one was required, …)."""


def _apply_compilation_cache(config: ServiceConfig) -> None:
    """Enable JAX's persistent on-disk compilation cache at the
    config's directory (no-op when unset).

    ``JAX_COMPILATION_CACHE_DIR`` in the environment wins: JAX roots
    the cache there itself, and the configured directory is then
    neither applied nor checked against it. Otherwise the cache is
    PROCESS-GLOBAL JAX state: every jit in the process — not just this
    service's plans — reads/writes it once enabled, and it cannot be
    re-rooted per service. Re-opening with the same directory is an
    idempotent no-op; a *different* directory raises rather than
    silently moving unrelated caches. JAX decides whether the cache is
    in use at its first compile, so rooting it later resets that
    decision. The compile-time / entry-size floors are lowered to zero
    so the small serving ticks actually persist (the JAX defaults skip
    sub-second compiles)."""
    target = config.compilation_cache_dir
    if target is None:
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        current = jax.config.jax_compilation_cache_dir
        if current is not None and current != target:
            raise ServiceConfigError(
                f"compilation_cache_dir={target!r} conflicts with the "
                f"process-global JAX compilation cache already rooted "
                f"at {current!r}; one process serves one cache "
                "directory")
        if current is None:
            jax.config.update("jax_compilation_cache_dir", target)
            compilation_cache.reset_cache()
    for knob, value in (
            ("jax_persistent_cache_min_compile_time_secs", 0),
            ("jax_persistent_cache_min_entry_size_bytes", -1)):
        if hasattr(jax.config, knob):
            jax.config.update(knob, value)


# One compiled slot read per (B,) score shape: the slot index is a
# traced scalar, so fleet-side per-tenant score reads never gather the
# full score vector and never fragment the jit cache per slot.
_score_at_jit = jax.jit(
    lambda scores, slot: jax.lax.dynamic_index_in_dim(
        scores, slot, 0, keepdims=False))


class WarmupHandle:
    """A `warm_next_layouts(background=True)` compile in flight.

    ``wait()`` joins the warming thread and returns the warmed-target
    list (re-raising any exception the thread hit); ``done()`` polls.
    The underlying `PlanCache` insertion is thread-safe, so the serving
    thread may keep ticking — but migrations should ``wait()`` first
    (a migration mid-warm would warm shapes that no longer exist).
    """

    def __init__(self, fn: Callable[[], list]):
        self._result: Optional[list] = None
        self._exc: Optional[BaseException] = None
        self._thread = threading.Thread(
            target=self._run, args=(fn,), daemon=True,
            name="finger-warmup")
        self._thread.start()

    def _run(self, fn) -> None:
        try:
            self._result = fn()
        except BaseException as e:  # re-raised at wait()
            self._exc = e

    def done(self) -> bool:
        return not self._thread.is_alive()

    def wait(self, timeout: Optional[float] = None) -> list:
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise ServiceLifecycleError(
                f"WarmupHandle.wait: background warming still compiling "
                f"after {timeout}s")
        if self._exc is not None:
            raise self._exc
        return self._result or []


@dataclasses.dataclass(frozen=True)
class TickReport:
    """One completed `poll`: the tick index and its (B,) scores, still
    on device — nothing here forces a host sync."""

    step: int
    scores: jax.Array


class FingerService:
    """Lifecycle facade for one declarative FINGER serving deployment.

    Build with `open` (fresh state from host graphs) or `restore`
    (resume from the config's checkpoint directory); never construct
    directly.
    """

    def __init__(self, config: ServiceConfig, plan: ExecutionPlan,
                 states: FingerState, step: int = 0,
                 remaps: Optional[Dict[int, np.ndarray]] = None,
                 remaps_gen: Optional[Dict[int, np.ndarray]] = None,
                 slot_maps: Optional[list] = None):
        self._config = config
        self._plan = plan
        self._states = states
        self._step = step
        if config.method == "sparse_tick":
            # Slot-space serving: the device capacity is the state's
            # SparseLayout; config.n_pad is the *virtual* addressing
            # bound the per-stream SlotMaps enforce host-side — no
            # device array is sized by it.
            self._capacity = states.layout
            if (self._capacity.n_slots, self._capacity.m_pad) != \
                    (config.n_slots, config.m_pad):
                raise ServiceConfigError(
                    f"FingerService: state capacities (n_slots="
                    f"{self._capacity.n_slots}, m_pad="
                    f"{self._capacity.m_pad}) != config "
                    f"(n_slots={config.n_slots}, m_pad={config.m_pad})")
            if slot_maps is None or len(slot_maps) != config.batch_size:
                raise ServiceConfigError(
                    f"FingerService: sparse serving needs one SlotMap "
                    f"per stream "
                    f"({0 if slot_maps is None else len(slot_maps)} "
                    f"for batch_size={config.batch_size})")
            self._slot_maps = list(slot_maps)
            self._layout = NodeLayout(config.n_pad)
        else:
            if slot_maps is not None:
                raise ServiceConfigError(
                    "FingerService: slot_maps are sparse-only state "
                    f"(method={config.method!r})")
            self._capacity = None
            self._slot_maps = None
            self._layout = states.layout if states.layout is not None \
                else NodeLayout(config.n_pad)
            if self._layout.n_pad != config.n_pad:
                raise ServiceConfigError(
                    f"FingerService: state layout n_pad="
                    f"{self._layout.n_pad} != config.n_pad="
                    f"{config.n_pad}")
        # old n_pad -> composed old→current index map (compact() grace,
        # legacy size-keyed best effort) ...
        self._remaps: Dict[int, np.ndarray] = dict(remaps or {})
        # ... and old generation -> old→current map (exact; every
        # migration adds an entry, grows as identity injections).
        self._remaps_gen: Dict[int, np.ndarray] = dict(remaps_gen or {})
        # Warm pool of pre-compiled plans for predicted next layouts
        # (see warm_next_layouts / PlanCachePolicy).
        self._plan_cache = PlanCache()
        self._ingestor = self._make_ingestor()
        self._last_scores: Optional[jax.Array] = None
        self._closed = False

    def _make_ingestor(self):
        return make_ingestor(self._config, self._plan, self._remaps,
                             self._remaps_gen,
                             generation=self._layout.generation)

    # -- construction ----------------------------------------------------
    @staticmethod
    def _resolve_plan(config: ServiceConfig, mesh: Optional[Mesh],
                      plan: Optional[ExecutionPlan]) -> ExecutionPlan:
        """The plan to serve with: the caller's shared one (validated
        compilation-compatible — how a fleet pool compiles its tick
        once for N shards) or a freshly built one."""
        if plan is None:
            return build_plan(config, mesh)
        if mesh is not None and mesh is not plan.mesh:
            raise ServiceConfigError(
                "open: both a mesh and a pre-built plan were passed "
                "but the plan was built for a different mesh")
        mine = PlanCache._key(config, plan.mesh)
        theirs = PlanCache._key(plan.config, plan.mesh)
        if mine != theirs:
            raise ServiceConfigError(
                f"open: the shared plan was compiled for a "
                f"compilation-incompatible config ({theirs} vs "
                f"{mine}); shards sharing a plan must agree on every "
                "shape/method/placement field")
        return plan

    @classmethod
    def open(cls, config: ServiceConfig, graphs: Sequence,
             mesh: Optional[Mesh] = None,
             plan: Optional[ExecutionPlan] = None) -> "FingerService":
        """Validate the config, compile its execution plan, and place
        the initial stacked state from B host graphs.

        ``plan`` (optional) installs a pre-built `ExecutionPlan` from a
        compilation-compatible sibling service instead of building a
        fresh one — shards of a fleet pool share one compiled tick this
        way (per-call donation keeps the shared jits safe)."""
        config.validate()
        _apply_compilation_cache(config)
        graphs = list(graphs)
        if len(graphs) != config.batch_size:
            raise ServiceConfigError(
                f"open: {len(graphs)} graph(s) != config.batch_size="
                f"{config.batch_size}")
        too_big = [g.n_nodes for g in graphs if g.n_nodes > config.n_pad]
        if too_big:
            raise ServiceConfigError(
                f"open: graph node count(s) {sorted(set(too_big))} "
                f"exceed config.n_pad={config.n_pad}; open with a "
                "larger n_pad (or repad() a running service)")
        plan = cls._resolve_plan(config, mesh, plan)
        if config.method == "sparse_tick":
            from repro.core.sparse import SparseLayout

            capacity = SparseLayout(n_slots=config.n_slots,
                                    m_pad=config.m_pad)
            states, slot_maps = StreamEngine.init_sparse_states(
                graphs, capacity, n_virtual=config.n_pad)
            return cls(config, plan, plan.shard_states(states),
                       slot_maps=slot_maps)
        states = StreamEngine.init_states(graphs, n_pad=config.n_pad)
        return cls(config, plan, plan.shard_states(states))

    @classmethod
    def restore(cls, config: ServiceConfig,
                mesh: Optional[Mesh] = None,
                directory: Optional[str] = None,
                plan: Optional[ExecutionPlan] = None) -> "FingerService":
        """Resume from the latest checkpoint under ``directory`` (default:
        the config's checkpoint directory). Mesh-agnostic: the saving
        job's placement is irrelevant — arrays come back on host and the
        new plan lays them out.

        Layout-generation aware: a checkpoint taken under an older
        `NodeLayout` is walked forward through the migrations journaled
        in the directory's layout log (pad for grows, index-map gather
        for compactions) until it reaches ``config.n_pad`` — so both
        "restore onto the layout I saved under" and "restore onto the
        layout I since migrated to" work, bit-exact."""
        config.validate()
        _apply_compilation_cache(config)
        ckpt_dir = directory or config.checkpoint.directory
        if ckpt_dir is None:
            raise ServiceConfigError(
                "restore: no checkpoint directory — pass one or set "
                "ServiceConfig.checkpoint.directory")
        plan = cls._resolve_plan(config, mesh, plan)
        states, step, meta = restore_stacked_state(
            ckpt_dir, exact_smax=config.exact_smax, method=config.method)
        if config.method == "sparse_tick":
            return cls._restore_sparse(config, plan, states, step, meta)
        b = int(states.q.shape[0])
        n_pad = int(states.strengths.shape[-1])
        if b != config.batch_size:
            raise ServiceConfigError(
                f"restore: checkpoint holds {b} stream(s) but "
                f"config.batch_size={config.batch_size}")
        log = migrate.load_layout_log(ckpt_dir)
        gen = int(meta.get("layout_generation", 0))
        if n_pad != config.n_pad:
            if not log:
                raise ServiceConfigError(
                    f"restore: checkpoint n_pad={n_pad} but config."
                    f"n_pad={config.n_pad} and the directory has no "
                    "layout log; restore with the saved layout, then "
                    "repad()/compact() to migrate it")
            strengths, node_mask, gen, _applied = \
                migrate.migrate_host_arrays(
                    np.asarray(states.strengths),
                    None if states.node_mask is None
                    else np.asarray(states.node_mask),
                    log, gen, config.n_pad)
            states = FingerState(
                q=states.q, s_total=states.s_total, s_max=states.s_max,
                strengths=jnp.asarray(strengths),
                node_mask=jnp.asarray(node_mask),
                layout=NodeLayout(config.n_pad, generation=gen))
        # Rebuild the ingestion grace table the live service had at this
        # generation: every journaled migration up to it, composed — so
        # a restored service keeps accepting the same old-layout deltas.
        recs = sorted((r for r in log if r["to_generation"] <= gen),
                      key=lambda r: r["from_generation"])
        remaps = migrate.remaps_from_records(recs)
        # Same retention policy as the live service: the rebuilt table
        # covers only the configured grace window, not the full journal.
        remaps_gen = migrate.prune_generation_remaps(
            migrate.remaps_by_generation(recs), gen,
            config.grace_generations)
        return cls(config, plan, plan.shard_states(states), step=step,
                   remaps=remaps, remaps_gen=remaps_gen)

    @classmethod
    def _restore_sparse(cls, config: ServiceConfig, plan, states, step,
                        meta) -> "FingerService":
        """Sparse tail of `restore`: rebuild the per-stream host
        `SlotMap`s from the checkpoint and re-validate the slot
        capacities against the config. No layout-log walk — slot
        capacities only grow in place (slot ids are preserved), so the
        saved state IS the current layout's."""
        b = int(states.q.shape[0])
        if b != config.batch_size:
            raise ServiceConfigError(
                f"restore: checkpoint holds {b} stream(s) but "
                f"config.batch_size={config.batch_size}")
        cap = states.layout
        if (cap.n_slots, cap.m_pad) != (config.n_slots, config.m_pad):
            raise ServiceConfigError(
                f"restore: checkpoint slot capacities (n_slots="
                f"{cap.n_slots}, m_pad={cap.m_pad}) != config "
                f"(n_slots={config.n_slots}, m_pad={config.m_pad}); "
                "restore with the saved capacities (a fleet manifest "
                "records them per shard)")
        payloads = meta.get("slot_maps")
        if payloads is None or len(payloads) != b:
            raise ServiceConfigError(
                "restore: sparse checkpoint carries "
                f"{0 if payloads is None else len(payloads)} SlotMap "
                f"payload(s) for {b} stream(s); it predates sparse "
                "persistence — rebuild these streams from their "
                "source graphs with FingerService.open")
        slot_maps = list(payloads)  # rebuilt by restore_stacked_state
        for slot, sm in enumerate(slot_maps):
            if sm.n_virtual > config.n_pad:
                raise ServiceConfigError(
                    f"restore: stream {slot}'s SlotMap addresses an "
                    f"n_pad={sm.n_virtual} virtual space but "
                    f"config.n_pad={config.n_pad}; virtual bounds "
                    "never shrink")
            if sm.n_virtual < config.n_pad:
                sm.grow_virtual(config.n_pad)  # host-only free repad
        return cls(config, plan, plan.shard_states(states), step=step,
                   slot_maps=slot_maps)

    # -- introspection ---------------------------------------------------
    @property
    def config(self) -> ServiceConfig:
        return self._config

    @property
    def plan(self) -> ExecutionPlan:
        return self._plan

    @property
    def step(self) -> int:
        """Number of completed ticks (== next checkpoint's step)."""
        return self._step

    @property
    def layout(self) -> NodeLayout:
        """The live `NodeLayout` (n_pad + migration generation). Under
        ``method="sparse_tick"`` the n_pad is the *virtual* addressing
        bound — see `capacity` for the device-side sizes."""
        return self._layout

    @property
    def capacity(self):
        """The live `SparseLayout` device capacity (n_slots, m_pad,
        generation) under ``method="sparse_tick"``; None otherwise."""
        return self._capacity

    @property
    def slot_maps(self) -> Optional[list]:
        """The per-stream virtual→slot `SlotMap`s (sparse only;
        read-only use — ingestion owns their mutation)."""
        return self._slot_maps

    @property
    def pending(self) -> int:
        """Ingested ticks not yet consumed by `poll`."""
        return len(self._ingestor)

    def states(self) -> FingerState:
        """The live stacked state (device-resident; read-only use)."""
        return self._states

    # -- serving loop ----------------------------------------------------
    def _check_open(self, what: str) -> None:
        if self._closed:
            raise ServiceLifecycleError(f"{what} on a closed "
                                        "FingerService")

    def ingest(self, deltas: Union[GraphDelta,
                                   Sequence[GraphDelta]]) -> None:
        """Queue one tick's deltas (a stacked (B, k_pad) GraphDelta, or
        a list of B per-stream deltas to stack). Under double-buffered
        ingestion the host→device transfer starts here, overlapping the
        in-flight tick's compute. Profiler span ``finger.shard_ingest``."""
        self._check_open("ingest")
        with TraceAnnotation("finger.shard_ingest"):
            if self._config.method == "sparse_tick":
                deltas = self._translate_sparse(deltas)
            self._ingestor.put(deltas)

    def _translate_sparse(self, deltas) -> List[GraphDelta]:
        """One tick's B per-stream *virtual* deltas → their slot-space
        deltas, through the per-stream `SlotMap`s, in a ``finger.slotmap``
        span counting the valid lanes in (``lanes``), the lanes
        translated (``kept``), the edge slots allocated (``new_edges``),
        the edge-index rebuilds and probe rounds the maps ran
        (``index_rebuilds``, ``probe_rounds``) and the incoming leaves
        that were device arrays, which `SlotMap.stage` reads back
        (``device_reads``; the fleet router hands host leaves).

        Atomic over the batch: every stream is staged (pure) before any
        map commits, so a rejection — out-of-capacity
        (`SparseCapacityError`), out-of-virtual-space addressing, a
        duplicate edge lane — leaves every SlotMap exactly as it was.
        The queue-depth check also runs first: a translated delta that
        could not be queued would desynchronize the maps from the
        applied ticks.
        """
        from repro.serving.ingest import IngestError

        if isinstance(deltas, GraphDelta):
            raise IngestError(
                "sparse ingestion is per-stream: pass the B per-stream "
                "virtual deltas as a sequence — the service translates "
                "each through its stream's SlotMap (stateful, "
                "tick-ordered) before stacking; a pre-stacked "
                "GraphDelta bypasses that translation")
        deltas = list(deltas)
        if len(deltas) != self._config.batch_size:
            raise IngestError(
                f"sparse ingest got {len(deltas)} per-stream delta(s) "
                f"!= config.batch_size={self._config.batch_size}")
        if self.pending >= self._config.max_queue:
            raise IngestError(
                f"ingestion queue full ({self._config.max_queue} "
                f"pending tick(s)); poll() before ingesting more")
        with TraceAnnotation("finger.slotmap") as span:
            before = np.sum([sm.index_counters for sm in self._slot_maps],
                            axis=0)
            staged = [sm.stage(d)
                      for sm, d in zip(self._slot_maps, deltas)]
            out = [sm.commit(st)
                   for sm, st in zip(self._slot_maps, staged)]
            rebuilds, rounds = np.sum(
                [sm.index_counters for sm in self._slot_maps],
                axis=0) - before
            span.set_metadata(
                lanes=sum(d.lane_count() for d in deltas),
                kept=sum(d.lane_count() for d in out),
                new_edges=sum(st.edge_slots.size for st in staged),
                index_rebuilds=int(rebuilds), probe_rounds=int(rounds),
                device_reads=sum(isinstance(getattr(d, f), jax.Array)
                                 for d in deltas for f in _SLOTMAP_READS))
        return out

    def poll(self) -> Optional[TickReport]:
        """Advance one tick if a delta is queued; None otherwise.

        Dispatch is asynchronous — the returned report's scores are a
        device array the tick is still free to be computing; only
        `scores()`/`top_anomalies()` (or the caller) force the sync.
        """
        self._check_open("poll")
        deltas = self._ingestor.get()
        if deltas is None:
            return None
        with TraceAnnotation("finger.dispatch"):
            dists, self._states = self._plan.tick(self._states, deltas)
        self._last_scores = dists
        self._step += 1
        every = self._config.checkpoint.every_ticks
        if every is not None and self._step % every == 0:
            self.save()
        return TickReport(step=self._step, scores=dists)

    # -- pool-stacked tick hooks (the fleet's batched poll) --------------
    def begin_pool_tick(self) -> GraphDelta:
        """Hand this shard's oldest queued tick to a pool-stacked launch
        (`fleet.pooltick.tick_pool`) *without* transferring it — the
        stacked jit's own argument transfer moves all S shards' deltas
        at once instead of S serialized `block_until_ready` syncs.

        Raises when the queue is empty: the fleet stages an (all-zero
        if need be) delta into every live shard each tick, so an empty
        queue here means ingest/poll alternation was broken.
        """
        self._check_open("begin_pool_tick")
        deltas = self._ingestor.pop()
        if deltas is None:
            raise ServiceLifecycleError(
                "begin_pool_tick with an empty ingestion queue — the "
                "fleet must stage every live shard (an empty stacked "
                "delta at minimum) before a pool-stacked poll")
        return deltas

    def finish_pool_tick(self, scores: jax.Array,
                         states: FingerState) -> TickReport:
        """Absorb one pool-stacked launch's result for this shard: its
        (B,) score row and updated stacked state (both unstacked inside
        the jit — no extra dispatch). Mirrors `poll`'s bookkeeping
        exactly, including the periodic checkpoint policy, so the
        management plane (migrations, save/restore, score_at) cannot
        tell the shard ticked as part of a stack.
        """
        self._check_open("finish_pool_tick")
        self._states = states
        self._last_scores = scores
        self._step += 1
        every = self._config.checkpoint.every_ticks
        if every is not None and self._step % every == 0:
            self.save()
        return TickReport(step=self._step, scores=scores)

    def scores(self) -> Optional[np.ndarray]:
        """Latest tick's (B,) per-stream JSdist scores on host (blocks
        until the tick lands); None before the first tick."""
        self._check_open("scores")
        if self._last_scores is None:
            return None
        with TraceAnnotation("finger.d2h"):
            return np.asarray(self._last_scores)

    def top_anomalies(self, k: Optional[int] = None,
                      per_pod: bool = False
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """The k highest-scoring streams of the latest tick, computed
        where the scores live: per-shard `lax.top_k` + a num_shards·k
        candidate merge — the (B,) score vector is never gathered.

        Returns ``(values, stream_ids)``, each (k,) descending — or
        (n_pods, k) with ``per_pod=True`` under the multipod placement.
        """
        self._check_open("top_anomalies")
        if self._last_scores is None:
            raise ServiceLifecycleError(
                "top_anomalies before the first completed tick")
        k = self._config.topk.k if k is None else k
        if per_pod:
            if not isinstance(self._plan, MultiPodPlan):
                raise ServiceConfigError(
                    "per_pod top-k needs placement='multipod', got "
                    f"{self._config.placement!r}")
            vals, ids = self._plan.pod_topk(self._last_scores, k)
        else:
            vals, ids = self._plan.topk(self._last_scores, k)
        with TraceAnnotation("finger.d2h"):
            vals = np.asarray(vals)
        with TraceAnnotation("finger.d2h"):
            ids = np.asarray(ids)
        return vals, ids

    def score_at(self, slot: int) -> Optional[float]:
        """The latest tick's score of one stream slot, read through a
        jitted dynamic index (one compile per (B,) shape, not per slot
        — and never a full (B,) gather). None before the first tick."""
        self._check_open("score_at")
        self._require_slot(slot, "score_at")
        if self._last_scores is None:
            return None
        score = _score_at_jit(self._last_scores, np.int32(slot))
        with TraceAnnotation("finger.d2h"):
            return float(np.asarray(score))

    # -- stream-slot hooks (the fleet's shard-facing surface) ------------
    def _require_slot(self, slot: int, what: str) -> None:
        if not 0 <= int(slot) < self._config.batch_size:
            raise ServiceConfigError(
                f"{what}: slot {slot} outside this service's "
                f"batch_size={self._config.batch_size}")

    def _require_idle(self, what: str) -> None:
        if self.pending:
            raise ServiceLifecycleError(
                f"{what} with {self.pending} ingested tick(s) still "
                "pending; poll() them first — swapping a stream row "
                "under a queued tick would tear the stream")

    def extract_stream(self, slot: int):
        """One stream's state row (slot axis dropped), still on device
        — the fleet migration's read half. A jitted dynamic gather with
        the slot traced, so extraction compiles once per stacked shape.
        The stacked state is not consumed. Requires an empty queue."""
        self._check_open("extract_stream")
        self._require_slot(slot, "extract_stream")
        self._require_idle("extract_stream")
        return migrate.take_stream(self._states, slot)

    def install_stream(self, slot: int, row, slot_map=None) -> None:
        """Write ``row`` (a single-stream state shaped/laid out like
        one row of this service's stacked state — e.g. another shard's
        `extract_stream` output re-embedded into this layout) into
        ``slot``. Host (numpy) rows transfer as part of the jitted
        update. Sparse services additionally take the stream's rebuilt
        `SlotMap`. Requires an empty queue."""
        self._check_open("install_stream")
        self._require_slot(slot, "install_stream")
        self._require_idle("install_stream")
        if self._config.method == "sparse_tick":
            if slot_map is None:
                raise ServiceConfigError(
                    "install_stream: sparse streams carry a host-side "
                    "SlotMap — pass the row's map")
            if (slot_map.layout.n_slots, slot_map.layout.m_pad) != \
                    (self._capacity.n_slots, self._capacity.m_pad):
                raise ServiceConfigError(
                    f"install_stream: SlotMap capacities "
                    f"(n_slots={slot_map.layout.n_slots}, "
                    f"m_pad={slot_map.layout.m_pad}) != this service's "
                    f"(n_slots={self._capacity.n_slots}, "
                    f"m_pad={self._capacity.m_pad})")
        elif slot_map is not None:
            raise ServiceConfigError(
                "install_stream: slot_maps are sparse-only state "
                f"(method={self._config.method!r})")
        self._states = migrate.put_stream(
            self._states, row, slot,
            out_shardings=self._plan.state_sharding())
        if slot_map is not None:
            slot_map.stream = slot
            self._slot_maps[slot] = slot_map

    def clear_stream(self, slot: int) -> None:
        """Zero one stream's row back to the free-slot state (inactive
        everywhere, all statistics 0 — its score against an empty delta
        is exactly 0). The fleet migration's source-side release.
        Requires an empty queue."""
        self._check_open("clear_stream")
        self._require_slot(slot, "clear_stream")
        self._require_idle("clear_stream")
        self._states = migrate.clear_stream(
            self._states, slot,
            out_shardings=self._plan.state_sharding())
        if self._config.method == "sparse_tick":
            from repro.core.sparse import SlotMap

            self._slot_maps[slot] = SlotMap(
                self._capacity, n_virtual=self._config.n_pad,
                stream=slot)

    # -- persistence -----------------------------------------------------
    def save(self, directory: Optional[str] = None) -> str:
        """Checkpoint the stacked state (atomic write, config-declared
        prune policy). Returns the checkpoint path.

        Sparse services checkpoint too: the host-side per-stream
        `SlotMap`s — part of the stream state (virtual-id → slot
        assignments and the free-list allocation order) — serialize
        into the manifest metadata next to the recorded slot
        capacities, so `restore` rebuilds translation exactly."""
        self._check_open("save")
        ckpt_dir = directory or self._config.checkpoint.directory
        if ckpt_dir is None:
            raise ServiceConfigError(
                "save: ServiceConfig.checkpoint.directory is None and "
                "no directory was passed — declare one in the config")
        states = jax.block_until_ready(self._states)
        meta = {
            "kind": _CKPT_KIND,
            "b": int(states.q.shape[0]),
            "n_pad": (self._config.n_pad
                      if self._config.method == "sparse_tick"
                      else int(states.strengths.shape[-1])),
            "has_node_mask": states.node_mask is not None,
            "layout_generation": self._layout.generation,
            "exact_smax": self._config.exact_smax,
            "method": self._config.method,
            "service": {"placement": self._config.placement,
                        "ingestion": self._config.ingestion,
                        "k_pad": self._config.k_pad},
        }
        extra = None
        if self._config.method == "sparse_tick":
            meta["sparse"] = {
                "n_slots": int(self._capacity.n_slots),
                "m_pad": int(self._capacity.m_pad),
                "generation": int(self._capacity.generation),
            }
            meta["slot_maps"], extra = slot_map_checkpoint(
                self._slot_maps)
        return save_checkpoint(ckpt_dir, self._step, states,
                               metadata=meta,
                               prune_policy=self._config.checkpoint.prune,
                               extra_arrays=extra)

    # -- live migration --------------------------------------------------
    def _journal(self, record: dict) -> None:
        """Append a migration record to the checkpoint directory's
        layout log (no-op for ephemeral services) so old-generation
        checkpoints stay restorable through the migration."""
        ckpt_dir = self._config.checkpoint.directory
        if ckpt_dir is not None:
            migrate.append_layout_record(ckpt_dir, record)

    def _install_migration(self, states: FingerState,
                           new_layout: NodeLayout, pending) -> None:
        """Common tail of repad/compact: swap config/plan/layout, rebuild
        the ingestor, and re-enqueue the prefetched ticks (already
        migrated into the new layout by the caller — applying them
        as-is after the migration would scatter into the wrong slots).

        The plan comes from the warm `PlanCache` when this layout was
        predicted (`warm_next_layouts`): the swap then installs an
        already-compiled tick and serving resumes without a compile
        pause; a cache miss falls back to the cold `build_plan` path.
        """
        self._config = self._config.with_(n_pad=new_layout.n_pad)
        if self._config.plan_cache.enabled:
            self._plan = self._plan_cache.get(self._config,
                                              self._plan.mesh,
                                              new_layout)
        else:
            self._plan = build_plan(self._config, self._plan.mesh)
        self._layout = new_layout
        self._states = states
        self._ingestor = self._make_ingestor()
        for deltas in pending:
            self._ingestor.put(deltas)

    def _take_pending_migrated(self, transform):
        """Drain the queue through ``transform`` (the migration's delta
        re-layout). Atomic: if any prefetched tick cannot be migrated
        (e.g. a queued join addressing a slot the compaction would
        drop), the queue is restored and the migration aborts with the
        service exactly as it was."""
        pending = self._ingestor.take_all()
        try:
            return [transform(d) for d in pending]
        except LayoutMigrationError:
            for d in pending:
                self._ingestor.put(d)
            raise

    def _commit_shrink(self, new_layout: NodeLayout,
                       states_new: FingerState,
                       index_map: np.ndarray) -> None:
        """Common commit of a shrinking migration (compact / repad
        truncation) whose new state has ALREADY been computed (the
        transforms are pure and non-donating, so nothing is mutated
        yet): migrate the prefetched queue first (clean abort path —
        a queued tick addressing a dropped slot raises with the
        service untouched), then install + journal."""
        pending = self._take_pending_migrated(
            lambda d: migrate.remap_delta(d, index_map,
                                          new_layout.n_pad))
        record = migrate.migration_record(
            "compact", self._layout, new_layout, index_map)
        self._absorb_index_map(index_map)
        self._install_migration(states_new, new_layout, pending)
        self._journal(record)

    def repad(self, new_n_pad: int) -> None:
        """Migrate the shared node layout to ``new_n_pad`` in place.

        Growth — the path for a tenant outgrowing `n_pad` (the old
        behavior was a hard constructor error with no way forward) — is
        a jitted device-side embed: new slots are inactive with zero
        strength (padding is exact for every FINGER statistic), the
        stacked state never round-trips through host, and under the
        sharded/multipod placements the same compiled call reshards in
        place. Shrinking is allowed only when every slot at/above
        ``new_n_pad`` is inactive in every stream; anything else would
        silently truncate live state and raises `LayoutMigrationError`
        (use `compact()` to also reclaim interior holes).

        Prefetched ticks still in the ingestion queue are re-laid-out
        into the new layout as part of the migration. Subsequent deltas
        must be built with ``n_pad=new_n_pad``.
        """
        self._check_open("repad")
        old = self._layout.n_pad
        if new_n_pad == old:
            raise ServiceConfigError(
                f"repad: already at n_pad={old}")
        if self._config.method == "sparse_tick":
            # Virtual-space bump: n_pad is a host-side addressing bound
            # only — no device array, no compiled program and no queued
            # slot-space delta depends on it — so the migration is free:
            # no state transform, no plan swap, no compile, no journal.
            if new_n_pad < old:
                raise LayoutMigrationError(
                    f"repad: the sparse virtual space only grows "
                    f"(new_n_pad={new_n_pad} < {old}); nothing is "
                    "sized by n_pad, so shrinking it reclaims nothing")
            self._config = self._config.with_(n_pad=new_n_pad)
            self._plan.config = self._plan.config.with_(n_pad=new_n_pad)
            self._ingestor.config = self._config
            for sm in self._slot_maps:
                sm.grow_virtual(new_n_pad)
            self._layout = NodeLayout(
                new_n_pad, generation=self._layout.generation)
            return
        if new_n_pad > old:
            migrate.check_journalable(self._config.checkpoint.directory,
                                      self._layout.generation)
            pending = self._take_pending_migrated(
                lambda d: migrate.embed_delta(d, new_n_pad))
            new_layout = self._layout.grown(new_n_pad)
            states = migrate.grow_stacked(
                self._states, new_layout,
                out_shardings=self._plan.state_sharding())
            record = migrate.migration_record(
                "grow", self._layout, new_layout, index_map=None)
            # Generation-stamped deltas survive a grow exactly (slot
            # ids are unchanged — an identity injection); raw old-size
            # deltas stay rejected (ambiguous by size alone).
            self._absorb_generation_map(identity_index_map(old))
            self._install_migration(states, new_layout, pending)
            self._journal(record)
            return
        occ = migrate.occupancy(self._states)
        lost = np.nonzero(occ[new_n_pad:])[0] + new_n_pad
        if lost.size:
            # Raise before touching the queue: a refused migration
            # must leave the service (and its prefetched ticks)
            # exactly as they were.
            raise LayoutMigrationError(
                f"repad: new_n_pad={new_n_pad} would truncate "
                f"active node slot(s) {lost[:8].tolist()} — a lossy "
                "migration; grow instead, or compact() after the "
                "tenants holding those slots leave")
        migrate.check_journalable(self._config.checkpoint.directory,
                                  self._layout.generation)
        new_layout = self._layout.compacted(new_n_pad)
        states = migrate.truncate_stacked(
            self._states, new_layout,
            out_shardings=self._plan.state_sharding())
        index_map = np.full((old,), -1, np.int32)
        index_map[:new_n_pad] = np.arange(new_n_pad, dtype=np.int32)
        self._commit_shrink(new_layout, states, index_map)

    def _absorb_generation_map(self, index_map: np.ndarray) -> None:
        """Chain the generation-keyed grace table through one more
        migration and give the just-retired generation a direct entry.
        Keys are migration generations, so nothing ever shadows — the
        table stays exact across size-reusing chains. Retention: the
        config's ``grace_generations`` bounds the table (one composed
        map per migration otherwise accumulates for the service's
        lifetime); a delta stamped with a pruned generation raises
        `ingest.GraceLapseError`."""
        self._remaps_gen = {g: compose_index_maps(m, index_map)
                            for g, m in self._remaps_gen.items()}
        self._remaps_gen[self._layout.generation] = \
            np.asarray(index_map, np.int32)
        self._remaps_gen = migrate.prune_generation_remaps(
            self._remaps_gen, self._layout.generation + 1,
            self._config.grace_generations)

    def _absorb_index_map(self, index_map: np.ndarray) -> None:
        """Compose a fresh old→new map into the ingestion grace tables.
        In the legacy size-keyed table, existing entries chain through
        it and the just-retired layout gains a direct entry keyed by
        its n_pad — the only address a *raw* `GraphDelta` carries, so a
        later migration re-using a size shadows the older generation of
        that size; the generation-keyed table has no such ambiguity."""
        self._remaps = {k: compose_index_maps(m, index_map)
                        for k, m in self._remaps.items()}
        self._remaps[self._layout.n_pad] = np.asarray(index_map, np.int32)
        self._absorb_generation_map(index_map)

    def compact(self, new_n_pad: Optional[int] = None) -> CompactionReport:
        """Drop permanently-left node slots and renumber the survivors.

        A slot is reclaimable when it is inactive in *every* stream —
        such a slot holds exactly zero strength and zero mask, so S,
        Σs², Σ_E w² and s_max are all invariant and only the addressing
        changes. The old→new index map stays installed: ingestion keeps
        remapping deltas addressed in the pre-compaction layout, and the
        checkpoint directory's layout log records the migration so
        old-generation checkpoints restore through it.

        Transfer-free state path: slot occupancy, the prefix-sum
        renumbering and the survivor gather all run ON DEVICE
        (`migrate.compact_stacked_auto` — transfer-guard-tested like
        `grow_stacked`). The only host readbacks are one scalar (the
        live-slot count, which fixes the static target size) and the
        small (n_pad,) index map the journal and ingestion grace table
        need host-side anyway; the stacked (B, n_pad) state never
        leaves the devices.

        ``new_n_pad`` defaults to exactly the live-slot count; passing a
        larger value leaves headroom for future joins, and a value below
        the live count raises `LayoutMigrationError`. Prefetched queue
        ticks are re-laid-out (remapped) as part of the migration.
        Returns a `CompactionReport`; when nothing is reclaimable (and
        no explicit ``new_n_pad`` asks for a resize) the service is left
        untouched with ``reclaimed == 0``.
        """
        self._check_open("compact")
        if self._config.method == "sparse_tick":
            raise ServiceConfigError(
                "compact: the sparse slot space self-compacts — freed "
                "node/edge slots return to each stream's SlotMap free "
                "list and are reused in place, so there is no "
                "cross-stream layout to renumber (grow_capacity() is "
                "the sparse migration)")
        n_live = migrate.live_slot_count(self._states)
        target = max(n_live, 1) if new_n_pad is None else int(new_n_pad)
        if target < n_live:
            raise LayoutMigrationError(
                f"compact: new_n_pad={target} < {n_live} live slot(s) — "
                "a lossy migration; only permanently-left slots can be "
                "reclaimed")
        if target >= self._layout.n_pad:
            if new_n_pad is None:
                # Nothing reclaimable: every slot is live somewhere.
                return CompactionReport(
                    old_n_pad=self._layout.n_pad,
                    new_n_pad=self._layout.n_pad, n_live=n_live,
                    generation=self._layout.generation,
                    index_map=np.arange(self._layout.n_pad,
                                        dtype=np.int32))
            raise LayoutMigrationError(
                f"compact: new_n_pad={target} does not shrink the "
                f"current n_pad={self._layout.n_pad} (repad() grows)")
        migrate.check_journalable(self._config.checkpoint.directory,
                                  self._layout.generation)
        new_layout = self._layout.compacted(target)
        # Pure device-side transform — nothing installed yet, so the
        # lossy-queued-tick abort below leaves the service untouched.
        states, imap_device = migrate.compact_stacked_auto(
            self._states, new_layout,
            out_shardings=self._plan.state_sharding())
        index_map = np.asarray(jax.device_get(imap_device), np.int32)
        self._commit_shrink(new_layout, states, index_map)
        return CompactionReport(
            old_n_pad=int(index_map.shape[0]),
            new_n_pad=new_layout.n_pad,
            n_live=n_live, generation=new_layout.generation,
            index_map=index_map)

    def grow_capacity(self, n_slots: Optional[int] = None,
                      m_pad: Optional[int] = None):
        """Grow the sparse device capacities (either axis) in place —
        the ``method="sparse_tick"`` counterpart of a growing `repad`.

        A jitted device-side pad of the stacked (B, n_slots) strengths/
        mask and (B, m_pad) edge store (`migrate.grow_sparse_stacked`):
        slot ids are preserved (growth appends free slots to every
        stream's `SlotMap`), so no state renumbering, no delta remap —
        prefetched queue ticks are re-embedded by a static size swap
        only — and no ingestion grace table. The plan swaps through the
        warm `PlanCache` when the target capacity was predicted
        (`warm_next_layouts`), so a warmed growth pays no compile
        pause. Returns the new `SparseLayout`.
        """
        self._check_open("grow_capacity")
        if self._config.method != "sparse_tick":
            raise ServiceConfigError(
                f"grow_capacity: a sparse-only migration "
                f"(method={self._config.method!r}); repad() migrates "
                "the dense layout")
        new_capacity = self._capacity.grown(n_slots=n_slots, m_pad=m_pad)
        pending = self._take_pending_migrated(
            lambda d: migrate.embed_sparse_delta(d, new_capacity.n_slots))
        states = migrate.grow_sparse_stacked(
            self._states, new_capacity,
            out_shardings=self._plan.state_sharding())
        self._config = self._config.with_(n_slots=new_capacity.n_slots,
                                          m_pad=new_capacity.m_pad)
        if self._config.plan_cache.enabled:
            self._plan = self._plan_cache.get(self._config,
                                              self._plan.mesh,
                                              new_capacity)
        else:
            self._plan = build_plan(self._config, self._plan.mesh)
        self._capacity = new_capacity
        for sm in self._slot_maps:
            sm.grow(new_capacity)
        self._states = states
        self._ingestor = self._make_ingestor()
        for d in pending:
            self._ingestor.put(d)
        return new_capacity

    def warm_next_layouts(self, targets: Optional[Sequence[int]] = None,
                          background: bool = False
                          ) -> Union[list, WarmupHandle]:
        """Pre-compile execution plans (and migration transforms) for
        predicted next layouts, so a later `repad`/`compact` swaps to
        an already-compiled plan without a compile pause.

        Call it from serving idle time (between polls) — warming costs
        the compiles the migration would otherwise pay while stalled.
        With ``background=True`` the compiles run on a daemon thread
        and a `WarmupHandle` is returned instead of the warmed list:
        ``handle.wait()`` joins (re-raising any warming error) — the
        caller no longer pays the compile inline. Target prediction
        (which reads the live state) still happens on the calling
        thread; `PlanCache` insertion is thread-safe. Do not migrate
        while a background warm is in flight — ``wait()`` first.
        ``targets`` is a list of n_pad values; the default prediction
        comes from `ServiceConfig.plan_cache`:

        - the repad growth schedule: ``round(n_pad * growth_factor)``;
        - the pending compaction target (``warm_compact``): the current
          live-slot count. The device-side compaction renumbers
          dynamically, so the warmed transform stays valid no matter
          which slots die — only the target size must still match when
          `compact()` runs.

        For each target this compiles (a) the post-migration tick +
        default top-k via `ExecutionPlan.warm_tick` and (b) the
        device-side state transform (`grow_stacked` /
        `compact_stacked_auto`) on zero dummies of the current shapes.
        Returns the list of warmed n_pad targets.

        Under ``method="sparse_tick"`` the targets are
        ``(n_slots, m_pad)`` capacity pairs instead of n_pad values
        (virtual repads are free and need no warming); the default
        prediction scales both capacities by ``growth_factor``, and the
        warmed transform is `grow_sparse_stacked`.
        """
        self._check_open("warm_next_layouts")
        policy = self._config.plan_cache
        if not policy.enabled:
            targets = []
        elif targets is None:
            targets = self._default_warm_targets(policy)
        else:
            targets = list(targets)
        if background:
            return WarmupHandle(lambda: self._warm_targets(targets))
        return self._warm_targets(targets)

    def _default_warm_targets(self, policy) -> list:
        """The `PlanCachePolicy` prediction: the geometric grow target
        plus (dense, ``warm_compact``) the pending compaction target.
        Reads the live state — always runs on the calling thread, even
        for a background warm."""
        if self._config.method == "sparse_tick":
            cap = self._capacity
            return [(int(round(cap.n_slots * policy.growth_factor)),
                     int(round(cap.m_pad * policy.growth_factor)))]
        n_pad = self._layout.n_pad
        targets = []
        grow = int(round(n_pad * policy.growth_factor))
        if grow > n_pad:
            targets.append(grow)
        if policy.warm_compact:
            n_live = migrate.live_slot_count(self._states)
            if 0 < n_live < n_pad:
                targets.append(n_live)
        return targets

    def _warm_targets(self, targets: Sequence) -> list:
        """The compile loop of `warm_next_layouts` (inline or on the
        warming thread)."""
        if self._config.method == "sparse_tick":
            cap = self._capacity
            warmed = []
            for n_slots, m_pad in targets:
                n_slots, m_pad = int(n_slots), int(m_pad)
                if (n_slots, m_pad) == (cap.n_slots, cap.m_pad) \
                        or n_slots < cap.n_slots or m_pad < cap.m_pad:
                    continue
                new_capacity = cap.grown(n_slots=n_slots, m_pad=m_pad)
                cfg = self._config.with_(n_slots=n_slots, m_pad=m_pad)
                plan = self._plan_cache.warm(cfg, self._plan.mesh,
                                             new_capacity)
                dummy = jax.tree_util.tree_map(jnp.zeros_like,
                                               self._states)
                migrate.grow_sparse_stacked(
                    dummy, new_capacity,
                    out_shardings=plan.state_sharding())
                warmed.append((n_slots, m_pad))
            return warmed
        n_pad = self._layout.n_pad
        warmed = []
        for target in targets:
            target = int(target)
            if target == n_pad or target <= 0:
                continue
            new_layout = self._layout.grown(target) if target > n_pad \
                else self._layout.compacted(target)
            cfg = self._config.with_(n_pad=target)
            plan = self._plan_cache.warm(cfg, self._plan.mesh,
                                         new_layout)
            # Dummies with the live state's shapes/layout/sharding
            # populate exactly the jit cache entry the migration hits.
            dummy = jax.tree_util.tree_map(jnp.zeros_like, self._states)
            if target > n_pad:
                migrate.grow_stacked(
                    dummy, new_layout,
                    out_shardings=plan.state_sharding())
            else:
                migrate.compact_stacked_auto(
                    dummy, new_layout,
                    out_shardings=plan.state_sharding())
            warmed.append(target)
        return warmed

    @property
    def plan_cache(self) -> PlanCache:
        """The warm plan pool (introspection: `len`, warmed layouts)."""
        return self._plan_cache

    # -- teardown --------------------------------------------------------
    def close(self) -> None:
        """Block on in-flight work and drop the queue. Idempotent; every
        other method raises `ServiceLifecycleError` afterwards."""
        if self._closed:
            return
        jax.block_until_ready(self._states)
        self._ingestor.drain()
        self._closed = True

    def __enter__(self) -> "FingerService":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
