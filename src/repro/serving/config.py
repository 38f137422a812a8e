"""ServiceConfig: the one declarative description of a FINGER service.

Every placement/ingestion/query/checkpoint decision that used to be
re-plumbed per call site (``method=``, ``n_pad``/``k_pad``, mesh
construction, ``shard_map`` vs vmap, checkpoint paths) is stated once
here, validated up front with named errors, and compiled once into an
`ExecutionPlan` by `FingerService.open`.

The config is a frozen dataclass and deliberately *static*: everything
in it participates in the single up-front compilation of the serving
tick, so changing any field means opening a new service (or, for the
one legal live migration, `FingerService.repad`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

# The accepted policy forms are documented (and enforced) in
# `train.checkpoint` — this module only re-exports the alias.
from repro.train.checkpoint import PrunePolicy

PLACEMENTS = ("local", "sharded", "multipod")
INGESTIONS = ("sync", "double_buffered")
METHODS = ("dense", "compact", "fused_tick", "sparse_tick")


class ServiceConfigError(ValueError):
    """A ServiceConfig field (or combination) is invalid.

    Raised at `validate()` / `FingerService.open` time — never from
    inside a compiled tick — so misconfiguration fails before any device
    state exists.
    """


@dataclasses.dataclass(frozen=True)
class CheckpointPolicy:
    """Where and how the stacked serving state persists.

    ``directory=None`` means the service is ephemeral: `save()` raises a
    named error instead of inventing a path. ``every_ticks`` (optional)
    lets `poll()` auto-save each time that many ticks complete.
    """

    directory: Optional[str] = None
    prune: PrunePolicy = 3
    every_ticks: Optional[int] = None

    def validate(self) -> None:
        if self.every_ticks is not None and self.every_ticks <= 0:
            raise ServiceConfigError(
                f"CheckpointPolicy.every_ticks must be positive, got "
                f"{self.every_ticks}")
        if self.every_ticks is not None and self.directory is None:
            raise ServiceConfigError(
                "CheckpointPolicy.every_ticks set but directory is None; "
                "periodic saves need somewhere to go")
        _validate_prune_policy(self.prune)


def _validate_prune_policy(policy: PrunePolicy) -> None:
    """Delegate to `train.checkpoint.resolve_prune_policy` — the single
    source of truth for accepted policy forms — re-raising its
    ValueError as the config-level named error."""
    from repro.train.checkpoint import resolve_prune_policy

    try:
        resolve_prune_policy(policy)
    except ValueError as e:
        raise ServiceConfigError(f"prune policy: {e}") from e


@dataclasses.dataclass(frozen=True)
class PlanCachePolicy:
    """Knobs of the warm `serving.plans.PlanCache` (pre-compiled plans
    for predicted next layouts, so `repad`/`compact` swap without a
    compile pause).

    ``enabled``       : migrations consult the cache at all (disabling
        restores the always-cold `build_plan` path).
    ``growth_factor`` : the predicted next *grow* target is
        ``round(n_pad * growth_factor)`` — `warm_next_layouts` compiles
        the tick and the grow transform for that layout ahead of time.
        Predicting the repad schedule only pays off when producers grow
        geometrically (the default doubling matches the usual
        amortized-growth policy); an exact target can always be passed
        to `FingerService.warm_next_layouts` explicitly.
    ``warm_compact``  : also pre-compile the *pending compaction*
        target (the current live-slot count). The device-side
        compaction's renumbering is dynamic, so the compiled transform
        is valid no matter which slots die — only the target size must
        match at `compact()` time.
    """

    enabled: bool = True
    growth_factor: float = 2.0
    warm_compact: bool = True

    def validate(self) -> None:
        if self.growth_factor <= 1.0:
            raise ServiceConfigError(
                f"PlanCachePolicy.growth_factor must exceed 1.0 "
                f"(a grow prediction must grow), got "
                f"{self.growth_factor}")


@dataclasses.dataclass(frozen=True)
class TopKSpec:
    """Default shape of `top_anomalies` queries.

    ``k`` bounds the per-shard `lax.top_k` width that the sharded plans
    compile, so it must not exceed the per-shard stream count (validated
    against placement in `ServiceConfig.validate`).
    """

    k: int = 8

    def validate(self) -> None:
        if self.k <= 0:
            raise ServiceConfigError(f"TopKSpec.k must be positive, "
                                     f"got {self.k}")


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Declarative FINGER serving configuration (see module docstring).

    Parameters
    ----------
    batch_size : number of concurrent streams B. Fixed for the life of
        the service (the stacked state has a static leading axis).
    n_pad : shared static node layout size. Growable only through the
        explicit `FingerService.repad` migration.
    k_pad : delta-edge slots per stream per tick.
    j_pad : node join/leave slots per delta (None = deltas carry no
        node slots).
    method : update path — ``"dense"`` / ``"compact"`` Δ-statistics
        through the vmapped op chain, ``"fused_tick"`` for the
        single-pass batched Pallas megakernel
        (`repro.kernels.stream_tick`; one kernel launch per tick,
        interpret mode off TPU, oversized tiles fall back to the
        vmapped chain), or ``"sparse_tick"`` for the slot-space sparse
        path (`repro.kernels.sparse_tick`): per-stream state is sized
        by the ``n_slots``/``m_pad`` capacities while ``n_pad`` becomes
        a purely *virtual* addressing bound — no device array scales
        with it, so `repad` is a free host-side bump and tick cost is
        flat in n_pad.
    n_slots : sparse only — active-node slot capacity per stream
        (device arrays are (B, n_slots), grown via
        `FingerService.grow_capacity`). Must be None for dense methods.
    m_pad : sparse only — edge-store slot capacity per stream. Must be
        None for dense methods.
    exact_smax : recompute s_max exactly after deletions (O(n)/stream).
    placement : ``"local"`` (single-device vmap), ``"sharded"``
        (shard_map over ``(data_axis,)``), or ``"multipod"``
        (shard_map over ``(pod_axis, data_axis)``).
    ingestion : ``"double_buffered"`` (default — the transfer of tick
        T+1's deltas overlaps tick T's compute) or ``"sync"`` (the
        explicitly-blocking baseline: host→device transfer serialized
        on the tick's critical path, kept for honest overlap
        measurements).
    max_queue : ingestion queue depth before `ingest` raises.
    checkpoint : CheckpointPolicy (directory, prune policy, cadence).
    topk : TopKSpec for `top_anomalies` queries.
    plan_cache : PlanCachePolicy — warm pre-compiled plans for
        predicted next layouts (`FingerService.warm_next_layouts`), so
        `repad`/`compact` swap without a compile pause.
    grace_generations : how many past migration generations keep a live
        old→new remap for grace-period ingestion. A delta stamped with
        a generation older than ``current - grace_generations`` raises
        `serving.ingest.GraceLapseError` by name. ``None`` retains
        every journaled generation (the remap table then grows without
        bound over the service's migration history — only sensible for
        short-lived services or tests).
    compilation_cache_dir : enable JAX's persistent on-disk compilation
        cache rooted at this directory when the service `open`s or
        `restore`s, so a restarted replica cold-opens near warm-swap
        latency (compiled ticks come back from disk instead of XLA).
        CAVEAT: the cache is **process-global** JAX state — the first
        service to set it wins for the whole process, and it affects
        every jit in the process, not just this service's plans.
        Setting a *different* directory in a process that already
        enabled one raises a named error rather than silently
        re-rooting unrelated caches. A ``JAX_COMPILATION_CACHE_DIR``
        environment variable takes precedence: the cache then stays
        where JAX put it and this field is ignored. Entry points pass a
        fixed path inside the checkout (``.jax_cache/``), never a
        temporary or per-run one — the path is part of the cache key.
    data_axis / pod_axis : mesh axis names the sharded placements bind.
    """

    batch_size: int
    n_pad: int
    k_pad: int
    j_pad: Optional[int] = None
    n_slots: Optional[int] = None
    m_pad: Optional[int] = None
    method: str = "dense"
    exact_smax: bool = False
    placement: str = "local"
    ingestion: str = "double_buffered"
    max_queue: int = 2
    checkpoint: CheckpointPolicy = CheckpointPolicy()
    topk: TopKSpec = TopKSpec()
    plan_cache: PlanCachePolicy = PlanCachePolicy()
    grace_generations: Optional[int] = 3
    compilation_cache_dir: Optional[str] = None
    data_axis: str = "data"
    pod_axis: str = "pod"

    def validate(self, num_shards: Optional[int] = None) -> None:
        """Fail fast with a named error; `num_shards` (the mesh's total
        shard count over the placement axes) adds the divisibility and
        top-k-width checks that need a concrete mesh."""
        if self.batch_size <= 0:
            raise ServiceConfigError(
                f"batch_size must be positive, got {self.batch_size}")
        if self.n_pad <= 0:
            raise ServiceConfigError(
                f"n_pad must be positive, got {self.n_pad}")
        if self.k_pad <= 0:
            raise ServiceConfigError(
                f"k_pad must be positive, got {self.k_pad}")
        if self.j_pad is not None and self.j_pad <= 0:
            raise ServiceConfigError(
                f"j_pad must be positive (or None), got {self.j_pad}")
        if self.method not in METHODS:
            raise ServiceConfigError(
                f"method {self.method!r} not in {METHODS}")
        if self.method == "sparse_tick":
            if self.n_slots is None or self.n_slots <= 0:
                raise ServiceConfigError(
                    f"method='sparse_tick' needs a positive n_slots "
                    f"slot capacity, got {self.n_slots}")
            if self.m_pad is None or self.m_pad <= 0:
                raise ServiceConfigError(
                    f"method='sparse_tick' needs a positive m_pad "
                    f"edge-store capacity, got {self.m_pad}")
        else:
            if self.n_slots is not None or self.m_pad is not None:
                raise ServiceConfigError(
                    f"n_slots/m_pad are sparse-only capacities; "
                    f"method={self.method!r} sizes its state by n_pad "
                    f"alone (got n_slots={self.n_slots}, "
                    f"m_pad={self.m_pad})")
        if self.placement not in PLACEMENTS:
            raise ServiceConfigError(
                f"placement {self.placement!r} not in {PLACEMENTS}")
        if self.ingestion not in INGESTIONS:
            raise ServiceConfigError(
                f"ingestion {self.ingestion!r} not in {INGESTIONS}")
        if self.max_queue <= 0:
            raise ServiceConfigError(
                f"max_queue must be positive, got {self.max_queue}")
        if self.placement == "multipod" and self.pod_axis == self.data_axis:
            raise ServiceConfigError(
                f"multipod placement needs distinct pod/data axes, got "
                f"{self.pod_axis!r} for both")
        if self.grace_generations is not None \
                and self.grace_generations < 0:
            raise ServiceConfigError(
                f"grace_generations must be >= 0 (or None for "
                f"unbounded retention), got {self.grace_generations}")
        if self.compilation_cache_dir is not None \
                and not str(self.compilation_cache_dir).strip():
            raise ServiceConfigError(
                "compilation_cache_dir must be a non-empty path "
                "(or None to leave the process-global JAX compilation "
                "cache untouched)")
        self.checkpoint.validate()
        self.topk.validate()
        self.plan_cache.validate()
        if num_shards is not None:
            if self.batch_size % num_shards != 0:
                raise ServiceConfigError(
                    f"batch_size={self.batch_size} must divide evenly "
                    f"over {num_shards} shard(s) of the "
                    f"{self.placement!r} placement")
            per_shard = self.batch_size // num_shards
            if self.topk.k > per_shard:
                raise ServiceConfigError(
                    f"topk.k={self.topk.k} exceeds the per-shard stream "
                    f"count {per_shard} (batch_size={self.batch_size} "
                    f"over {num_shards} shards); the sharded top-k "
                    f"merge needs k ≤ B/shards")

    def with_(self, **updates) -> "ServiceConfig":
        """`dataclasses.replace` spelled as a method (repad uses it)."""
        return dataclasses.replace(self, **updates)
