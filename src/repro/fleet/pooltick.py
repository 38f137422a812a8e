"""Pool-stacked shard ticks: one device launch per pool per fleet tick.

PR 8's steady-state `FingerFleet.poll()` dispatched each live shard's
`FingerService.poll()` sequentially from Python — S launches (plus S
blocking host→device delta transfers through `SyncIngestor.get`) per
pool per tick, even though every shard of a pool runs the *same*
compiled tick body over identically-shaped `(B, n_pad)` state. This
module collapses that to ONE jitted launch per pool: the per-shard
states are stacked along a leading shard axis *inside* the jit (so the
stack itself is device work, not S extra dispatches), advanced as one
(S, B, …) program, and unstacked back to per-shard states and
per-shard score rows, again inside the same jit.

The per-shard `FingerService`s stay the management-plane view:
migrations, kill/recover, and save/restore peel a shard out of the
stack (it simply stops appearing in the group passed here) and back in,
and `warm_pool_tick` pre-compiles the stacked program for a predicted
shard grouping exactly like `PlanCache.warm` does for per-shard plans.

Stacking requires every shard in a group to share its static tick
signature: same `NodeLayout` (n_pad AND generation — both are static
aux of the state pytree), same sparse capacity where applicable, and
the same per-shard delta statics. The fleet groups live shards by
`service.layout` (plus `service.capacity` for sparse pools) before
calling `tick_pool`. The group size S is part of the pytree structure,
so jit transparently keys one compiled program per (S, layout) — a
shard leaving the stack (kill/compact) changes the group and hits a
different cache entry, which the rebalancer pre-warms.

All four methods stack. The vmappable dense methods (``"dense"``,
``"compact"``) wrap the engine's batched tick body in an outer
shard-axis `jax.vmap` — plain jax ops, so the outer vmap is exact. The
Pallas megakernel methods (``"fused_tick"``, ``"sparse_tick"``) do NOT
vmap their `pallas_call` (vmapping a kernel changes its grid
semantics); they dispatch the stacked (S, B, ·) pytrees straight into
the kernels' shard-stacked entry points
(`kernels.stream_tick.ops.stream_tick_fused_stacked`,
`kernels.sparse_tick.ops.sparse_tick_fused_stacked`) — ONE
`pallas_call` over an extended (S, B) grid, per-grid-step bodies and
VMEM footprint unchanged. `group_fits` is the admission guard: a group
whose S-stacked operand set exceeds the device-residency budget
(`kernels.dispatch.stacked_budget_bytes`) is routed back to sequential
per-shard `poll()` launches by the fleet instead of failing device
allocation mid-serve.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.engine.stream import StreamEngine
from repro.fleet.errors import PoolGroupError
from repro.serving.plans import dummy_tick_args

#: Every serving method ticks as one stacked launch per layout group.
#: Dense methods stack by outer vmap; the megakernels by their native
#: (S, B)-gridded stacked entry points.
_STACKABLE_METHODS = ("dense", "compact", "fused_tick", "sparse_tick")


def stackable(method: str) -> bool:
    """True when ``method``'s pool can tick as one stacked launch."""
    return method in _STACKABLE_METHODS


def group_fits(configs: Sequence) -> bool:
    """Whether one layout-group is admissible as a single stacked
    launch under the device-residency budget.

    ``configs`` are the group members' live `ServiceConfig`s (len = S).
    Dense/compact groups always fit (their stacked operands are the
    same arrays the sequential path already keeps resident). Megakernel
    groups consult the kernel packages' stacked admission checks —
    per-grid-step VMEM fit (unchanged by stacking) AND total S-stacked
    operand residency (`dispatch.stacked_budget_bytes`). The fleet
    routes a failing group to sequential per-shard `poll()` launches.
    """
    configs = list(configs)
    if not configs:
        return True
    cfg = configs[0]
    s = len(configs)
    if cfg.method == "fused_tick":
        from repro.kernels.stream_tick.ops import fits_fused_tick_stacked

        return fits_fused_tick_stacked(s, cfg.batch_size, cfg.n_pad,
                                       cfg.k_pad, cfg.j_pad)
    if cfg.method == "sparse_tick":
        from repro.kernels.sparse_tick.ops import fits_sparse_tick_stacked

        return fits_sparse_tick_stacked(s, cfg.batch_size, cfg.n_slots,
                                        cfg.m_pad, cfg.k_pad, cfg.j_pad)
    return True


@functools.lru_cache(maxsize=None)
def pool_tick_fn(exact_smax: bool, method: str):
    """The jitted stacked-pool tick for one engine config.

    Signature: ``(states_seq, deltas_seq) -> (dists, rows, shard_states)``
    where the inputs are same-length tuples of per-shard stacked
    `(B, ...)` pytrees sharing one static layout, ``dists`` is the
    on-device (S, B) score matrix (the fleet's score plane), ``rows``
    are its S per-shard (B,) rows and ``shard_states`` the S updated
    per-shard states — both unstacked INSIDE the jit, so handing them
    back to the per-shard `FingerService`s costs zero extra launches.

    The stacked body is method-dependent: dense/compact shard-vmap the
    engine's batched tick; fused/sparse call the kernels' shard-stacked
    megakernel entry points on the stacked pytrees directly (one
    (S, B)-gridded `pallas_call`, never a vmapped kernel).

    The whole per-shard state tuple is donated: the fleet owns those
    states and immediately rebinds each shard to its returned one.
    Cached per (exact_smax, method); jit itself keys per group size S
    (tuple length is pytree structure) and per static layout.
    """
    if not stackable(method):
        raise ValueError(
            f"pool_tick_fn: method {method!r} is not stackable; gate "
            "with stackable() and fall back to per-shard poll()")
    if method == "fused_tick":
        from repro.kernels.stream_tick.ops import stream_tick_fused_stacked

        def body(stacked, sdeltas):
            return stream_tick_fused_stacked(stacked, sdeltas,
                                             exact_smax=exact_smax)
    elif method == "sparse_tick":
        from repro.kernels.sparse_tick.ops import sparse_tick_fused_stacked

        def body(stacked, sdeltas):
            return sparse_tick_fused_stacked(stacked, sdeltas,
                                             exact_smax=exact_smax)
    else:
        engine = StreamEngine(exact_smax=exact_smax, method=method)
        body = jax.vmap(engine._tick_body)

    def run(states_seq, deltas_seq):
        # The device operations of the pool tick carry this name in the
        # profiler's trace, whatever the jitted function is called.
        with jax.named_scope("finger.tick"):
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *states_seq)
            sdeltas = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *deltas_seq)
            dists, new_states = body(stacked, sdeltas)
            s = len(states_seq)
            rows = tuple(dists[i] for i in range(s))
            shard_states = tuple(
                jax.tree_util.tree_map(lambda x, _i=i: x[_i], new_states)
                for i in range(s))
            return dists, rows, shard_states

    return jax.jit(run, donate_argnums=(0,))


def tick_pool(services: Sequence) -> jax.Array:
    """Advance one layout-group of live shards as a single launch.

    ``services`` are `FingerService`s sharing one `ServiceConfig` shape
    and one current `NodeLayout` (and sparse capacity — the fleet
    groups by layout first). Each shard's queued stacked delta is
    popped un-transferred (`begin_pool_tick`), the whole group runs
    through `pool_tick_fn`, and each shard absorbs its row + updated
    state (`finish_pool_tick`). Returns the on-device (S, B) score
    matrix in ``services`` order — the fleet's per-pool score plane.
    """
    svcs = list(services)
    first = svcs[0].config
    fn = pool_tick_fn(first.exact_smax, first.method)
    states = tuple(svc.states() for svc in svcs)
    deltas = tuple(svc.begin_pool_tick() for svc in svcs)
    with TraceAnnotation("finger.dispatch"):
        dists, rows, shard_states = fn(states, deltas)
    for svc, row, st in zip(svcs, rows, shard_states):
        svc.finish_pool_tick(row, st)
    return dists


def warm_pool_tick(entries: Sequence[Tuple[object, object]]) -> None:
    """Pre-compile the stacked tick for one predicted shard grouping.

    ``entries`` is the group as (ServiceConfig, layout) pairs — a
    `NodeLayout` for the dense methods, a `SparseLayout` capacity for
    ``"sparse_tick"`` — the same prediction surface `PlanCache.warm`
    uses, so the rebalancer warms the stacked program for the *current*
    grouping and for every predicted post-migration regrouping (a
    compaction peels a shard out of the group AND re-keys that shard's
    own singleton group). Runs the jit once on zero dummies and blocks,
    exactly like `ExecutionPlan.warm_tick`.

    Every entry must share one tick method: a stacked launch compiles
    ONE body, so a mixed-method entry list cannot be a real group —
    it raises `PoolGroupError` by name instead of silently warming the
    first entry's program for shards that will never run it. A group
    failing `group_fits` is skipped: the fleet ticks it shard by shard,
    so there is no stacked program to warm (the rebalancer warms those
    shards' own plans).
    """
    entries = list(entries)
    if not entries:
        return
    methods = sorted({cfg.method for cfg, _ in entries})
    if len(methods) > 1:
        raise PoolGroupError(
            f"warm_pool_tick: mixed-method entry list {methods} — a "
            "stacked launch compiles one tick body; group shards by "
            "pool (method) before warming")
    first = entries[0][0]
    if not stackable(first.method):
        return
    if not group_fits([cfg for cfg, _ in entries]):
        return
    fn = pool_tick_fn(first.exact_smax, first.method)
    args = [dummy_tick_args(cfg, layout) for cfg, layout in entries]
    states = tuple(a[0] for a in args)
    deltas = tuple(a[1] for a in args)
    dists, _, _ = fn(states, deltas)
    jax.block_until_ready(dists)


def group_by_layout(services: Sequence) -> List[List]:
    """Split a pool's live shards into stackable layout groups.

    Shards of one pool share a `ServiceConfig` at open time, but
    compaction gives individual shards private layouts (smaller n_pad,
    bumped generation) — those tick in their own (possibly singleton)
    group. Sparse shards additionally key on their live `SparseLayout`
    capacity (n_slots, m_pad, generation): a shard whose capacity grew
    (`grow_capacity`) no longer shares a compiled stacked program with
    its siblings. Order within each group follows ``services`` order,
    and group order follows first appearance, so the fleet's shard→row
    bookkeeping is deterministic.
    """
    groups: dict = {}
    for svc in services:
        key = (svc.layout, svc.config.n_pad, svc.capacity)
        groups.setdefault(key, []).append(svc)
    return list(groups.values())
