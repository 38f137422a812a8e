"""HLO plan auditor: forbidden-op checks on the compiled serving paths.

Lowers and compiles every `ExecutionPlan` tick (all three placements —
multipod via the same 1×N host-mesh trick as the serving smoke tests)
and every `serving.migrate` device-side transform (grow / compact /
truncate), then audits the *optimized* HLO for the invariants the
serving stack's performance claims rest on:

- ``host-transfer-in-tick`` — no infeed/outfeed/send/recv or
  host-memory-space copies anywhere in a compiled hot path;
- ``missing-donation`` — the stacked `FingerState` buffers must be
  donated into the tick (``input_output_alias`` on every state leaf):
  an undonated tick doubles peak HBM for the state;
- ``unexpected-collective`` — the tick is per-stream data-parallel in
  every placement; a collective inside it means a resharding snuck into
  the hot path (cross-shard reductions belong in the top-k query, not
  the tick);
- ``dtype-upcast`` — no f64/c128 anywhere (an accidental weak-type
  promotion can silently double memory traffic).

Note on collectives: on a single-device mesh XLA elides cross-device
ops, so the collective check is only load-bearing when the host exposes
multiple devices (the CLI sets ``--xla_force_host_platform_device_count``
for exactly this reason; under the default test runner it's a trivially
green check, documented as such).

The report is machine-readable (`AuditReport.to_dict`); the ``analysis``
benchmark suite and `python -m repro.analysis audit` fail on any
violation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import hlo_analysis
from repro.core.state import FingerState
from repro.distributed.sharding import auto_mesh
from repro.graphs.layout import NodeLayout
from repro.graphs.types import GraphDelta
from repro.serving.config import ServiceConfig, TopKSpec

PLACEMENTS = ("local", "sharded", "multipod")


@dataclasses.dataclass
class AuditViolation:
    rule: str
    target: str
    message: str

    def to_dict(self) -> Dict[str, str]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class TargetAudit:
    """Audit result for one compiled function."""
    target: str
    placement: Optional[str]
    donated_params: List[int]
    n_state_leaves: int
    host_transfers: List[Tuple[str, str, str]]
    collectives: Dict[str, float]
    upcasts: List[Tuple[str, str, str]]
    violations: List[AuditViolation]

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> Dict[str, Any]:
        return {
            "target": self.target, "placement": self.placement,
            "ok": self.ok,
            "donated_params": self.donated_params,
            "n_state_leaves": self.n_state_leaves,
            "host_transfers": [list(h) for h in self.host_transfers],
            "collectives": {k: v for k, v in self.collectives.items()
                            if v},
            "upcasts": [list(u) for u in self.upcasts],
            "violations": [v.to_dict() for v in self.violations],
        }


@dataclasses.dataclass
class AuditReport:
    targets: List[TargetAudit]

    @property
    def violations(self) -> List[AuditViolation]:
        return [v for t in self.targets for v in t.violations]

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> Dict[str, Any]:
        return {"ok": self.ok,
                "targets": [t.to_dict() for t in self.targets]}


def mesh_for_placement(placement: str):
    """The 1×N host-mesh trick from the serving smoke tests: multipod
    runs with a size-1 pod axis, which still exercises the
    ("pod", "data") shard_map code path on one host."""
    if placement == "local":
        return None
    if placement == "sharded":
        return auto_mesh((jax.device_count(),), ("data",))
    return auto_mesh((1, jax.device_count()), ("pod", "data"))


def _dummy_tick_args(config: ServiceConfig,
                     layout) -> Tuple[FingerState, GraphDelta]:
    """Zero-filled (states, deltas) of the plan's declared shapes —
    delegated to `serving.plans.dummy_tick_args`, the single source of
    dummy-argument truth, so the audit compiles exactly the jit cache
    entry `ExecutionPlan.warm_tick` populates (dense and slot-space
    sparse alike)."""
    from repro.serving.plans import dummy_tick_args

    return dummy_tick_args(config, layout)


def _audit_text(target: str, placement: Optional[str], text: str,
                n_state_leaves: int,
                require_donation: bool) -> TargetAudit:
    comps = hlo_analysis.parse_hlo(text)
    aliases = hlo_analysis.parse_input_output_aliases(text)
    donated = sorted({p for p in aliases.values()})
    transfers = hlo_analysis.host_transfer_ops(comps)
    upcasts = hlo_analysis.ops_with_dtypes(comps)
    stats = hlo_analysis.analyze(text)
    coll = stats.get("collectives", {})

    violations: List[AuditViolation] = []
    for cname, opname, reason in transfers:
        violations.append(AuditViolation(
            "host-transfer-in-tick", target,
            f"{reason} ({cname}/{opname}) — the compiled hot path "
            "must stay on device"))
    if require_donation:
        missing = [i for i in range(n_state_leaves) if i not in donated]
        if missing:
            violations.append(AuditViolation(
                "missing-donation", target,
                f"state leaves at parameter indices {missing} are not "
                "donated (no input_output_alias) — the tick would keep "
                "two live copies of the stacked state in HBM; jit the "
                "tick with donate_argnums=(0,)"))
    for name, v in coll.items():
        if v:
            violations.append(AuditViolation(
                "unexpected-collective", target,
                f"'{name}' ({v:.0f} B) inside the compiled tick — the "
                "tick is per-stream data-parallel; collectives belong "
                "in the query path"))
    for cname, opname, dt in upcasts:
        violations.append(AuditViolation(
            "dtype-upcast", target,
            f"op {cname}/{opname} produces {dt} — the serving stack "
            "is f32/i32 end to end; check for a weak-type promotion"))

    return TargetAudit(
        target=target, placement=placement,
        donated_params=donated, n_state_leaves=n_state_leaves,
        host_transfers=transfers, collectives=dict(coll),
        upcasts=upcasts, violations=violations)


def audit_plan_tick(config: ServiceConfig, mesh=None) -> TargetAudit:
    """Compile one placement's tick on dummy shapes and audit its HLO."""
    from repro.serving.plans import build_plan

    plan = build_plan(config, mesh)
    if config.method == "sparse_tick":
        from repro.core.sparse import SparseLayout

        layout = SparseLayout(n_slots=config.n_slots,
                              m_pad=config.m_pad)
        name = f"sparse_tick[{config.placement}]"
    else:
        layout = NodeLayout(n_pad=config.n_pad, generation=0)
        name = f"tick[{config.placement}]"
    states, deltas = _dummy_tick_args(config, layout)
    tick = plan.engine._tick if config.placement == "local" \
        else plan._tick
    text = tick.lower(states, deltas).compile().as_text()
    n_leaves = len(jax.tree_util.tree_leaves(states))
    return _audit_text(name, config.placement,
                       text, n_leaves, require_donation=True)


def audit_migrations(n_pad: int = 16, batch_size: int = 4) -> List[TargetAudit]:
    """Audit the three device-side migration transforms (grow /
    compact / truncate). Donation is not required here: every leaf
    changes shape across a migration, so XLA could never alias the
    buffers (see the note in `serving.migrate._grow_jit`)."""
    from repro.serving import migrate

    small = NodeLayout(n_pad=n_pad, generation=0)
    big = NodeLayout(n_pad=2 * n_pad, generation=1)
    b, f32 = batch_size, jnp.float32
    states_small = FingerState(
        q=jnp.zeros((b,), f32), s_total=jnp.zeros((b,), f32),
        s_max=jnp.zeros((b,), f32),
        strengths=jnp.zeros((b, n_pad), f32),
        node_mask=jnp.zeros((b, n_pad), f32), layout=small)
    states_big = FingerState(
        q=jnp.zeros((b,), f32), s_total=jnp.zeros((b,), f32),
        s_max=jnp.zeros((b,), f32),
        strengths=jnp.zeros((b, 2 * n_pad), f32),
        node_mask=jnp.zeros((b, 2 * n_pad), f32), layout=big)
    n_leaves = len(jax.tree_util.tree_leaves(states_small))

    targets = []
    for name, fn, args in (
            ("migrate.grow", migrate._grow_jit(None),
             (states_small,), ),
            ("migrate.compact", migrate._compact_auto_jit(None),
             (states_big,), ),
            ("migrate.truncate", migrate._truncate_jit(None),
             (states_big,), ),
    ):
        new_layout = big if name == "migrate.grow" else small
        text = fn.lower(*args, new_layout=new_layout) \
            .compile().as_text()
        targets.append(_audit_text(name, None, text, n_leaves,
                                   require_donation=False))

    # The sparse capacity growth (grow_capacity's device transform):
    # same rules — the stacked slot-space state must never touch host.
    from repro.core.sparse import SparseLayout, SparseStreamState

    sl_small = SparseLayout(n_slots=n_pad, m_pad=2 * n_pad)
    sl_big = sl_small.grown(n_slots=2 * n_pad, m_pad=4 * n_pad)
    sparse_states = SparseStreamState(
        q=jnp.zeros((b,), f32), s_total=jnp.zeros((b,), f32),
        s_max=jnp.zeros((b,), f32),
        strengths=jnp.zeros((b, sl_small.n_slots), f32),
        node_mask=jnp.zeros((b, sl_small.n_slots), f32),
        edge_weights=jnp.zeros((b, sl_small.m_pad), f32),
        layout=sl_small)
    text = migrate._grow_sparse_jit(None) \
        .lower(sparse_states, new_layout=sl_big).compile().as_text()
    targets.append(_audit_text(
        "migrate.grow_sparse", None, text,
        len(jax.tree_util.tree_leaves(sparse_states)),
        require_donation=False))
    return targets


def audit_repo(batch_size: Optional[int] = None, n_pad: int = 16,
               k_pad: int = 3) -> AuditReport:
    """The full audit: every placement's tick + every migration
    transform, on small dummy shapes (the checks are structural — the
    compiled program's op mix doesn't depend on the sizes).

    ``batch_size`` defaults to two streams per device so the sharded
    placements validate on any forced device count."""
    if batch_size is None:
        batch_size = max(4, 2 * jax.device_count())
    targets: List[TargetAudit] = []
    for placement in PLACEMENTS:
        mesh = mesh_for_placement(placement)
        config = ServiceConfig(
            batch_size=batch_size, n_pad=n_pad, k_pad=k_pad,
            placement=placement, topk=TopKSpec(k=2))
        targets.append(audit_plan_tick(config, mesh))
        # The sparse serving tick, same rules per placement: donation
        # of every slot-space state leaf (edge store included), no
        # host transfer, no collective, no upcast.
        sparse_config = ServiceConfig(
            batch_size=batch_size, n_pad=1 << 20, k_pad=k_pad,
            method="sparse_tick", n_slots=n_pad, m_pad=2 * n_pad,
            placement=placement, topk=TopKSpec(k=2))
        targets.append(audit_plan_tick(sparse_config,
                                       mesh_for_placement(placement)))
    targets.extend(audit_migrations())
    return AuditReport(targets)
