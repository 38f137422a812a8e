"""Host→device delta ingestion for FingerService.

The ROADMAP bottleneck: `examples/serve_streams.py` was host-synthesis
bound because every tick synchronously stacked + transferred its deltas
on the tick's critical path. The queue here decouples the two:

- ``SyncIngestor``          : the baseline. Deltas stay on host until
  the tick that consumes them; the transfer is on the critical path
  (explicitly blocked on, so the comparison is honest).
- ``DoubleBufferedIngestor``: `ingest` starts the (asynchronous) device
  transfer immediately, so tick T+1's deltas stream host→device while
  tick T's compute occupies the device. By the time `poll` consumes
  them the transfer has usually already landed.

Both validate the stacked delta against the service layout up front
with named errors, and bound their queue at ``config.max_queue`` so a
producer that outruns the device fails loudly instead of hoarding
host memory.

Layout migrations: after a `FingerService.compact`, producers may still
emit deltas addressed in a pre-compaction layout for a grace period.
The ingestor holds TWO layout-owned old→new index-map tables and remaps
such deltas on ``put`` (`serving.migrate.remap_delta`) before
validation — a delta addressing a *dropped* slot is a lossy remap and
raises:

- **generation-keyed** (exact): a delta stamped with its layout's
  migration generation (``GraphDelta.from_arrays(..., layout=...)``)
  is renumbered through precisely the journaled migrations since that
  generation — exact across size-reusing chains (grow 128 → compact
  96 → grow 128 keeps generation 0 and generation 2 distinct) and
  across pure grows. An unknown generation raises by name.
- **size-keyed** (legacy best effort): a raw delta only declares a
  layout *size*; the newest migration from that size wins (a
  size-reusing chain shadows older same-size layouts), and grows
  reject old-size raw deltas outright.

The generation stamp is consumed HERE, host-side: it is stripped before
the delta is queued, so compiled ticks always see
``layout_generation=None`` and the jit cache never fragments across
migration generations. ``take_all`` hands the in-flight queue back to
the service so a migration can re-lay-out prefetched ticks instead of
refusing to run.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, Optional, Sequence, Union

import numpy as np

import jax
from jax.profiler import TraceAnnotation

from repro.engine.stream import stack_deltas
from repro.graphs.types import GraphDelta
from repro.serving.config import ServiceConfig
from repro.serving.plans import ExecutionPlan


class IngestError(ValueError):
    """A stacked delta does not fit the service's compiled layout (or
    the ingestion queue overflowed)."""


class GraceLapseError(IngestError):
    """A generation-stamped delta addresses a layout generation whose
    grace window has lapsed: the service's retention policy
    (``ServiceConfig.grace_generations``) has pruned that generation's
    old→new remap. The producer must rebuild its deltas against the
    current layout (`FingerService.layout`)."""


def validate_stacked_delta(config: ServiceConfig,
                           deltas: GraphDelta) -> None:
    """Layout check before anything touches the device: every mismatch
    here would otherwise surface as a silent recompile (new shapes) or
    an opaque shard_map error."""
    if deltas.dw.ndim != 2:
        raise IngestError(
            f"ingest expects a stacked (B, k_pad) delta, got dw shape "
            f"{tuple(deltas.dw.shape)}; stack per-stream deltas with "
            "engine.stack_deltas (or pass the list and let the service "
            "stack them)")
    b, k_pad = deltas.dw.shape
    if b != config.batch_size:
        raise IngestError(
            f"stacked delta batch {b} != config.batch_size="
            f"{config.batch_size}")
    if k_pad != config.k_pad:
        raise IngestError(
            f"stacked delta k_pad {k_pad} != config.k_pad="
            f"{config.k_pad}; a different edge-slot width would "
            "recompile the serving tick")
    if config.method == "sparse_tick":
        if deltas.edge_slots is None:
            raise IngestError(
                "sparse serving queues hold slot-space deltas, but "
                "this one carries no edge_slots (it is still addressed "
                "in the virtual space); pass the B per-stream virtual "
                "deltas to FingerService.ingest as a sequence — the "
                "service translates each through its stream's SlotMap "
                "(stateful, tick-ordered), which a pre-stacked delta "
                "bypasses")
        if deltas.n_nodes != config.n_slots:
            raise IngestError(
                f"slot-space delta n_slots {deltas.n_nodes} != "
                f"config.n_slots={config.n_slots}; after a "
                "grow_capacity(), queued deltas are re-embedded "
                "automatically — a mismatch here means the delta was "
                "translated against a stale capacity")
        if deltas.edge_slots.shape != deltas.dw.shape:
            raise IngestError(
                f"delta edge_slots shape "
                f"{tuple(deltas.edge_slots.shape)} != dw shape "
                f"{tuple(deltas.dw.shape)}")
    elif deltas.edge_slots is not None:
        raise IngestError(
            f"delta carries edge_slots (a sparse slot-space delta) but "
            f"config.method={config.method!r} serves the dense path; "
            "slot-space deltas only make sense under "
            "method='sparse_tick'")
    elif deltas.n_nodes != config.n_pad:
        raise IngestError(
            f"stacked delta n_pad {deltas.n_nodes} != config.n_pad="
            f"{config.n_pad}; after a repad, rebuild deltas with the "
            "new n_pad (deltas in a pre-compact() layout are remapped "
            "automatically while its index map is installed)")
    has_slots = deltas.node_ids is not None
    want_slots = config.j_pad is not None
    if has_slots != want_slots:
        raise IngestError(
            f"delta node-slot presence ({has_slots}) != config.j_pad="
            f"{config.j_pad!r}; node join/leave slots must be declared "
            "in the ServiceConfig so every tick shares one compiled "
            "program")
    if want_slots and deltas.node_ids.shape[-1] != config.j_pad:
        raise IngestError(
            f"delta j_pad {deltas.node_ids.shape[-1]} != config.j_pad="
            f"{config.j_pad}")


class SyncIngestor:
    """Transfer-on-consume baseline: `get` puts the delta on device and
    blocks until the transfer lands, serializing it before the tick."""

    def __init__(self, config: ServiceConfig, plan: ExecutionPlan,
                 remaps: Optional[Dict[int, np.ndarray]] = None,
                 remaps_by_gen: Optional[Dict[int, np.ndarray]] = None,
                 generation: int = 0):
        self.config = config
        self.plan = plan
        # old n_pad -> old→current index map (installed by compact()).
        self.remaps: Dict[int, np.ndarray] = dict(remaps or {})
        # old layout generation -> old→current index map (every
        # journaled migration; exact across size-reusing chains).
        self.remaps_by_gen: Dict[int, np.ndarray] = \
            dict(remaps_by_gen or {})
        self.generation = int(generation)
        self._queue: deque = deque()

    def __len__(self) -> int:
        return len(self._queue)

    def _maybe_remap(self, deltas: GraphDelta) -> GraphDelta:
        """Renumber a delta still addressed in a pre-migration layout
        (the grace path; steady-state deltas pass through). The
        generation stamp, when present, is consumed and stripped here —
        compiled ticks never see it."""
        from repro.serving.migrate import remap_delta

        gen = deltas.layout_generation
        if gen is not None:
            if gen == self.generation:
                if deltas.n_nodes != self.config.n_pad:
                    raise IngestError(
                        f"delta declares layout generation {gen} (the "
                        f"current one) but n_pad={deltas.n_nodes} != "
                        f"the layout's n_pad={self.config.n_pad} — a "
                        "mis-stamped delta")
                return dataclasses.replace(deltas,
                                           layout_generation=None)
            imap = self.remaps_by_gen.get(gen)
            if imap is None:
                if 0 <= gen < self.generation:
                    # A real past generation with no retained remap:
                    # the retention policy pruned it.
                    raise GraceLapseError(
                        f"delta is addressed in layout generation "
                        f"{gen} but the service is at generation "
                        f"{self.generation} and its grace window "
                        f"(grace_generations="
                        f"{self.config.grace_generations}) retains "
                        f"only {sorted(self.remaps_by_gen)} — rebuild "
                        "deltas against the current layout")
                raise IngestError(
                    f"delta declares layout generation {gen} but the "
                    f"service is at generation {self.generation} "
                    f"(known past generations: "
                    f"{sorted(self.remaps_by_gen)}) — a mis-stamped "
                    "delta")
            if deltas.n_nodes != imap.shape[0]:
                # Without this, a wrong-size stamp would either escape
                # as a raw IndexError from the remap gather or be
                # silently renumbered as if addressed in the old layout.
                raise IngestError(
                    f"delta declares layout generation {gen} but "
                    f"n_pad={deltas.n_nodes} != that generation's "
                    f"n_pad={imap.shape[0]} — a mis-stamped delta")
            out = remap_delta(deltas, imap, self.config.n_pad)
            return dataclasses.replace(out, layout_generation=None)
        if deltas.n_nodes == self.config.n_pad \
                or deltas.n_nodes not in self.remaps:
            return deltas
        return remap_delta(deltas, self.remaps[deltas.n_nodes],
                           self.config.n_pad)

    def _prepare(self, deltas: GraphDelta) -> GraphDelta:
        """What `put` enqueues — the host delta (transfer deferred)."""
        return deltas

    def put(self, deltas: Union[GraphDelta, Sequence[GraphDelta]]
            ) -> None:
        """Queue one tick: a stacked (B, k_pad) delta, or the B
        per-stream deltas to stack. Stacking, remap, validation and the
        queue check run in a ``finger.stack`` span; the transfer
        (`ExecutionPlan.put_deltas`) is outside it."""
        with TraceAnnotation("finger.stack"):
            if not isinstance(deltas, GraphDelta):
                deltas = stack_deltas(list(deltas))
            deltas = self._maybe_remap(deltas)
            validate_stacked_delta(self.config, deltas)
            if len(self._queue) >= self.config.max_queue:
                raise IngestError(
                    f"ingestion queue full ({self.config.max_queue} "
                    f"pending tick(s)); poll() before ingesting more")
        self._queue.append(self._prepare(deltas))

    def take_all(self) -> list:
        """Pop every pending tick, oldest first (migration re-layout)."""
        out = list(self._queue)
        self._queue.clear()
        return out

    def get(self) -> Optional[GraphDelta]:
        if not self._queue:
            return None
        deltas = self.plan.put_deltas(self._queue.popleft())
        return jax.block_until_ready(deltas)

    def pop(self) -> Optional[GraphDelta]:
        """Pop the oldest pending tick exactly as held — host-side for
        the sync ingestor, device-resident for the double-buffered one.

        The pool-stacked fleet tick path consumes through this instead
        of `get`: the stacked launch's own argument transfer moves the
        delta, so a per-shard ``block_until_ready(put_deltas(...))``
        here would reintroduce exactly the S serialized host syncs the
        stacked path removes.
        """
        if not self._queue:
            return None
        return self._queue.popleft()

    def drain(self) -> None:
        self._queue.clear()


class DoubleBufferedIngestor(SyncIngestor):
    """Transfer-on-ingest: `put` starts the device transfer immediately
    so it overlaps the in-flight tick's compute; `get` just hands the
    (usually already resident) delta to the tick."""

    def _prepare(self, deltas: GraphDelta) -> GraphDelta:
        return self.plan.put_deltas(deltas)

    def get(self) -> Optional[GraphDelta]:
        if not self._queue:
            return None
        return self._queue.popleft()


def make_ingestor(config: ServiceConfig, plan: ExecutionPlan,
                  remaps: Optional[Dict[int, np.ndarray]] = None,
                  remaps_by_gen: Optional[Dict[int, np.ndarray]] = None,
                  generation: int = 0) -> SyncIngestor:
    cls = DoubleBufferedIngestor \
        if config.ingestion == "double_buffered" else SyncIngestor
    return cls(config, plan, remaps, remaps_by_gen, generation)
