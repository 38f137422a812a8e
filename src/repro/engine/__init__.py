"""Batched multi-stream FINGER serving engine (plan-internal executor).

.. deprecated::
    New serving code should use `repro.serving.FingerService`, which
    declares placement/ingestion/checkpoint/top-k policy once in a
    `ServiceConfig` instead of per call site. `StreamEngine` remains
    fully API-compatible and is what the serving plans execute
    underneath; see `examples/README.md` for the migration table.

One FingerState per user/session stream, stacked along a leading batch
axis and advanced in lockstep by vmapped Theorem-2 updates — the batched
form of the paper's Algorithm 2, sized for serving many concurrent graph
streams from one program.

Streams need not share a true node count: the engine pads every tenant
graph to one static `n_pad` layout with a per-stream dynamic node mask
(inactive slots contribute exactly zero to every statistic), supports
node join/leave deltas mid-stream, and persists/restores the stacked
state through `serving.checkpoint` so serving restarts resume instead of
replaying.
"""
from repro.engine.stream import (
    StreamEngine,
    restore_stacked_state,
    stack_deltas,
    stack_states,
    unstack_states,
)

__all__ = [
    "StreamEngine",
    "restore_stacked_state",
    "stack_deltas",
    "stack_states",
    "unstack_states",
]
