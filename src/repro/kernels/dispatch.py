"""Shared kernel dispatch policy: backend detection, interpret-mode
fallback, lane geometry and the one-row block layout every per-stream
kernel uses, and the configurable per-grid-step VMEM budget.

Every kernel package's ``ops.py`` dispatches the same way — Pallas on
TPU, interpret mode elsewhere (CPU CI), and a size guard that routes
oversized tiles to the XLA reference path.  This module is the single
home for that policy, and `repro.analysis.vmem` consumes the same
budget so the static checker and the runtime guard can never disagree
on what "fits" means.

The VMEM budget defaults to a conservative 8 MB: half the 16 MiB of
scoped VMEM a Mosaic kernel gets by default on TPU v5e. That limit is
per kernel, not the core's whole VMEM: compiling for a described v5e
chip refuses an oversized kernel with "scoped allocation ... limit
16.00M exceeded". The kernels' hand estimates are about twice what
Mosaic actually allocates — the fused tick at (2k, n) = (2048, 512),
B = 8, needs 36.84 MB against an estimated 71 MB, and (1024, 512)
compiles within the limit although estimated at 19 MB — so the halved
budget leaves headroom for the compiler's own temporaries on top of an
already generous estimate. It can be overridden three ways, in
increasing precedence:

- the ``REPRO_VMEM_BUDGET_BYTES`` environment variable (read once at
  import);
- ``set_vmem_budget_bytes(n)`` — process-wide override (``None``
  restores the env/default value);
- ``vmem_budget(n)`` — a scoped context-manager override.

Shard-stacked launches (the ``(S, B)``-gridded megakernel entry points)
add a second, independent guard: stacking leaves the per-grid-step VMEM
footprint unchanged (each step still loads one stream's row), but the
whole stacked operand set must be resident on the device for the
launch's lifetime.  ``stacked_residency_bytes_ok`` checks the total
S-stacked operand bytes against ``stacked_budget_bytes()`` (default
256 MB, ``REPRO_STACKED_BUDGET_BYTES`` env override) so an absurdly
large layout-group is routed back to sequential per-shard launches
instead of failing device allocation mid-serve.
"""
from __future__ import annotations

import contextlib
import os
import threading
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# TPU vector-memory lane geometry: the last axis tiles to 128 lanes,
# the second-to-last to 8 sublanes (f32).
LANE = 128
SUBLANE = 8

# Lane width of the packed per-stream scalar slab.
SCALAR_LANES = LANE

# One-row block layout. Mosaic refuses a ``(1, width)`` block of a
# ``(B, width)`` array (its sublane dim is neither 8-aligned nor the
# full axis) and refuses scalar stores to VMEM. So per-stream operands
# travel as ``(B, 1, width)`` with ``(None, 1, width)`` blocks — the
# trailing block dims equal the array's — and per-stream scalars as one
# ``(B, 1, SCALAR_LANES)`` lane slab, stored as one vector row.


def pad_last(x: jax.Array, width: int, value=0) -> jax.Array:
    """Pad the last axis of ``x`` to ``width`` with ``value``."""
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    cfg = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, cfg, constant_values=value)


def pack_scalar_slab(*scalars: jax.Array) -> jax.Array:
    """(…,) per-stream scalars → the ``(…, 1, SCALAR_LANES)`` lane slab
    (scalar i in lane i)."""
    slab = jnp.stack(scalars, axis=-1).astype(jnp.float32)
    return pad_last(slab, SCALAR_LANES)[..., None, :]


def as_rows(*xs: jax.Array):
    """(…, w) operands → one-row ``(…, 1, w)`` blocks."""
    return tuple(x[..., None, :] for x in xs)


def pack_lanes(shape, *values):
    """Inside a kernel: a ``shape`` row holding ``values[i]`` in lane i,
    zero elsewhere — the vector store Mosaic accepts in place of
    per-scalar stores."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    out = jnp.zeros(shape, jnp.float32)
    for i, v in enumerate(values):
        out = jnp.where(lane == i, v, out)
    return out


def row_spec(width):
    """Per-stream block of a ``(B, 1, width)`` operand, grid ``(B,)``."""
    return pl.BlockSpec((None, 1, width), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)


def stacked_row_spec(width):
    """Per-stream block of an ``(S, B, 1, width)`` operand, grid
    ``(S, B)``: the shard and stream axes are both squeezed, so the
    kernel sees the same ``(1, width)`` ref as under `row_spec`."""
    return pl.BlockSpec((None, None, 1, width),
                        lambda si, bi: (si, bi, 0, 0),
                        memory_space=pltpu.VMEM)

DEFAULT_VMEM_BUDGET_BYTES = 8 * 1024 * 1024

_env = os.environ.get("REPRO_VMEM_BUDGET_BYTES")
_BASE_VMEM_BUDGET_BYTES = int(_env) if _env else DEFAULT_VMEM_BUDGET_BYTES
del _env

_override = threading.local()


def ceil_to(x: int, m: int) -> int:
    """Round ``x`` up to the next multiple of ``m`` (at least ``m``)."""
    return ((max(int(x), 1) + m - 1) // m) * m


def on_tpu() -> bool:
    """Whether the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def default_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel wrapper's ``interpret`` argument: explicit value
    wins; ``None`` means Pallas on TPU, interpret mode elsewhere."""
    if interpret is None:
        return not on_tpu()
    return bool(interpret)


def vmem_budget_bytes() -> int:
    """The active per-grid-step VMEM budget (innermost override wins)."""
    stack = getattr(_override, "stack", None)
    if stack:
        return stack[-1]
    if _process_override[0] is not None:
        return _process_override[0]
    return _BASE_VMEM_BUDGET_BYTES


# one-slot mutable cell so set_vmem_budget_bytes works without `global`
_process_override: list = [None]


def set_vmem_budget_bytes(n: Optional[int]) -> None:
    """Process-wide VMEM budget override; ``None`` restores the
    env/default value. Affects every kernel's size guard and the static
    checker in `repro.analysis.vmem`."""
    if n is not None and int(n) <= 0:
        raise ValueError(f"VMEM budget must be positive, got {n}")
    _process_override[0] = None if n is None else int(n)


DEFAULT_STACKED_BUDGET_BYTES = 256 * 1024 * 1024

_env = os.environ.get("REPRO_STACKED_BUDGET_BYTES")
_BASE_STACKED_BUDGET_BYTES = int(_env) if _env \
    else DEFAULT_STACKED_BUDGET_BYTES
del _env


def stacked_budget_bytes() -> int:
    """Device-residency budget for one shard-stacked launch's operands
    (inputs + outputs across all S shards; see module docstring)."""
    return _BASE_STACKED_BUDGET_BYTES


def stacked_residency_bytes_ok(total_bytes: int) -> bool:
    """Whether a stacked launch's total operand residency fits the
    stacked budget. The per-grid-step VMEM guard is separate (and
    unchanged by stacking); a group failing THIS check must be routed
    to sequential per-shard launches, not to the vmapped reference."""
    return int(total_bytes) <= stacked_budget_bytes()


@contextlib.contextmanager
def vmem_budget(n: int) -> Iterator[int]:
    """Scoped VMEM budget override (thread-local, reentrant)."""
    if int(n) <= 0:
        raise ValueError(f"VMEM budget must be positive, got {n}")
    stack = getattr(_override, "stack", None)
    if stack is None:
        stack = _override.stack = []
    stack.append(int(n))
    try:
        yield int(n)
    finally:
        stack.pop()
