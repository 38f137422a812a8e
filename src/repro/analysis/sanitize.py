"""Runtime sanitizers: compile-count budgets, transfer guards, NaN mode.

Reusable context managers for the invariants the serving stack's perf
claims rest on, replacing the ad-hoc assertions that used to live
inline in the tests:

- `compile_budget(n)` — a jit-cache-miss sentinel. Counts XLA backend
  compiles while the block runs (via JAX's monitoring events) and
  raises `CompileBudgetExceeded` if more than ``n`` happened — e.g.
  "mixed-n ticks across a migration chain compile ≤ P plans".
- `no_transfers()` — `jax.transfer_guard` enforcement: any implicit
  host↔device transfer inside the block raises.
- `transfer_budget(n)` — a device→host *materialization* sentinel.
  Counts actual on-device arrays being brought to host (uncached
  `ArrayImpl._value` reads: `np.asarray`, `float(...)`,
  `jax.device_get`) and raises `TransferBudgetExceeded` past ``n`` —
  e.g. "`fleet.scores()` syncs at most once per pool per tick".
  Unlike `no_transfers` this counts *explicit* pulls too, which is
  exactly the score-plane contract.
- `debug_nan_checks()` — debug-NaN tick mode: jitted computations
  re-run op-by-op on a NaN result and raise at the producing op.

All of these nest with each other and with user code arbitrarily.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Iterator, List, Optional

import jax

# One duration event per XLA backend compile (fires on every jit cache
# miss that reaches the compiler; cache hits don't).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileBudgetExceeded(AssertionError):
    """More backend compiles happened than the sentinel's budget."""


@dataclasses.dataclass
class CompileCount:
    """Live view of the sentinel's counter (yielded by
    `compile_budget`); ``count`` keeps updating inside the block."""
    budget: Optional[int]
    what: str = ""
    count: int = 0
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False)

    def _bump(self) -> None:
        with self._lock:
            self.count += 1


@contextlib.contextmanager
def compile_budget(max_compiles: Optional[int],
                   what: str = "") -> Iterator[CompileCount]:
    """Assert at most ``max_compiles`` XLA backend compiles in-block.

    ``max_compiles=None`` only counts (never raises) — useful for
    calibrating a budget before pinning it. Counts *backend* compiles:
    jit cache hits are free, and auxiliary one-off compiles (a first
    `jnp.ones`, a host-side argsort) count too, so warm those up before
    entering the block when the budget is tight.
    """
    counter = CompileCount(budget=max_compiles, what=what)

    def _listener(event: str, duration: float, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            counter._bump()

    jax.monitoring.register_event_duration_secs_listener(_listener)
    try:
        yield counter
    finally:
        jax.monitoring.unregister_event_duration_listener(_listener)
    if max_compiles is not None and counter.count > max_compiles:
        label = f" ({what})" if what else ""
        raise CompileBudgetExceeded(
            f"compile budget exceeded{label}: {counter.count} backend "
            f"compiles > budget {max_compiles} — a jit cache is "
            "fragmenting (static-arg churn, layout-keyed retrace, or a "
            "missing warm plan)")


class TransferBudgetExceeded(AssertionError):
    """More device→host materializations happened than budgeted."""


@dataclasses.dataclass
class TransferCount:
    """Live view of the transfer sentinel's counter (yielded by
    `transfer_budget`); ``count`` keeps updating inside the block."""
    budget: Optional[int]
    what: str = ""
    count: int = 0
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False)

    def _bump(self) -> None:
        with self._lock:
            self.count += 1


@contextlib.contextmanager
def transfer_budget(max_transfers: Optional[int],
                    what: str = "") -> Iterator[TransferCount]:
    """Assert at most ``max_transfers`` device→host materializations.

    Counts uncached reads of ``ArrayImpl._value`` — the single funnel
    every host materialization of a committed device array goes
    through (`np.asarray(x)`, `float(x)`, `jax.device_get(x)`,
    `x.__array__()`). Cached re-reads of the same array are free, like
    the runtime itself. ``max_transfers=None`` only counts (never
    raises) — useful for calibrating a budget before pinning it.

    On the CPU backend ``np.asarray`` of a *ready* array takes the
    buffer-protocol shortcut — a zero-copy view that really is not a
    transfer, and is not counted. `float(...)` of a fresh device value
    and `jax.device_get` funnel through `_value` on every backend, so
    per-item-sync regressions still trip the budget on CPU CI; the
    zero-copy view `_value` returns there is cached like a copied value,
    so each array counts once on every backend.

    Implementation: temporarily swaps the `_value` property on
    ``jax._src.array.ArrayImpl`` for a counting wrapper and restores
    the predecessor on exit, so nested budgets each see every
    materialization inside their own block. Scalar ``.item()`` takes a
    C++ shortcut on some jaxlib builds and may not be counted — the
    static `per-item-host-sync` lint rule covers that form.
    """
    from jax._src import array as _array_mod

    impl = _array_mod.ArrayImpl
    counter = TransferCount(budget=max_transfers, what=what)
    prev = impl._value
    prev_fget = prev.fget

    def _counting_value(self):
        if self._npy_value is not None:
            return prev_fget(self)
        counter._bump()
        out = prev_fget(self)
        if self._npy_value is None:
            # A zero-copy host view (the CPU backend) is not cached by
            # the runtime; cache it so a re-read stays free, exactly as
            # a copied device value is.
            self._npy_value = out
        return out

    impl._value = property(_counting_value)
    try:
        yield counter
    finally:
        impl._value = prev
    if max_transfers is not None and counter.count > max_transfers:
        label = f" ({what})" if what else ""
        raise TransferBudgetExceeded(
            f"transfer budget exceeded{label}: {counter.count} "
            f"device→host materializations > budget {max_transfers} — "
            "a hot path is syncing per item (per-slot float()/"
            "np.asarray() reads) instead of batching one pull per "
            "plane")


@contextlib.contextmanager
def no_transfers(level: str = "disallow") -> Iterator[None]:
    """Forbid implicit host↔device transfers inside the block.

    Thin wrapper over ``jax.transfer_guard`` with the serving-stack
    default of ``"disallow"`` (explicit `jax.device_put` / `np.asarray`
    escapes still work — the guard catches *implicit* transfers only,
    which is exactly the hot-path contract).
    """
    with jax.transfer_guard(level):
        yield


@contextlib.contextmanager
def debug_nan_checks(enable: bool = True) -> Iterator[None]:
    """Debug-NaN tick mode: NaN-producing jitted ops raise with the op
    named, instead of the NaN surfacing ticks later in a score."""
    with jax.debug_nans(enable):
        yield


def assert_compiles_at_most(fn, max_compiles: int, *args,
                            what: str = "", **kwargs):
    """One-shot form: run ``fn(*args, **kwargs)`` under a compile
    budget; returns fn's result."""
    with compile_budget(max_compiles, what=what or getattr(
            fn, "__name__", "fn")):
        return fn(*args, **kwargs)
