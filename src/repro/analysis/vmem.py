"""Static VMEM checker: per-grid-step footprints from real BlockSpecs.

The kernels' size guards (``fits_fused_tick``, the delta-stats endpoint
cap) are hand-maintained estimates; nothing used to stop them drifting
from the kernels they guard. This module closes that gap mechanically:

1. `capture_pallas_launches` monkeypatches ``pallas.pallas_call`` to
   record every launch's grid, BlockSpecs, scratch shapes and operand
   shapes as the kernel traces;
2. `collect_footprints` clears the jit caches, drives every kernel
   package's parity check (auto-discovered, interpret mode) under the
   capture, and derives each launch's per-grid-step VMEM demand — input
   blocks + output blocks + scratch — from the captured specs;
3. the derived demand is validated against the shared
   `repro.kernels.dispatch.vmem_budget_bytes()` budget, and
   ``stream_tick``'s hand-maintained `fused_tick_vmem_bytes` estimate
   is cross-validated against the BlockSpec-level demand recovered from
   the capture (the estimate must dominate it; the guard can't silently
   undercount what the kernel actually stages).

Block-level demand is a *lower* bound on true VMEM use (the compiler
adds its own temporaries — which is exactly why the hand estimates
model the big intermediates explicitly and why the budget is half the
16 MiB scoped-VMEM limit of one v5e kernel; see `kernels.dispatch`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.experimental import pallas


@dataclasses.dataclass
class CapturedLaunch:
    """One recorded ``pl.pallas_call`` launch."""
    kernel_name: str
    module: str
    grid: Optional[Tuple[int, ...]]
    in_specs: Any
    out_specs: Any
    out_shape: Any
    scratch_shapes: Any
    operand_shapes: List[Tuple[int, ...]]
    operand_dtypes: List[Any]

    @property
    def package(self) -> str:
        # repro.kernels.<pkg>.kernel → <pkg>
        parts = self.module.split(".")
        return parts[-2] if len(parts) >= 2 else self.module


@contextlib.contextmanager
def capture_pallas_launches() -> Iterator[List[CapturedLaunch]]:
    """Record every pallas_call launch traced inside the block.

    Patches the ``pallas.pallas_call`` module attribute — the kernels
    resolve ``pl.pallas_call`` at call time, so tracing through any of
    them lands here. Launches only record when tracing actually runs;
    clear the jit caches first if the shapes may already be cached.
    """
    captured: List[CapturedLaunch] = []
    real = pallas.pallas_call

    def patched(kernel, *args, **kwargs):
        inner = real(kernel, *args, **kwargs)

        fn = kernel
        while isinstance(fn, functools.partial):
            fn = fn.func

        def wrapper(*operands):
            captured.append(CapturedLaunch(
                kernel_name=getattr(fn, "__name__", str(fn)),
                module=getattr(fn, "__module__", "?"),
                grid=kwargs.get("grid"),
                in_specs=kwargs.get("in_specs"),
                out_specs=kwargs.get("out_specs"),
                out_shape=kwargs.get("out_shape"),
                scratch_shapes=kwargs.get("scratch_shapes"),
                operand_shapes=[tuple(x.shape) for x in operands],
                operand_dtypes=[x.dtype for x in operands],
            ))
            return inner(*operands)

        return wrapper

    pallas.pallas_call = patched
    try:
        yield captured
    finally:
        pallas.pallas_call = real


def _as_seq(x) -> Sequence:
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _block_bytes(spec, shape: Tuple[int, ...], dtype) -> int:
    """Per-grid-step bytes one BlockSpec stages for an operand of the
    given shape: the block shape, with ``None`` entries (and a missing
    spec/block_shape, meaning whole-array residency) falling back to
    the operand's full extent."""
    block = getattr(spec, "block_shape", None) if spec is not None else None
    if block is None:
        dims = shape
    else:
        dims = tuple(shape[i] if b is None else int(b)
                     for i, b in enumerate(block))
    return int(math.prod(dims)) * np.dtype(dtype).itemsize


@dataclasses.dataclass
class LaunchFootprint:
    kernel_name: str
    package: str
    grid: Optional[Tuple[int, ...]]
    in_bytes: int
    out_bytes: int
    scratch_bytes: int

    @property
    def step_bytes(self) -> int:
        return self.in_bytes + self.out_bytes + self.scratch_bytes

    def to_dict(self) -> Dict[str, Any]:
        return {
            "kernel": self.kernel_name, "package": self.package,
            "grid": list(self.grid) if self.grid else None,
            "in_bytes": self.in_bytes, "out_bytes": self.out_bytes,
            "scratch_bytes": self.scratch_bytes,
            "step_bytes": self.step_bytes,
        }


def launch_footprint(launch: CapturedLaunch) -> LaunchFootprint:
    """Derive a launch's per-grid-step VMEM demand from its specs."""
    in_specs = _as_seq(launch.in_specs)
    if not in_specs:
        in_specs = [None] * len(launch.operand_shapes)
    in_bytes = sum(
        _block_bytes(spec, shape, dtype)
        for spec, shape, dtype in zip(in_specs, launch.operand_shapes,
                                      launch.operand_dtypes))

    outs = _as_seq(launch.out_shape)
    out_specs = _as_seq(launch.out_specs)
    if not out_specs:
        out_specs = [None] * len(outs)
    out_bytes = sum(
        _block_bytes(spec, tuple(o.shape), o.dtype)
        for spec, o in zip(out_specs, outs))

    scratch_bytes = sum(
        int(math.prod(s.shape)) * np.dtype(s.dtype).itemsize
        for s in _as_seq(launch.scratch_shapes))

    return LaunchFootprint(
        kernel_name=launch.kernel_name, package=launch.package,
        grid=launch.grid, in_bytes=in_bytes, out_bytes=out_bytes,
        scratch_bytes=scratch_bytes)


@dataclasses.dataclass
class VmemViolation:
    rule: str
    kernel: str
    message: str

    def to_dict(self) -> Dict[str, str]:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class VmemReport:
    budget_bytes: int
    footprints: List[LaunchFootprint]
    violations: List[VmemViolation]

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> Dict[str, Any]:
        return {
            "budget_bytes": self.budget_bytes,
            "ok": self.ok,
            "kernels": sorted({f.package for f in self.footprints}),
            "footprints": [f.to_dict() for f in self.footprints],
            "violations": [v.to_dict() for v in self.violations],
        }


def _check_stream_tick_estimate(
        launches: List[CapturedLaunch],
        footprints: List[LaunchFootprint]) -> List[VmemViolation]:
    """Cross-validate `fused_tick_vmem_bytes` against the captured
    BlockSpec demand: the hand estimate must dominate what the specs
    actually stage per grid step (it additionally models the big kernel
    temporaries on top)."""
    from repro.kernels.stream_tick.ops import fused_tick_vmem_bytes

    out: List[VmemViolation] = []
    for launch, fp in zip(launches, footprints):
        if launch.package != "stream_tick":
            continue
        # operand order fixed by prepare_stream_tick: scalar slab,
        # strengths(b,1,n), mask(b,1,n), ep_ids(b,1,2k), 3×payload,
        # nid(b,1,j), nflag(b,1,j)
        n_al = launch.operand_shapes[1][-1]
        two_k = launch.operand_shapes[3][-1]
        j_al = launch.operand_shapes[7][-1]
        est = fused_tick_vmem_bytes(n_al, two_k // 2, j_al)
        if est < fp.step_bytes:
            out.append(VmemViolation(
                rule="vmem-estimate-undercounts", kernel="stream_tick",
                message=(
                    f"fused_tick_vmem_bytes(n={n_al}, k={two_k // 2}, "
                    f"j={j_al}) = {est} B undercounts the kernel's own "
                    f"BlockSpec demand of {fp.step_bytes} B/grid-step — "
                    "the guard has drifted from the kernel it guards")))
    return out


def collect_footprints(budget_bytes: Optional[int] = None) -> VmemReport:
    """Run every kernel's parity check under launch capture and
    validate all derived footprints against the VMEM budget."""
    from repro.kernels import dispatch
    from repro.kernels.parity import discover_parity_checks

    budget = budget_bytes if budget_bytes is not None \
        else dispatch.vmem_budget_bytes()

    checks = discover_parity_checks()
    jax.clear_caches()  # force retracing so every launch is captured
    seen: Dict[str, List[CapturedLaunch]] = {name: [] for name in checks}
    launches: List[CapturedLaunch] = []
    with capture_pallas_launches() as captured:
        for name, check in checks.items():
            before = len(captured)
            check(None)
            seen[name] = captured[before:]
        launches = list(captured)

    footprints = [launch_footprint(l) for l in launches]
    violations: List[VmemViolation] = []

    for name, pkg_launches in seen.items():
        if not pkg_launches:
            violations.append(VmemViolation(
                rule="vmem-no-launch", kernel=name,
                message=(
                    f"kernel package '{name}' produced no pallas_call "
                    "launch during its parity check — its Pallas path "
                    "is not exercised, so its footprint cannot be "
                    "validated")))

    for fp in footprints:
        if fp.step_bytes > budget:
            violations.append(VmemViolation(
                rule="vmem-over-budget", kernel=fp.package,
                message=(
                    f"{fp.package}.{fp.kernel_name}: BlockSpec demand "
                    f"{fp.step_bytes} B/grid-step exceeds the VMEM "
                    f"budget {budget} B "
                    "(repro.kernels.dispatch.vmem_budget_bytes)")))

    violations.extend(_check_stream_tick_estimate(launches, footprints))
    return VmemReport(budget_bytes=budget, footprints=footprints,
                      violations=violations)
