"""From a profiler trace to the numbers the per-layer metrics read.

`load` reads the ``.xplane.pb`` that `jax.profiler` writes into a flat
list of `Event`s. Everything after that works on the flat list, so the
reduction can be checked on a small recorded trace kept as a fixture:

- device operations are the events of the ``XLA Ops`` line of each
  ``/device:`` plane, named by their HLO instruction (``%fusion.3``,
  ``%stream_tick_pallas_stacked.1``: a Pallas kernel's instruction is
  named after its ``pallas_call``);
- the benchmark's own spans are the host events whose names start
  with ``bench.`` (``jax.profiler.TraceAnnotation``);
- busy time is the union of a device's operation intervals within the
  traced window, and an idle gap is a stretch of the window that no
  operation covers. Each gap is named by the innermost benchmark span
  open at its midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    end_ns: float


def load(trace_dir: str) -> List[Event]:
    """Every event of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        keep_plane = plane.name.startswith(DEVICE_PLANE) \
            or plane.name == HOST_PLANE
        if not keep_plane:
            continue
        for line in plane.lines:
            for e in line.events:
                if plane.name == HOST_PLANE \
                        and not e.name.startswith(SPAN_PREFIX):
                    continue
                if plane.name.startswith(DEVICE_PLANE) \
                        and line.name != OPS_LINE:
                    continue
                name = e.name.split(" = ", 1)[0] \
                    if plane.name.startswith(DEVICE_PLANE) else e.name
                out.append(Event(plane.name, line.name, name,
                                 float(e.start_ns), float(e.end_ns)))
    return out


def read(path: str) -> List[Event]:
    """Events saved as JSON rows of `Event`'s fields (the fixtures)."""
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def device_ops(events: Iterable[Event]) -> Dict[str, List[Event]]:
    """Device operation events, by device plane."""
    out: Dict[str, List[Event]] = defaultdict(list)
    for e in events:
        if e.plane.startswith(DEVICE_PLANE) and e.line == OPS_LINE:
            out[e.plane].append(e)
    return dict(out)


def spans(events: Iterable[Event], name: Optional[str] = None
          ) -> List[Event]:
    """The benchmark's host spans, all or those named ``name``."""
    return [e for e in events if e.plane == HOST_PLANE
            and e.name.startswith(SPAN_PREFIX)
            and (name is None or e.name == name)]


def mean_span_ms(events: Iterable[Event], name: str) -> Optional[float]:
    """Mean duration of the spans named ``name``, in ms (None if none)."""
    found = spans(events, name)
    if not found:
        return None
    return sum(s.end_ns - s.start_ns for s in found) / len(found) * 1e-6


def window(events: Iterable[Event], name: str = "bench.window"
           ) -> Tuple[float, float]:
    """(start, end) in ns of the span that marks the traced window."""
    marks = spans(events, name)
    if len(marks) != 1:
        raise ValueError(f"expected one {name!r} span, found {len(marks)}")
    return marks[0].start_ns, marks[0].end_ns


def merged(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    """The union of ``intervals`` clipped to [lo, hi], as sorted,
    disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(ops: Sequence[Event], lo: float, hi: float) -> float:
    """Length of the union of ``ops``' intervals within [lo, hi]."""
    return sum(b - a for a, b in merged(((e.start_ns, e.end_ns)
                                         for e in ops), lo, hi))


def gaps(ops: Sequence[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] that no operation covers."""
    out, at = [], lo
    for a, b in merged(((e.start_ns, e.end_ns) for e in ops), lo, hi):
        if a > at:
            out.append((at, a))
        at = b
    if hi > at:
        out.append((at, hi))
    return out


class SpanIndex:
    """Which benchmark span is innermost at a given time.

    Spans of one name never overlap each other (each is one call of the
    client loop), so a bisection per name finds the candidate, and the
    names are tried shortest first."""

    def __init__(self, host_spans: Sequence[Event]):
        by_name: Dict[str, List[Event]] = defaultdict(list)
        for s in host_spans:
            by_name[s.name].append(s)
        self._levels = []
        for name, group in by_name.items():
            group.sort(key=lambda s: s.start_ns)
            mean = sum(s.end_ns - s.start_ns for s in group) / len(group)
            self._levels.append((mean, name, [s.start_ns for s in group],
                                 group))
        self._levels.sort(key=lambda level: level[0])

    def at(self, t: float) -> str:
        """Name of the shortest span open at ``t`` ("no span" if none)."""
        for _, name, starts, group in self._levels:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t < group[i].end_ns:
                return name
        return "no span"


def idle_by_span(ops: Sequence[Event], host_spans: Sequence[Event],
                 lo: float, hi: float) -> List[Tuple[str, float]]:
    """Idle seconds within [lo, hi], summed by the span open during each
    gap, largest first."""
    index = SpanIndex(host_spans)
    total: Dict[str, float] = defaultdict(float)
    for a, b in gaps(ops, lo, hi):
        total[index.at((a + b) / 2)] += (b - a) * 1e-9
    return sorted(total.items(), key=lambda kv: -kv[1])


def op_seconds(ops: Sequence[Event], lo: float, hi: float
               ) -> List[Tuple[str, float]]:
    """Device seconds within [lo, hi] by operation name, largest first."""
    total: Dict[str, float] = defaultdict(float)
    for e in ops:
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            total[e.name] += (b - a) * 1e-9
    return sorted(total.items(), key=lambda kv: -kv[1])
