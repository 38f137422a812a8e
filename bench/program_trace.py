#!/usr/bin/env python3
"""The program's own spans in a profiler trace, and what they say per tick.

The fleet writes `jax.profiler.TraceAnnotation` spans named
``finger.*`` into the profiler's host plane, on the device trace's
clock, with integer counters as event stats (``lanes``, ``kept``,
``bytes``, ``launches``, ``step``; PERF.md section 3 maps each to what
it measures). `bench.trace.load` keeps only the benchmark's own
``bench.*`` spans and the device's operations, so the harness's result
line does not carry these yet. This module reads them beside it:

- `load` and `read`: the ``finger.*`` spans of a trace directory, or of
  a recorded fixture, with their counters;
- `per_tick`: the per-tick numbers the spans give (`PER_TICK`);
- `self_ms`: each span's time less that of the spans inside it;
- `idle_by_program_span`: the device's idle time, each gap put down to
  the innermost ``finger.*`` span open at its midpoint.

Run as a script, it runs one cell traced, as ``bench/run.py --trace 1``
does (correctness checks included), prints the run's result line, then
one more JSON line with the program's numbers for the same window:

    python3 bench/program_trace.py --workload dos.replay --seed 7 \\
        --seconds 20 [--fixture PATH --fixture-ticks 4]

``--fixture`` also writes a few ticks of the trace from the middle of
the window as rows ``[plane, line, name, start_ns, end_ns, stats]``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import json
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "finger."


@dataclasses.dataclass(frozen=True)
class Span:
    """One ``finger.*`` host span (on the host thread ``line``) and its
    counters."""
    line: str
    name: str
    start_ns: float
    end_ns: float
    stats: Dict[str, int]

    @property
    def ns(self) -> float:
        return self.end_ns - self.start_ns


def load(trace_dir: str) -> List[Span]:
    """The ``finger.*`` host spans of the one ``.xplane.pb`` under
    ``trace_dir``, in start order."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    out.append(Span(line.name, e.name, float(e.start_ns),
                                    float(e.end_ns),
                                    {k: int(v) for k, v in e.stats}))
    return sorted(out, key=lambda s: (s.start_ns, -s.end_ns))


def read(path: str) -> Tuple[List[trace.Event], List[Span]]:
    """A fixture's rows: (the device operations and benchmark spans as
    `trace.Event`s, the program's spans)."""
    with open(path) as f:
        rows = json.load(f)
    events = [trace.Event(*row[:5]) for row in rows
              if not row[2].startswith(PROGRAM_PREFIX)]
    spans = sorted((Span(*row[1:]) for row in rows
                    if row[2].startswith(PROGRAM_PREFIX)),
                   key=lambda s: (s.start_ns, -s.end_ns))
    return events, spans


def write(path: str, events: Sequence[trace.Event],
          spans: Sequence[Span], lo: float, hi: float) -> None:
    """The events and spans that overlap [lo, hi], as fixture rows."""
    rows = [[e.plane, e.line, e.name, e.start_ns, e.end_ns, {}]
            for e in events if e.end_ns > lo and e.start_ns < hi]
    rows += [[trace.HOST_PLANE, s.line, s.name, s.start_ns, s.end_ns,
              s.stats] for s in spans if s.end_ns > lo and s.start_ns < hi]
    with open(path, "w") as f:
        json.dump(rows, f)


def inside(spans: Sequence[Span], lo: float, hi: float,
           name: Optional[str] = None) -> List[Span]:
    """The spans (named ``name``) that lie wholly within [lo, hi]."""
    return [s for s in spans if lo <= s.start_ns and s.end_ns <= hi
            and (name is None or s.name == name)]


def _per_tick_ms(name: str):
    def read(spans, lo, hi, ticks):
        return sum(s.ns for s in inside(spans, lo, hi, name)) \
            * 1e-6 / ticks
    return read


def _slotmap_us_per_lane(spans, lo, hi, ticks):
    found = inside(spans, lo, hi, "finger.slotmap")
    lanes = sum(s.stats.get("lanes", 0) for s in found)
    return sum(s.ns for s in found) * 1e-3 / lanes if lanes else None


def _h2d_bytes_per_tick(spans, lo, hi, ticks):
    return sum(s.stats.get("bytes", 0)
               for s in inside(spans, lo, hi, "finger.h2d")) / ticks


def _d2h_reads_per_tick(spans, lo, hi, ticks):
    return len(inside(spans, lo, hi, "finger.d2h")) / ticks


# name -> reader(spans, lo, hi, ticks); each reads the spans that lie
# wholly inside the window and gives a mean over its ticks (the unit is
# the name's suffix: ms, us, bytes, reads).
PER_TICK = {
    "route_ms": _per_tick_ms("finger.route"),
    "wal_ms": _per_tick_ms("finger.wal"),
    "slotmap_ms": _per_tick_ms("finger.slotmap"),
    "stage_ms": _per_tick_ms("finger.stack"),
    "slotmap_us_per_lane": _slotmap_us_per_lane,
    "h2d_bytes_per_tick": _h2d_bytes_per_tick,
    "launch_ms": _per_tick_ms("finger.poll"),
    "d2h_wait_ms": _per_tick_ms("finger.d2h"),
    "d2h_reads_per_tick": _d2h_reads_per_tick,
}


def per_tick(spans: Sequence[Span], lo: float, hi: float, ticks: int
             ) -> Dict[str, float]:
    """Every `PER_TICK` number the spans in [lo, hi] give (a number
    with nothing to read is left out)."""
    if not ticks or not inside(spans, lo, hi):
        return {}
    out = {}
    for name, reader in PER_TICK.items():
        value = reader(spans, lo, hi, ticks)
        if value is not None:
            out[name] = value
    return out


def self_ms(spans: Sequence[Span], lo: float, hi: float, ticks: int
            ) -> Dict[str, float]:
    """Per tick, by name: the spans' time in [lo, hi] less the time of
    the spans directly inside them (spans of one thread nest)."""
    if not ticks:
        return {}
    total: Dict[str, float] = defaultdict(float)
    stacks: Dict[str, List[Span]] = defaultdict(list)
    for s in inside(spans, lo, hi):
        stack = stacks[s.line]
        while stack and stack[-1].end_ns <= s.start_ns:
            stack.pop()
        total[s.name] += s.ns
        if stack and s.end_ns <= stack[-1].end_ns:
            total[stack[-1].name] -= s.ns
        stack.append(s)
    return {name: ns * 1e-6 / ticks for name, ns in total.items()}


def idle_by_program_span(events: Sequence[trace.Event],
                         spans: Sequence[Span], lo: float, hi: float
                         ) -> List[Tuple[str, float]]:
    """Idle device seconds in [lo, hi] by the innermost program span
    open in each gap ("no span" where none is), largest first."""
    ops = [e for plane in trace.device_ops(events).values() for e in plane]
    return trace.idle_by_span(ops, spans, lo, hi)


def ticks_in(events: Sequence[trace.Event], lo: float, hi: float
             ) -> List[trace.Event]:
    """The benchmark's ``bench.tick`` spans within [lo, hi]."""
    return sorted((s for s in trace.spans(events, "bench.tick")
                   if lo <= s.start_ns and s.end_ns <= hi),
                  key=lambda s: s.start_ns)


def report(events: Sequence[trace.Event], spans: Sequence[Span],
           lo: float, hi: float) -> dict:
    """The program's numbers for the window [lo, hi]: the `PER_TICK`
    numbers, self times per tick, and how much of the benchmark's own
    ``bench.ingest`` and ``bench.readout`` spans and of the device's
    idle time the program's spans account for."""
    ticks = len(ticks_in(events, lo, hi))
    selfs = self_ms(spans, lo, hi, ticks)
    values = per_tick(spans, lo, hi, ticks)
    ingest = trace.mean_span_ms(events, "bench.ingest")
    readout = trace.mean_span_ms(events, "bench.readout")
    idle = idle_by_program_span(events, spans, lo, hi)
    idle_s = sum(s for _, s in idle)
    covered = {}
    if ingest and values:
        covered["ingest_pct"] = 100 * (
            values["route_ms"] + values["wal_ms"] + values["slotmap_ms"]
            + values["stage_ms"] + selfs.get("finger.h2d", 0.0)
            + selfs.get("finger.shard_ingest", 0.0)) / ingest
    if readout and values:
        covered["readout_pct"] = 100 * (
            values["d2h_wait_ms"] + selfs.get("finger.scores", 0.0)
            + selfs.get("finger.top_anomalies", 0.0)) / readout
    if idle_s:
        covered["no_span_idle_pct"] = 100 * dict(idle).get("no span",
                                                           0.0) / idle_s
    return {"ticks": ticks, "per_tick": values, "self_ms": selfs,
            "bench_ms": {"ingest_ms": ingest, "readout_ms": readout},
            "covered": covered,
            "idle_by_program_span": [[n, s] for n, s in idle[:10]]}


@contextlib.contextmanager
def keeping_program_spans():
    """Within the block, each trace the harness loads also gives its
    program spans: the yielded dict then holds the last trace's
    ``events`` (as `bench.trace.load` returns them) and ``spans``.
    The harness deletes a trace once it has read it, so they are read
    in the same call."""
    kept = {}
    load_events = trace.load

    def load_both(trace_dir):
        kept["spans"] = load(trace_dir)
        kept["events"] = load_events(trace_dir)
        return kept["events"]

    trace.load = load_both
    try:
        yield kept
    finally:
        trace.load = load_events


def main(argv=None) -> int:
    import argparse

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--fixture", help="also write a few ticks here")
    ap.add_argument("--fixture-ticks", type=int, default=4)
    args, rest = ap.parse_known_args(argv)
    if "--trace" in rest:
        ap.error("the run is always traced")
    with keeping_program_spans() as kept:
        rc = run.main(rest + ["--trace", "1"])
    if rc or "events" not in kept:
        return rc or 1
    events, spans = kept["events"], kept["spans"]
    lo, hi = trace.window(events)
    print(json.dumps({"program": report(events, spans, lo, hi)}),
          flush=True)
    if args.fixture:
        ticks = ticks_in(events, lo, hi)
        mid = max(0, len(ticks) // 2 - args.fixture_ticks // 2)
        part = ticks[mid:mid + args.fixture_ticks]
        write(args.fixture, events, spans, part[0].start_ns,
              part[-1].end_ns)
    return 0


if __name__ == "__main__":
    sys.exit(main())
