"""The reduction from a profiler trace to the per-layer metrics: the
union of device-operation intervals and idle gaps named by the span
open during them."""
import os

import pytest

from bench import trace
from bench.harness import TraceContext
from bench.tests import tiny

DEV, HOST = "/device:TPU:0", trace.HOST_PLANE
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _op(name, a, b):
    return trace.Event(DEV, trace.OPS_LINE, name, a, b)


def _span(name, a, b):
    return trace.Event(HOST, "python", name, a, b)


def _load(name):
    from bench import harness

    return harness.load_module(tiny.ROOT, "metrics", name)


def test_busy_is_the_union_clipped_to_the_window():
    ops = [_op("a", 0, 10), _op("b", 5, 20), _op("c", 30, 40),
           _op("d", 95, 130)]
    assert trace.busy_ns(ops, 0, 100) == 20 + 10 + 5
    assert trace.gaps(ops, 0, 100) == [(20, 30), (40, 95)]
    assert trace.gaps([], 0, 100) == [(0, 100)]


def test_idle_gaps_are_named_by_the_innermost_span():
    ops = [_op("tick", 10, 20), _op("tick", 60, 70)]
    spans = [_span("bench.tick", 0, 50), _span("bench.ingest", 0, 8),
             _span("bench.readout", 22, 50), _span("bench.tick", 50, 100),
             _span("bench.ingest", 50, 58)]
    idle = dict(trace.idle_by_span(ops, spans, 0, 100))
    # gaps: (0,10) mid 5 ingest; (20,60) mid 40 readout; (70,100) mid 85
    assert idle == pytest.approx({"bench.ingest": 10e-9,
                                  "bench.readout": 40e-9,
                                  "bench.tick": 30e-9})
    assert trace.SpanIndex(spans).at(200) == "no span"


def test_op_seconds_rank_the_device_operations():
    ops = [_op("x", 0, 10), _op("y", 10, 40), _op("x", 50, 60)]
    ranked = trace.op_seconds(ops, 0, 55)
    assert [n for n, _ in ranked] == ["y", "x"]
    assert dict(ranked) == pytest.approx({"y": 30e-9, "x": 15e-9})


def test_readers_without_device_ops_return_nothing():
    events = [_span("bench.window", 0, 1e6)]
    ctx = TraceContext(events=events, lo_ns=0, hi_ns=1e6, ticks=4,
                       config={}, device_kind="TPU v5 lite",
                       root=tiny.ROOT)
    assert _load("device_busy_ms").read(ctx) is None
    assert _load("device_idle_pct").read(ctx) is None
    assert _load("ingest_ms").read(ctx) is None


@pytest.fixture(scope="module")
def recorded():
    """Four ticks of `dos.replay`'s traced window on a TPU v5 lite
    (normalized by `trace.load`)."""
    events = trace.read(os.path.join(FIXTURES, "trace_dos_replay.json"))
    ticks = sorted(trace.spans(events, "bench.tick"),
                   key=lambda s: s.start_ns)
    return events, ticks[0].start_ns, ticks[-1].end_ns


def test_recorded_trace_busy_and_gaps_cover_the_window(recorded):
    events, lo, hi = recorded
    ops, = trace.device_ops(events).values()
    busy = trace.busy_ns(ops, lo, hi)
    idle = trace.gaps(ops, lo, hi)
    assert busy + sum(b - a for a, b in idle) == pytest.approx(hi - lo)
    assert 0 < busy < 0.05 * (hi - lo)
    named = trace.idle_by_span(ops, [s for s in trace.spans(events)
                                     if s.name != "bench.window"], lo, hi)
    assert sum(s for _, s in named) == pytest.approx((hi - lo - busy)
                                                     * 1e-9)
    assert named[0][0] == "bench.ingest"
    assert {n for n, _ in named} <= {"bench.ingest", "bench.poll",
                                     "bench.readout", "bench.tick",
                                     "no span"}


def test_recorded_trace_metrics(recorded):
    from bench import harness

    events, lo, hi = recorded
    config = harness.load_cell("dos.replay").config
    ctx = TraceContext(events=events, lo_ns=lo, hi_ns=hi, ticks=4,
                       config=config, device_kind="TPU v5 lite",
                       root=tiny.ROOT)
    idle = _load("device_idle_pct").read(ctx)
    busy_ms = _load("device_busy_ms").read(ctx)
    assert 0 < busy_ms and 0 < idle < 100
    assert idle == pytest.approx(
        100 * (1 - busy_ms * 4 * 1e6 / (hi - lo)))
    assert _load("ingest_ms").read(ctx) > _load("readout_ms").read(ctx)
