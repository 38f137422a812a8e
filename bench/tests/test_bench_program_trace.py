"""The program's ``finger.*`` spans read from a profiler trace
(`bench.program_trace`): on hand-made spans, on a CPU rehearsal of the
cell through the real fleet, and on a few ticks of `dos.replay` traced
on a TPU v5 lite; and the benchmark's own readers, which this reading
leaves as they were."""
import os

import pytest

from bench import harness, program_trace as pt, trace
from bench.harness import TraceContext
from bench.tests import tiny

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _span(name, a, b, **stats):
    return pt.Span("python", name, a, b, stats)


def test_self_time_subtracts_the_spans_directly_inside():
    spans = [_span("finger.ingest", 0, 100), _span("finger.route", 5, 30),
             _span("finger.shard_ingest", 40, 90),
             _span("finger.slotmap", 45, 70),
             _span("finger.h2d", 75, 80), _span("finger.poll", 100, 110)]
    got = pt.self_ms(spans, 0, 200, ticks=1)
    assert got == pytest.approx({
        "finger.ingest": 25e-6, "finger.route": 25e-6,
        "finger.shard_ingest": 20e-6, "finger.slotmap": 25e-6,
        "finger.h2d": 5e-6, "finger.poll": 10e-6})


def test_per_tick_counts_only_spans_inside_the_window():
    spans = [_span("finger.slotmap", 0, 10, lanes=5, kept=5),
             _span("finger.slotmap", 20, 60, lanes=10, kept=9),
             _span("finger.h2d", 61, 62, bytes=96),
             _span("finger.d2h", 70, 71), _span("finger.d2h", 72, 74),
             _span("finger.d2h", 95, 105)]
    got = pt.per_tick(spans, 15, 100, ticks=2)
    assert got["slotmap_ms"] == pytest.approx(20e-6)
    assert got["slotmap_us_per_lane"] == pytest.approx(40e-3 / 10)
    assert got["h2d_bytes_per_tick"] == 48
    assert got["d2h_reads_per_tick"] == 1
    assert got["d2h_wait_ms"] == pytest.approx(1.5e-6)
    assert got["route_ms"] == got["launch_ms"] == 0
    assert set(got) == set(pt.PER_TICK)
    assert pt.per_tick([], 0, 100, ticks=2) == {}


def test_idle_gaps_go_to_the_innermost_program_span():
    dev = "/device:TPU:0"
    events = [trace.Event(dev, trace.OPS_LINE, "op", 50, 60)]
    spans = [_span("finger.ingest", 0, 40), _span("finger.route", 0, 30),
             _span("finger.poll", 45, 100), _span("finger.d2h", 62, 98)]
    idle = dict(pt.idle_by_program_span(events, spans, 0, 100))
    # gaps (0,50) mid 25 route; (60,100) mid 80 d2h
    assert idle == pytest.approx({"finger.route": 50e-9,
                                  "finger.d2h": 40e-9})


def test_existing_readers_read_as_before_on_the_recorded_trace():
    """The benchmark's four readers and its idle breakdown give, on the
    first recorded trace, exactly what they gave before the program
    had spans."""
    events = trace.read(os.path.join(FIXTURES, "trace_dos_replay.json"))
    ticks = sorted(trace.spans(events, "bench.tick"),
                   key=lambda s: s.start_ns)
    lo, hi = ticks[0].start_ns, ticks[-1].end_ns
    ctx = TraceContext(events=events, lo_ns=lo, hi_ns=hi, ticks=4,
                       config=harness.load_cell("dos.replay").config,
                       device_kind="TPU v5 lite", root=tiny.ROOT)
    got = {m: harness.load_module(tiny.ROOT, "metrics", m).read(ctx)
           for m in ("ingest_ms", "readout_ms", "device_busy_ms",
                     "device_idle_pct")}
    assert got == {"ingest_ms": 49.629608749999996,
                   "readout_ms": 12.94446475,
                   "device_busy_ms": 1.6336935,
                   "device_idle_pct": 97.40660934833026}
    ops = [e for plane in trace.device_ops(events).values() for e in plane]
    idle = trace.idle_by_span(ops, [s for s in trace.spans(events)
                                    if s.name != "bench.window"], lo, hi)
    assert idle == [("bench.ingest", 0.2025393029999998),
                    ("bench.readout", 0.04290389199999997),
                    ("bench.poll", 3.4999999999999996e-08)]


@pytest.fixture(scope="module")
def rehearsal():
    """The tiny `dos.replay` traced on the CPU through the real fleet,
    with the program's spans kept."""
    with pt.keeping_program_spans() as kept:
        out = tiny.run("dos.replay", trace=True)
    events, spans = kept["events"], kept["spans"]
    lo, hi = trace.window(events)
    return out, pt.report(events, spans, lo, hi)


def test_rehearsal_reports_every_program_number(rehearsal):
    out, got = rehearsal
    assert out["correct"], out["checks"]
    # Off the chip only the device's metrics have nothing to read.
    wanted = {m["name"] for m in harness.load_cell("dos.replay").per_layer}
    assert wanted - set(out["metrics"]) \
        <= {"device_busy_ms", "device_idle_pct"}
    assert set(got["per_tick"]) == set(pt.PER_TICK)
    for name, value in got["per_tick"].items():
        assert value > 0, name


def test_rehearsal_counters_follow_the_pool(rehearsal):
    _, got = rehearsal
    pool, = tiny.tiny(harness.load_cell("dos.replay")).config["pools"]
    # one stacked launch: the score plane is the tick's one read, and
    # each tick moves six (B, k_pad) four-byte leaves
    assert got["per_tick"]["d2h_reads_per_tick"] == 1
    assert got["per_tick"]["h2d_bytes_per_tick"] \
        == pool["streams_per_shard"] * pool["k_pad"] * 4 * 6
    assert got["covered"]["readout_pct"] >= 90
    assert got["covered"]["no_span_idle_pct"] <= 10


@pytest.fixture(scope="module")
def recorded():
    """Four ticks of `dos.replay` traced on a TPU v5 lite through
    `bench/program_trace.py`: (events, spans, lo, hi)."""
    events, spans = pt.read(os.path.join(
        FIXTURES, "trace_dos_replay_program.json"))
    ticks = sorted(trace.spans(events, "bench.tick"),
                   key=lambda s: s.start_ns)
    assert len(ticks) == 4
    return events, spans, ticks[0].start_ns, ticks[-1].end_ns


def test_recorded_program_numbers(recorded):
    events, spans, lo, hi = recorded
    got = pt.per_tick(spans, lo, hi, ticks=4)
    assert set(got) == set(pt.PER_TICK)
    pool, = harness.load_cell("dos.replay").config["pools"]
    # six (B, k_pad) four-byte leaves a tick: 9 x 896 x 24 = 193,536
    assert got["h2d_bytes_per_tick"] \
        == pool["streams_per_shard"] * pool["k_pad"] * 4 * 6 == 193536
    # the pool ticks per shard: one `score_at` read per tenant, then
    # values and ids of the device top-k
    assert got["d2h_reads_per_tick"] == pool["streams_per_shard"] + 2
    for name in ("route_ms", "slotmap_ms", "stage_ms", "launch_ms",
                 "d2h_wait_ms", "slotmap_us_per_lane"):
        assert got[name] > 0, name
    lanes = [s.stats["lanes"] for s in pt.inside(spans, lo, hi,
                                                 "finger.slotmap")]
    assert lanes == [s.stats["lanes"] for s in pt.inside(
        spans, lo, hi, "finger.ingest")]


def test_recorded_spans_under_ingest_cover_it(recorded):
    _, spans, lo, hi = recorded
    for ingest in pt.inside(spans, lo, hi, "finger.ingest"):
        children = [s for s in pt.inside(spans, ingest.start_ns,
                                         ingest.end_ns)
                    if s.name in ("finger.route", "finger.wal",
                                  "finger.shard_ingest")]
        assert sum(s.ns for s in children) >= 0.9 * ingest.ns


def test_recorded_fleet_spans_of_a_tick_share_its_step(recorded):
    events, spans, lo, hi = recorded
    for tick in pt.ticks_in(events, lo, hi):
        steps = {s.stats["step"] for s in pt.inside(
            spans, tick.start_ns, tick.end_ns)
            if s.name in ("finger.ingest", "finger.poll", "finger.scores",
                          "finger.top_anomalies")}
        assert len(steps) == 1


def test_recorded_idle_time_lies_in_program_spans(recorded):
    events, spans, lo, hi = recorded
    idle = dict(pt.idle_by_program_span(events, spans, lo, hi))
    assert idle.get("no span", 0.0) <= 0.1 * sum(idle.values())


def test_fixture_rows_round_trip(recorded, tmp_path):
    events, spans, lo, hi = recorded
    path = str(tmp_path / "rows.json")
    pt.write(path, events, spans, lo, hi)
    again_events, again_spans = pt.read(path)
    assert [(s.name, s.start_ns, s.stats) for s in again_spans] \
        == [(s.name, s.start_ns, s.stats) for s in spans
            if s.end_ns > lo and s.start_ns < hi]
    assert len(again_events) <= len(events)
