"""Mean host milliseconds per tick inside `FingerFleet.ingest`: routing,
SlotMap translation, the WAL and staging."""
from bench import trace


def read(ctx):
    return trace.mean_span_ms(ctx.events, "bench.ingest")
