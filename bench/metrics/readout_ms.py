"""Mean host milliseconds per tick in `scores` + `top_anomalies`,
including the wait for the tick's device work."""
from bench import trace


def read(ctx):
    return trace.mean_span_ms(ctx.events, "bench.readout")
