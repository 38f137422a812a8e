"""Device milliseconds per tick: the union of the device's operation
intervals in the traced window, over the ticks run (mean over chips)."""
from bench import trace


def read(ctx):
    ops = trace.device_ops(ctx.events)
    if not ops or not ctx.ticks:
        return None
    busy = [trace.busy_ns(o, ctx.lo_ns, ctx.hi_ns) for o in ops.values()]
    return sum(busy) / len(busy) / ctx.ticks * 1e-6
