"""Share of the traced window in which no operation ran on the device
(mean over chips)."""
from bench import trace


def read(ctx):
    ops = trace.device_ops(ctx.events)
    if not ops or ctx.hi_ns <= ctx.lo_ns:
        return None
    busy = [trace.busy_ns(o, ctx.lo_ns, ctx.hi_ns) for o in ops.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (ctx.hi_ns - ctx.lo_ns))
