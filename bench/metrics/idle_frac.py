"""Share of the window in which no operation ran on the device: one
minus the union of the trace's operation intervals over the window,
averaged over the chips."""
from fedbench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.lo + ctx.trace.offset, ctx.hi + ctx.trace.offset
    if not trace.devices(ctx.trace.events):
        return None
    return 1.0 - trace.busy_s(ctx.trace.events, lo, hi) / (hi - lo)
