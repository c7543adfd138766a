"""The fp32 server combine's share of its roofline, in percent: the
least time for the bytes it must move ((N + 1) rows of T float32: N in,
the sum out) at the chip's HBM bandwidth, over the device time of every
operation the ``masked_sum`` program runs, pad copies included, inside
the window. One combine runs per ``kernel:masked_sum_stream`` span."""
from fedbench import flops, trace


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    calls = [s for s in ctx.spans if s.name == "kernel:masked_sum_stream"
             and ctx.lo <= s.t0 and s.t1 <= ctx.hi]
    lo, hi = ctx.lo + ctx.trace.offset, ctx.hi + ctx.trace.offset
    device_s = trace.module_s(ctx.trace.events, "jit_masked_sum", lo, hi)
    if not calls or device_s <= 0.0:
        return None
    need = sum(flops.masked_sum_bytes(int(s.attrs["cohort"]), ctx.t)
               for s in calls)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / device_s
