"""Client encode seconds per silo update: the ``client.compress`` spans
inside the window (pack, weight, mask or quantize; the span ends once
the encoded buffer is on the host, so its device work is inside)."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "client.compress"
             and ctx.lo <= s.t0 and s.t1 <= ctx.hi]
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_updates
