"""Client evaluation seconds per silo update: the ``client.eval`` spans
inside the window (fetch and decrypt the global, copy it to the device,
the evaluation passes, post the loss), over the updates posted."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "client.eval"
             and ctx.lo <= s.t0 and s.t1 <= ctx.hi]
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_updates
