"""Local training seconds per silo update: the ``client.train`` spans
inside the window (the job's local AdamW steps)."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "client.train"
             and ctx.lo <= s.t0 and s.t1 <= ctx.hi]
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_updates
