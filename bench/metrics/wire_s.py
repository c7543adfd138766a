"""Board and crypto seconds per silo update: the silos' ``client.fetch``
(get, decrypt, unpack the global) and ``client.post`` (pack, encrypt,
put the update) spans inside the window, over the updates posted."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name in ("client.fetch", "client.post")
             and ctx.lo <= s.t0 and s.t1 <= ctx.hi]
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_updates
