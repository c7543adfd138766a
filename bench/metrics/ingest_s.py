"""Server ingest seconds per silo update: the ``server.ingest`` spans
inside the window (one per update the server takes in: get, decrypt and
unpack it, fold it into the round's sink), over the updates posted."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "server.ingest"
             and ctx.lo <= s.t0 and s.t1 <= ctx.hi]
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_updates
