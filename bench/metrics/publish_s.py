"""Server distribute seconds per silo update: the
``server.publish_global`` spans inside the window (the global's host
copy, pack, encrypt and put on the board), over the updates posted."""


def read(ctx):
    spans = [s for s in ctx.spans if s.name == "server.publish_global"
             and ctx.lo <= s.t0 and s.t1 <= ctx.hi]
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_updates
