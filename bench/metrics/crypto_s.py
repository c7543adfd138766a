"""Cipher seconds per silo update: every actor's ``wire.encrypt`` and
``wire.decrypt`` spans inside the window, over the updates posted. The
cipher's share of ``wire_s``, ``ingest_s``, ``publish_s`` and
``eval_s``, so it overlaps each of them."""


def read(ctx):
    spans = [s for s in ctx.spans
             if s.name in ("wire.encrypt", "wire.decrypt")
             and ctx.lo <= s.t0 and s.t1 <= ctx.hi]
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / ctx.n_updates
