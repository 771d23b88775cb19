"""Scheduler: decode tokens per decode step in the window.  Every token a
decode step emits carries that step's timestamp, so the distinct stamps of
the non-first tokens are the steps.  Moves ``output_tok_s``."""


def read(ctx):
    stamps, n = set(), 0
    for r in ctx.recs:
        for t in r.req.token_times[1:]:
            if ctx.t0 < t <= ctx.t1:
                stamps.add(t)
                n += 1
    return n / len(stamps) if stamps else None
