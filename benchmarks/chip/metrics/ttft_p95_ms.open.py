"""Scheduler: 95th percentile, over every request due in the window, of
first token minus due time; a request with no first token by the window's
end enters with its wait so far.  Per layer, not end to end: at the open
mix's load it lands among a window's dozen audio first asks, so it moves
by whole ticks from seed to seed.  Moves ``itl_p95_ms``: the ticks that
carry prefill set both."""
from chipbench import stats


def read(ctx):
    v = stats.percentile(stats.ttft_samples(
        [(r.due, r.req.token_times) for r in ctx.recs], ctx.t0, ctx.t1), 95)
    return None if v is None else v * 1e3
