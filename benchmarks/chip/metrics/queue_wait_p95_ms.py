"""Scheduler: 95th percentile of admission (``Request.t_admit``, when the
engine starts a request's prefill) minus due time, over every request due
in the window; one not admitted by the window's end enters with its wait so
far.  Moves ``itl_p95_ms``: requests wait for admission behind the
prefill that lengthens those ticks."""
from chipbench import stats


def read(ctx):
    v = stats.percentile(stats.wait_samples(
        [(r.due, r.req.t_admit) for r in ctx.recs], ctx.t0, ctx.t1), 95)
    return None if v is None else v * 1e3
