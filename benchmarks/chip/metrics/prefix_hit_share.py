"""KV cache manager: prompt tokens served from cached prefix pages, as a
share of the prompt tokens admitted in the window (the engine's
``prefix_tokens_reused`` counter over the window).  Moves ``itl_p95_ms``: each
token reused is one less to prefill in a tick."""


def read(ctx):
    admitted = sum(r.item.prompt_len for r in ctx.recs
                   if ctx.t0 < r.req.t_admit <= ctx.t1)
    if not admitted:
        return None
    return 100.0 * ctx.counters.get("prefix_tokens_reused", 0) / admitted
