"""Model step: model FLOPs of the window's work over the chips' bf16 peak
for the window.  Counted from the harness's own requests
(``chipbench.counts``): each decode token in the window at its context,
with the output head; each prefill-computed token (the engine's
``prefill_tokens_computed`` over the window) without the head, its
attention at half the prompt of the requests whose prefill ended in the
window; one head per first token.  Moves ``output_tok_s``."""
from chipbench import counts


def read(ctx):
    m = ctx.model
    flops, firsts, prompts = 0.0, 0, []
    for r in ctx.recs:
        n = r.item.prompt_len
        for k, t in enumerate(r.req.token_times):
            if not ctx.t0 < t <= ctx.t1:
                continue
            if k == 0:
                firsts += 1
                prompts.append(n)
            else:
                flops += counts.token_flops(m, n + k)
    pre = ctx.counters.get("prefill_tokens_computed", 0)
    ctx_mean = sum(prompts) / (2 * len(prompts)) if prompts else 0
    flops += pre * (counts.token_flops(m, 0, head=False)
                    + counts.attention_flops(m, ctx_mean))
    flops += firsts * 2 * counts.head_params(m)
    if not flops:
        return None
    window = ctx.t1 - ctx.t0
    return 100.0 * flops / (window * ctx.chips * ctx.peak["bf16_flops"])
