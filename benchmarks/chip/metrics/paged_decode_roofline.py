"""Kernel: the paged-decode attention kernel's share of its roofline, on the
busiest chip.  The least time of a call is the larger of its FLOPs over the
bf16 peak and its bytes over HBM bandwidth, counted from the work
(``chipbench.counts.paged_decode_work``: the K/V of each active slot's live
positions, q, the output and the block table) of the decode steps the
harness stamped in the traced stretch; the kernel time is the summed device
time of its calls there.  Moves ``output_tok_s``."""
from chipbench import counts
from chipbench import trace as tr

KERNEL = r"paged_decode"


def step_work(ctx):
    """{decode-step stamp: [live positions per active slot]} in the traced
    stretch: the k-th token of a request was made at position
    prompt + k - 1, with prompt + k positions cached."""
    steps = {}
    for r in ctx.recs:
        n = r.item.prompt_len
        for k, t in enumerate(r.req.token_times):
            if k and ctx.trace_t0 < t <= ctx.trace_t1:
                steps.setdefault(t, []).append(n + k)
    return steps


def read(ctx):
    if ctx.trace is None:
        return None
    _, spans = tr.busiest(ctx.trace, KERNEL)
    steps = step_work(ctx)
    if not spans or not steps:
        return None
    sv, m = ctx.serving, ctx.model
    least = 0.0
    for live in steps.values():
        f, b = counts.paged_decode_work(
            m, live, tp=sv["tp"], kv_bytes=2,
            max_blocks=sv["max_seq"] // sv["page_size"])
        least += counts.least_time(f, b, ctx.peak)[0]
    per_call_least = least / len(steps)
    per_call_time = tr.total_s(spans) / len(spans)
    return 100.0 * per_call_least / per_call_time
