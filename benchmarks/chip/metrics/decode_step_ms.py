"""Model step: mean device time of one decode-step program (the engine's
jitted ``step_fn`` over ``serve_step_paged``, sharded at TP>1) in the
traced stretch, on the busiest chip.  Moves ``output_tok_s``."""
from chipbench import trace as tr

PROGRAM = r"step_fn"


def read(ctx):
    if ctx.trace is None:
        return None
    _, spans = tr.busiest(ctx.trace, PROGRAM, line="modules")
    return tr.total_s(spans) / len(spans) * 1e3 if spans else None
