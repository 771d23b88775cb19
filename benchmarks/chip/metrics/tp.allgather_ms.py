"""Tensor parallel: device time of the all-gather ops inside the decode-step
program, per decode step, on the busiest chip.  Moves ``output_tok_s``."""
from chipbench import trace as tr

OPS = r"all-gather"
PROGRAM = r"step_fn"


def read(ctx):
    if ctx.trace is None:
        return None
    dev, spans = tr.busiest(ctx.trace, OPS, within=PROGRAM)
    if dev is None:
        return None
    steps = tr.events(ctx.trace, dev, PROGRAM, line="modules")
    return tr.total_s(spans) / len(steps) * 1e3 if steps else None
