"""Tensor parallel: the rate at which the decode step's all-gathers move
data into a chip, in GB/s.  The bytes are the program's own count
(``tp_gather_bytes_decode``, summed over chips, added per decode step) over
the window's decode steps and the cell's chips; the time is the all-gather
device time of one decode step on the busiest chip, as ``tp.allgather_ms``
reads it.  Moves ``output_tok_s``."""
from chipbench import trace as tr

OPS = r"all-gather"
PROGRAM = r"step_fn"
COUNTER = "tp_gather_bytes_decode"


def decode_steps(ctx) -> int:
    """Decode steps run since the window opened: the distinct stamps of the
    tokens after each request's first (a step stamps every token it makes
    with one time), a last step that ends after the close included, as the
    counter includes it."""
    return len({t for r in ctx.recs for t in r.req.token_times[1:]
                if t > ctx.t0})


def read(ctx):
    moved = ctx.counters.get(COUNTER)
    if ctx.trace is None or not moved:
        return None
    n = decode_steps(ctx)
    dev, spans = tr.busiest(ctx.trace, OPS, within=PROGRAM)
    if dev is None or not n:
        return None
    steps = tr.events(ctx.trace, dev, PROGRAM, line="modules")
    gather_s = tr.total_s(spans) / len(steps) if steps else 0.0
    if gather_s <= 0:
        return None
    return moved / n / ctx.chips / gather_s * 1e-9
