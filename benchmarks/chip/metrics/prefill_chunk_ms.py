"""Model step: mean device time of one chunked-prefill program
(``prefill_chunk_paged``) in the traced stretch, on the busiest chip.
Moves ``itl_p95_ms``: a tick that carries chunks is one of the long gaps."""
from chipbench import trace as tr

PROGRAM = r"prefill_chunk_paged"


def read(ctx):
    if ctx.trace is None:
        return None
    _, spans = tr.busiest(ctx.trace, PROGRAM, line="modules")
    return tr.total_s(spans) / len(spans) * 1e3 if spans else None
