"""KV cache manager: host time of admission into the paged cache, per
admission.  The time the ``engine.admit`` spans (prefix-trie peek and
lookup, page reservation, copy-on-write) and ``engine.kv.register`` spans
(publishing a chunk's full prompt blocks) cover in the traced stretch,
over the number of ``engine.admit`` spans there.  Moves ``itl_p95_ms``:
admission runs between the device steps of the ticks that carry
prefill."""
from chipbench import spans


def read(ctx):
    if ctx.trace is None:
        return None
    found = spans.program(ctx.trace, (spans.ADMIT, spans.REGISTER))
    n = sum(1 for name, _, _ in found if name == spans.ADMIT)
    if not n:
        return None
    t = spans.Intervals((a, b) for _, a, b in found)
    return sum(b - a for a, b in t.merged) / n * 1e-6
