"""Scheduler: the engine's host time in a decode tick, less its waits on
the chip.  Median, over the ``engine.tick`` spans wholly in the traced
stretch that hold an ``engine.decode.wait``, of the tick's duration less
the time its ``engine.decode.wait`` and ``engine.prefill.wait`` spans
cover.  Reads every ``tick_host_ms.<part>`` entry; each names the
end-to-end metric it moves in its cell (``itl_p50_ms`` open,
``output_tok_s`` closed)."""
import statistics

from chipbench import spans


def read(ctx):
    if ctx.trace is None:
        return None
    ticks = spans.decode_ticks(ctx.trace)
    if not ticks:
        return None
    return statistics.median(
        (t.b - t.a) - t.covered_ns(spans.WAITS) for t in ticks) * 1e-6
