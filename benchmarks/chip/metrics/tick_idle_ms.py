"""Scheduler: the device idle a decode tick exposes.  Mean, over the
``engine.tick`` spans wholly in the traced stretch that hold an
``engine.decode.wait``, of the time inside the tick in which no op ran,
averaged over the cell's chips.  It tracks ``tick_host_ms`` while the host
works between device steps; host work overlapped with a running step
lowers this and not that.  Reads every ``tick_idle_ms.<part>`` entry;
each names the end-to-end metric it moves in its cell."""
import statistics

from chipbench import spans


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices:
        return None
    ticks = spans.decode_ticks(t)
    if not ticks:
        return None
    per_chip = []
    for dev in t.devices:
        busy = spans.device_busy(t, dev)
        per_chip.append(statistics.mean(
            (k.b - k.a) - busy.within(k.a, k.b) for k in ticks))
    return statistics.mean(per_chip) * 1e-6
