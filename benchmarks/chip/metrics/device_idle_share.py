"""Device: share of the traced stretch in which no op ran, averaged over
the cell's chips.  Reads every ``device_idle_share.<part>`` entry; each
entry names the end-to-end metric it moves in its cell."""
from chipbench import trace as tr


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s(t) / t.window_s)
