"""idle_share.serve: share of the traced window in which no op ran on
the device (%): one minus the union of the op intervals over the window.
Layer: device."""

from bench import tracing

UNIT = "%"


def read(ctx):
    if ctx.window_s <= 0:
        return None
    busy = tracing.busy_seconds(ctx.trace, ctx.lo, ctx.hi)
    return 100.0 * (1.0 - busy / ctx.window_s)
