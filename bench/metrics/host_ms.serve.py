"""host_ms.serve: host time per engine step outside the blocking fetch
(ms).

The mean, over the engine's ``gateway.step`` spans inside the traced
span, of each step's duration less its ``gateway.fetch`` child (the
blocking device-to-host argmax): admission, staging, dispatch of the
gather, step and scatter, and the emit loop, waits at dispatch included
(``bench/engine_trace.step_self_ms``).  Nothing to read in a trace
without the engine's spans.  Layer: serving/engine + serving/scheduler
(host).
"""

from bench import engine_trace

UNIT = "ms"


def read(ctx):
    steps = engine_trace.step_self_ms(ctx.trace, ctx.lo, ctx.hi)
    return sum(steps) / len(steps) if steps else None
