"""step_ms.serve: device time of the jitted gateway step, per step (ms).

Sum of the runs of the step program in the trace's module line (the
one-token ``gateway_step`` or the chunked ``prefill_step``) over their
count.  Layer: models/lm, the jitted gateway step.
"""

from bench import tracing

UNIT = "ms"
STEP = r"^jit_(gateway_step|prefill_step)\b"


def read(ctx):
    seconds, n = tracing.module_runs(ctx.trace, STEP, ctx.lo, ctx.hi)
    return 1e3 * seconds / n if n else None
