"""mfu.serve: model FLOPs of the tokens served in the traced span, over
the span at the chip's bf16 peak (%).

Every position a request ran through the model inside the span (its
prompt and each emitted token but the last; counts from the engine's
step and the requests' admission, exact to a step at either end) counts
two W-equivalent matmuls per parameter of the linears and the
unembedding, and attention at its live context
(``bench/flops.serve_model_flops``).  Recomposing W from its
factors at every call, padding, idle slots and padded chunk columns do
not count.  Layer: the whole serving step.
"""

from bench import flops

UNIT = "%"


def read(ctx):
    spans = ctx.counts.get("spans")
    if not spans or ctx.window_s <= 0:
        return None
    done = flops.serve_model_flops(ctx.cfg, spans)
    return 100.0 * done / (ctx.window_s * ctx.peaks["bf16_flops"])
