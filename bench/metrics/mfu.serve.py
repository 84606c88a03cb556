"""mfu.serve: model FLOPs of the tokens served in the whole window, over
the window's time at the chip's bf16 peak (%).

Every position a request ran through the model in the window (its
prompt and each emitted token but the last; counts from the engine's
step and the requests' admission, exact to the step of the cut) counts
two W-equivalent matmuls per parameter of the linears it runs through,
experts and latent attention included, and of the unembedding, and
attention at its live context (``bench/flops.serve_model_flops``).  The
time is the host clock's over the window, as ``tok_s`` reads it, less
the seconds the profiler took to stop inside it (``served_s``); the
traced seconds run with the profiler on.  Recomposing W from its
factors at every call, padding, idle slots and padded chunk columns do
not count.  Layer: the whole serving step.
"""

from bench import flops

UNIT = "%"


def read(ctx):
    spans, seconds = ctx.counts.get("spans"), ctx.counts.get("served_s")
    if not spans or not seconds:
        return None
    done = flops.serve_model_flops(ctx.cfg, spans)
    return 100.0 * done / (seconds * ctx.peaks["bf16_flops"])
