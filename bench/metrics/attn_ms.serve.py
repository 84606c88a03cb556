"""attn_ms.serve: device time of attention per run of the step program
(ms).

The step program's ops whose HLO ``op_name`` lies under an ``s{i}.attn``
scope and under no PTC leaf (``bench/engine_trace.layer_of``): q/k
norms and rotary, the splice of the new rows into the views, the GQA
repeat, the scores, softmax and weighted sum, or ``prefill_attention``;
summed over the traced span and divided by the step program's runs.
Needs the scope map of the step program (``ctx.scopes``,
``engine_trace.step_scopes``); nothing to read where no op carries a
layer scope.  Layer: models/lm attention.
"""

from bench import engine_trace

UNIT = "ms"


def read(ctx):
    return engine_trace.layer_ms(ctx, "attn")
