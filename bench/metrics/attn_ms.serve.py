"""attn_ms.serve: device time of attention per run of the step program
(ms).

The step program's ops whose HLO ``op_name`` has a scope matching
``s{i}.attn`` and no PTC leaf scope (``bench/engine_trace.in_layer``):
q/k norms and rotary, the splice of the new rows into the views, the
GQA repeat, the scores, softmax and weighted sum, or
``prefill_attention``; summed over the traced span and divided by the
step program's runs.  Needs the scope map of the step program
(``ctx.scopes``, ``engine_trace.step_scopes``) and the PTC leaves
(``ctx.ptc_leaves``); nothing to read where no op lies under an
``s{i}.attn`` scope.  Layer: models/lm attention.
"""

from bench import engine_trace

UNIT = "ms"
SCOPE = r"^s\d+\.attn$"


def read(ctx):
    return engine_trace.layer_ms(ctx, SCOPE, exclude_ptc=True)
