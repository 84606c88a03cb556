"""ptc_ms.serve: device time of the PTC linears per run of the step
program (ms).

The step program's ops whose HLO ``op_name`` lies under a PTC leaf scope
(``wq wk wv wo gate up down``, ``bench/engine_trace.layer_of``): the
recomposition of W = U·Σ·V* from its factors on every call and the
matmul; summed over the traced span and divided by the step program's
runs.  The tied unembedding is not a PTC linear and is not counted.
Needs the scope map of the step program (``ctx.scopes``); nothing to
read where no op carries a layer scope.  Layer: models/lm PTC linears.
"""

from bench import engine_trace

UNIT = "ms"


def read(ctx):
    return engine_trace.layer_ms(ctx, "ptc")
