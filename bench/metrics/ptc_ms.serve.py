"""ptc_ms.serve: device time of the PTC linears per run of the step
program (ms).

The step program's ops whose HLO ``op_name`` has a PTC leaf scope (a
key of the parameters that holds ``u``, ``s`` and ``v``,
``ctx.ptc_leaves``; ``wq wk wv wo gate up down`` in both dense cells):
the recomposition of W = U·Σ·V* from its factors on every call and the
matmul; summed over the traced span and divided by the step program's
runs.  The tied unembedding is not a PTC linear and is not counted.
Needs the scope map of the step program (``ctx.scopes``); nothing to
read where no op lies under a PTC leaf.  Layer: models/lm PTC linears.
"""

from bench import engine_trace

UNIT = "ms"


def read(ctx):
    leaves = getattr(ctx, "ptc_leaves", frozenset())
    return engine_trace.layer_ms(ctx, engine_trace.leaf_pattern(leaves))
