"""paged_scatter_roofline: the paged KV scatter kernel's share of its
roofline (%), one-row (prefill chunk 1) and multi-row (chunk C > 1)
alike.

Bytes from shapes: each call's rows (layers x slots x C) read and
written (``bench/flops.paged_scatter_bytes``); time: the device time of
the kernel's ops in the trace, without the copy of the pool that the
caller's jit puts before it (that copy shows under its own name in the
breakdown).  Layer: kernels/paged_kv.
"""

from bench import flops, tracing

UNIT = "%"
KERNEL = r"/paged_scatter\b"


def read(ctx):
    seconds, n = tracing.op_seconds(ctx.trace, KERNEL, ctx.lo, ctx.hi)
    if not n or seconds <= 0:
        return None
    cfg, mix = ctx.cfg, ctx.mix
    rows = cfg["num_hidden_layers"] * mix["slots"] * mix["prefill_chunk"]
    per_call = flops.paged_scatter_bytes(rows, cfg["num_key_value_heads"],
                                         cfg["head_dim"], 2)
    return flops.roofline_share(0, n * per_call, seconds, ctx.peaks)[0]
