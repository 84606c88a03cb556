"""paged_scatter_roofline: the paged KV scatter kernel's share of its
roofline (%), one-row (prefill chunk 1) and multi-row (chunk C > 1)
alike.

Bytes from shapes: each call's rows (layers x slots x C) read and
written (``bench/flops.paged_scatter_bytes``), at the bytes of a row of
the pools the gateway built (``kv_pool``, ``kv_layers``); none where the
pool's tensors differ in row bytes.  Time: the device time of the
kernel's ops in the trace.  Layer: kernels/paged_kv.
"""

from bench import flops, tracing

UNIT = "%"
KERNEL = r"/paged_scatter\b"


def read(ctx):
    seconds, n = tracing.op_seconds(ctx.trace, KERNEL, ctx.lo, ctx.hi)
    rows = set(flops.pool_row_bytes(ctx.counts.get("kv_pool", {})))
    if not n or seconds <= 0 or len(rows) != 1:
        return None
    mix = ctx.mix
    per_call = flops.paged_scatter_bytes(
        ctx.counts["kv_layers"] * mix["slots"] * mix["prefill_chunk"],
        rows.pop())
    return flops.roofline_share(0, n * per_call, seconds, ctx.peaks)[0]
