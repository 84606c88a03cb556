"""paged_gather_roofline: the paged KV gather kernel's share of its
roofline (%).

Bytes from shapes: every page of the (layers x slots, pages) table read
and the view written, per call (``bench/flops.paged_gather_bytes``), at
the bytes of a row of the pools the gateway built (``kv_pool``,
``kv_layers``); none where the pool's tensors differ in row bytes.
Time: the device time of the kernel's ops in the trace.  The gather
moves bytes only, so the bound is HBM bandwidth.  Layer:
kernels/paged_kv.
"""

from bench import flops, tracing

UNIT = "%"
KERNEL = r"/paged_gather\b"


def read(ctx):
    seconds, n = tracing.op_seconds(ctx.trace, KERNEL, ctx.lo, ctx.hi)
    rows = set(flops.pool_row_bytes(ctx.counts.get("kv_pool", {})))
    if not n or seconds <= 0 or len(rows) != 1:
        return None
    mix = ctx.mix
    per_call = flops.paged_gather_bytes(
        ctx.counts["kv_layers"] * mix["slots"], mix["max_pages_per_slot"],
        mix["page_size"], rows.pop())
    return flops.roofline_share(0, n * per_call, seconds, ctx.peaks)[0]
