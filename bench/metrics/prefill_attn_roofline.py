"""prefill_attn_roofline: the chunked prefill attention kernel's share
of its roofline (%).

FLOPs and bytes from shapes (``bench/flops.prefill_attn_cost``): each
call's (slots, C) queries against the whole (slots, S_max) view as the
kernel's grid walks it, the head width and the view's bytes a position
taken from the pool the gateway built (``kv_pool``: one attention
position whose tensors share a head width; none otherwise); the least
time is the longer of FLOPs at the bf16 peak and bytes at HBM
bandwidth.  Time: the kernel's ops in the trace.  Only cells with a
prefill chunk C > 1 call it.  Layer: kernels/prefill_attn.
"""

from bench import flops, tracing

UNIT = "%"
KERNEL = r"/prefill_attention\b"


def read(ctx):
    mix, pool = ctx.mix, ctx.counts.get("kv_pool", {})
    if mix["prefill_chunk"] <= 1 or len(pool) != 1:
        return None
    seconds, n = tracing.op_seconds(ctx.trace, KERNEL, ctx.lo, ctx.hi)
    dims = {row[-1] for t in pool.values() for row, _ in t.values()}
    if not n or seconds <= 0 or len(dims) != 1:
        return None
    f, b = flops.prefill_attn_cost(
        mix["slots"], mix["prefill_chunk"], ctx.cfg["num_attention_heads"],
        dims.pop(), mix["max_pages_per_slot"] * mix["page_size"],
        sum(flops.pool_row_bytes(pool)), 2)
    return flops.roofline_share(n * f, n * b, seconds, ctx.peaks)[0]
