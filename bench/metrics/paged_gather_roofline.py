"""paged_gather_roofline: the paged KV gather kernel's share of its
roofline (%).

Bytes from shapes: every page of the (layers x slots, pages) table read
and the view written, per call (``bench/flops.paged_gather_bytes``);
time: the device time of the kernel's ops in the trace.  The gather
moves bytes only, so the bound is HBM bandwidth.  Layer: kernels/paged_kv.
"""

from bench import flops, tracing

UNIT = "%"
KERNEL = r"/paged_gather\b"


def read(ctx):
    seconds, n = tracing.op_seconds(ctx.trace, KERNEL, ctx.lo, ctx.hi)
    if not n or seconds <= 0:
        return None
    cfg, mix = ctx.cfg, ctx.mix
    per_call = flops.paged_gather_bytes(
        cfg["num_hidden_layers"] * mix["slots"], mix["max_pages_per_slot"],
        mix["page_size"], cfg["num_key_value_heads"], cfg["head_dim"], 2)
    return flops.roofline_share(0, n * per_call, seconds, ctx.peaks)[0]
