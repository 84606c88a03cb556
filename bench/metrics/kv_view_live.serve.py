"""kv_view_live.serve: share of the gathered KV view positions that hold
live tokens (%).

The engine's ``kv_live_positions`` (each active slot's cache length
before the step plus the tokens it takes in, summed over busy steps),
taken as a difference across the traced span, over busy steps x slots x
S_max, the positions every step gathers, copies and attends over.  The
ceiling on what a length-aware gather or attention could save.  Layer:
kernels/paged_kv + attention views.
"""

UNIT = "%"


def read(ctx):
    c, mix = ctx.counts, ctx.mix
    live = c.get("kv_live_positions")
    if not live or not c.get("busy_steps"):
        return None
    s_max = mix["max_pages_per_slot"] * mix["page_size"]
    return 100.0 * live / (c["busy_steps"] * c["slots"] * s_max)
