"""occupancy.serve: active slots over busy steps, over slots (%).

Counts of ``ServingGateway`` (``slot_steps`` over ``busy_steps``, the
report's ``occupancy``), taken as differences across the traced span
and divided by the slot count.  Layer: serving/engine + scheduler.
"""

UNIT = "%"


def read(ctx):
    c = ctx.counts
    if not c.get("busy_steps"):
        return None
    return 100.0 * c["slot_steps"] / c["busy_steps"] / c["slots"]
