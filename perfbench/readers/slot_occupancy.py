"""Live rows / slots, averaged over the decode steps of the whole window,
from the program's ``ServingStats`` counters."""


def read(ctx):
    c = ctx.window.counters
    steps, slots = c.get("decode_steps", 0), ctx.pool["num_slots"]
    if not steps:
        return None
    return 100.0 * c["occupancy_sum"] / (steps * slots)
