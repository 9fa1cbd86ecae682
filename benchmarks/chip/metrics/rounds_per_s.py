"""Simulated rounds completed in the timed span over its host-clock length.
The span holds whole scan windows, each ending in its host sync."""


def read(ctx):
    c = ctx.counts
    return c["rounds"] / c["elapsed_s"] if c.get("rounds") else None
