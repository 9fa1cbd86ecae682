"""Device time of the `agg_kernel` layer per round (see _fleet_layers.py)."""
from benchmarks.chip.metrics import _fleet_layers


def read(ctx):
    return _fleet_layers.ms_per_round(ctx, "agg_kernel")
