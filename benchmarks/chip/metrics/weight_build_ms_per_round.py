"""Device time of the `weight_build` scope per round (see _fleet_sublayers.py)."""
from benchmarks.chip.metrics import _fleet_sublayers


def read(ctx):
    return _fleet_sublayers.ms_per_round(ctx, "weight_build")
