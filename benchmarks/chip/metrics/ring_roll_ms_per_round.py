"""Device time of the `ring_roll` scope per round (see _fleet_sublayers.py)."""
from benchmarks.chip.metrics import _fleet_sublayers


def read(ctx):
    return _fleet_sublayers.ms_per_round(ctx, "ring_roll")
