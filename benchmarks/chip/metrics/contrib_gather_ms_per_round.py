"""Device time of the `contrib_gather` scope per round (see _fleet_sublayers.py)."""
from benchmarks.chip.metrics import _fleet_sublayers


def read(ctx):
    return _fleet_sublayers.ms_per_round(ctx, "contrib_gather")
