"""Host time per round drawing the agents' batches (span ``host.batches``)."""
from benchmarks.chip.metrics import _fleet_sublayers


def read(ctx):
    return _fleet_sublayers.host_ms_per_round(ctx, "batches")
