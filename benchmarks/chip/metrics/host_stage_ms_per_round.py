"""Host time per round stacking the window's scan inputs and handing them to
the device (span ``host.stage``)."""
from benchmarks.chip.metrics import _fleet_sublayers


def read(ctx):
    return _fleet_sublayers.host_ms_per_round(ctx, "stage")
