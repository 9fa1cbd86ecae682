"""Host time per round of the LOSSY control plane: the window's fate draws
and its W control rounds (spans ``host.fate_draw`` and ``host.control``)."""
from benchmarks.chip.metrics import _fleet_sublayers


def read(ctx):
    return _fleet_sublayers.host_ms_per_round(ctx, "fate_draw", "control")
