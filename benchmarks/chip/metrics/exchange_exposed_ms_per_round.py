"""Device time per round with a cross-chip collective in flight and no other
op running on the chip: the exchange the step does not hide (see
_exchange.py)."""
from benchmarks.chip.metrics import _exchange


def read(ctx):
    ns = _exchange.ns_per_round(ctx)
    return None if ns is None else ns[1] * 1e-6
