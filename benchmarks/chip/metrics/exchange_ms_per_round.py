"""Device time per round with a cross-chip collective in flight (the IPLS
exchange; see _exchange.py)."""
from benchmarks.chip.metrics import _exchange


def read(ctx):
    ns = _exchange.ns_per_round(ctx)
    return None if ns is None else ns[0] * 1e-6
