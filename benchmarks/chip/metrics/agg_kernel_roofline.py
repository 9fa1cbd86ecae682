"""The aggregation kernel's share of its roofline. HBM bounds it (about one
FLOP per byte), so the least time is the bytes the aggregation requires
(bytes.py, from the cell's shapes and the rounds traced) over the HBM peak;
the share is that over the kernel's device time. None where the kernel did
not run."""
from benchmarks.chip import bytes as agg_bytes
from benchmarks.chip.metrics import _fleet_layers


def read(ctx):
    parts = _fleet_layers.ns_per_round(ctx)
    if not parts or parts["agg_kernel"] <= 0:
        return None
    c = ctx.counts
    rounds = range(c["first_round"], c["first_round"] + c["rounds"])
    need = agg_bytes.fleet_agg_bytes(ctx.config, ctx.traffic, ctx.seed, rounds)
    least_s = need / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (parts["agg_kernel"] * c["rounds"] * 1e-9)
