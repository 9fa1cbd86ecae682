"""Model FLOPs of the rounds in the traced window (flops.py: local SGD
forward and backward plus the evaluations the rounds run) over the window's
length, as a share of the chips' bf16 peak."""
from benchmarks.chip import flops


def read(ctx):
    s = ctx.trace_summary
    if not s or not ctx.counts.get("rounds"):
        return None
    done = flops.fleet_round_flops(ctx.config, ctx.traffic) * ctx.counts["rounds"]
    return 100.0 * done / s["window_s"] / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
