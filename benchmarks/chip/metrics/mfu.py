"""Model FLOPs of the rounds in the traced window over the window's length,
as a share of the chips' bf16 peak. The FLOPs of one round come from the
cell's entry (``entries/<entry>.py:round_flops``), which counts them from
the cell's shapes."""
import importlib


def read(ctx):
    s = ctx.trace_summary
    if not s or not ctx.counts.get("rounds"):
        return None
    entry = importlib.import_module(f"benchmarks.chip.entries.{ctx.config['entry']}")
    done = entry.round_flops(ctx.config, ctx.traffic) * ctx.counts["rounds"]
    return 100.0 * done / s["window_s"] / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
