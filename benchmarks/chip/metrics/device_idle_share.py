"""Share of the traced window in which no op ran on the device (averaged
over the chips used)."""


def read(ctx):
    s = ctx.trace_summary
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
