"""Device time of the fused round engine's layers, from each op's path in
the trace. The four parts partition the device's busy time: an instant in
which several ops run goes to the first class that claims one of them."""
from benchmarks.chip import trace

CLASSES = (
    # the Pallas call itself, not the pads and reshapes of its jit wrapper
    ("agg_kernel", lambda ev: ev[2].startswith("ipls_aggregate_batched")
     or ("ipls_aggregate_batched" in ev[3] and ev[3].endswith("pallas_call"))),
    ("sgd", lambda ev: "sgd_steps_flat" in ev[3]),
    ("eval", lambda ev: "jit(evaluate)" in ev[3]),
)
REST = "gather_merge"


def ns_per_round(ctx):
    """{layer: device ns per round} on the first device, or None."""
    tr = ctx.trace
    if not tr or not ctx.counts.get("rounds"):
        return None
    evs = next((d for d in tr["devices"].values() if d), None)
    if evs is None:
        return None
    t0, t1 = trace.window(tr)
    parts = trace.attribute(evs, CLASSES, t0, t1, rest=REST)
    return {k: v / ctx.counts["rounds"] for k, v in parts.items()}


def ms_per_round(ctx, layer):
    parts = ns_per_round(ctx)
    if parts is None:
        return None
    return parts[layer] * 1e-6
