"""The parts of `gather and merge` on the device, and the engine's host
phases, from what the program writes into the profiler's trace.

Device: the engine puts each part of the scanned body's aggregation in a
``jax.named_scope`` (``contrib_gather``, ``weight_build``, ``wire_codec``,
``ring_roll``, ``replica_merge``), so every device op's path names its part.
The classes of ``_fleet_layers`` claim an instant first, then one class per
scope; what no scope claims is ``unscoped``. So the parts and ``unscoped``
add up to ``gather_merge`` exactly. The ops of the kernel's jit wrapper that
are not its ``pallas_call`` (pads and reshapes into the kernel's layout)
count as ``contrib_gather``: the same cost of feeding the kernel.

Some ops carry no path: the compiler made them, as the loop of dynamic
slices it expands a row gather into, or a copy of a loop-carried buffer.
Such an op takes the scope of the last op before it on the device that has
a path (none if that op is in no scope), since it runs inside or right after
the lowering of that op.

Host: the engine's window phases are spans named ``host.<phase>``. A
phase's time is the length of its spans inside ``bench.measure``.

A program that writes no scope (or no such span) reads None.
"""
from benchmarks.chip import trace
from benchmarks.chip.metrics import _fleet_layers

SCOPES = ("contrib_gather", "weight_build", "wire_codec", "ring_roll", "replica_merge")
REST = "unscoped"
# the kernel wrapper's ops, which feed the kernel its layout
WRAPPER = "jit(ipls_aggregate_batched"


def scope_of(path: str):
    if WRAPPER in path:
        return "contrib_gather"
    return next((s for s in SCOPES if f"/{s}/" in path), None)


def _with_scopes(evs):
    """The events, in start order, each with its scope appended (inherited
    by ops that have no path)."""
    out, last = [], None
    for ev in sorted(evs):
        if ev[3]:
            last = scope_of(ev[3])
        out.append(list(ev[:4]) + [last])
    return out


CLASSES = _fleet_layers.CLASSES + tuple(
    (s, lambda ev, s=s: ev[4] == s) for s in SCOPES
)


def ns_per_round(ctx):
    """{part: device ns per round} on the first device (the classes of
    ``_fleet_layers`` included), or None where no op carries a scope."""
    tr = ctx.trace
    if not tr or not ctx.counts.get("rounds"):
        return None
    evs = next((d for d in tr["devices"].values() if d), None)
    if evs is None or not any(f"/{s}/" in ev[3] for ev in evs for s in SCOPES):
        return None
    t0, t1 = trace.window(tr)
    parts = trace.attribute(_with_scopes(evs), CLASSES, t0, t1, rest=REST)
    return {k: v / ctx.counts["rounds"] for k, v in parts.items()}


def ms_per_round(ctx, part: str):
    parts = ns_per_round(ctx)
    if parts is None:
        return None
    return parts[part] * 1e-6


def host_ms_per_round(ctx, *phases: str):
    """Host milliseconds per round in the spans ``host.<phase>`` for the
    given phases, clipped to ``bench.measure``; None where none was written."""
    tr = ctx.trace
    if not tr or not ctx.counts.get("rounds"):
        return None
    names = {f"host.{p}" for p in phases}
    spans = [h for h in tr["host"] if h[2] in names]
    if not spans:
        return None
    t0, t1 = trace.window(tr)
    return trace.busy_ns(spans, t0, t1) / ctx.counts["rounds"] * 1e-6
