"""Device time of the IPLS exchange on the mesh: the cross-chip collectives
(all-gather, reduce-scatter, all-reduce, collective-permute, all-to-all),
named by their HLO opcode, which starts every such op's name. They carry
LoadModel (the all-gather of the updated parameters) and UpdateModel (the
reduction of the gradients).

A collective is in flight over its event, or, for an asynchronous pair,
from the start of its ``-start`` event to the end of its ``-done`` (a
``-done`` closes the earliest open ``-start`` of its kind). Its exposed part
is the in-flight time in which no other op runs on that chip. Both are
averaged over the chips and given per round; None where the trace holds no
collective."""
import re

from benchmarks.chip import trace

COLLECTIVE = re.compile(r"(all-gather|reduce-scatter|all-reduce|collective-permute|all-to-all)")


def _in_flight(evs):
    """[(start, end)] of the collectives of one chip, and the events of
    every other op."""
    spans, other, open_ = [], [], {}
    for ev in sorted(evs):
        m = COLLECTIVE.match(ev[2])
        if not m:
            other.append(ev)
            continue
        kind, a, b = m.group(1), ev[0], ev[0] + ev[1]
        if "-start" in ev[2]:
            open_.setdefault(kind, []).append(a)
        elif "-done" in ev[2] and open_.get(kind):
            spans.append((open_[kind].pop(0), b))
        else:
            spans.append((a, b))
    return spans, other


def _intersect(xs, ys) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            total += hi - lo
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def ns_per_round(ctx):
    """(in flight, exposed) ns per round, averaged over the chips, or None."""
    tr = ctx.trace
    if not tr or not ctx.counts.get("rounds"):
        return None
    t0, t1 = trace.window(tr)
    flight = exposed = 0.0
    chips = 0
    for evs in tr["devices"].values():
        spans, other = _in_flight(evs)
        if not spans:
            continue
        chips += 1
        u = [(max(a, t0), min(b, t1)) for a, b in trace.union(spans) if min(b, t1) > max(a, t0)]
        busy = trace.union([(a, b) for a, b, _ in trace._clip(other, t0, t1)])
        length = sum(b - a for a, b in u)
        flight += length
        exposed += length - _intersect(u, busy)
    if not chips:
        return None
    rounds = ctx.counts["rounds"]
    return flight / chips / rounds, exposed / chips / rounds
