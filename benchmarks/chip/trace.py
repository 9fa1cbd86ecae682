"""From a profiler trace to the numbers the per-layer metrics read.

``record(log_dir, paths)`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote with ``jax.profiler.ProfileData`` and keeps a plain form of it:

  devices  {plane name: [[start_ns, duration_ns, op name, op path], ...]}
           the events on each TPU's "XLA Ops" line, but for control flow
           (while, conditional, call), whose events span their bodies' ops.
           A TPU trace names each event by its HLO instruction; ``op path``
           is that instruction's ``op_name`` metadata (the jit / scope path
           of the JAX code that made it), from ``paths``, which
           ``op_paths`` reads from the optimized HLO of the programs run
  host     [[start_ns, duration_ns, name], ...] the spans this benchmark
           writes (names starting with ``bench.`` or ``host.``)

Everything else here works on that plain form, so the reduction can be
checked on a small recorded trace without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("bench.", "host.")
INSTRUCTION = re.compile(r"%?([^\s=]+)")
CONTROL_FLOW = re.compile(r"(while|conditional|call)(\.\d+)?")
METADATA = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = .*?op_name="([^"]*)"', re.M)


def op_paths(hlo_texts) -> Dict[str, str]:
    """{HLO instruction name: op_name metadata} of optimized HLO modules."""
    out: Dict[str, str] = {}
    for text in hlo_texts:
        out.update(METADATA.findall(text))
    return out


def record(log_dir: str, paths: Dict[str, str]) -> dict:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    devices: Dict[str, list] = {}
    host: List[list] = []
    for plane in pd.planes:
        if DEVICE_PLANE.fullmatch(plane.name):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = INSTRUCTION.match(ev.name).group(1)
                    if CONTROL_FLOW.fullmatch(name):
                        continue
                    evs.append([float(ev.start_ns), float(ev.duration_ns), name,
                                paths.get(name, "")])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append([float(ev.start_ns), float(ev.duration_ns), ev.name])
    return {"devices": devices, "host": host}


# ------------------------------------------------------------ intervals ---
def window(tr: dict, name: str = "bench.measure") -> Tuple[float, float]:
    """(start_ns, end_ns) of the harness's span ``name``."""
    spans = [h for h in tr["host"] if h[2] == name]
    if not spans:
        raise ValueError(f"no {name} span in the trace")
    s = max(spans, key=lambda h: h[1])
    return s[0], s[0] + s[1]


def _clip(evs, t0: float, t1: float):
    for ev in evs:
        a, b = max(ev[0], t0), min(ev[0] + ev[1], t1)
        if b > a:
            yield a, b, ev


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(evs, t0: float, t1: float) -> float:
    return sum(b - a for a, b in union([(a, b) for a, b, _ in _clip(evs, t0, t1)]))


def attribute(evs, classes: Sequence[Tuple[str, Callable[[list], bool]]],
              t0: float, t1: float, rest: str = "other") -> Dict[str, float]:
    """Busy time (ns) in [t0, t1] split over ``classes``: each instant the
    device is busy goes to the first class whose test accepts an op running
    then, or to ``rest``. The parts add up to the busy time exactly."""
    labels = [c[0] for c in classes] + [rest]
    rank = {}
    points = []
    for a, b, ev in _clip(evs, t0, t1):
        r = next((i for i, (_, test) in enumerate(classes) if test(ev)), len(classes))
        points.append((a, 1, r))
        points.append((b, -1, r))
    points.sort(key=lambda p: (p[0], p[1]))
    out = {lab: 0.0 for lab in labels}
    last = None
    for t, step, r in points:
        if last is not None and rank:
            out[labels[min(rank)]] += t - last
        rank[r] = rank.get(r, 0) + step
        if rank[r] == 0:
            del rank[r]
        last = t
    return out


def gaps(evs, t0: float, t1: float) -> List[Tuple[float, float]]:
    """Idle intervals of one device inside [t0, t1]."""
    out, cur = [], t0
    for a, b in union([(a, b) for a, b, _ in _clip(evs, t0, t1)]):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if t1 > cur:
        out.append((cur, t1))
    return out


def idle_by_host_span(evs, host: list, t0: float, t1: float) -> Dict[str, float]:
    """Idle seconds of the device, put down to the innermost host span that
    was open at each instant (``idle`` where none was)."""
    spans = sorted(host, key=lambda h: (h[0], -h[1]))
    out: Dict[str, float] = {}
    for a, b in gaps(evs, t0, t1):
        cuts = {a, b}
        for s in spans:
            for t in (s[0], s[0] + s[1]):
                if a < t < b:
                    cuts.add(t)
        cuts = sorted(cuts)
        for lo, hi in zip(cuts[:-1], cuts[1:]):
            mid = 0.5 * (lo + hi)
            inner = [s for s in spans if s[0] <= mid < s[0] + s[1]]
            name = min(inner, key=lambda s: s[1])[2] if inner else "idle"
            out[name] = out.get(name, 0.0) + (hi - lo) * 1e-9
    return out


def top_ops(evs, t0: float, t1: float, n: int = 10) -> List[list]:
    """The ``n`` device ops that took the most time in [t0, t1], each named
    by its HLO instruction and its op path."""
    tot: Dict[str, float] = {}
    for a, b, ev in _clip(evs, t0, t1):
        key = f"{ev[2]} {ev[3]}".strip()
        tot[key] = tot.get(key, 0.0) + (b - a) * 1e-9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def summary(tr: dict) -> dict:
    """Busy and window seconds (averaged over the devices) and the
    ``breakdown`` of the result line."""
    t0, t1 = window(tr)
    devs = [d for d in tr["devices"].values() if d]
    if not devs:
        raise ValueError("no device ops in the trace")
    busy = sum(busy_ns(d, t0, t1) for d in devs) / len(devs) * 1e-9
    first = devs[0]
    idle = idle_by_host_span(first, tr["host"], t0, t1)
    return {
        "busy_s": busy,
        "window_s": (t1 - t0) * 1e-9,
        "breakdown": {
            "device_ops": top_ops(first, t0, t1),
            "idle_gaps": [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
        },
    }
