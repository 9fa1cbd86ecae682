"""One run of one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Finds everything by name from BENCHMARK.json at the checkout's root: the
cell's configuration (``configs/<config>.json``, which names its entry in
``entries/``), its traffic mix (``traffic/<traffic>.json``), the limits of
its comparison (``limits/<cell>.json``) and one reader per metric
(``metrics/<metric>.py``). Set-up (inputs from the seed, build, compile or
cache load, the first window) is timed from the top of this file; then the
entry runs whole windows for ``--seconds``; with ``--trace 1`` under the
profiler, whose trace gives the per-layer metrics. After the window the
device's peak memory is read, the program's state is freed, and the plain
reference decides ``correct``.

The last line of standard output is one JSON object; the numbers compared
for ``correct`` come last in it and, beside their limits, last on standard
error. A machine without the chips the cell needs exits 2 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# libtpu would otherwise log under a fixed /tmp path shared by every run
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# run as a script, the first path entry is this directory, whose module
# names (trace, bytes, ...) must not shadow the standard library's
if sys.path and Path(sys.path[0] or ".").resolve() == HERE:
    sys.path.pop(0)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


class NoChip(RuntimeError):
    pass


def load_cell(name: str) -> dict:
    """The cell's entry in BENCHMARK.json, its files, and the metrics it
    reports."""
    from benchmarks.chip import traffic as gen

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]

    def applies(m):
        return name in m.get("workloads", [cell["name"]])

    return {
        "cell": cell,
        "config": gen.load("configs", cell["config"]),
        "traffic": gen.load("traffic", cell["traffic"]),
        "limits": gen.load("limits", cell["name"]),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def devices_for(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(
            f"needs {chips} TPU chip(s); JAX finds {len(devs)} {devs[0].platform} device(s)"
        )
    return devs


def read_metrics(specs, ctx) -> dict:
    out = {}
    for m in specs:
        value = importlib.import_module(f"benchmarks.chip.metrics.{m['name']}").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(spec: dict, seed: int, seconds: float, traced: bool,
        require_chip: bool = True, t_start: float = T_START) -> dict:
    """One run of the cell ``spec`` (as ``load_cell`` gives it). Tests call
    this with ``require_chip=False`` and small configurations."""
    from benchmarks.chip import common

    cell, config, traffic, limits = (spec[k] for k in ("cell", "config", "traffic", "limits"))
    if require_chip:
        common.use_compile_cache()
    devs = devices_for(cell["chips"], require_chip)
    used = devs[: cell["chips"]]
    import jax

    entry = importlib.import_module(f"benchmarks.chip.entries.{config['entry']}")
    state = entry.build(config, traffic, seed, annotate=traced)
    setup_s = time.perf_counter() - t_start

    log_dir = None
    if traced:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(log_dir)
    try:
        counts = state.measure(seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
    tr = tsum = None
    if traced:
        from benchmarks.chip import trace

        tr = trace.record(log_dir, trace.op_paths(state.programs()))
        shutil.rmtree(log_dir, ignore_errors=True)
        tsum = trace.summary(tr)

    stats = [d.memory_stats() or {} for d in used]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    state.free()
    readings = state.readings()
    compared = {
        k: {"value": float(readings[k]), "limit": float(lim)} for k, lim in limits.items()
    }
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    kind = devs[0].device_kind
    ctx = types.SimpleNamespace(
        config=config, traffic=traffic, counts=counts, seed=seed, setup_s=setup_s,
        chips=cell["chips"], trace=tr, trace_summary=tsum,
        peaks=common.peaks(kind) if traced else None,
    )
    metrics = read_metrics(spec["per_layer"] if traced else spec["end_to_end"], ctx)
    device = {
        "platform": devs[0].platform, "kind": kind, "count": len(devs),
        "memory_peak_bytes": mem_peak,
    }
    result = {
        "correct": correct, "attempted": counts["rounds"], "failed": 0,
        "metrics": metrics, "device": device,
    }
    if traced:
        device["busy_s"] = tsum["busy_s"]
        device["window_s"] = tsum["window_s"]
        result["breakdown"] = tsum["breakdown"]
    result["counts"] = counts
    result["compared"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_cell(args.workload)
        result = run(spec, args.seed % (1 << 63), args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    # counts are printed on an earlier line: they are not metrics
    print(json.dumps({"counts": result.pop("counts")}), flush=True)
    for k, c in result["compared"].items():
        print(f"compared {k} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
