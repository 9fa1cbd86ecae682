"""The readings that the limits of a cell are set from, at the cell's own
size, on the chip, in one process:

    python3 benchmarks/chip/readings.py --workload <cell> \
        --program 1,2,...   --control 1,2,3   --faults 1,2,3

For each seed it runs the plain float32 reference over the first window's
rounds and compares with it, by the cell's numbers. One JSON line per seed
and variant on standard output. The benchmark's own runs do not run this.

A fleet cell (entry ``fused_engine``; reference/compare.py):

  program         the program as the benchmark runs it (the lower reading)
  control         the reference with local SGD in bfloat16, in the program's
                  place (the upper reading)
  half_batch      the program with local SGD on half of each batch
  state_unchanged the program with local SGD that returns its input

A mesh cell (entry ``mesh_step``; reference/mesh_step.py): ``program`` as
above; the controls and faults are the reference's ``VARIANTS`` put in the
program's place (``--control`` runs ``control_fp8``, ``--faults`` the rest):
the matrix products' inputs in float8 (the control); the learning
rate halved, eps off, absent agents counted in, half of the batch left out,
one layer's gradient dropped, the exchange left out (each partition updated
from its owner's rows alone).
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if sys.path and Path(sys.path[0] or ".").resolve() == HERE:
    sys.path.pop(0)
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def faulty_sgd(kind: str, real):
    """``mlp_mnist.sgd_steps_flat`` with a fault planted: ``half_batch``
    trains on the first half of each batch, ``state_unchanged`` returns
    the weights it was given."""

    def half_batch(w, x, y, lr, iters, layout):
        h = x.shape[0] // 2
        return real(w, x[:h], y[:h], lr, iters, layout)

    def state_unchanged(w, x, y, lr, iters, layout):
        return w

    return {"half_batch": half_batch, "state_unchanged": state_unchanged}[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)

    from benchmarks.chip import common
    from benchmarks.chip import run as harness

    spec = harness.load_cell(args.workload)
    common.use_compile_cache()
    harness.devices_for(spec["cell"]["chips"], require_chip=True)
    read = {"fused_engine": fleet_readings, "mesh_step": mesh_readings}[spec["config"]["entry"]]
    for seed, what, numbers, seconds in read(spec, args):
        print(json.dumps({
            "workload": args.workload, "seed": seed, "variant": what, **numbers,
            "seconds": seconds,
        }), flush=True)
    return 0


def fleet_readings(spec: dict, args):
    import jax.numpy as jnp

    from benchmarks.chip import traffic as gen
    from benchmarks.chip.entries import fused_engine
    from benchmarks.chip.reference import compare, ipls
    from repro.models import mlp_mnist

    config, traffic = spec["config"], spec["traffic"]
    rounds = traffic["scan_rounds"]
    plan = [(s, "program") for s in _seeds(args.program)]
    plan += [(s, "control") for s in _seeds(args.control)]
    for kind in ("half_batch", "state_unchanged"):
        plan += [(s, kind) for s in _seeds(args.faults)]
    plan.sort(key=lambda p: p[0])  # one reference per seed
    refs = {}
    for seed, what in plan:
        t0 = time.perf_counter()
        if seed not in refs:
            shards, x_te, y_te = gen.fleet_inputs(config, seed)
            ref = ipls.run_fleet(config["fleet"], traffic, seed, shards, x_te, y_te, rounds)
            refs = {seed: (ref, x_te, y_te, shards)}
        ref, x_te, y_te, shards = refs[seed]
        if what == "control":
            got = ipls.run_fleet(
                config["fleet"], traffic, seed, shards, x_te, y_te, rounds,
                sgd_dtype=jnp.bfloat16,
            )
        else:
            real = mlp_mnist.sgd_steps_flat
            if what != "program":
                mlp_mnist.sgd_steps_flat = faulty_sgd(what, real)
            try:
                run = fused_engine.build(config, traffic, seed)
            finally:
                mlp_mnist.sgd_steps_flat = real
            got = run.first
            run.free()
        yield seed, what, compare.fleet_numbers(got, ref, x_te, y_te), time.perf_counter() - t0


def mesh_readings(spec: dict, args):
    import jax

    from benchmarks.chip import traffic as gen
    from benchmarks.chip.entries import mesh_step
    from benchmarks.chip.reference import mesh_step as ref

    config, traffic = spec["config"], spec["traffic"]
    n = config["mesh"]["data"]
    devices = jax.devices()[:n]
    program, control, faults = (set(_seeds(a)) for a in (args.program, args.control, args.faults))
    for seed in sorted(program | control | faults):
        t0 = time.perf_counter()
        if seed in program:
            run = mesh_step.build(config, traffic, seed)
            got, batches = run.first, run.batches
            run.free()
            del run
        else:
            got, batches = None, [
                gen.round_batch(traffic, n, config["model"]["vocab_size"], seed, r)
                for r in range(traffic["first_window_rounds"])
            ]
        base = ref.run(config, traffic, seed, batches, devices)
        if got is not None:
            numbers = {**ref.numbers(got, base), "worst": ref.worst_leaves(got, base)}
            yield seed, "program", numbers, time.perf_counter() - t0
        for name, variant in ref.VARIANTS.items():
            is_control = name.startswith("control")
            if name == "program":
                continue
            if seed not in (control if is_control else faults):
                continue
            t0 = time.perf_counter()
            low = ref.run(config, traffic, seed, batches, devices, variant)
            yield seed, name, ref.numbers(low, base), time.perf_counter() - t0


if __name__ == "__main__":
    sys.exit(main())
