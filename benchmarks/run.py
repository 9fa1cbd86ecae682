"""Benchmark entry point: one function per paper table/figure + the roofline
and kernel harnesses. Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced sizes (CI)")
    ap.add_argument(
        "--only", default=None,
        help="comma list of: convergence,fault,scalability,roofline,kernels,rounds",
    )
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from benchmarks.common import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        bench_convergence,
        bench_fault_tolerance,
        bench_kernels,
        bench_roofline,
        bench_rounds,
        bench_scalability,
    )

    print("name,us_per_call,derived")
    sys.stdout.flush()
    t0 = time.time()
    # one runner-stamped timestamp for every artifact this invocation writes
    stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())

    def want(name):
        return only is None or name in only

    if want("kernels"):
        for r in bench_kernels.run():
            print(r)
        sys.stdout.flush()
    if want("rounds"):
        rounds = 2 if args.quick else 4
        counts = (10, 32) if args.quick else (10, 32, 100)
        lossy_counts = (10,) if args.quick else (10, 32)
        # BENCH_rounds.json lives at the repo root: it is the persisted perf
        # trajectory for the round engines and is uploaded as a CI artifact
        for r in bench_rounds.run(
            rounds=rounds, agent_counts=counts, lossy_agent_counts=lossy_counts,
            out_json="BENCH_rounds.json", timestamp=stamp,
        ):
            print(r)
        sys.stdout.flush()
    if want("roofline"):
        for r in bench_roofline.run():
            print(r)
        sys.stdout.flush()
    if want("scalability"):
        rounds = 2 if args.quick else 3
        for r in bench_scalability.run(rounds=rounds, out_json="benchmarks/out_scalability.json"):
            print(r)
        sys.stdout.flush()
    if want("fault"):
        rounds = 8 if args.quick else 30
        for r in bench_fault_tolerance.run(rounds=rounds, out_json="benchmarks/out_fault.json"):
            print(r)
        sys.stdout.flush()
    if want("convergence"):
        rounds = 6 if args.quick else 40
        counts = (5,) if args.quick else (10, 25, 50)
        for r in bench_convergence.run(
            rounds=rounds, agent_counts=counts,
            out_json="benchmarks/out_convergence.json", timestamp=stamp,
        ):
            print(r)
        sys.stdout.flush()
    print(f"# total_wall_s={time.time()-t0:.1f}")


if __name__ == "__main__":
    main()
