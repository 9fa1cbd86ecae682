"""Fault-tolerance demo: agents leave, crash, disconnect and rejoin while
training continues (paper §2.2 Terminate + Fig 3b).

    PYTHONPATH=src python examples/churn_demo.py
    PYTHONPATH=src python examples/churn_demo.py --engine vectorized --scan-rounds 7
    PYTHONPATH=src python examples/churn_demo.py --metrics-out churn.jsonl --trace-out churn.trace.json

Both engines run the same membership-event schedule: the vectorized engine
replays each event round on its embedded scalar oracle and re-snapshots the
dense planes at the boundary (docs/ENGINE.md "Churn re-snapshot"), so with
--engine vectorized the demo runs the real schedule fused — optionally
lax.scan-windowed — and then re-runs it on the scalar engine to assert the
final accuracies match.
"""
import argparse

from repro.data import iid_split, synth_mnist
from repro.fl import SimConfig, make_simulation
from repro.p2p.network import LOSSY

def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--engine", default="scalar", choices=["scalar", "vectorized"],
        help="round engine; vectorized runs the same churn schedule via "
        "event-boundary re-snapshot and is verified against the scalar oracle",
    )
    ap.add_argument(
        "--scan-rounds", type=int, default=0,
        help="vectorized only: fuse this many rounds per lax.scan device call "
        "(0: one round a call)",
    )
    ap.add_argument(
        "--telemetry", action="store_true",
        help="record the per-round metric stream (docs/TELEMETRY.md)",
    )
    ap.add_argument(
        "--metrics-out", default=None, metavar="PATH",
        help="write the metric stream as JSONL (implies --telemetry)",
    )
    ap.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="write a Chrome trace-event JSON timeline (implies --telemetry); "
        "open at https://ui.perfetto.dev",
    )
    args = ap.parse_args()
    telemetry = args.telemetry or bool(args.metrics_out or args.trace_out)

    x_tr, y_tr, x_te, y_te = synth_mnist(num_train=8000, num_test=2000, seed=0)
    shards = iid_split(x_tr, y_tr, num_agents=6, seed=0)

    churn = {
        3: [(5, "offline")],              # agent 5 loses connectivity
        5: [(4, "leave")],                # agent 4 leaves gracefully (Terminate)
        7: [(5, "online")],               # agent 5 rejoins (with memory)
        9: [(3, "crash")],                # agent 3 fails without handoff
    }
    cfg = SimConfig(
        num_agents=6, num_partitions=12, pi=3, rho=2, rounds=14,
        local_iters=8, churn=churn, memory=True, conditions=LOSSY,
        engine=args.engine, scan_rounds=args.scan_rounds,
        telemetry=telemetry, trace=bool(args.trace_out),
    )
    sim = make_simulation(cfg, shards, x_te, y_te)
    for m in sim.run():
        rnd = m["round"]
        events = ",".join(a for _, a in churn.get(rnd, [])) or "-"
        print(
            f"round {rnd:2d} active={m['active']} acc={m['acc_mean']:.4f} "
            f"(+/-{m['acc_std']:.4f}) churn=[{events}]"
        )
    assert sim.table.coverage(), "partition coverage lost!"
    print("\npartition coverage preserved through leave/crash/rejoin ✓")
    if args.engine == "vectorized":
        print(f"device dispatches: {sim.device_dispatches} for {cfg.rounds} rounds")
        # same schedule on the scalar oracle: the re-snapshot path must land
        # on the identical final accuracy (weights match to float noise)
        import dataclasses

        ref = make_simulation(
            dataclasses.replace(
                cfg, engine="scalar", scan_rounds=0, telemetry=False, trace=False
            ),
            shards, x_te, y_te,
        )
        ref_acc = ref.run()[-1]["acc_mean"]
        acc = sim.history[-1]["acc_mean"]
        assert abs(acc - ref_acc) < 1e-6, (acc, ref_acc)
        print(f"scalar-oracle check: final acc {acc:.4f} == {ref_acc:.4f} ✓")
    if args.metrics_out:
        sim.recorder.write_jsonl(
            args.metrics_out,
            meta={"example": "churn_demo", "engine": args.engine},
        )
        print(f"metrics stream -> {args.metrics_out}")
    if args.trace_out:
        sim.recorder.trace.write(args.trace_out)
        print(f"trace timeline -> {args.trace_out} (open in perfetto)")

if __name__ == "__main__":
    main()
