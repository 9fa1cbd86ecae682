"""Run the fused IPLS round engine on a TPU, in this one process.

    python chip_smoke.py              # phases a-c on one chip
    python chip_smoke.py --chips 4    # the mesh train step: 4 chips vs 1

One chip, through the normal entry point ``make_simulation(SimConfig(
engine="vectorized", scan_rounds=8, ...))``, training the paper's MLP at full
width (784-500-100-10, N = 443,610) on ``synth_mnist(60000, 10000)`` split
IID, with K=10, pi=2, rho=2, local_iters=10, batch 128 and 16 rounds:

  a. PERFECT, f32 wire, A=100, alpha=0.1
                                      -> ``ipls_aggregate_batched``
  b. LOSSY + one leave + one join, f32, A=32
                                      -> ``ipls_aggregate_batched`` and the
                                         churn re-snapshot on device arrays
  c. LOSSY, int8 wire, A=32          -> ``ipls_aggregate_batched_q``

Each phase checks that the engine took the Pallas path and that the kernel,
compiled at the phase's shapes, is a ``tpu_custom_call``; that accuracy is
finite and above chance; and that the same configuration with
``use_kernel=False`` on the same chip gives identical traffic counters and
weights and accuracy within the tolerances below.

``--chips 4`` runs only the datacenter path: the ``launch/steps.py`` train
step of gemma3-1b at full width on a ("data"=4, "model"=1) mesh, with one
agent's participation row zeroed, against the same global batch on a 1-chip
mesh.

The last line of standard output is ``{"ok": true, "device": {...}}``. Any
failed check exits non-zero and prints no such line; so does a host with no
TPU. Timings printed on the way are not benchmark figures.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
ROUNDS = 16
WINDOW = 8  # scan_rounds: the second window reuses the first one's program
NUM_TRAIN, NUM_TEST = 60_000, 10_000
PROTOCOL = dict(num_partitions=10, pi=2, rho=2, local_iters=10, batch_size=128)
CHANCE = 0.1  # 10 classes

# Kernel vs use_kernel=False on the same chip. Both programs sum the
# contributors in the same slot order, so the aggregation differs at most by
# how the compiler contracts ``w - eps * acc`` (one f32 rounding per round).
# Local SGD then runs its matmuls at the chip's default f32 precision
# (bf16 passes): a one-ulp change in a weight can flip the bf16 rounding of
# that input, which moves the next gradients by ~2**-8 relative on the
# touched terms, and 16 rounds of 10 steps compound it. The int8 wire can
# move a code by one step where a pre-quantization value sits on a rounding
# edge. A wrong aggregation (a lost contributor, a wrong eps, a stale cache)
# moves whole partitions by the size of a delta, i.e. relative error of
# order 1e-1; these bounds sit two orders below that.
W_REL_TOL = 1e-3  # ||W_kernel - W_ref|| / ||W_ref|| over the (A, N) matrix
ACC_TOL = 0.01  # |acc_mean| difference: 100 of the 10,000 test samples

# --chips 4: the same global batch on 4 chips and on 1, as the same masked-
# mean SGD step. eps is off (use_eps=False) in both: the mesh's "data" size is
# the agent count, and with eps on it enters the step as eps*r (r = 3 of 4
# agents vs 1 of 1), so the two meshes would run different protocols — on a
# v5e that alone made step 2's loss differ by 2.2e-2. Activations and params
# are bf16 and the split reassociates the sums (~2**-8 relative per rounding);
# step 1 (same params) differed by 2.3e-4 on the chip. The bound sits an
# order above that and well under what a 2x step size does.
LOSS_REL_TOL = 5e-3
LM_ARCH = "gemma3-1b"
LM_SEQ, LM_BATCH, LM_STEPS, LM_LR = 128, 8, 3, 0.1


class Compiles:
    """Counts backend compiles (persistent-cache loads included)."""

    def __init__(self):
        import jax

        self.n = 0
        self._jax = jax

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __enter__(self):
        self._jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def kernel_is_compiled(sim) -> bool:
    """Compile the phase's aggregation kernel at the engine's current
    (K_inst, R_cap, S) and look for the Mosaic custom call."""
    import jax
    import jax.numpy as jnp

    from repro.core.wire import BLOCK
    from repro.kernels.ipls_aggregate.ipls_aggregate import (
        ipls_aggregate_batched,
        ipls_aggregate_batched_q,
    )

    K, R, S = sim.K_inst, sim.R_cap, sim.S
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    if sim._int8:
        lowered = ipls_aggregate_batched_q.lower(
            f32(K, S), f32(K, S), jax.ShapeDtypeStruct((K, R, S), jnp.int8),
            f32(K, R, S // BLOCK), f32(K, R), f32(K), f32(K),
        )
    else:
        lowered = ipls_aggregate_batched.lower(f32(K, S), f32(K, R, S), f32(K, R), f32(K))
    return "tpu_custom_call" in lowered.compile().as_text()


def run_sim(sim, churn: bool) -> dict:
    """Drive the engine; without churn window by window, counting compiles
    in each window."""
    out = {"window_s": [], "compiles": []}
    if churn:
        t0 = time.perf_counter()
        with Compiles() as c:
            sim.run()
        out["window_s"].append(time.perf_counter() - t0)
        out["compiles"].append(c.n)
        return out
    for r0 in range(0, ROUNDS, WINDOW):
        t0 = time.perf_counter()
        with Compiles() as c:
            sim.run_window(r0, WINDOW)
        out["window_s"].append(time.perf_counter() - t0)
        out["compiles"].append(c.n)
    return out


def engine_phase(name, data, **sim_kw):
    """One phase of the one-chip run; ``sim_kw`` goes to ``SimConfig`` on top
    of the common settings. Returns (summary, failures)."""
    import numpy as np

    from repro.data import iid_split
    from repro.fl import SimConfig, make_simulation
    from repro.fl.vectorized import VectorizedIPLSSimulation

    x_tr, y_tr, x_te, y_te = data
    cfg = SimConfig(
        rounds=ROUNDS, seed=SEED, engine="vectorized", scan_rounds=WINDOW,
        **PROTOCOL, **sim_kw,
    )
    churn = bool(cfg.churn)
    shards = iid_split(x_tr, y_tr, cfg.num_agents, seed=SEED)
    fails = []

    sim = make_simulation(cfg, shards, x_te, y_te)
    if not sim._use_kernel:
        fails.append("engine did not select the Pallas kernel")
    run = run_sim(sim, churn=churn)
    if not kernel_is_compiled(sim):
        fails.append("aggregation kernel is not a tpu_custom_call")
    ref = VectorizedIPLSSimulation(cfg, shards, x_te, y_te, use_kernel=False)
    ref_run = run_sim(ref, churn=churn)

    acc, acc_ref = sim.history[-1]["acc_mean"], ref.history[-1]["acc_mean"]
    if not (np.isfinite(acc) and acc > CHANCE):
        fails.append(f"acc_mean {acc} not finite and above chance")
    counters = {}
    for key in ("bytes_total", "messages_sent", "messages_dropped"):
        got, want = (
            (sim.history[-1][key], ref.history[-1][key])
            if key == "bytes_total"
            else (getattr(sim, key), getattr(ref, key))
        )
        counters[key] = int(got)
        if got != want:
            fails.append(f"{key}: kernel {got} != reference {want}")
    W, W_ref = sim.agent_weights(), ref.agent_weights()
    w_rel = float(np.linalg.norm(W - W_ref) / np.linalg.norm(W_ref))
    w_max = float(np.max(np.abs(W - W_ref)))
    if not (np.isfinite(W).all() and w_rel <= W_REL_TOL):
        fails.append(f"weights: relative difference {w_rel} > {W_REL_TOL}")
    if not abs(acc - acc_ref) <= ACC_TOL:
        fails.append(f"acc_mean: kernel {acc} vs reference {acc_ref}")
    summary = {
        "phase": name, "agents": cfg.num_agents, "wire": cfg.wire_dtype, "alpha": cfg.alpha,
        "K_inst": sim.K_inst, "R_cap": sim.R_cap, "S": sim.S,
        "acc_mean": acc, "acc_mean_ref": acc_ref, **counters,
        "w_rel_diff": w_rel, "w_max_abs_diff": w_max,
        "window_s": run["window_s"], "compiles_per_window": run["compiles"],
        "ref_window_s": ref_run["window_s"],
    }
    if not churn and run["compiles"][-1] != 0:
        fails.append(f"second window compiled {run['compiles'][-1]} programs")
    return summary, fails


def one_chip(log) -> list:
    from repro.data import synth_mnist
    from repro.p2p.network import LOSSY, PERFECT

    data = synth_mnist(num_train=NUM_TRAIN, num_test=NUM_TEST, seed=SEED)
    phases = [
        # eps starts at 1 and alpha=0.5 halves its distance to 1/r per round,
        # so the first aggregation applies ~r/2 times the mean delta; at
        # A=100 (r ~ 50 contributors) that kills the MLP on every engine,
        # the scalar oracle included, and accuracy sits at chance. alpha=0.1
        # keeps the eps recursion live with a first step of ~6x the mean.
        ("a", dict(num_agents=100, conditions=PERFECT, alpha=0.1)),
        # agent 5 leaves after the first window; a new agent 32 joins
        # (taking the freed shard) four rounds later
        ("b", dict(num_agents=32, conditions=LOSSY,
                   churn={8: [(5, "leave")], 12: [(32, "join")]})),
        ("c", dict(num_agents=32, conditions=LOSSY, wire_dtype="int8")),
    ]
    fails = []
    for name, kw in phases:
        summary, f = engine_phase(name, data, **kw)
        log(summary)
        fails += [f"phase {name}: {m}" for m in f]
    return fails


def four_chips(log) -> list:
    """The launch/steps.py train step on ("data"=4, "model"=1) vs one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import build_model, get_config
    from repro.configs.registry import ShapeSpec
    from repro.core.sharded import IplsStepConfig, init_state
    from repro.data.synthetic import synth_tokens
    from repro.launch.mesh import make_data_mesh
    from repro.launch.steps import build_train_step
    from repro.optim import sgd

    model = build_model(get_config(LM_ARCH))
    shape = ShapeSpec("smoke", seq_len=LM_SEQ, global_batch=LM_BATCH, kind="train")
    opt = sgd(LM_LR)
    tokens = synth_tokens(LM_BATCH, LM_SEQ, model.cfg.vocab, seed=SEED)
    # the "data" axis is the agent axis: each of the 4 agents owns
    # LM_BATCH/4 rows, and the last agent sits this step out
    part = np.ones(LM_BATCH, np.float32)
    part[-(LM_BATCH // 4):] = 0.0
    batch = {"tokens": jnp.asarray(tokens), "participation": jnp.asarray(part)}
    params = model.init(SEED)

    fails, losses = [], {}
    for n in (4, 1):
        mesh = make_data_mesh(n)
        built = build_train_step(
            model, mesh, shape, optimizer=opt,
            step_cfg=IplsStepConfig(grad_clip=1.0, use_eps=False),
        )
        state = jax.device_put(init_state(params, opt), built.in_shardings[0])
        b = jax.device_put(batch, built.in_shardings[1])
        step = jax.jit(
            built.fn, in_shardings=built.in_shardings, out_shardings=built.out_shardings
        )
        with mesh:
            t0 = time.perf_counter()
            compiled = step.lower(state, b).compile()
            compile_s = time.perf_counter() - t0
            text = compiled.as_text()
            mem = compiled.memory_analysis()
            ls = []
            for _ in range(LM_STEPS):
                state, m = compiled(state, b)
                ls.append(float(m["loss"]))
        losses[n] = ls
        reduces = sum(text.count(op) for op in ("all-reduce", "reduce-scatter"))
        log({
            "mesh": {"data": n, "model": 1}, "arch": LM_ARCH, "losses": ls,
            "participation": float(m["participation"]), "compile_s": compile_s,
            "cross_data_reductions_in_hlo": reduces,
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "arg_bytes": getattr(mem, "argument_size_in_bytes", None),
        })
        if n > 1 and reduces == 0:
            fails.append("no all-reduce / reduce-scatter across 'data' in the HLO")
        if not np.isfinite(ls).all():
            fails.append(f"{n}-chip losses not finite: {ls}")
        del state, b, compiled
    rel = np.abs(np.asarray(losses[4]) - np.asarray(losses[1])) / np.abs(losses[1])
    log({"loss_rel_diff_4_vs_1": rel.tolist()})
    if not (rel <= LOSS_REL_TOL).all():
        fails.append(f"4-chip losses {losses[4]} vs 1-chip {losses[1]}")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    from benchmarks.common import use_compile_cache

    cache = use_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, found {len(devices)}", file=sys.stderr)
        return 2

    def log(obj):
        print(json.dumps(obj), flush=True)

    log({"device_kind": dev.device_kind, "devices": len(devices),
         "compile_cache": cache, "note": "timings are not benchmark figures"})
    fails = four_chips(log) if args.chips == 4 else one_chip(log)
    for f in fails:
        print(f"FAIL {f}", file=sys.stderr)
    if fails:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
