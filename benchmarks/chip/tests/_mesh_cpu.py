"""Drives the mesh cell through the harness's own ``run`` on four virtual
CPU devices at a small size (InternLM2's ``reduced()`` widths, 32-token
sequences), the look for a chip skipped: a sound run, then the program with
one fault planted per run. Prints one JSON line per case. Run by
test_mesh_step.py in a process of its own, since the device count is fixed
when JAX starts:

    python3 benchmarks/chip/tests/_mesh_cpu.py <case> [<case> ...]
"""
import copy
import dataclasses
import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

CELL = "internlm2-1.8b.data4.train4k"
SEED = 2**40 + 2**31 + 17
# limits at this size, set as the cell's are from readings at this size
# (sound runs on four seeds read up to loss 8.1e-5, grad 3.7e-3, change
# 0.025; the float8 control 3.6e-4, 0.021; the planted faults at least 0.19
# on change or 1 on participants and eps): bfloat16 rounds coarser against
# a 64-wide model than against the cell's
SMALL_LIMITS = {"loss_gap": 2e-4, "grad_gap": 0.01, "change_gap": 0.06,
                "participants_gap": 0, "eps_gap": 1e-6}
SMALL = dict(
    num_hidden_layers=2, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, intermediate_size=128, vocab_size=256, rope_theta=10000.0,
)


def small_spec() -> dict:
    from benchmarks.chip import run as harness

    spec = copy.deepcopy(harness.load_cell(CELL))
    spec["config"]["model"].update(SMALL)
    spec["traffic"]["seq_len"] = 32
    spec["limits"] = dict(SMALL_LIMITS)
    return spec


def _patch(undo: list, module, name: str, make) -> None:
    """Replace ``module.name`` by ``make(original)``; ``undo`` restores it."""
    real = getattr(module, name)
    setattr(module, name, make(real))
    undo.append(lambda: setattr(module, name, real))


def _wrap_fn(undo: list, wrap) -> None:
    """Patch ``build_train_step`` so that the step it builds is ``wrap(fn)``."""
    from repro.launch import steps

    def make(real):
        def build(*a, **k):
            built = real(*a, **k)
            return dataclasses.replace(built, fn=wrap(built.fn))
        return build

    _patch(undo, steps, "build_train_step", make)


def _masked(fn, mask_of):
    def step(state, batch):
        return fn(state, {**batch, "participation": mask_of(batch["participation"])})
    return step


def plant(case: str, n: int) -> list:
    """Plant ``case`` in the program; returns the undo list."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.reference.mesh_step import part_dim

    undo: list = []
    if case == "state_unchanged":
        _wrap_fn(undo, lambda fn: lambda state, batch: (state, fn(state, batch)[1]))
    elif case == "half_batch":
        def half(p):
            return p * (jnp.arange(p.shape[0]) < p.shape[0] // 2)
        _wrap_fn(undo, lambda fn: _masked(fn, half))
    elif case == "absent_counted":
        _wrap_fn(undo, lambda fn: _masked(fn, jnp.ones_like))
    elif case == "no_exchange":
        # each agent updates the partition it owns from its own rows alone
        def wrap(fn):
            def step(state, batch):
                p = batch["participation"]
                own = jnp.arange(p.shape[0]) // (p.shape[0] // n)
                outs = [_masked(fn, lambda q, a=a: q * (own == a))(state, batch)[0]
                        for a in range(n)]

                def stitch(*xs):
                    d = part_dim(xs[0].shape, n)
                    if d is None:
                        return xs[0]
                    return jnp.concatenate(
                        [jnp.split(x, n, axis=d)[a] for a, x in enumerate(xs)], axis=d)

                new = jax.tree.map(stitch, *outs)
                return new, fn(state, batch)[1]
            return step
        _wrap_fn(undo, wrap)
    elif case == "eps_off":
        from repro.core import sharded

        _patch(undo, sharded, "IplsStepConfig",
               lambda real: lambda **k: real(**{**k, "use_eps": False}))
    elif case == "lr_halved":
        from repro.optim import schedules

        _patch(undo, schedules, "cosine_warmup",
               lambda real: lambda peak, *a, **k: real(0.5 * peak, *a, **k))
    elif case == "layer_dropped":
        from repro.optim import optimizers

        def make(real):
            def adamw(*a, **k):
                opt = real(*a, **k)

                def update(grads, state, params, step):
                    grads = {**grads, "g0": jax.tree.map(lambda g: g.at[0].set(0), grads["g0"])}
                    return opt.update(grads, state, params, step)
                return optimizers.Optimizer(opt.init, update)
            return adamw
        _patch(undo, optimizers, "adamw", make)
    elif case != "sound":
        raise ValueError(case)
    return undo


def main(cases) -> int:
    from benchmarks.chip import run as harness
    from benchmarks.chip.entries import mesh_step
    from repro.configs import internlm2_1_8b

    mesh_step.arch_config = lambda config: internlm2_1_8b.reduced()
    spec = small_spec()
    for case in cases:
        if case.startswith("control"):
            import jax

            from benchmarks.chip import traffic as gen
            from benchmarks.chip.reference import mesh_step as ref

            c, t = spec["config"], spec["traffic"]
            batches = [gen.round_batch(t, 4, SMALL["vocab_size"], SEED, r)
                       for r in range(t["first_window_rounds"])]
            devs = jax.devices()[:4]
            base = ref.run(c, t, SEED, batches, devs)
            low = ref.run(c, t, SEED, batches, devs, ref.VARIANTS[case])
            numbers = ref.numbers(low, base)
            compared = {k: {"value": numbers[k], "limit": lim} for k, lim in spec["limits"].items()}
            print(json.dumps({"case": case, "correct": all(
                v["value"] <= v["limit"] for v in compared.values()), "compared": compared}))
            continue
        undo = plant(case, spec["config"]["mesh"]["data"])
        try:
            result = harness.run(spec, SEED, seconds=0.2, traced=False, require_chip=False)
        finally:
            for u in reversed(undo):
                u()
        print(json.dumps({"case": case, "correct": result["correct"],
                          "compared": result["compared"], "metrics": result["metrics"],
                          "counts": result["counts"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
