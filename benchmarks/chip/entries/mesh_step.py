"""Entry: the IPLS train step on a TPU mesh, ``launch/steps.py:
build_train_step`` over ``core/sharded.py:make_train_step``, one agent per
chip of a ("data"=n, "model"=1) mesh. One round is one call of the compiled
step: the participation-masked mean of the agents' gradients, the
UpdateModel reduce-scatter, the owners' update, the LoadModel all-gather.

Set-up makes the weights from the seed on the devices in one jitted call
(``reference/mesh_step.py:init_params``, so the reference starts from the
same values), builds and compiles the step, and runs the first window
(``first_window_rounds`` rounds) through the same compiled step and the same
feed as the timed span; what the program produced there is what the
reference is compared with. The timed span then runs whole rounds, drawing
each round's batch on the host while the device runs the round before, and
ends each round in a host read of its loss.
"""
from __future__ import annotations

import functools
import gc
import re
import time

import jax
import jax.numpy as jnp

from benchmarks.chip import flops
from benchmarks.chip import traffic as gen
from benchmarks.chip.common import Compiles, span
from benchmarks.chip.reference import mesh_step as ref

METRICS = ("loss", "grad_norm", "participation", "eps")
STACKED = re.compile(r"g\d+/")  # the program's groups of layers, stacked for its scan


def arch_config(config: dict):
    """The program's ``ArchConfig`` of the configuration's ``arch``."""
    from repro.configs.registry import get_config

    return get_config(config["arch"])


def _check_layout(model, config: dict) -> None:
    """The program's parameters are the leaves the weights are made for, and
    the model reference's own ``check``, where it has one, passes."""
    mref = ref.model_ref(config)
    want = {k: s for k, (s, _) in mref.leaf_shapes(config["model"]).items()}
    got = {k: tuple(v.shape) for k, v in ref.flatten(model.param_shapes()).items()}
    if got != want:
        raise ValueError(f"{config['arch']}: parameters {got} are not the configuration's {want}")
    if hasattr(mref, "check"):
        mref.check(model.cfg, config["model"])


def _leaf_layer_norms(tree: dict) -> dict:
    """{leaf: norm} of a parameter-shaped tree, the stacked layers' leaves
    (groups ``g<i>/``) one norm per layer; traceable."""
    import jax.numpy as jnp

    out = {}
    for name, x in ref.flatten(tree).items():
        x = x.astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if STACKED.match(name) else None
        out[name] = jnp.sqrt(jnp.sum(x * x, axis=axes))
    return out


@functools.partial(jax.jit, static_argnums=2)
def _delta_norm(now, then, stacked: bool):
    """Norm of now - then in float32, per layer where ``stacked``."""
    d = now.astype(jnp.float32) - then.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(d * d, axis=tuple(range(1, d.ndim)) if stacked else None))


def _expand(norms: dict) -> dict:
    """Per-layer names ``leaf#l`` for stacked leaves, as floats."""
    import numpy as np

    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim:
            out.update({f"{name}#{i}": float(x) for i, x in enumerate(v)})
        else:
            out[name] = float(v)
    return out


class MeshRun:
    def __init__(self, config: dict, traffic: dict, seed: int, annotate: bool = False):
        import jax

        from repro.configs.registry import ShapeSpec, build_model
        from repro.core.sharded import IplsStepConfig, init_state
        from repro.launch.mesh import make_data_mesh
        from repro.launch.steps import build_train_step
        from repro.optim.optimizers import adamw
        from repro.optim.schedules import cosine_warmup

        self.config, self.traffic, self.seed = config, traffic, seed
        n = config["mesh"]["data"]
        if config["mesh"]["model"] != 1:
            raise ValueError("the entry runs a ('data'=n, 'model'=1) mesh")
        self.n = n
        model = build_model(arch_config(config))
        _check_layout(model, config)
        self.vocab = config["model"]["vocab_size"]
        o, ip = config["optimizer"], config["ipls"]
        self.optimizer = adamw(
            cosine_warmup(o["peak_lr"], o["warmup_steps"], o["total_steps"], o["min_ratio"]),
            b1=o["b1"], b2=o["b2"], eps=o["eps"], wd=o["weight_decay"],
        )
        shape = ShapeSpec(
            "bench", seq_len=traffic["seq_len"],
            global_batch=n * traffic["sequences_per_agent"], kind="train",
        )
        self.mesh = make_data_mesh(n)
        self.built = build_train_step(
            model, self.mesh, shape, optimizer=self.optimizer,
            step_cfg=IplsStepConfig(
                alpha=ip["alpha"], use_eps=ip["use_eps"], fsdp=ip["fsdp"], grad_clip=ip["grad_clip"],
            ),
        )
        state_sh, self.batch_sh = self.built.in_shardings
        key = ref.seed_key(seed)
        shapes = ref.model_ref(config).leaf_shapes(config["model"])
        make_state = jax.jit(
            lambda k: init_state(ref.init_params(shapes, k), self.optimizer),
            out_shardings=state_sh,
        )
        self.norms = jax.jit(_leaf_layer_norms)
        step = jax.jit(
            self.built.fn, in_shardings=self.built.in_shardings,
            out_shardings=self.built.out_shardings, donate_argnums=(0,),
        )
        self.state = make_state(key)
        self.batches = [self._feed_host(r) for r in range(traffic["first_window_rounds"])]
        with self.mesh:
            self.step = step.lower(self.state, self._put(self.batches[0])).compile()

        # the first window: the same compiled step and feed as the timed span
        first = {"loss": [], "r": [], "eps": []}
        with span("bench.first_window"):
            for r, b in enumerate(self.batches):
                self.state, m = self.step(self.state, self._put(b))
                m = {k: float(m[k]) for k in METRICS}
                first["loss"].append(m["loss"])
                first["r"].append(m["participation"] * n)
                first["eps"].append(m["eps"])
                if r == 0:
                    b1 = o["b1"]
                    first["grad"] = {
                        k: v / (1 - b1) for k, v in _expand(self.norms(
                            jax.tree.map(lambda s: s.m, self.state.opt_state, is_leaf=_is_adam)
                        )).items()
                    }
            first["change"] = self._change(shapes, key)
        self.first = first
        self.next_round = len(self.batches)

    def _change(self, shapes: dict, key) -> dict:
        """Norm of each leaf's change since the first round, per layer for
        the stacked leaves. Each initial leaf is made again, one at a time,
        and handed to the difference as an argument (``init_leaf``)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        made = jax.jit(ref.init_leaf, static_argnums=(0, 1),
                       out_shardings=NamedSharding(self.mesh, PartitionSpec()))
        params = ref.flatten(self.state.params)
        out = {}
        for i, (name, (shape, std)) in enumerate(sorted(shapes.items())):
            then = made(shape, std, key, i)
            out.update(_expand({name: _delta_norm(params[name], then, bool(STACKED.match(name)))}))
        return out

    def _feed_host(self, rnd: int) -> dict:
        return gen.round_batch(self.traffic, self.n, self.vocab, self.seed, rnd)

    def _put(self, batch: dict) -> dict:
        import jax

        return jax.device_put(batch, self.batch_sh)

    def measure(self, seconds: float) -> dict:
        """Whole rounds until ``seconds`` have passed. Round r+1's batch is
        drawn while the device runs round r; each round ends in the host's
        read of its loss, which waits for the device."""
        r0 = self.next_round
        with Compiles() as c, span("bench.measure"):
            t0 = time.perf_counter()
            with span("host.batches"):
                batch = self._put(self._feed_host(self.next_round))
            while True:
                with span("host.step"):
                    self.state, m = self.step(self.state, batch)
                self.next_round += 1
                with span("host.batches"):
                    batch = self._put(self._feed_host(self.next_round))
                with span("host.wait"):
                    float(m["loss"])
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
        return {
            "rounds": self.next_round - r0, "elapsed_s": elapsed, "first_round": r0,
            "compiles": c.n, "agents": self.n, "seq_len": self.traffic["seq_len"],
        }

    def programs(self) -> list:
        """Optimized HLO text of the compiled step (traced runs)."""
        return [self.step.as_text()]

    def free(self) -> None:
        """Drop the step and its device state."""
        self.state = self.step = None
        gc.collect()

    def readings(self) -> dict:
        """The reference over the first window's rounds, from the seed; the
        numbers that decide ``correct``."""
        import jax

        devices = jax.devices()[: self.n]
        got = ref.run(self.config, self.traffic, self.seed, self.batches, devices)
        return ref.numbers(self.first, got)


def _is_adam(x) -> bool:
    return hasattr(x, "m") and hasattr(x, "v")


def build(config: dict, traffic: dict, seed: int, annotate: bool = False) -> MeshRun:
    return MeshRun(config, traffic, seed, annotate)


def round_flops(config: dict, traffic: dict) -> float:
    """Model FLOPs of one round: forward and backward of every row of the
    global batch (absent agents' rows too, which the step computes and
    masks), counted from the program's ``ArchConfig`` block by block."""
    rows = config["mesh"]["data"] * traffic["sequences_per_agent"]
    return float(rows * flops.lm_train_flops(arch_config(config), traffic["seq_len"]))
