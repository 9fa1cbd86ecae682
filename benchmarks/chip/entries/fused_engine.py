"""Entry: the fused IPLS round engine, ``make_simulation(SimConfig(
engine="vectorized", scan_rounds=W, ...))`` driven window by window through
``run_window``.

Set-up makes the inputs from the seed, builds the engine and runs its first
window (rounds 0 to W-1), which compiles every program the later windows
use; the state after that window is what the reference is compared with.
The timed span then runs whole windows, each ending in the engine's own host
sync, until ``seconds`` have passed.
"""
from __future__ import annotations

import gc
import time

from benchmarks.chip import flops
from benchmarks.chip import traffic as gen
from benchmarks.chip.common import Compiles, span
from benchmarks.chip.reference import compare, ipls

# wrapped with a host span in traced runs, so that the device's idle gaps
# can be put down to what the host was doing
HOST_CALLS = ("_control_round", "_draw_batches", "_scan_window_j")
# the jitted programs the window runs; traced runs keep their argument
# shapes, to name each device op of the trace by the program's HLO
PROGRAMS = ("_scan_window_j",)


def _network(traffic: dict):
    from repro.p2p.network import NetworkConditions

    n = traffic["network"]
    return NetworkConditions(
        loss_prob=n["loss_prob"], delay_prob=n["delay_prob"],
        max_delay_rounds=n["max_delay_ticks"],
    )


def _annotate(obj, name: str, shapes: dict) -> None:
    """Wrap ``obj.name`` in a host span; for a program, keep its argument
    shapes in ``shapes`` at the first call."""
    import jax

    fn = getattr(obj, name)

    def wrapped(*a):
        if name in PROGRAMS and name not in shapes:
            shapes[name] = (fn, jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), a
            ))
        with span(f"host.{name.strip('_')}"):
            return fn(*a)

    setattr(obj, name, wrapped)


class FleetRun:
    def __init__(self, config: dict, traffic: dict, seed: int, annotate: bool = False):
        from repro.fl import SimConfig, make_simulation

        self.config, self.traffic, self.seed = config, traffic, seed
        fleet = config["fleet"]
        self.W = traffic["scan_rounds"]
        shards, self.x_te, self.y_te = gen.fleet_inputs(config, seed)
        self.shards = shards
        cfg = SimConfig(
            num_agents=fleet["num_agents"], num_partitions=fleet["num_partitions"],
            pi=fleet["pi"], rho=fleet["rho"], alpha=fleet["alpha"], lr=fleet["lr"],
            wire_dtype=fleet["wire_dtype"], local_iters=traffic["local_iters"],
            batch_size=traffic["batch_size"], eval_agents=traffic["eval_agents"],
            eval_cadence=traffic["eval_cadence"], scan_rounds=self.W,
            conditions=_network(traffic), seed=seed, engine="vectorized",
            # beyond any round a window reaches: the final-round eval rule
            # never fires inside the timed span
            rounds=1 << 40,
        )
        self.sim = make_simulation(cfg, shards, self.x_te, self.y_te)
        self._program_args: dict = {}
        if annotate:
            for name in HOST_CALLS:
                _annotate(self.sim, name, self._program_args)
        with span("bench.first_window"):
            hist = self.sim.run_window(0, self.W)
        self.first = {
            "models": self.sim.agent_weights(),
            "acc_mean": hist[-1]["acc_mean"],
            "bytes_total": hist[-1]["bytes_total"],
            "messages_sent": self.sim.messages_sent,
            "messages_dropped": self.sim.messages_dropped,
        }
        self.next_round = self.W
        self.shapes = {
            "K_inst": int(self.sim.K_inst), "R_cap": int(self.sim.R_cap), "S": int(self.sim.S),
            "use_kernel": bool(self.sim._use_kernel),
        }

    def measure(self, seconds: float) -> dict:
        """Whole windows until ``seconds`` have passed; every window ends in
        the engine's host read of its accuracies, which waits for the device."""
        r0 = self.next_round
        windows = 0
        with Compiles() as c, span("bench.measure"):
            t0 = time.perf_counter()
            while True:
                with span("bench.window"):
                    self.sim.run_window(self.next_round, self.W)
                self.next_round += self.W
                windows += 1
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds:
                    break
        return {
            "rounds": self.next_round - r0, "windows": windows, "elapsed_s": elapsed,
            "first_round": r0, "compiles": c.n, "dispatches": self.sim.device_dispatches,
            **self.shapes,
        }

    def programs(self) -> list:
        """Optimized HLO text of each program the window ran (traced runs):
        compiled again here with the persistent cache off, so the text is
        the compiler's own, op metadata included. Compilation is
        deterministic, so its op names are those in the trace."""
        import jax

        jax.config.update("jax_enable_compilation_cache", False)
        try:
            return [
                fn.lower(*args).compile().as_text() for fn, args in self._program_args.values()
            ]
        finally:
            jax.config.update("jax_enable_compilation_cache", True)

    def free(self) -> None:
        """Drop the engine and its device state."""
        self.sim = None
        gc.collect()

    def readings(self) -> dict:
        """The reference over the first window's rounds, from the seed; the
        numbers that decide ``correct``."""
        ref = ipls.run_fleet(
            self.config["fleet"], self.traffic, self.seed, self.shards,
            self.x_te, self.y_te, rounds=self.W,
        )
        return compare.fleet_numbers(self.first, ref, self.x_te, self.y_te)


def build(config: dict, traffic: dict, seed: int, annotate: bool = False) -> FleetRun:
    return FleetRun(config, traffic, seed, annotate)


def round_flops(config: dict, traffic: dict) -> float:
    """Model FLOPs of one round: every agent's local SGD and the round's
    share of the evaluations (flops.py)."""
    return flops.fleet_round_flops(config, traffic)
