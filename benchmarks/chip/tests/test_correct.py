"""What decides ``correct``, driven through the harness's own ``run`` on the
CPU (the look for a chip skipped) at a small size: a sound run passes; the
control and each fault the cells can have fail."""
import jax.numpy as jnp
import pytest

from benchmarks.chip import run as harness
from benchmarks.chip.reference import compare, ipls

SEED = 2**31 + 101


def _run(spec):
    return harness.run(spec, SEED, seconds=0.2, traced=False, require_chip=False)


def _fails(result):
    return [k for k, c in result["compared"].items() if c["value"] > c["limit"]]


@pytest.mark.parametrize("spec_name", ["perfect_spec", "lossy_spec"])
def test_sound_run_is_correct(spec_name, request):
    result = _run(request.getfixturevalue(spec_name))
    assert result["correct"], result["compared"]
    assert set(result["metrics"]) == {"rounds_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["counts"]["compiles"] == 0


FAULTS = ["state_unchanged", "half_batch", "counter_altered"]


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_caught(fault, perfect_spec, monkeypatch):
    """Each fault planted in the program under the timed path: local SGD
    that leaves the weights as they were, or that uses half of each batch;
    a traffic counter off by one."""
    from benchmarks.chip.readings import faulty_sgd
    from repro.fl import vectorized
    from repro.models import mlp_mnist

    if fault in ("state_unchanged", "half_batch"):
        fake = faulty_sgd(fault, mlp_mnist.sgd_steps_flat)
        monkeypatch.setattr(mlp_mnist, "sgd_steps_flat", fake)
    else:
        real = vectorized.VectorizedIPLSSimulation._perfect_traffic

        def traffic(self, rnd):
            real(self, rnd)
            self.messages_sent = self.messages_sent + 1

        monkeypatch.setattr(vectorized.VectorizedIPLSSimulation, "_perfect_traffic", traffic)
    result = _run(perfect_spec)
    assert not result["correct"], result["compared"]
    assert _fails(result)


@pytest.mark.parametrize("spec_name", ["perfect_spec", "lossy_spec"])
def test_control_is_not_correct(spec_name, request):
    """The reference computed in bfloat16, in the program's place, against
    the float32 reference: it fails at least one of the cell's limits."""
    spec = request.getfixturevalue(spec_name)
    from benchmarks.chip import traffic as gen

    shards, x_te, y_te = gen.fleet_inputs(spec["config"], SEED)
    rounds = spec["traffic"]["scan_rounds"]
    args = (spec["config"]["fleet"], spec["traffic"], SEED, shards, x_te, y_te, rounds)
    ref = ipls.run_fleet(*args)
    low = ipls.run_fleet(*args, sgd_dtype=jnp.bfloat16)
    numbers = compare.fleet_numbers(low, ref, x_te, y_te)
    assert any(numbers[k] > lim for k, lim in spec["limits"].items()), numbers
