"""Compile the main-path Pallas kernels for a described (not attached) TPU
v5e at the fused engine's real widths.

Interpret mode runs a kernel body on the CPU but never meets the TPU
lowering's rules (block tiling, supported primitives, SMEM/VMEM budgets);
these compiles do, with no chip. Widths: the paper's MNIST MLP
(784-500-100-10, N = 443,610) split into K = 10 partitions (S = 44,361
floats each) with rho = 2 replicas, so K_inst = 20 aggregation instances.
Contributor slots R per instance:

  * PERFECT, A = 100: the owner plus 49 routed non-owners -> R = 50;
  * LOSSY (max delay 2 ticks -> one in-flight round), A = 32:
    1 + 31 * 2 = 63 for the f32 kernel, 62 remote rows for the int8 one;
  * LOSSY, A = 100: 1 + 99 * 2 = 199 — the largest table the smoke run's
    fleet sizes put in SMEM.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and under pytest-xdist each worker
imports every test file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

N_MODEL = 443_610
K_INST = 20
S = 44_361
S_INT8 = 45_056  # S rounded up to whole 1024-element quantization blocks


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "R", [50, 63, 199], ids=["perfect-A100", "lossy-A32", "lossy-A100"]
)
def test_ipls_aggregate_batched_compiles(one_chip, R):
    from repro.kernels.ipls_aggregate.ipls_aggregate import ipls_aggregate_batched

    fn = jax.jit(functools.partial(ipls_aggregate_batched, interpret=False))
    text = _compiled_text(
        fn,
        _sds((K_INST, S), jnp.float32, one_chip),
        _sds((K_INST, R, S), jnp.float32, one_chip),
        _sds((K_INST, R), jnp.float32, one_chip),
        _sds((K_INST,), jnp.float32, one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("R", [62], ids=["lossy-A32"])
def test_ipls_aggregate_batched_q_compiles(one_chip, R):
    from repro.kernels.ipls_aggregate.ipls_aggregate import (
        QBLOCK,
        ipls_aggregate_batched_q,
    )

    fn = jax.jit(functools.partial(ipls_aggregate_batched_q, interpret=False))
    text = _compiled_text(
        fn,
        _sds((K_INST, S_INT8), jnp.float32, one_chip),
        _sds((K_INST, S_INT8), jnp.float32, one_chip),
        _sds((K_INST, R, S_INT8), jnp.int8, one_chip),
        _sds((K_INST, R, S_INT8 // QBLOCK), jnp.float32, one_chip),
        _sds((K_INST, R), jnp.float32, one_chip),
        _sds((K_INST,), jnp.float32, one_chip),
        _sds((K_INST,), jnp.float32, one_chip),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("N", [S, N_MODEL], ids=["partition", "model"])
def test_quantize_compiles(one_chip, N):
    from repro.kernels.quantize.quantize import quantize

    fn = jax.jit(functools.partial(quantize, interpret=False))
    text = _compiled_text(
        fn, _sds((N,), jnp.float32, one_chip), _sds((N,), jnp.float32, one_chip)
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("N", [S, N_MODEL], ids=["partition", "model"])
def test_dequantize_compiles(one_chip, N):
    from repro.kernels.quantize.quantize import dequantize, num_blocks

    fn = jax.jit(functools.partial(dequantize, interpret=False))
    text = _compiled_text(
        fn,
        _sds((N,), jnp.int8, one_chip),
        _sds((num_blocks(N),), jnp.float32, one_chip),
    )
    assert "tpu_custom_call" in text


class _Captured(Exception):
    pass


def _window_program(conditions, wire_dtype, sharding):
    """The engine's scanned window program at a tiny fleet on the kernel
    path, and its argument shapes placed on ``sharding``: the engine's first
    call to it is stopped once its arguments are seen, so nothing runs."""
    from repro.data import iid_split, synth_mnist
    from repro.fl import SimConfig
    from repro.fl.vectorized import VectorizedIPLSSimulation

    x_tr, y_tr, x_te, y_te = synth_mnist(num_train=400, num_test=64, seed=0)
    cfg = SimConfig(
        num_agents=4, num_partitions=4, pi=2, rho=2, rounds=100, local_iters=1,
        batch_size=32, eval_agents=2, engine="vectorized", scan_rounds=2,
        conditions=conditions, wire_dtype=wire_dtype,
    )
    sim = VectorizedIPLSSimulation(
        cfg, iid_split(x_tr, y_tr, cfg.num_agents, seed=0), x_te, y_te, use_kernel=True
    )
    fn, seen = sim._scan_window_j, []

    def spy(*args):
        seen.append(jax.tree.map(lambda x: _sds(x.shape, x.dtype, sharding), args))
        raise _Captured

    sim._scan_window_j = spy
    with pytest.raises(_Captured):
        sim.run_window(0, 2)
    return fn, seen[0]


_window_texts: dict = {}
_window_args: dict = {}


def _window_text(path, sharding) -> str:
    """The optimized HLO of `_window_program` on ``path``'s kernel path
    (``<perfect|lossy>-<wire dtype>``), compiled for ``sharding``'s chip once
    per module."""
    if path not in _window_texts:
        from repro.kernels.ipls_aggregate import ipls_aggregate
        from repro.p2p.network import LOSSY, PERFECT

        conditions, wire_dtype = path.split("-")
        conditions = {"perfect": PERFECT, "lossy": LOSSY}[conditions]
        fn, args = _window_program(conditions, wire_dtype, sharding)
        _window_args[path] = args
        try:
            with pytest.MonkeyPatch.context() as mp:
                # the kernel picks its mode from the default backend (the CPU here)
                mp.setattr(ipls_aggregate, "default_interpret", lambda: False)
                _window_texts[path] = _compiled_text(fn, *args)
        finally:
            # the kernel's jit caches its trace under interpret=None: the trace
            # made for the TPU must not serve a later CPU call of the same shapes
            jax.clear_caches()
    return _window_texts[path]


@pytest.mark.parametrize("path", ["perfect-f32", "lossy-int8"])
def test_window_program_carries_scopes(one_chip, path):
    """Each device sub-layer of `gather and merge` is a named scope, and the
    scopes survive the TPU compiler into the op_name metadata the benchmark's
    trace reader maps device ops through."""
    if path == "perfect-f32":
        scopes = ("weight_build", "contrib_gather", "replica_merge")
    else:
        scopes = ("weight_build", "contrib_gather", "wire_codec", "ring_roll", "replica_merge")
    text = _window_text(path, one_chip)
    assert "tpu_custom_call" in text
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in scopes:
        assert any(f"/{scope}/" in n for n in names), scope
    # the kernel keeps the path the benchmark's readers find it by
    assert any(n.endswith("pallas_call") and "jit(ipls_aggregate_batched" in n for n in names)


@pytest.mark.parametrize("path", ["perfect-f32", "lossy-int8", "lossy-f32"])
def test_contrib_gather_moves_whole_rows(one_chip, path):
    """The kernel's contributor layout comes from whole-row (slab) gathers of
    the partition plane. A gather whose slices end in a single element,
    such as one (row, column) index pair per output element, runs at ~18 ns
    an element on a v5e: the PERFECT fleet's round spent 96% of its device
    time in one."""
    text = _window_text(path, one_chip)
    gathers = [
        line for line in text.splitlines()
        if " gather(" in line and "/contrib_gather/" in line
    ]
    assert gathers
    for line in gathers:
        sizes = re.search(r"slice_sizes=\{([^}]*)\}", line).group(1).split(",")
        assert int(sizes[-1]) > 1, line


@pytest.mark.parametrize("path", ["perfect-f32", "lossy-int8"])
def test_window_program_gathers_batches_on_device(one_chip, path):
    """The window program takes the fleet's training set as one flat
    (n, 784) table and each round's batches as int32 (W, rows, bs) row
    indices: no (W, rows, bs, 784) image input. The gather keeps its
    `batch_gather` scope into op_name and moves whole 784-wide rows."""
    text = _window_text(path, one_chip)
    leaves = jax.tree.leaves(_window_args[path])
    # the tiny fleet: 4 agents of 100 rows, batch 32, windows of 2 rounds
    assert any(a.shape == (400, 784) and a.dtype == jnp.float32 for a in leaves)
    assert any(a.shape == (2, 4, 32) and a.dtype == jnp.int32 for a in leaves)
    assert not any(a.ndim == 4 and a.shape[-1] == 784 for a in leaves)
    gathers = [
        line for line in text.splitlines()
        if " gather(" in line and "/batch_gather/" in line
    ]
    sizes = {re.search(r"slice_sizes=\{([^}]*)\}", line).group(1) for line in gathers}
    # whole image rows and whole labels, nothing element-indexed
    assert "1,784" in sizes and sizes <= {"1,784", "1"}, sizes


@pytest.mark.parametrize("path", ["lossy-int8", "lossy-f32"])
def test_weight_build_compiles_to_no_loop(one_chip, path):
    """The LOSSY path assembles the agents' weights without a per-row loop:
    on a TPU a row gather of its (A*K, S) cache plane compiled to one loop
    of dynamic slices per partition, about six device ops a row each
    round."""
    text = _window_text(path, one_chip)
    loops = [
        line for line in text.splitlines()
        if " while(" in line and "/weight_build/" in line
    ]
    assert not loops, loops[:1]
