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
