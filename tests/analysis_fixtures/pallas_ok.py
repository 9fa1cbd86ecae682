"""No-fire twin for the pallas pack: aligned tiles, covered grid, budget-
sized blocks, and the revisited-accumulator pattern done right (init /
accumulate under @pl.when guards)."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def run_copy(x):
    return pl.pallas_call(
        copy_kernel,
        grid=(4,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
    )(x)


def acc_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(pl.program_id(1) > 0)
    def _acc():
        o_ref[...] += x_ref[...]


def run_acc(x):
    return pl.pallas_call(
        acc_kernel,
        grid=(2, 4),
        in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
    )(x)


def prefetch_acc_kernel(tbl_ref, x_ref, o_ref):
    # the scalar-prefetch ref leads, so o_ref is the revisited output
    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(pl.program_id(1) > 0)
    def _acc():
        o_ref[...] += tbl_ref[pl.program_id(1)] * x_ref[...]


def run_prefetch_acc(tbl, x):
    return pl.pallas_call(
        prefetch_acc_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(2, 4),
            in_specs=[pl.BlockSpec((8, 128), lambda i, j, tbl: (i, j))],
            out_specs=pl.BlockSpec((8, 128), lambda i, j, tbl: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
    )(tbl, x)
