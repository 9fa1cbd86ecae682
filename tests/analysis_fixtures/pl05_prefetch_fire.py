"""PL05 fire: with a scalar-prefetch operand leading the kernel's refs, the
revisited output is o_ref, and it is written outside any @pl.when guard."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def kernel(tbl_ref, x_ref, o_ref):
    o_ref[...] = tbl_ref[pl.program_id(1)] * x_ref[...]


def run(tbl, x):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(2, 4),
            in_specs=[pl.BlockSpec((8, 128), lambda i, j, tbl: (i, j))],
            out_specs=pl.BlockSpec((8, 128), lambda i, j, tbl: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32),
    )(tbl, x)
