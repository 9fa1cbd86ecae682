"""PL01 fire: under PrefetchScalarGridSpec every index_map also receives the
scalar-prefetch refs after the grid indices; this one takes the grid
indices only."""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def kernel(tbl_ref, x_ref, o_ref):
    o_ref[...] = x_ref[...] * tbl_ref[pl.program_id(0)]


def run(tbl, x):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i, tbl: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
    )(tbl, x)
