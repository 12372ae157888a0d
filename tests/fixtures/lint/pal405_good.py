"""PAL405 good twin: semantics arity matches the grid and the
accumulation axis is declared "arbitrary".
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _red(x_ref, o_ref, acc_scr):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += x_ref[...].astype(jnp.float32)

    @pl.when(ki == nk - 1)
    def _write():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def reduce_rows(x):
    grid = (4, 8)
    return pl.pallas_call(
        _red,
        grid=grid,
        in_specs=[pl.BlockSpec((8, 128), lambda i, k: (i, k))],
        out_specs=pl.BlockSpec((8, 128), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((32, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x)
