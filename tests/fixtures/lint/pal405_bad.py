"""PAL405 bad twin, two violations: ``copy_op`` declares three
dimension_semantics entries for a rank-2 grid, and ``reduce_rows``
declares its accumulation axis "parallel".
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _copy(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def copy_op(x):
    grid = (4, 4)
    return pl.pallas_call(
        _copy,
        grid=grid,
        in_specs=[pl.BlockSpec((8, 128), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((8, 128), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((32, 512), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x)


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
            dimension_semantics=("parallel", "parallel")),
    )(x)
