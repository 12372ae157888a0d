"""Fused RMSNorm Pallas TPU kernel.

The XLA fallback runs rms_norm as several elementwise HLO kernels (square,
mean, rsqrt, mul ×2) — each a full HBM round-trip of the activation. The
fused kernel reads x once and writes once; the row statistics live in
registers/VMEM. Rows are tiled (block_rows, d); d is the minor 128-lane
dim. Oracle: models.layers.rms_norm.

``packed_rmsnorm`` is the lane-batched variant for the pool hot path:
x (J, rows, d) with per-lane weights (J, d) and an optional per-lane
``active`` predicate (SMEM), so a partially-occupied lane pool normalizes
only live lanes — the same masking contract as packed_gemm's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # (rows, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (out * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def fused_rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-5,
                  block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x (..., d); w (d,). Flattens leading dims into a row grid."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in x.shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = ((rows + pad) // br,)
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows + pad, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, w)
    return out[:rows].reshape(orig_shape)


def _packed_rmsnorm_kernel(x_ref, w_ref, act_ref, o_ref, *, eps: float):
    ji = pl.program_id(0)

    @pl.when(act_ref[ji] != 0)
    def _compute():
        x = x_ref[0].astype(jnp.float32)               # (br, d)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        out = x * jax.lax.rsqrt(var + eps)
        o_ref[0] = (out * w_ref[0].astype(jnp.float32)).astype(o_ref.dtype)

    @pl.when(act_ref[ji] == 0)
    def _zero():
        o_ref[0] = jnp.zeros_like(o_ref[0])


def packed_rmsnorm(x: jax.Array, w: jax.Array, *,
                   active: jax.Array | None = None, eps: float = 1e-5,
                   block_rows: int = 256,
                   interpret: bool = False) -> jax.Array:
    """x (J, rows, d) normalized with per-lane weights w (J, d).

    ``active`` (bool/int (J,), optional): inactive lanes' outputs are
    exact zeros and their rows do no arithmetic. Active lanes match
    fused_rmsnorm on the corresponding slice bit-for-bit (same kernel
    body, same f32 statistics).
    """
    J, rows, d = x.shape
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    if active is None:
        act = jnp.ones((J,), jnp.int32)
    else:
        act = jnp.asarray(active, jnp.int32).reshape(J)
    grid = (J, (rows + pad) // br)
    # w as (J, 1, d): a (1, 1, d) block spans the array's last two dims,
    # which the TPU lowering requires of a block that is not (8, 128)-tiled
    w = w.reshape(J, 1, d)
    out = pl.pallas_call(
        functools.partial(_packed_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[pl.BlockSpec((1, br, d), lambda j, i: (j, i, 0)),
                  pl.BlockSpec((1, 1, d), lambda j, i: (j, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((1, br, d), lambda j, i: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((J, rows + pad, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, w, act)
    return out[:, :rows]
