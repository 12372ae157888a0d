"""Multi-job packed GEMM Pallas TPU kernel — the paper's GPU-sharing idea
expressed at the MXU level.

Triples-mode packing stacks K independent tasks' small matmuls into
(J, M, K) × (J, K, N). A lone small GEMM leaves the MXU idle between
kernel dispatches (the gap the paper observes disappearing in its Fig. 7
"kernel queue backlog"); here ONE kernel invocation walks all jobs' tiles
back-to-back, so the systolic array never drains between jobs. Tiles are
padded to MXU-aligned (128, 128) blocks.

Lane masking (``active=``): the lane pool attaches/detaches jobs without
recompiling, so at partial occupancy some lanes are dead. The masked
variant takes a per-lane predicate in SMEM and gates the MXU accumulate
with ``pl.when`` — an inactive lane's grid tiles issue no dot_generals and
its output block is written as deterministic zeros from the cleared
accumulator. (Block pipelining still streams the inactive tiles from HBM;
pruning those copies too needs scalar-prefetch grid reduction — see
DESIGN.md §12.) Oracle: kernels.ref.packed_gemm_ref (+ where-zero).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pg_kernel(x_ref, w_ref, o_ref, acc_scr):
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)       # (bm, bk)
    w = w_ref[0].astype(jnp.float32)       # (bk, bn)
    acc_scr[...] += jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())))

    @pl.when(ki == nk - 1)
    def _write():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def _pg_masked_kernel(x_ref, w_ref, act_ref, o_ref, acc_scr):
    ji = pl.program_id(0)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(act_ref[ji] != 0)
    def _accum():
        x = x_ref[0].astype(jnp.float32)   # (bm, bk)
        w = w_ref[0].astype(jnp.float32)   # (bk, bn)
        acc_scr[...] += jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())))

    @pl.when(ki == nk - 1)
    def _write():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def packed_gemm(x: jax.Array, w: jax.Array, *,
                active: jax.Array | None = None, block_m: int = 128,
                block_n: int = 128, block_k: int = 128,
                interpret: bool = False) -> jax.Array:
    """x (J, M, K) @ w (J, K, N) -> (J, M, N), per-job.

    ``active`` (optional, bool/int (J,)): per-lane predicate. Inactive
    lanes' tiles skip the MXU and their output rows are exact zeros; the
    unmasked program is untouched when ``active`` is None.
    """
    J, M, K = x.shape
    _, _, N = w.shape
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, 0), (0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, 0), (0, pk), (0, pn)))
    Mp, Np, Kp = M + pm, N + pn, K + pk

    grid = (J, Mp // bm, Np // bn, Kp // bk)
    in_specs = [
        pl.BlockSpec((1, bm, bk), lambda j, i, n, k: (j, i, k)),
        pl.BlockSpec((1, bk, bn), lambda j, i, n, k: (j, k, n)),
    ]
    operands = [x, w]
    kernel = _pg_kernel
    if active is not None:
        kernel = _pg_masked_kernel
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(active, jnp.int32).reshape(J))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda j, i, n, k: (j, i, n)),
        out_shape=jax.ShapeDtypeStruct((J, Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*operands)
    return out[:, :M, :N]
