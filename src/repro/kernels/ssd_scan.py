"""Mamba2 SSD chunked-scan Pallas TPU kernel.

Grid (B, nh, S/Q): heads are independent recurrences (B/C are shared
across heads, ngroups=1), so each grid row scans one (batch, head) pair.
The chunk axis is sequential ("arbitrary") and the head's running
inter-chunk state (N, hd) lives in VMEM scratch — the recurrent state
never leaves VMEM. Intra-chunk work is the dual (attention-like) form:
dense (Q, Q) and (Q, N) matmuls that feed the MXU. Every operation in
the body is a 2-D matmul, an elementwise op or a reduction. The
in-chunk cumulative log-decay is an XLA cumsum in the wrapper (Mosaic
has no cumsum), the same op ``ssd_chunked`` runs, and it enters the
kernel once as a column and once as a row so no in-kernel transpose is
needed. Oracle: kernels.ref.ssd_ref / models.ssm.ssd_chunked.

Tracked debt (the one LINT_BASELINE entry, PAL403): this kernel has no
in-kernel lane gate yet — ``ops.ssd`` masks lanes with a post-hoc
where-zero, so inactive lanes still feed the MXU. Threading an SMEM
predicate through the grid is the remaining half of ROADMAP 3(a); the
flash-attention kernel shows the pattern.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _ssd_kernel(x_ref, dtc_ref, lac_ref, lar_ref, bt_ref, c_ref, y_ref,
                state_ref, state_scr, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)            # (Q, hd)
    dt_c = dtc_ref[0, 0].astype(jnp.float32)       # (Q, 1)
    la_c = lac_ref[0, 0]                           # (Q, 1) log-decay
    la_r = lar_ref[0, 0]                           # (1, Q) same, as a row
    Bt = bt_ref[0].astype(jnp.float32)             # (N, Q)
    Cm = c_ref[0].astype(jnp.float32)              # (Q, N)

    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # la at the chunk's last step (a masked sum of one term: exact)
    la_total = jnp.sum(jnp.where(jq[:1] == chunk - 1, la_r, 0.0), axis=1,
                       keepdims=True)              # (1, 1)
    xb = x * dt_c                                  # (Q, hd)

    # intra-chunk (dual form)
    CB = _mm(Cm, Bt)                               # (Q, Q)
    decay = jnp.where(iq >= jq, jnp.exp(la_c - la_r), 0.0)
    y = _mm(CB * decay, xb)                        # (Q, hd)

    # inter-chunk from the carried state
    state_in = state_scr[...]                      # (N, hd)
    y += _mm(Cm * jnp.exp(la_c), state_in)

    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update
    decay_out = jnp.exp(la_total - la_c)           # (Q, 1)
    state_scr[...] = (state_in * jnp.exp(la_total)
                      + _mm(Bt, xb * decay_out))

    @pl.when(ci == nc - 1)
    def _emit_state():
        state_ref[0, 0] = state_scr[...]


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, *, chunk: int = 128,
             interpret: bool = False):
    """x (b,S,nh,hd); dt (b,S,nh); A (nh,); B/C (b,S,N).
    Returns (y (b,S,nh,hd), final_state (b,nh,hd,N) fp32)."""
    b, S, nh, hd = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, f"seq {S} % chunk {chunk} != 0"
    nc = S // chunk

    # inclusive in-chunk cumsum of the log-decay dt * A, as ssd_chunked
    f32 = jnp.float32
    dA = dt.astype(f32).reshape(b, nc, chunk, nh) * A.astype(f32)
    la = jnp.moveaxis(jnp.cumsum(dA, axis=2).reshape(b, S, nh), 2, 1)

    # head-major layouts: every block's last two dims are 2-D tiles
    xh = jnp.moveaxis(x, 2, 1)                     # (b, nh, S, hd)
    dt_col = jnp.moveaxis(dt, 2, 1)[..., None]     # (b, nh, S, 1)
    la_col = la[..., None]                         # (b, nh, S, 1)
    la_row = la[:, :, None, :]                     # (b, nh, 1, S)
    Bt = jnp.swapaxes(B, 1, 2)                     # (b, N, S)

    grid = (b, nh, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, state = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda i, h, c: (i, h, 0, c)),
            pl.BlockSpec((1, N, chunk), lambda i, h, c: (i, 0, c)),
            pl.BlockSpec((1, chunk, N), lambda i, h, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1, 1, N, hd), lambda i, h, c: (i, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, S, hd), x.dtype),
            jax.ShapeDtypeStruct((b, nh, N, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xh, dt_col, la_col, la_row, Bt, C)
    return jnp.moveaxis(y, 1, 2), jnp.swapaxes(state, 2, 3)
