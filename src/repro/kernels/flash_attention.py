"""Flash attention Pallas TPU kernel (fwd) with GQA + causal/window masks.

Blocked online-softmax [arXiv:2205.14135] adapted to TPU:
  * grid (B, Hq, Sq/Bq, Sk/Bk); the KV-block axis is "arbitrary"
    (sequential) so the running max/denominator/accumulator live in VMEM
    scratch across KV steps;
  * q/k/v tiles are MXU-aligned (block sizes multiples of (8, 128) lanes;
    head_dim is the minor-most 128-lane dim);
  * GQA: the q-head grid index maps to kv-head q_head // group via the
    BlockSpec index_map — no KV repeat is materialized;
  * causal / sliding-window masking is done with block-level skips
    (pl.when) plus an in-block iota mask, so fully-masked KV blocks do no
    FLOPs.

Lane masking (``active=``): the pool hot path batches independent jobs
on the batch axis, so at partial occupancy some batch lanes are dead.
The masked variant carries a per-lane predicate in SMEM and folds it
into the block-level skip — an inactive lane issues no QK/PV dots and
finalizes to exact zeros from the untouched scratch (the packed_gemm
masking pattern; PAL403 in repro.analysis enforces it). Block
pipelining still streams inactive tiles from HBM; pruning those copies
needs scalar-prefetch grid reduction (ROADMAP 3(b), fed by
repro.analysis.kernel_report).

Backward runs as recompute through the jnp reference (ops.py wires the
custom_vjp); a fused bwd kernel is a possible future §Perf item.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                causal: bool, window: int, bq: int, bk: int, sk: int,
                scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level skip: causal => skip blocks entirely above the diagonal;
    # window => skip blocks entirely older than the window
    run = True
    if causal:
        run = jnp.asarray(k_start <= q_start + bq - 1)
    if window:
        run = jnp.logical_and(run, k_start + bk - 1 >= q_start - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < sk
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                   # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _fwd_masked_kernel(q_ref, k_ref, v_ref, act_ref, o_ref, m_scr, l_scr,
                       acc_scr, *, causal: bool, window: int, bq: int,
                       bk: int, sk: int, scale: float):
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # lane predicate folded into the block-level skip: an inactive lane's
    # KV blocks issue no dots at all, and its scratch stays at the init
    # state (l = 0, acc = 0), so _finalize emits exact zeros
    lane = act_ref[bi] != 0
    run = lane
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window:
        run = jnp.logical_and(run,
                              k_start + bk - 1 >= q_start - window + 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < sk
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                   # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        active: jax.Array | None = None,
                        scale: float | None = None,
                        interpret: bool = False) -> jax.Array:
    """q (B,Sq,Hq,D); k/v (B,Sk,Hkv,D) -> (B,Sq,Hq,D).

    Sq/Sk are padded to block multiples internally; D should be a multiple
    of 128 for MXU alignment (not enforced — smaller D still works; a D
    that is no multiple of 128, as zamba2's 224, is one block of the full
    width, which the chip pads to the next 128 lanes inside the kernel).
    ``scale`` multiplies the scores (1/sqrt(D) when None).

    ``active`` (bool/int (B,), optional): per-batch-lane predicate in
    SMEM. Inactive lanes' KV blocks skip the QK/PV dots entirely and
    their outputs are exact zeros; active lanes run the same compute
    body as the unmasked kernel (bit-identical). ``active=None`` leaves
    the unmasked program untouched.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale

    # layout: (B, H, S, D) for clean 2D tiles
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)

    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sq_p, Sk_p = Sq + pad_q, Sk + pad_k

    grid = (B, Hq, Sq_p // bq, Sk_p // bk)

    in_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // G, j, 0)),
    ]
    operands = [qt, kt, vt]
    kernel_fn = _fwd_kernel
    if active is not None:
        kernel_fn = _fwd_masked_kernel
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(active, jnp.int32).reshape(B))

    kernel = functools.partial(
        kernel_fn, causal=causal, window=window, bq=bq, bk=bk, sk=Sk,
        scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(*operands)

    out = out[:, :, :Sq]
    return jnp.moveaxis(out, 1, 2)
