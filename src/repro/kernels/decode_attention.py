"""Decode attention Pallas TPU kernel: one new token per sequence, against
its layer of a layer-stacked KV cache with heads merged into the minor axis.

The serving pool keeps K and V as ``(L, B, S, Hkv*D)``
(``attention.init_kv_cache``): a TPU stores that shape unpadded and as it
is, and a token's row is one contiguous write. This kernel reads each
layer's blocks straight out of the stack, the layer picked by a
scalar-prefetched index, so the decode step neither slices a layer out of
the pool nor copies it into another layout (XLA's own lowering of the same
math splits the minor axis into (Hkv, D) and so copies each layer's slab
into a float32 layout of its own, every step).

  * grid (B, Hkv / hb, S / bs): one sequence's ``hb`` KV heads, ``bs``
    cache positions per step. The head block is as wide as the 128-lane
    tile allows up to ``BLOCK_WIDTH`` (or every head, the full width);
    the position block holds at most ``BLOCK_ELEMS`` elements of K, so
    VMEM use is bounded whatever the cache length. The position axis is
    "arbitrary": the running max, denominator and accumulator of the
    online softmax live in VMEM scratch across it (flash-decoding);
  * scores on the MXU without splitting the minor axis: the queries come
    in block-diagonal, ``qbd[g*hb + h, h'*D + d] = q[h, g, d]`` where
    h == h' and 0 elsewhere, so ``qbd @ K.T`` gives every (query head,
    position) score; then the float32 online softmax over the valid
    positions, ``p @ V``, and the diagonal blocks of the accumulated
    product are the outputs;
  * the math of ``attention.sdpa_decode`` in float32 (the added terms are
    exact zeros); validity (written, causal, inside the window) comes in
    as a ``(B, 1, S)`` int32 mask computed from the cache's ``pos``;
  * each lane reads only its live position blocks: the first and last
    block holding a valid position come in as scalar-prefetched bounds,
    the K, V and mask index maps clamp the position block into them (a
    block index that repeats between grid steps starts no new DMA) and
    the body runs only inside them. A block outside holds no valid
    position, so it would add exact zeros to the online softmax: the
    output of every lane with a valid position is the same as reading
    every block. A lane with none reads block 0 alone (a finite output
    nobody reads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128                 # minor tile width of a TPU vector register
BLOCK_WIDTH = 512           # widest head block, in lanes (hb * D)
BLOCK_ELEMS = 1 << 19       # most elements of one K or V block (bs * hb*D)


def head_block(num_kv_heads: int, head_dim: int) -> int:
    """KV heads per grid step: the most that divide ``num_kv_heads`` and
    give a block width that is a multiple of 128 lanes and at most
    ``BLOCK_WIDTH``, or than the narrowest such width where that is wider
    (4 heads of 224: 896 lanes); every head (the full width, always a
    legal block) where no such count exists."""
    legal = [h for h in range(1, num_kv_heads + 1)
             if num_kv_heads % h == 0 and (h * head_dim) % LANES == 0]
    if not legal:
        return num_kv_heads
    cap = max(BLOCK_WIDTH, min(legal) * head_dim)
    return max(h for h in legal if h * head_dim <= cap)


def seq_block(seq: int, width: int) -> int:
    """Cache positions per grid step for blocks ``width`` lanes wide: the
    whole cache when it fits in ``BLOCK_ELEMS``, else the largest multiple
    of 128 that divides it and fits."""
    cap = max(BLOCK_ELEMS // width, LANES)
    if seq <= cap:
        return seq
    fits = [b for b in range(LANES, cap + 1, LANES) if seq % b == 0]
    if not fits:
        raise ValueError(f"decode attention: a cache of {seq} positions "
                         f"has no block of a multiple of {LANES} positions "
                         f"up to {cap} that divides it")
    return max(fits)


def position_block(seq: int, num_kv_heads: int, head_dim: int) -> int:
    """Cache positions per grid step over a cache of ``seq`` positions of
    ``num_kv_heads`` heads of ``head_dim``: a lane at position p (and
    every position before it valid) reads ``p // position_block + 1`` of
    the ``seq // position_block`` blocks."""
    return seq_block(seq, head_block(num_kv_heads, head_dim) * head_dim)


def _live_blocks(valid: jax.Array, bs: int) -> tuple:
    """(first, last) (B,) int32: each lane's first and last position
    block of ``bs`` holding a valid position; (0, 0) for a lane with
    none."""
    B, S = valid.shape
    live = valid.reshape(B, S // bs, bs).any(axis=2)
    blocks = jnp.arange(S // bs, dtype=jnp.int32)
    first = jnp.min(jnp.where(live, blocks, S // bs), axis=1)
    last = jnp.max(jnp.where(live, blocks, 0), axis=1)
    return jnp.minimum(first, last), last


def _kernel(layer_ref, first_ref, last_ref, q_ref, k_ref, v_ref, valid_ref,
            o_ref, m_scr, l_scr, acc_scr, *, groups: int, heads: int,
            head_dim: int, scale: float):
    del layer_ref                                    # used by the index maps
    b = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when((si >= first_ref[b]) & (si <= last_ref[b]))
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)          # (G*hb, hb*D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bs, hb*D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bs, hb*D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = jnp.where(valid_ref[0] > 0, s, NEG_INF)  # (G*hb, bs)
        m_prev = m_scr[...]                          # (G*hb, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(p, v)
        m_scr[...] = m_new

    @pl.when(si == pl.num_programs(2) - 1)
    def _finalize():
        full = acc_scr[...] / l_scr[...]             # (G*hb, hb*D)
        shape = (heads, heads * head_dim)
        own = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) ==
               jax.lax.broadcasted_iota(jnp.int32, shape, 1) // head_dim)
        for g in range(groups):
            blk = full[g * heads:(g + 1) * heads]
            o_ref[0, g:g + 1] = jnp.sum(jnp.where(own, blk, 0.0), axis=0,
                                        keepdims=True)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def decode_attention_fwd(q, k, v, valid, layer, *, scale=None,
                         interpret: bool = False):
    """q (B, Hq, D); k/v (L, B, S, Hkv*D); valid (B, S) bool; layer ()
    int. Returns (B, Hq, D) float32: attention of each sequence's query
    over layer ``layer`` of its cache, the scores times ``scale``
    (D ** -0.5 when None). Each lane reads only its live position
    blocks."""
    D = q.shape[2]
    first, last = _live_blocks(valid, position_block(k.shape[2],
                                                     k.shape[3] // D, D))
    return _decode_attention(q, k, v, valid, layer, first, last, scale=scale,
                             interpret=interpret)


def _decode_attention(q, k, v, valid, layer, first, last, *, scale,
                      interpret: bool):
    """``decode_attention_fwd`` reading lane b's position blocks
    ``first[b]`` to ``last[b]`` (B,) int32."""
    B, Hq, D = q.shape
    L, _, S, W = k.shape
    Hkv = W // D
    G = Hq // Hkv
    hb = head_block(Hkv, D)
    nb = Hkv // hb
    bs = position_block(S, Hkv, D)
    # block-diagonal queries per head block: rows (g, h), columns (h', d)
    q6 = q.reshape(B, nb, hb, G, D)
    qbd = jnp.einsum("bjhgd,hk->bjghkd", q6, jnp.eye(hb, dtype=q.dtype))
    qbd = qbd.reshape(B, nb, G * hb, hb * D)
    mask = valid.astype(jnp.int32).reshape(B, 1, S)
    lay = jnp.reshape(layer, (1,)).astype(jnp.int32)
    grid = (B, nb, S // bs)
    out = pl.pallas_call(
        functools.partial(_kernel, groups=G, heads=hb, head_dim=D,
                          scale=D ** -0.5 if scale is None else scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G * hb, hb * D),
                             lambda b, j, i, lay, lo, hi: (b, j, 0, 0)),
                pl.BlockSpec((1, 1, bs, hb * D),
                             lambda b, j, i, lay, lo, hi: (
                                 lay[0], b,
                                 jnp.minimum(jnp.maximum(i, lo[b]), hi[b]),
                                 j)),
                pl.BlockSpec((1, 1, bs, hb * D),
                             lambda b, j, i, lay, lo, hi: (
                                 lay[0], b,
                                 jnp.minimum(jnp.maximum(i, lo[b]), hi[b]),
                                 j)),
                pl.BlockSpec((1, 1, bs), lambda b, j, i, lay, lo, hi: (
                    b, 0, jnp.minimum(jnp.maximum(i, lo[b]), hi[b]))),
            ],
            out_specs=pl.BlockSpec((1, G, hb * D),
                                   lambda b, j, i, lay, lo, hi: (b, 0, j)),
            scratch_shapes=[
                pltpu.VMEM((G * hb, 1), jnp.float32),
                pltpu.VMEM((G * hb, 1), jnp.float32),
                pltpu.VMEM((G * hb, hb * D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, G, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lay, first, last, qbd, k, v, mask)
    # (B, G, Hkv, D) -> query heads in the order h*G + g
    return out.reshape(B, G, Hkv, D).transpose(0, 2, 1, 3).reshape(B, Hq, D)
