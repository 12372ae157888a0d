"""Attention: GQA, causal/bidirectional/sliding-window, KV cache, kernels.

Three execution paths, selected by ``impl`` (``kernels.ops.resolve_impl``
decides ``None``: "pallas" on a TPU, "xla" elsewhere):
  * ``"xla"``            — memory-efficient chunked online-softmax in pure
                           jnp (lax.scan over KV chunks). Default on CPU and
                           the path the multi-pod dry-run compiles.
  * ``"pallas"``         — the flash-attention Pallas TPU kernel
                           (kernels/flash_attention.py).
  * ``"pallas_interpret"`` — same kernel, interpret mode (CPU correctness).

All paths share the same signature and are cross-checked in tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import layers


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_attention(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, dtype, d_out: Optional[int] = None) -> dict:
    """q/k/v from ``d_model``-wide inputs; the output ``d_out`` wide
    (``d_model`` when not given)."""
    ks = jax.random.split(key, 4)
    return {
        "w_q": layers.dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "w_k": layers.dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype),
        "w_v": layers.dense_init(ks[2], d_model, num_kv_heads * head_dim, dtype),
        "w_o": layers.dense_init(ks[3], num_heads * head_dim,
                                 d_out or d_model, dtype),
    }


# ---------------------------------------------------------------------------
# core scaled-dot-product (XLA chunked path)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _chunk_mask(q_pos, k_pos, causal: bool, window: int):
    """(Sq, Ck) boolean mask. window==0 => unbounded look-back."""
    m = None
    if causal:
        m = q_pos[:, None] >= k_pos[None, :]
    if window:
        w = q_pos[:, None] - k_pos[None, :] < window
        m = w if m is None else (m & w)
    return m


def sdpa_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 causal: bool, window: int = 0,
                 q_offset: int = 0,
                 chunk_k: int = 1024,
                 kv_valid_len: Optional[jax.Array] = None,
                 scale: Optional[float] = None) -> jax.Array:
    """Online-softmax attention, scanning KV chunks.

    q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D); Hq % Hkv == 0.
    q_offset: absolute position of q[0] (prefill continuation / decode).
    kv_valid_len: optional (B,) number of valid cache entries.
    scale: the scores' factor (D ** -0.5 when None).
    Returns (B, Sq, Hq, D) in q.dtype.
    """
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    chunk_k = min(chunk_k, Sk)
    # the whole body runs under a named scope so the roofline analyzer can
    # attribute its HBM traffic (replaced by the flash kernel on real TPU)
    with jax.named_scope("sdpa"):
        return _sdpa_chunked_tagged(q, k, v, causal=causal, window=window,
                                    q_offset=q_offset, chunk_k=chunk_k,
                                    kv_valid_len=kv_valid_len, scale=scale)


def _sdpa_chunked_tagged(q, k, v, *, causal, window, q_offset, chunk_k,
                         kv_valid_len, scale):
    B, Sq, Hq, D = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    # pad Sk to a multiple of chunk_k (masked out below)
    pad = (-Sk) % chunk_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (Sk + pad) // chunk_k

    scale = D ** -0.5 if scale is None else scale
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, Hkv, G, D)
    q_pos = q_offset + jnp.arange(Sq)

    kc = k.reshape(B, n_chunks, chunk_k, Hkv, D)
    vc = v.reshape(B, n_chunks, chunk_k, Hkv, D)

    def body(carry, inp):
        m_prev, l_prev, acc = carry
        idx, k_blk, v_blk = inp                         # (B,Ck,Hkv,D)
        k_pos = idx * chunk_k + jnp.arange(chunk_k)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k_blk.astype(jnp.float32))
        mask = _chunk_mask(q_pos, k_pos, causal, window)
        valid = k_pos[None, :] < (Sk if kv_valid_len is None
                                  else kv_valid_len[:, None])  # (B,Ck) or (1,Ck)
        if mask is not None:
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
    idxs = jnp.arange(n_chunks)
    # checkpoint per KV chunk: backward recomputes the chunk's softmax
    # instead of saving (B,H,Sq,Ck) residuals per chunk (flash-style bwd)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0),
        (idxs, jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]        # (B,Hkv,G,Sq,D)
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, Hq, D)
    return out.astype(q.dtype)


def sdpa_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                valid: jax.Array, scale: Optional[float] = None) -> jax.Array:
    """Single-token decode attention over a cache with explicit validity.

    q: (B, 1, Hq, D); caches: (B, Smax, Hkv*D); valid: (B, Smax) bool.
    """
    B, _, Hq, D = q.shape
    Smax = k_cache.shape[1]
    Hkv = k_cache.shape[2] // D
    G = Hq // Hkv
    scale = D ** -0.5 if scale is None else scale
    qf = (q.astype(jnp.float32) * scale).reshape(B, Hkv, G, D)
    k = k_cache.reshape(B, Smax, Hkv, D).astype(jnp.float32)
    v = v_cache.reshape(B, Smax, Hkv, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, k)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v)
    return out.reshape(B, 1, Hq, D).astype(q.dtype)


def decode_attention(q: jax.Array, k_row: jax.Array, v_row: jax.Array,
                     pos: jax.Array, kv_cache: dict,
                     layer: Optional[jax.Array], window: int,
                     impl: Optional[str] = None,
                     scale: Optional[float] = None) -> tuple:
    """One token's attention over its ring KV cache, written in place.

    q (B,1,Hq,D); k_row/v_row (B,Hkv,D); pos (B,) absolute positions.
    With ``layer`` given, the cache leaves are stacked over layers
    (``(L,B,Smax,Hkv*D)``, ``len`` (L,B), ``pos`` (L,B,Smax)): only the
    token's K/V row, its ``pos`` entry and ``len`` of that layer are
    written, by scatters into the (donated) stack, and the layer's cache is
    read once by the attention (``kernels.ops.decode_attention``; the
    kernel reads it straight out of the stack). Nothing else
    of the stack is copied. Returns (out (B,1,Hq,D), new cache in the
    layout given).
    """
    stacked = layer is not None
    if not stacked:
        kv_cache = jax.tree_util.tree_map(lambda a: a[None], kv_cache)
        layer = 0
    B = q.shape[0]
    Smax = kv_cache["k"].shape[2]
    idx = kv_cache["len"][layer]                         # (B,) tokens so far
    slot = idx % Smax
    bidx = jnp.arange(B)
    new = {"k": kv_cache["k"].at[layer, bidx, slot].set(k_row.reshape(B, -1)),
           "v": kv_cache["v"].at[layer, bidx, slot].set(v_row.reshape(B, -1)),
           "pos": kv_cache["pos"].at[layer, bidx, slot].set(pos),
           "len": kv_cache["len"].at[layer].set(idx + 1)}
    # validity from absolute positions: written, and inside the window
    p = new["pos"][layer]                                # (B,Smax)
    cur = pos[:, None]
    valid = (p >= 0) & (p <= cur)
    if window:
        valid = valid & (p > cur - window)
    out = kops.decode_attention(q, new["k"], new["v"], valid, layer, impl,
                                scale=scale)
    if not stacked:
        new = jax.tree_util.tree_map(lambda a: a[0], new)
    return out, new


def project_kv(params: dict, ctx: jax.Array, num_kv_heads: int,
               head_dim: int) -> tuple:
    """K/V projections of an encoder memory (no rope). ctx (B, Sk, d)."""
    B, Sk, _ = ctx.shape
    cdt = ctx.dtype
    k = (ctx @ params["w_k"].astype(cdt)).reshape(B, Sk, num_kv_heads, head_dim)
    v = (ctx @ params["w_v"].astype(cdt)).reshape(B, Sk, num_kv_heads, head_dim)
    return k, v


def attn_with_kv(params: dict, x: jax.Array, k: jax.Array, v: jax.Array,
                 num_heads: int, head_dim: int) -> jax.Array:
    """Attention of x onto precomputed K/V (cross-attention path)."""
    B, S, _ = x.shape
    cdt = x.dtype
    q = (x @ params["w_q"].astype(cdt)).reshape(B, S, num_heads, head_dim)
    out = sdpa_chunked(q, k, v, causal=False, window=0)
    out = out.reshape(B, S, num_heads * head_dim)
    return out @ params["w_o"].astype(cdt)


# ---------------------------------------------------------------------------
# full attention block (proj + rope + sdpa + out-proj)
# ---------------------------------------------------------------------------

def attention_block(params: dict, x: jax.Array, *,
                    num_heads: int, num_kv_heads: int, head_dim: int,
                    positions: jax.Array,
                    rope_theta: float,
                    mrope_positions: Optional[jax.Array] = None,
                    causal: bool = True,
                    window: int = 0,
                    kv_cache: Optional[dict] = None,
                    impl: Optional[str] = None,
                    kv_ctx: Optional[jax.Array] = None,
                    layer: Optional[jax.Array] = None,
                    scale: Optional[float] = None) -> tuple:
    """Returns (out, new_kv_cache). ``scale`` multiplies the scores
    (head_dim ** -0.5 when None).

    Modes:
      * kv_cache is None, kv_ctx is None   -> self-attention over x (train/prefill)
      * kv_cache given & x is 1 token      -> cached decode step
      * kv_ctx given                       -> cross-attention onto kv_ctx
    kv_cache = {"k": (B,Smax,Hkv*D), "v": ..., "len": (B,) int32,
    "pos": (B,Smax) int32}. In a decode step with ``layer`` given, every
    leaf carries a leading layer axis and the step reads and writes layer
    ``layer`` of it (``decode_attention``).
    """
    impl = kops.resolve_impl(impl)
    B, S, _ = x.shape
    cdt = x.dtype
    q = (x @ params["w_q"].astype(cdt)).reshape(B, S, num_heads, head_dim)

    if kv_ctx is not None:  # cross attention (no rope, no cache update here)
        Sk = kv_ctx.shape[1]
        k = (kv_ctx @ params["w_k"].astype(cdt)).reshape(B, Sk, num_kv_heads, head_dim)
        v = (kv_ctx @ params["w_v"].astype(cdt)).reshape(B, Sk, num_kv_heads, head_dim)
        out = sdpa_chunked(q, k, v, causal=False, window=0)
        out = out.reshape(B, S, num_heads * head_dim)
        return out @ params["w_o"].astype(cdt), None

    k = (x @ params["w_k"].astype(cdt)).reshape(B, S, num_kv_heads, head_dim)
    v = (x @ params["w_v"].astype(cdt)).reshape(B, S, num_kv_heads, head_dim)

    if mrope_positions is not None:
        q = layers.apply_mrope(q, mrope_positions, rope_theta)
        k = layers.apply_mrope(k, mrope_positions, rope_theta)
    else:
        q = layers.apply_rope(q, positions, rope_theta)
        k = layers.apply_rope(k, positions, rope_theta)

    if kv_cache is not None and S == 1:  # decode step (ring write: idx % Smax)
        out, new_cache = decode_attention(q, k[:, 0], v[:, 0], positions[:, 0],
                                          kv_cache, layer, window, impl,
                                          scale=scale)
    else:  # train / prefill
        if impl == "xla":
            out = sdpa_chunked(q, k, v, causal=causal, window=window,
                               scale=scale)
        else:
            out = kops.flash_attention(q, k, v, causal=causal,
                                       window=window, impl=impl, scale=scale)
        if kv_cache is not None:  # prefill into cache (keep last Smax if S>Smax)
            Smax = kv_cache["k"].shape[1]
            if S >= Smax:
                k_w, v_w, p_w = (k[:, -Smax:], v[:, -Smax:],
                                 positions[:, -Smax:])
            else:
                k_w, v_w, p_w = k, v, positions
            k_w, v_w = (a.reshape(B, a.shape[1], -1) for a in (k_w, v_w))
            k_new = jax.lax.dynamic_update_slice_in_dim(kv_cache["k"], k_w, 0, axis=1)
            v_new = jax.lax.dynamic_update_slice_in_dim(kv_cache["v"], v_w, 0, axis=1)
            pos_new = jax.lax.dynamic_update_slice_in_dim(
                kv_cache["pos"], p_w.astype(jnp.int32), 0, axis=1)
            new_cache = {"k": k_new, "v": v_new,
                         "len": jnp.full((B,), S, jnp.int32), "pos": pos_new}
        else:
            new_cache = None

    out = out.reshape(B, S, num_heads * head_dim)
    return out @ params["w_o"].astype(cdt), new_cache


def init_kv_cache(batch: int, max_len: int, num_kv_heads: int, head_dim: int,
                  dtype) -> dict:
    """Ring KV cache. ``pos`` holds the absolute position stored in each
    slot (-1 = empty); windowed caches set max_len == window. K and V keep
    heads and head_dim merged in one minor axis, ``(B, Smax, Hkv*D)``: a
    TPU lays that out as it is, unpadded, where a minor head_dim of 64
    would be padded to the 128-wide tile (or the array stored permuted),
    and a token's row is one contiguous write."""
    return {
        "k": jnp.zeros((batch, max_len, num_kv_heads * head_dim), dtype),
        "v": jnp.zeros((batch, max_len, num_kv_heads * head_dim), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
    }
