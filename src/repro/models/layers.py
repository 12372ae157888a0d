"""Shared neural-net building blocks (pure JAX, pytree params).

Conventions:
  * params are nested dicts of jnp arrays, stored in ``param_dtype``;
  * forward code casts to ``compute_dtype`` (norms/softmax stay fp32);
  * weight matrices are stored FOLDED: attention projections are
    (d_model, n_heads*head_dim) so the TP-sharded dim is always divisible
    by the mesh "model" axis even when n_heads is not (e.g. 28, 56 heads).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    out = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape (head_dim//2,)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate pairs. x: (B, S, H, D); positions: (B, S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)                      # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# M-RoPE (Qwen2-VL): head_dim split into (t, h, w) sections, each section
# rotated by its own position stream. Section split follows the paper's
# 16/24/24 ratio scaled to head_dim/2.
MROPE_SECTIONS = (2, 3, 3)  # ratios; scaled so sum == head_dim//2


def mrope_section_sizes(head_dim: int) -> tuple:
    half = head_dim // 2
    unit = half // sum(MROPE_SECTIONS)
    sizes = [r * unit for r in MROPE_SECTIONS]
    sizes[-1] += half - sum(sizes)
    return tuple(sizes)


def apply_mrope(x: jax.Array, positions_thw: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions_thw: (3, B, S) int32 (t/h/w streams)."""
    D = x.shape[-1]
    half = D // 2
    freqs = rope_freqs(D, theta)                                # (D/2,)
    sizes = mrope_section_sizes(D)
    # per-frequency position stream: first sizes[0] freqs use t, then h, then w
    sec_id = jnp.repeat(jnp.arange(3), jnp.array(sizes), total_repeat_length=half)
    pos = positions_thw.astype(jnp.float32)                     # (3, B, S)
    pos_per_freq = pos[sec_id]                                  # (D/2, B, S)
    ang = jnp.moveaxis(pos_per_freq, 0, -1) * freqs             # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, mlp_type: str, dtype) -> dict:
    ks = jax.random.split(key, 3)
    if mlp_type in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], d_model, d_ff, dtype),
            "w_up": dense_init(ks[1], d_model, d_ff, dtype),
            "w_down": dense_init(ks[2], d_ff, d_model, dtype),
        }
    return {
        "w_up": dense_init(ks[0], d_model, d_ff, dtype),
        "w_down": dense_init(ks[1], d_ff, d_model, dtype),
    }


def init_adapter(key, d_model: int, d_ff: int, rank: int, dtype) -> dict:
    """A rank-``rank`` additive adapter on a gated MLP's gate and up
    projections: ``x @ a`` then ``@ b_gate`` and ``@ b_up``."""
    ks = jax.random.split(key, 3)
    return {"a": dense_init(ks[0], d_model, rank, dtype),
            "b_gate": dense_init(ks[1], rank, d_ff, dtype),
            "b_up": dense_init(ks[2], rank, d_ff, dtype)}


def mlp(params: dict, x: jax.Array, mlp_type: str,
        adapter: Optional[dict] = None) -> jax.Array:
    """SwiGLU, GeGLU (the exact, erf GELU) or a plain GELU MLP. A gated
    MLP's gate and up projections each add ``adapter``'s low-rank term
    when one is given."""
    cdt = x.dtype
    if mlp_type in ("swiglu", "geglu"):
        g = x @ params["w_gate"].astype(cdt)
        u = x @ params["w_up"].astype(cdt)
        if adapter is not None:
            r = x @ adapter["a"].astype(cdt)
            g = g + r @ adapter["b_gate"].astype(cdt)
            u = u + r @ adapter["b_up"].astype(cdt)
        act = (jax.nn.silu if mlp_type == "swiglu"
               else functools.partial(jax.nn.gelu, approximate=False))
        h = act(g) * u
    else:
        h = jax.nn.gelu(x @ params["w_up"].astype(cdt))
    return h @ params["w_down"].astype(cdt)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       ignore_id: int = -1) -> jax.Array:
    """Mean token NLL in fp32; labels == ignore_id are masked.

    Sharding-friendly formulation: the gold logit is extracted with a
    one-hot reduction instead of take_along_axis — a gather over a
    TP-sharded vocab dim forces GSPMD to all-gather the full logits
    (measured: 3×26 GB/device temps on stablelm train_4k), while
    elementwise × + reduce keeps the vocab dim sharded end-to-end.
    """
    logits = logits.astype(jnp.float32)
    mask = (labels != ignore_id).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    onehot = jax.nn.one_hot(safe, logits.shape[-1], dtype=logits.dtype)
    gold = jnp.sum(logits * onehot, axis=-1)
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)
