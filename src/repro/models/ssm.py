"""Mamba2 / SSD (state-space duality) sequence mixer [arXiv:2405.21060].

The chunked SSD algorithm: within a chunk, the recurrence is computed in
its dual (attention-like) matrix form with MXU-friendly matmuls; across
chunks a small recurrent state (B, nh, hd, N) is carried by lax.scan.
``ssd_chunked`` here is the pure-jnp path (and the oracle for the Pallas
kernel in kernels/ssd_scan.py). Decode uses the recurrent step directly.

Conventions: x (B,S,nh,hd); dt (B,S,nh); A (nh,) negative reals;
B/C (B,S,N) shared across heads (ngroups=1, as in mamba2-130m), or
(B,S,G,N) in G groups, head h reading group h // (nh/G) (zamba2-7b).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import SSMConfig
from repro.models import layers


# ---------------------------------------------------------------------------
# core SSD scan (pure jnp, fp32 internals)
# ---------------------------------------------------------------------------

def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array,
                B: jax.Array, C: jax.Array, *, chunk: int,
                init_state: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,nh,hd), final_state (B,nh,hd,N)). B/C in groups
    (B,S,G,N): each group's heads are scanned as their own problem."""
    if B.ndim == 4:
        return _ssd_grouped(x, dt, A, B, C, chunk=chunk,
                            init_state=init_state)
    with jax.named_scope("ssd"):
        return _ssd_chunked_tagged(x, dt, A, B, C, chunk=chunk,
                                   init_state=init_state)


def _ssd_grouped(x, dt, A, B, C, *, chunk, init_state):
    """``ssd_chunked`` mapped over the B/C groups: heads split into G
    consecutive runs of nh/G, run g reading B[:, :, g] and C[:, :, g]."""
    b, S, nh, hd = x.shape
    G, N = B.shape[2:]
    hg = nh // G
    s0 = (None if init_state is None
          else init_state.reshape(b, G, hg, hd, N))
    one = lambda x_, dt_, A_, B_, C_, s_: ssd_chunked(
        x_, dt_, A_, B_, C_, chunk=chunk, init_state=s_)
    y, state = jax.vmap(one, in_axes=(2, 2, 0, 2, 2, None if s0 is None else 1),
                        out_axes=(2, 1))(
        x.reshape(b, S, G, hg, hd), dt.reshape(b, S, G, hg),
        A.reshape(G, hg), B, C, s0)
    return y.reshape(b, S, nh, hd), state.reshape(b, nh, hd, N)


def _ssd_chunked_tagged(x, dt, A, B, C, *, chunk, init_state=None):
    b, S, nh, hd = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, f"seq {S} % chunk {chunk} != 0"
    nc = S // chunk

    f32 = jnp.float32
    xc = x.astype(f32).reshape(b, nc, chunk, nh, hd)
    dtc = dt.astype(f32).reshape(b, nc, chunk, nh)
    Bc = B.astype(f32).reshape(b, nc, chunk, N)
    Cc = C.astype(f32).reshape(b, nc, chunk, N)

    # per-step log decay  la_t = dt_t * A  (A < 0)
    dA = dtc * A.astype(f32)                              # (b,nc,Q,nh)
    la = jnp.cumsum(dA, axis=2)                           # inclusive cumsum
    la_total = la[:, :, -1]                               # (b,nc,nh)

    xb = xc * dtc[..., None]                              # dt-weighted inputs

    # ---- intra-chunk (dual / attention-like form) ----
    CB = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)            # (b,nc,Q,Q)
    # decay[i,j,h] = exp(la_i - la_j) for i >= j else 0. Mask BEFORE the
    # exp: above the diagonal la_i - la_j > 0 can overflow to inf, and
    # where(tri, inf, 0) has a NaN gradient (inf * 0)
    diff = la[:, :, :, None, :] - la[:, :, None, :, :]    # (b,nc,Q,Q,nh)
    iq = jnp.arange(chunk)
    tri = (iq[:, None] >= iq[None, :])[None, None, :, :, None]
    decay = jnp.exp(jnp.where(tri, diff, -jnp.inf))
    y_intra = jnp.einsum("bcij,bcijh,bcjhp->bcihp", CB, decay, xb)

    # ---- chunk-boundary states ----
    # state contribution of chunk c: sum_j exp(la_Q - la_j) * xb_j ⊗ B_j
    decay_out = jnp.exp(la_total[:, :, None, :] - la)     # (b,nc,Q,nh)
    chunk_state = jnp.einsum("bcjh,bcjhp,bcjn->bchpn", decay_out, xb, Bc)

    def carry_fn(state, inp):
        cs, ltot = inp                                     # (b,nh,hd,N),(b,nh)
        new = state * jnp.exp(ltot)[:, :, None, None] + cs
        return new, state                                  # emit state BEFORE chunk

    s0 = (jnp.zeros((b, nh, hd, N), f32) if init_state is None
          else init_state.astype(f32))
    final_state, states_in = jax.lax.scan(
        carry_fn, s0,
        (jnp.moveaxis(chunk_state, 1, 0), jnp.moveaxis(la_total, 1, 0)))
    states_in = jnp.moveaxis(states_in, 0, 1)             # (b,nc,nh,hd,N)

    # ---- inter-chunk: y_i += exp(la_i) * C_i . state_in ----
    c_decayed = Cc[:, :, :, None, :] * jnp.exp(la)[..., None]  # (b,nc,Q,nh,N)
    y_inter = jnp.einsum("bcihn,bchpn->bcihp", c_decayed, states_in)

    y = (y_intra + y_inter).reshape(b, S, nh, hd)
    return y.astype(x.dtype), final_state


def ssd_decode_step(state: jax.Array, x_t: jax.Array, dt_t: jax.Array,
                    A: jax.Array, B_t: jax.Array, C_t: jax.Array,
                    ) -> Tuple[jax.Array, jax.Array]:
    """One recurrent step. state (B,nh,hd,N); x_t (B,nh,hd); dt_t (B,nh);
    B_t/C_t (B,N), or (B,G,N) in groups. Returns (y_t (B,nh,hd),
    new_state)."""
    f32 = jnp.float32
    a = jnp.exp(dt_t.astype(f32) * A.astype(f32))          # (B,nh)
    xb = x_t.astype(f32) * dt_t.astype(f32)[..., None]     # (B,nh,hd)
    if B_t.ndim == 2:      # one group
        B_t, C_t = B_t[:, None], C_t[:, None]
    rep = x_t.shape[1] // B_t.shape[1]    # each head reads its group's B, C
    B_h = jnp.repeat(B_t.astype(f32), rep, axis=1)         # (B,nh,N)
    C_h = jnp.repeat(C_t.astype(f32), rep, axis=1)
    new_state = state * a[:, :, None, None] + xb[..., None] * B_h[:, :, None]
    y = jnp.einsum("bhpn,bhn->bhp", new_state, C_h)
    return y.astype(x_t.dtype), new_state


# ---------------------------------------------------------------------------
# depthwise causal conv1d (width <= 4 unrolled shifts)
# ---------------------------------------------------------------------------

def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """x (B,S,Ch); w (width,Ch); b (Ch,). Causal depthwise conv."""
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    S = x.shape[1]
    out = sum(pad[:, i:i + S] * w[i] for i in range(width))
    return out + b


def causal_conv1d_step(conv_state: jax.Array, x_t: jax.Array,
                       w: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """conv_state (B,width-1,Ch) holds previous inputs; x_t (B,Ch)."""
    full = jnp.concatenate([conv_state, x_t[:, None]], axis=1)  # (B,width,Ch)
    y = jnp.einsum("bwc,wc->bc", full, w) + b
    return y, full[:, 1:]


# ---------------------------------------------------------------------------
# full Mamba2 block
# ---------------------------------------------------------------------------

def dims(d_model: int, s: SSMConfig):
    d_in = s.expand * d_model
    nh = s.num_heads or d_in // s.head_dim
    ch = d_in + 2 * s.ngroups * s.state_dim   # conv channels: x_ssm + B + C
    return d_in, nh, ch


def init_mamba2(key, d_model: int, s: SSMConfig, dtype) -> dict:
    d_in, nh, ch = dims(d_model, s)
    ks = jax.random.split(key, 6)
    # in_proj emits [z(d_in), xBC(ch), dt(nh)]
    d_proj = d_in + ch + nh
    dt = jnp.exp(jax.random.uniform(ks[2], (nh,), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "w_in": layers.dense_init(ks[0], d_model, d_proj, dtype),
        "conv_w": (jax.random.normal(ks[1], (s.conv_width, ch), jnp.float32)
                   / math.sqrt(s.conv_width)).astype(dtype),
        "conv_b": jnp.zeros((ch,), dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),  # inv softplus
        "A_log": jnp.log(jax.random.uniform(ks[3], (nh,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((nh,), jnp.float32),
        "norm_w": jnp.ones((d_in,), dtype),
        "w_out": layers.dense_init(ks[4], d_in, d_model, dtype),
    }


def _project(params, x, d_model, s: SSMConfig):
    d_in, nh, ch = dims(d_model, s)
    proj = x @ params["w_in"].astype(x.dtype)
    z = proj[..., :d_in]
    xBC = proj[..., d_in:d_in + ch]
    dt_raw = proj[..., d_in + ch:]
    return z, xBC, dt_raw, (d_in, nh, ch)


def _split_xBC(xBC, d_in: int, s: SSMConfig):
    """x (…, d_in) and B, C: (…, N) for one group, else (…, G, N)."""
    gn = s.ngroups * s.state_dim
    xs, Bm, Cm = xBC[..., :d_in], xBC[..., d_in:d_in + gn], xBC[..., d_in + gn:]
    if s.ngroups > 1:
        Bm = Bm.reshape(*Bm.shape[:-1], s.ngroups, s.state_dim)
        Cm = Cm.reshape(*Cm.shape[:-1], s.ngroups, s.state_dim)
    return xs, Bm, Cm


def gated_norm(y, z, weight, ngroups: int, eps: float = 1e-5):
    """RMSNorm of ``y * silu(z)``, over each of ``ngroups`` equal groups of
    channels, times ``weight``."""
    g = y * jax.nn.silu(z)
    if ngroups == 1:
        return layers.rms_norm(g, weight, eps)
    g32 = g.astype(jnp.float32).reshape(*g.shape[:-1], ngroups, -1)
    g32 = g32 * jax.lax.rsqrt(jnp.mean(g32 * g32, -1, keepdims=True) + eps)
    return (g32.reshape(g.shape) * weight.astype(jnp.float32)).astype(y.dtype)


def mamba2_block(params: dict, x: jax.Array, d_model: int, s: SSMConfig,
                 init_state: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence Mamba2. x (B,S,d). Returns (y, final_ssm_state)."""
    z, xBC, dt_raw, (d_in, nh, ch) = _project(params, x, d_model, s)
    xBC = jax.nn.silu(causal_conv1d(xBC, params["conv_w"].astype(x.dtype),
                                    params["conv_b"].astype(x.dtype)))
    xs, Bm, Cm = _split_xBC(xBC, d_in, s)
    b, S, _ = x.shape
    xh = xs.reshape(b, S, nh, s.head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    y, state = ssd_chunked(xh, dt, A, Bm, Cm, chunk=s.chunk_size,
                           init_state=init_state)
    y = y + params["D"].astype(x.dtype)[None, None, :, None] * xh
    y = y.reshape(b, S, d_in)
    y = gated_norm(y, z, params["norm_w"], s.ngroups)
    return y @ params["w_out"].astype(x.dtype), state


def mamba2_decode_step(params: dict, x_t: jax.Array, state: dict,
                       d_model: int, s: SSMConfig) -> Tuple[jax.Array, dict]:
    """One-token decode. x_t (B,d). state={'conv':(B,w-1,ch),'ssm':(B,nh,hd,N)}."""
    z, xBC, dt_raw, (d_in, nh, ch) = _project(params, x_t, d_model, s)
    xBC, conv_state = causal_conv1d_step(
        state["conv"], xBC, params["conv_w"].astype(x_t.dtype),
        params["conv_b"].astype(x_t.dtype))
    xBC = jax.nn.silu(xBC)
    xs, Bm, Cm = _split_xBC(xBC, d_in, s)
    xh = xs.reshape(-1, nh, s.head_dim)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    y, ssm_state = ssd_decode_step(state["ssm"], xh, dt, A, Bm, Cm)
    y = y + params["D"].astype(x_t.dtype)[None, :, None] * xh
    y = y.reshape(-1, d_in)
    y = gated_norm(y, z, params["norm_w"], s.ngroups)
    return y @ params["w_out"].astype(x_t.dtype), {"conv": conv_state, "ssm": ssm_state}


def init_decode_state(batch: int, d_model: int, s: SSMConfig, dtype) -> dict:
    d_in, nh, ch = dims(d_model, s)
    return {
        "conv": jnp.zeros((batch, s.conv_width - 1, ch), dtype),
        "ssm": jnp.zeros((batch, nh, s.head_dim, s.state_dim), jnp.float32),
    }


def ssd_reference_recurrent(x, dt, A, B, C):
    """O(S) sequential oracle for tests: literal recurrence, no chunking."""
    b, S, nh, hd = x.shape

    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp
        y, new = ssd_decode_step(state, x_t, dt_t, A, B_t, C_t)
        return new, y

    s0 = jnp.zeros((b, nh, hd, B.shape[-1]), jnp.float32)   # B (b,S,[G,]N)
    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0))
    final, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1), final
