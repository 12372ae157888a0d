"""Mixture-of-Experts FFN: top-k routing, shared experts, dense residual,
expert parallelism.

Two execution paths with identical math:
  * ``moe_dense_oracle`` — every expert on every token; O(E) compute; the
    correctness oracle for tests and tiny smoke configs.
  * ``moe_routed``       — sort-free capacity dispatch: tokens are scattered
    into per-expert capacity buffers (E, C, d), experts run as one batched
    einsum (MXU-friendly), results scatter-add back. Dropless when
    capacity_factor <= 0. Runs locally or, with ``ep_axis`` set, inside a
    shard_map with experts sharded over the mesh "model" axis
    (replicated-activation EP: no all-to-all, one psum at the end;
    all-to-all dispatch EP remains an open perf experiment).

Shared experts (DeepSeek) are algebraically fused into one dense FFN of
width n_shared*d_ff (block-diagonal equivalence). The Arctic dense residual
is a separate dense FFN added in parallel.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models import layers


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_moe(key, d_model: int, m: MoEConfig, dtype) -> dict:
    ks = jax.random.split(key, 5)
    E, dff = m.num_experts, m.expert_d_ff
    p = {
        "router": layers.dense_init(ks[0], d_model, E, jnp.float32),
        "w_gate": jnp.stack([layers.dense_init(k, d_model, dff, dtype)
                             for k in jax.random.split(ks[1], E)]),
        "w_up": jnp.stack([layers.dense_init(k, d_model, dff, dtype)
                           for k in jax.random.split(ks[2], E)]),
        "w_down": jnp.stack([layers.dense_init(k, dff, d_model, dtype)
                             for k in jax.random.split(ks[3], E)]),
    }
    if m.num_shared_experts:
        p["shared"] = layers.init_mlp(
            ks[4], d_model, m.num_shared_experts * dff, "swiglu", dtype)
    return p


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

def route(router_w: jax.Array, x: jax.Array, top_k: int,
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (T,d) -> (weights (T,k) fp32 renormalized, idx (T,k) i32, aux loss)."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)   # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)             # renorm
    # load-balance aux (Switch): E * sum_e f_e * P_e
    E = router_w.shape[1]
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)              # (T,k,E)
    f = onehot.sum((0, 1)) / (x.shape[0] * top_k)
    P = probs.mean(0)
    aux = E * jnp.sum(f * P)
    return w, idx, aux


# ---------------------------------------------------------------------------
# oracle path
# ---------------------------------------------------------------------------

def moe_dense_oracle(params: dict, x: jax.Array, m: MoEConfig,
                     ) -> Tuple[jax.Array, jax.Array]:
    """x (T,d). All experts computed densely; exact (dropless) combine."""
    T, d = x.shape
    w, idx, aux = route(params["router"], x, m.top_k)
    cdt = x.dtype
    g = jnp.einsum("td,edf->tef", x, params["w_gate"].astype(cdt))
    u = jnp.einsum("td,edf->tef", x, params["w_up"].astype(cdt))
    h = jax.nn.silu(g) * u
    y_all = jnp.einsum("tef,efd->ted", h, params["w_down"].astype(cdt))
    sel = jnp.take_along_axis(y_all, idx[:, :, None], axis=1)       # (T,k,d)
    y = jnp.sum(sel * w[:, :, None].astype(cdt), axis=1)
    return y, aux


# ---------------------------------------------------------------------------
# capacity-dispatch path (local or EP shard region)
# ---------------------------------------------------------------------------

def _dispatch_compute_combine(x, w, idx, params, m: MoEConfig,
                              e_start: int, e_local: int,
                              capacity: int) -> jax.Array:
    """Compute routed output for experts [e_start, e_start+e_local).

    x (T,d); w/idx (T,k). Scatter tokens into (E_local, C, d) buffers,
    batched SwiGLU, scatter-add combine into (T,d). Tokens routed to
    non-local experts (or overflowing capacity) contribute zero here.
    """
    T, d = x.shape
    k = idx.shape[1]
    cdt = x.dtype
    flat_e = idx.reshape(-1)                         # (T*k,) global expert ids
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = w.reshape(-1)

    local = (flat_e >= e_start) & (flat_e < e_start + e_local)
    le = jnp.where(local, flat_e - e_start, e_local)  # e_local = trash row
    # slot within expert: stable rank among same-expert assignments
    onehot = jax.nn.one_hot(le, e_local + 1, dtype=jnp.int32)   # (T*k, E_l+1)
    slot = (jnp.cumsum(onehot, axis=0) - 1)[jnp.arange(T * k), le]
    keep = local & (slot < capacity)
    le_s = jnp.where(keep, le, e_local)               # overflow -> trash row
    slot_s = jnp.where(keep, slot, 0)

    buf = jnp.zeros((e_local + 1, capacity, d), cdt)
    buf = buf.at[le_s, slot_s].add(jnp.where(keep[:, None], x[flat_t], 0))
    buf = buf[:e_local]

    wg = jax.lax.dynamic_slice_in_dim(params["w_gate"], e_start, e_local, 0)
    wu = jax.lax.dynamic_slice_in_dim(params["w_up"], e_start, e_local, 0)
    wd = jax.lax.dynamic_slice_in_dim(params["w_down"], e_start, e_local, 0)
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(cdt))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(cdt))
    yb = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd.astype(cdt))

    vals = yb[le_s, slot_s] * flat_w[:, None].astype(cdt)
    vals = jnp.where(keep[:, None], vals, 0)
    y = jnp.zeros((T, d), cdt).at[flat_t].add(vals)
    return y


def capacity_for(T: int, m: MoEConfig, num_shards: int = 1) -> int:
    if m.capacity_factor <= 0:
        return T * m.top_k                           # dropless
    cap = int(T * m.top_k * m.capacity_factor / m.num_experts) * num_shards
    return max(cap, 8)


def moe_routed(params: dict, x: jax.Array, m: MoEConfig, *,
               capacity: Optional[int] = None,
               ep_axis: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """Routed-experts output for x (T,d). Inside a shard_map, set ep_axis to
    the mesh axis name sharding the expert dim of the weights; the psum over
    that axis completes the combine."""
    E = m.num_experts
    if ep_axis is None:
        cap = capacity if capacity is not None else capacity_for(x.shape[0], m)
        w, idx, aux = route(params["router"], x, m.top_k)
        y = _dispatch_compute_combine(x, w, idx, params, m, 0, E, cap)
        return y, aux
    size = jax.lax.axis_size(ep_axis)
    rank = jax.lax.axis_index(ep_axis)
    e_local = E // size
    cap = capacity if capacity is not None else capacity_for(x.shape[0], m)
    w, idx, aux = route(params["router"], x, m.top_k)
    y = _dispatch_compute_combine(x, w, idx, params, m,
                                  rank * e_local, e_local, cap)
    return jax.lax.psum(y, ep_axis), aux


# ---------------------------------------------------------------------------
# full MoE FFN block (shared + routed + optional dense residual)
# ---------------------------------------------------------------------------

def moe_ffn(params: dict, x: jax.Array, m: MoEConfig, *,
            dense_params: Optional[dict] = None,
            oracle: bool = False,
            ep_axis: Optional[str] = None,
            dropless: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x (B,S,d) -> (y (B,S,d), aux loss). ``dense_params`` is the Arctic
    parallel dense-residual FFN (cfg.moe.dense_residual). ``dropless``
    gives every expert room for every token (capacity = tokens), so no
    token's output depends on the others routed with it."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    if oracle:
        y, aux = moe_dense_oracle(params, xt, m)
    else:
        y, aux = moe_routed(params, xt, m,
                            capacity=B * S if dropless else None,
                            ep_axis=ep_axis)
    y = y.reshape(B, S, d)
    if "shared" in params:
        y = y + layers.mlp(params["shared"], x, "swiglu")
    if dense_params is not None:
        y = y + layers.mlp(dense_params, x, "swiglu")
    return y, aux
