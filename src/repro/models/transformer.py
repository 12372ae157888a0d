"""Transformer stacks: decoder-only LM, encoder-decoder, and the zamba2-style
hybrid (Mamba2 backbone + one SHARED attention block applied periodically).

Layers are scanned (``jax.lax.scan`` over stacked params) so the lowered HLO
is one layer body regardless of depth — essential for dry-run compile times
at 126 layers × 512 devices. Remat (full per-layer activation checkpointing)
wraps the scan body when cfg.remat.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, layers, moe, ssm


@dataclasses.dataclass(frozen=True)
class ParallelCtx:
    """How the forward pass should parallelize / specialize.

    mesh          — Mesh when running under pjit (None on single device)
    ep            — expert parallelism via shard_map over the "model" axis
    moe_oracle    — tiny dense-oracle MoE path (smoke tests only)
    attn_impl     — attention impl override ("xla"|"pallas"|"pallas_interpret")
    constrain     — insert with_sharding_constraint at layer boundaries
    """
    mesh: Any = None
    ep: bool = False
    moe_oracle: bool = False
    attn_impl: Optional[str] = None
    constrain: bool = True
    score_bf16: bool = False    # §Perf: bf16 softmax-prob traffic
    ep_bf16: bool = False       # §Perf: bf16 EP combine psum payload

    def batch_axes(self):
        if self.mesh is None:
            return None
        return tuple(n for n in self.mesh.axis_names if n != "model")


def _constrain_act(x, pctx: ParallelCtx):
    """Activations (B, S, d): batch sharded over data axes, rest replicated."""
    if pctx.mesh is None or not pctx.constrain:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = P(pctx.batch_axes(), *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(pctx.mesh, spec))


# ---------------------------------------------------------------------------
# block init
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, kind: str, dtype) -> dict:
    ks = jax.random.split(key, 4)
    hd = cfg.resolved_head_dim
    if kind == "ssm":
        return {"ln": jnp.ones((cfg.d_model,), dtype),
                "mamba": ssm.init_mamba2(ks[0], cfg.d_model, cfg.ssm, dtype)}
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "attn": attention.init_attention(
            ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, hd, dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
    }
    if kind == "dense":
        p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    elif kind == "moe":
        p["moe"] = moe.init_moe(ks[1], cfg.d_model, cfg.moe, dtype)
        if cfg.moe.dense_residual:
            p["dense_mlp"] = layers.init_mlp(
                ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    elif kind == "cross":  # encoder-decoder decoder block
        p["cross_attn"] = attention.init_attention(
            ks[1], cfg.d_model, cfg.num_heads, cfg.num_kv_heads, hd, dtype)
        p["ln_cross"] = jnp.ones((cfg.d_model,), dtype)
        p["mlp"] = layers.init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    else:
        raise ValueError(kind)
    return p


def init_stack(key, cfg: ModelConfig, kind: str, n: int, dtype):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_block(k, cfg, kind, dtype))(keys)


# ---------------------------------------------------------------------------
# block forward
# ---------------------------------------------------------------------------

def _ep_moe_call(p_moe, xt, cfg, pctx: ParallelCtx, dropless: bool = False):
    """Routed experts under shard_map EP (experts over the "model" axis).
    ``dropless``: capacity = the shard's tokens (``moe.moe_ffn``)."""
    from jax.sharding import PartitionSpec as P
    mesh = pctx.mesh
    data_axes = pctx.batch_axes()
    m = cfg.moe

    def body(router, wg, wu, wd, xt_l):
        prm = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        y, aux = moe.moe_routed(prm, xt_l, m, ep_axis="model",
                                capacity=xt_l.shape[0] if dropless else None,
                                combine_dtype=(jnp.bfloat16 if pctx.ep_bf16
                                               else None))
        aux = jax.lax.pmean(aux, data_axes)
        return y, aux

    in_specs = (P(), P("model"), P("model"), P("model"), P(data_axes, None))
    out_specs = (P(data_axes, None), P())
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(p_moe["router"], p_moe["w_gate"], p_moe["w_up"],
              p_moe["w_down"], xt)


def attn_block_fwd(p: dict, x, cfg: ModelConfig, *, positions,
                   mrope_positions=None, window: int, causal: bool,
                   cache=None, layer=None, pctx: ParallelCtx):
    hd = cfg.resolved_head_dim
    out, new_cache = attention.attention_block(
        p["attn"], layers.rms_norm(x, p["ln1"], cfg.norm_eps),
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads, head_dim=hd,
        positions=positions, rope_theta=cfg.rope_theta,
        mrope_positions=mrope_positions, causal=causal, window=window,
        kv_cache=cache, layer=layer, impl=pctx.attn_impl,
        prob_dtype=jnp.bfloat16 if pctx.score_bf16 else jnp.float32)
    return out, new_cache


def block_fwd(p: dict, x, cfg: ModelConfig, kind: str, *, positions,
              mrope_positions=None, window: int = 0, causal: bool = True,
              cache=None, layer=None, enc_memory=None, pctx: ParallelCtx,
              ) -> Tuple[jax.Array, Any, jax.Array]:
    """Returns (x, new_cache, aux). With ``layer`` given (a decode step),
    ``cache`` is the whole stack, every leaf with a leading layer axis,
    and the block reads and writes its layer of it in place."""
    aux = jnp.zeros((), jnp.float32)
    self_cache = cache["self"] if (kind == "cross" and cache is not None) else cache
    if kind == "ssm":
        h = layers.rms_norm(x, p["ln"], cfg.norm_eps)
        if cache is None:
            y, _ = ssm.mamba2_block(p["mamba"], h, cfg.d_model, cfg.ssm)
            new_cache = None
        elif h.shape[1] == 1:  # decode: the layer's state, rewritten whole
            y, state = ssm.mamba2_decode_step(
                p["mamba"], h[:, 0],
                jax.tree_util.tree_map(lambda c: c[layer], cache),
                cfg.d_model, cfg.ssm)
            new_cache = jax.tree_util.tree_map(
                lambda c, n: c.at[layer].set(n), cache, state)
            y = y[:, None]
        else:  # prefill: run full seq, produce states for decode
            y, ssm_state = ssm.mamba2_block(p["mamba"], h, cfg.d_model, cfg.ssm)
            # conv state: last (width-1) post-projection inputs
            z, xBC, dt_raw, (d_in, nh, ch) = ssm._project(
                p["mamba"], h, cfg.d_model, cfg.ssm)
            conv_state = xBC[:, -(cfg.ssm.conv_width - 1):]
            new_cache = {"conv": conv_state, "ssm": ssm_state}
        return _constrain_act(x + y, pctx), new_cache, aux

    # attention blocks
    out, new_self = attn_block_fwd(
        p, x, cfg, positions=positions, mrope_positions=mrope_positions,
        window=window, causal=causal, cache=self_cache, layer=layer,
        pctx=pctx)
    x = _constrain_act(x + out, pctx)
    new_cache = new_self

    if kind == "cross":
        hd = cfg.resolved_head_dim
        if cache is not None and enc_memory is None:      # decode: cached KV
            ck, cv = cache["cross_k"][layer], cache["cross_v"][layer]
        else:                                             # train / prefill
            ck, cv = attention.project_kv(
                p["cross_attn"], enc_memory, cfg.num_kv_heads, hd)
        out = attention.attn_with_kv(
            p["cross_attn"], layers.rms_norm(x, p["ln_cross"], cfg.norm_eps),
            ck, cv, cfg.num_heads, hd)
        x = _constrain_act(x + out, pctx)
        if layer is not None:      # decode: the read-only cross K/V ride along
            new_cache = dict(cache, self=new_self)
        elif cache is not None:
            new_cache = {"self": new_self, "cross_k": ck, "cross_v": cv}

    h = layers.rms_norm(x, p["ln2"], cfg.norm_eps)
    if kind == "moe":
        # a decode step (``layer`` given) routes dropless, so that no
        # sequence's output depends on the others in its batch
        m = cfg.moe
        if pctx.moe_oracle:
            y, aux = moe.moe_ffn(p["moe"], h, m,
                                 dense_params=p.get("dense_mlp"), oracle=True)
        elif pctx.ep and pctx.mesh is not None:
            B, S, d = h.shape
            y, aux = _ep_moe_call(p["moe"], h.reshape(B * S, d), cfg, pctx,
                                  dropless=layer is not None)
            y = y.reshape(B, S, d)
            if "shared" in p["moe"]:
                y = y + layers.mlp(p["moe"]["shared"], h, "swiglu")
            if "dense_mlp" in p:
                y = y + layers.mlp(p["dense_mlp"], h, "swiglu")
        else:
            y, aux = moe.moe_ffn(p["moe"], h, m,
                                 dense_params=p.get("dense_mlp"), oracle=False,
                                 dropless=layer is not None)
    else:
        y = layers.mlp(p["mlp"], h, cfg.mlp_type)
    return _constrain_act(x + y, pctx), new_cache, aux


# ---------------------------------------------------------------------------
# stacks (scan over layers)
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ModelConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def run_stack(params_stack, x, cfg: ModelConfig, kind: str, *, positions,
              mrope_positions=None, window: int = 0, causal: bool = True,
              caches=None, enc_memory=None, pctx: ParallelCtx):
    """Scan a homogeneous stack. caches: pytree stacked on leading L dim.
    Returns (x, new_caches, aux_sum).

    A decode step (one token, caches given) carries the stacked caches
    through the scan, and each layer writes its own token's entries into
    them in place (``block_fwd`` with ``layer``): no layer's cache is
    sliced out as a scan input or re-stacked as a scan output."""

    def body(carry, inp):
        h = carry
        p_l, cache_l = inp
        h, new_cache, aux = block_fwd(
            p_l, h, cfg, kind, positions=positions,
            mrope_positions=mrope_positions, window=window, causal=causal,
            cache=cache_l, enc_memory=enc_memory, pctx=pctx)
        return h, (new_cache, aux)

    if caches is None:
        def body_nc(carry, p_l):
            h, (_, aux) = body(carry, (p_l, None))
            return h, aux
        x, auxs = jax.lax.scan(_maybe_remat(body_nc, cfg), x, params_stack)
        return x, None, auxs.sum()
    if x.shape[1] == 1:
        def body_decode(carry, inp):
            h, c = carry
            p_l, layer = inp
            h, c, aux = block_fwd(
                p_l, h, cfg, kind, positions=positions,
                mrope_positions=mrope_positions, window=window, causal=causal,
                cache=c, layer=layer, enc_memory=enc_memory, pctx=pctx)
            return (h, c), aux
        n = jax.tree_util.tree_leaves(params_stack)[0].shape[0]
        (x, new_caches), auxs = jax.lax.scan(
            body_decode, (x, caches), (params_stack, jnp.arange(n)))
        return x, new_caches, auxs.sum()
    x, (new_caches, auxs) = jax.lax.scan(
        _maybe_remat(body, cfg), x, (params_stack, caches))
    return x, new_caches, auxs.sum()


# ---------------------------------------------------------------------------
# hybrid (zamba2): scan over superblocks of (period × mamba) + shared attn
# ---------------------------------------------------------------------------

def hybrid_layout(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_super, period, n_tail): num_layers = n_super*period + n_tail."""
    period = cfg.hybrid_attn_period
    n_super = cfg.num_layers // period
    return n_super, period, cfg.num_layers - n_super * period


def init_hybrid(key, cfg: ModelConfig, dtype) -> dict:
    n_super, period, n_tail = hybrid_layout(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    scanned = init_stack(k1, cfg, "ssm", n_super * period, dtype)
    scanned = jax.tree_util.tree_map(
        lambda a: a.reshape(n_super, period, *a.shape[1:]), scanned)
    p = {"blocks": scanned,
         "shared": init_block(k2, cfg, "dense", dtype)}
    if n_tail:
        p["tail"] = init_stack(k3, cfg, "ssm", n_tail, dtype)
    return p


def run_hybrid(params, x, cfg: ModelConfig, *, positions, window: int = 0,
               caches=None, pctx: ParallelCtx):
    """caches = {"ssm": stacked (n_super, period, ...), "attn": stacked
    (n_super, ...), "tail": (n_tail, ...)} or None."""
    n_super, period, n_tail = hybrid_layout(cfg)
    shared = params["shared"]

    def super_body(carry, inp):
        h = carry
        p_sb, cache_sb = inp
        ssm_c = cache_sb["ssm"] if cache_sb is not None else None
        h, new_ssm, aux = run_stack(
            p_sb, h, cfg, "ssm", positions=positions, window=window,
            caches=ssm_c, pctx=dataclasses.replace(pctx),)
        attn_c = cache_sb["attn"] if cache_sb is not None else None
        h, new_attn, aux2 = block_fwd(
            shared, h, cfg, "dense", positions=positions, window=window,
            causal=True, cache=attn_c, pctx=pctx)
        new_cache = (None if cache_sb is None
                     else {"ssm": new_ssm, "attn": new_attn})
        return h, (new_cache, aux + aux2)

    if caches is None:
        def sb_nc(carry, p_sb):
            h, (_, aux) = super_body(carry, (p_sb, None))
            return h, aux
        x, auxs = jax.lax.scan(_maybe_remat(sb_nc, cfg), x, params["blocks"])
        aux_total = auxs.sum()
        new_caches = None
        if n_tail:
            x, _, a = run_stack(params["tail"], x, cfg, "ssm",
                                positions=positions, window=window, pctx=pctx)
            aux_total = aux_total + a
        return x, None, aux_total

    sb_caches = {"ssm": caches["ssm"], "attn": caches["attn"]}
    if x.shape[1] == 1 and n_super:
        # decode: carry the stacks and write superblock j's entries in place
        # (the shared block's K/V row; its Mamba states, rewritten whole)
        def super_decode(carry, inp):
            h, c = carry
            p_sb, j = inp
            ssm_j = jax.tree_util.tree_map(lambda a: a[j], c["ssm"])
            h, ssm_j, aux = run_stack(
                p_sb, h, cfg, "ssm", positions=positions, window=window,
                caches=ssm_j, pctx=pctx)
            h, attn, aux2 = block_fwd(
                shared, h, cfg, "dense", positions=positions, window=window,
                causal=True, cache=c["attn"], layer=j, pctx=pctx)
            ssm_c = jax.tree_util.tree_map(lambda a, n: a.at[j].set(n),
                                           c["ssm"], ssm_j)
            return (h, {"ssm": ssm_c, "attn": attn}), aux + aux2
        (x, new_caches), auxs = jax.lax.scan(
            super_decode, (x, sb_caches),
            (params["blocks"], jnp.arange(n_super)))
    else:
        x, (new_caches, auxs) = jax.lax.scan(
            _maybe_remat(super_body, cfg), x, (params["blocks"], sb_caches))
    aux_total = auxs.sum()
    if n_tail:
        x, new_tail, a = run_stack(params["tail"], x, cfg, "ssm",
                                   positions=positions, window=window,
                                   caches=caches["tail"], pctx=pctx)
        aux_total = aux_total + a
        new_caches["tail"] = new_tail
    return x, new_caches, aux_total
