"""Transformer stacks: decoder-only LM, encoder-decoder, and the zamba2
hybrid (a Mamba2 backbone into which shared attention blocks feed at the
configuration's ``hybrid_layer_ids``).

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
    """
    mesh: Any = None
    ep: bool = False
    moe_oracle: bool = False
    attn_impl: Optional[str] = None

    def batch_axes(self):
        if self.mesh is None:
            return None
        return tuple(n for n in self.mesh.axis_names if n != "model")


def _constrain_act(x, pctx: ParallelCtx):
    """Activations (B, S, d): batch sharded over data axes, rest replicated."""
    if pctx.mesh is None:
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
                                capacity=xt_l.shape[0] if dropless else None)
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
        kv_cache=cache, layer=layer, impl=pctx.attn_impl)
    return out, new_cache


def block_fwd(p: dict, x, cfg: ModelConfig, kind: str, *, positions,
              mrope_positions=None, window: int = 0, causal: bool = True,
              cache=None, layer=None, enc_memory=None, pctx: ParallelCtx,
              inject=None) -> Tuple[jax.Array, Any, jax.Array]:
    """Returns (x, new_cache, aux). With ``layer`` given (a decode step),
    ``cache`` is the whole stack, every leaf with a leading layer axis,
    and the block reads and writes its layer of it in place. ``inject``
    (a hybrid layer's shared-block output) is added to an SSM block's
    input, not to its residual: ``x + mamba(norm(x + inject))``."""
    aux = jnp.zeros((), jnp.float32)
    self_cache = cache["self"] if (kind == "cross" and cache is not None) else cache
    if kind == "ssm":
        h = layers.rms_norm(x if inject is None else x + inject, p["ln"],
                            cfg.norm_eps)
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
# hybrid (zamba2): shared blocks feeding Mamba layers at hybrid_layer_ids
# ---------------------------------------------------------------------------

def hybrid_layout(cfg: ModelConfig) -> Tuple[tuple, int]:
    """(runs, n_tail). Each hybrid layer id closes a segment: the plain
    Mamba layers since the previous one, then the hybrid layer, where
    application i (in order) runs shared block i mod ``num_mem_blocks``.
    A unit is ``num_mem_blocks`` consecutive segments (blocks 0, 1, ...;
    the last unit may be shorter); consecutive units with the same plain
    counts form a run ``(count, plain)``, scanned over its units.
    ``n_tail`` plain Mamba layers follow the last hybrid layer.

    Zamba2-7B's ids (6, 11, 17, ..., 77) give the runs (1, (6, 4)),
    (5, (5, 5)), (1, (5,)) and a tail of 3."""
    ids = cfg.hybrid_layer_ids
    m = cfg.num_mem_blocks
    plain = [i - p - 1 for p, i in zip((-1,) + ids[:-1], ids)]
    runs: list = []
    for k in range(0, len(plain), m):
        unit = tuple(plain[k:k + m])
        if runs and runs[-1][1] == unit:
            runs[-1] = (runs[-1][0] + 1, unit)
        else:
            runs.append((1, unit))
    return tuple(runs), cfg.num_layers - 1 - ids[-1]


def init_shared_block(key, cfg: ModelConfig, dtype) -> dict:
    """A shared block: norm over concat(h, embedding), attention from
    that 2d-wide input back to d, norm, gated MLP."""
    d = cfg.d_model
    ks = jax.random.split(key, 2)
    return {"ln_in": jnp.ones((2 * d,), dtype),
            "attn": attention.init_attention(
                ks[0], 2 * d, cfg.num_heads, cfg.num_kv_heads,
                cfg.resolved_head_dim, dtype, d_out=d),
            "ln_ff": jnp.ones((d,), dtype),
            "mlp": layers.init_mlp(ks[1], d, cfg.d_ff, cfg.mlp_type, dtype)}


def _init_segment(key, cfg: ModelConfig, count: int, n_plain: int, dtype):
    """One segment position of a run, stacked over the run's ``count``
    units: its plain Mamba layers (count, n_plain, ...), the hybrid
    layer's Mamba block, the application's adapter and linear."""
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    seg = {"layer": init_stack(ks[0], cfg, "ssm", count, dtype),
           "adapter": jax.vmap(lambda k: layers.init_adapter(
               k, d, cfg.d_ff, cfg.adapter_rank, dtype))(
                   jax.random.split(ks[1], count)),
           "linear": jax.vmap(lambda k: layers.dense_init(k, d, d, dtype))(
               jax.random.split(ks[2], count))}
    if n_plain:
        seg["plain"] = jax.tree_util.tree_map(
            lambda a: a.reshape(count, n_plain, *a.shape[1:]),
            init_stack(ks[3], cfg, "ssm", count * n_plain, dtype))
    return seg


def init_hybrid(key, cfg: ModelConfig, dtype) -> dict:
    """{"shared": [block per num_mem_blocks], "runs": [[segment per
    position of the run's unit]], "tail": plain Mamba stack}."""
    runs, n_tail = hybrid_layout(cfg)
    k_shared, k_runs, k_tail = jax.random.split(key, 3)
    p = {"shared": [init_shared_block(k, cfg, dtype) for k in
                    jax.random.split(k_shared, cfg.num_mem_blocks)],
         "runs": [[_init_segment(jax.random.fold_in(
                                     jax.random.fold_in(k_runs, r), s),
                                 cfg, count, n, dtype)
                   for s, n in enumerate(plain)]
                  for r, (count, plain) in enumerate(runs)]}
    if n_tail:
        p["tail"] = init_stack(k_tail, cfg, "ssm", n_tail, dtype)
    return p


def shared_fwd(blk: dict, adapter: dict, x, emb, cfg: ModelConfig, *,
               positions, window: int, cache, layer, pctx: ParallelCtx):
    """One application of a shared block: ``mlp(norm(attn(norm(concat(x,
    emb)))))`` with the application's adapter on the MLP; no residual.
    The attention scales its scores by (head_dim / 2) ** -0.5, its
    head_dim being that of a 2d-wide input. Returns (out, new K/V)."""
    hd = cfg.resolved_head_dim
    u = layers.rms_norm(jnp.concatenate([x, emb], -1), blk["ln_in"],
                        cfg.norm_eps)
    a, new_cache = attention.attention_block(
        blk["attn"], u, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=hd, positions=positions,
        rope_theta=cfg.rope_theta, causal=True, window=window,
        kv_cache=cache, layer=layer, impl=pctx.attn_impl,
        scale=(hd / 2) ** -0.5)
    y = layers.mlp(blk["mlp"], layers.rms_norm(a, blk["ln_ff"], cfg.norm_eps),
                   cfg.mlp_type, adapter=adapter)
    return y, new_cache


def _segment_fwd(seg: dict, blk: dict, h, emb, cfg: ModelConfig, *,
                 positions, window: int, cache, unit, pctx: ParallelCtx):
    """The plain Mamba layers of one segment, then its hybrid layer.
    ``cache``: None (training), the unit's own (prefill: filled and
    returned), or the run's whole stack with ``unit`` the index of the
    unit (decode: its entries written in place)."""
    new = {}
    if "plain" in seg and unit is not None:
        # decode: layer by layer, each reading and writing its own state
        # in the stack; a scan over the layers would copy their stacked
        # weights and states into the loop's layout every step
        c = cache["plain"]
        for i in range(jax.tree_util.tree_leaves(seg["plain"])[0].shape[0]):
            h, c, _ = block_fwd(
                jax.tree_util.tree_map(lambda a: a[i], seg["plain"]), h, cfg,
                "ssm", positions=positions, window=window, cache=c,
                layer=(unit, i), pctx=pctx)
        new["plain"] = c
    elif "plain" in seg:
        h, c, _ = run_stack(seg["plain"], h, cfg, "ssm", positions=positions,
                            window=window,
                            caches=None if cache is None else cache["plain"],
                            pctx=pctx)
        if cache is not None:
            new["plain"] = c
    t, kv = shared_fwd(blk, seg["adapter"], h, emb, cfg, positions=positions,
                       window=window,
                       cache=None if cache is None else cache["kv"],
                       layer=unit, pctx=pctx)
    t = t @ seg["linear"].astype(t.dtype)
    h, st, _ = block_fwd(seg["layer"], h, cfg, "ssm", positions=positions,
                         window=window,
                         cache=None if cache is None else cache["layer"],
                         layer=unit, pctx=pctx, inject=t)
    return h, None if cache is None else dict(new, kv=kv, layer=st)


def run_hybrid(params, x, cfg: ModelConfig, *, positions, window: int = 0,
               caches=None, pctx: ParallelCtx):
    """Scan each run of ``hybrid_layout`` over its units, then the tail.
    The embedding ``x`` is carried beside ``h`` into every shared block.
    caches = {"runs": [[{"plain": (count, n_plain, B, ...) states,
    "layer": (count, B, ...) states, "kv": (count, B, Smax, H*hd) K/V}]],
    "tail": (n_tail, B, ...)} or None. A decode step (one token) carries
    each run's caches through its scan and writes unit j's entries in
    place: the K/V rows, and the Mamba states, rewritten whole."""
    runs, n_tail = hybrid_layout(cfg)
    shared = params["shared"]
    emb = x
    kw = dict(positions=positions, window=window, pctx=pctx)
    new_runs = []
    for r, (count, plain) in enumerate(runs):
        p_run = params["runs"][r]
        if caches is None:
            def unit_nc(h, p_unit):
                for s, seg in enumerate(p_unit):
                    h, _ = _segment_fwd(seg, shared[s], h, emb, cfg,
                                        cache=None, unit=None, **kw)
                return h, None
            x, _ = jax.lax.scan(_maybe_remat(unit_nc, cfg), x, p_run)
        elif x.shape[1] == 1:
            def unit_decode(carry, inp):
                h, c = carry
                p_unit, j = inp
                c = list(c)
                for s, seg in enumerate(p_unit):
                    h, c[s] = _segment_fwd(seg, shared[s], h, emb, cfg,
                                           cache=c[s], unit=j, **kw)
                return (h, c), None
            (x, c_run), _ = jax.lax.scan(
                unit_decode, (x, caches["runs"][r]), (p_run, jnp.arange(count)))
            new_runs.append(c_run)
        else:
            def unit_fill(h, inp):
                p_unit, c_unit = inp
                out = []
                for s, seg in enumerate(p_unit):
                    h, c = _segment_fwd(seg, shared[s], h, emb, cfg,
                                        cache=c_unit[s], unit=None, **kw)
                    out.append(c)
                return h, out
            x, c_run = jax.lax.scan(_maybe_remat(unit_fill, cfg), x,
                                    (p_run, caches["runs"][r]))
            new_runs.append(c_run)
    aux = jnp.zeros((), jnp.float32)
    if caches is None:
        if n_tail:
            x, _, _ = run_stack(params["tail"], x, cfg, "ssm", **kw)
        return x, None, aux
    new_caches = {"runs": new_runs}
    if n_tail:
        x, new_caches["tail"], _ = run_stack(
            params["tail"], x, cfg, "ssm", caches=caches["tail"], **kw)
    return x, new_caches, aux
