"""Sharding rules: parameter/batch PartitionSpecs for any assigned arch.

Strategy:
  * TP ("model" axis): attention q/o folded head dims, MLP d_ff, MoE expert
    dim (EP), vocab dim of embed/unembed. Folded dims keep divisibility even
    for 28/56-head archs; vocab dims may shard unevenly (GSPMD pads).
  * FSDP (all non-"model" axes, e.g. ("pod","data")): the OTHER large dim
    of each weight — ZeRO-3-style; XLA all-gathers per layer inside scan.
  * small vectors (norms, biases, scalars) replicate.

Rules are name-based over the flattened param path with shape-aware
fallbacks, and every spec is validated for axis-divisibility (uneven dims
are allowed only on the vocab axis where GSPMD padding is intended).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def fsdp_axes_of(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(n for n in mesh.axis_names if n != "model")


def _dotted(path) -> str:
    """keystr gives \"['blocks']['attn']['w_q']\"; normalize to dotted."""
    return ".".join(
        str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def _axsize(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(np.prod([mesh.shape[a] for a in axes]))


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Maps param-path -> PartitionSpec. ``fsdp=False`` => params replicated
    over data axes (pure TP), used by small packed-sweep models."""
    mesh: Mesh
    fsdp: bool = True
    allow_uneven: Tuple[str, ...] = ()   # vocab is padded; nothing uneven

    def _fsdp(self):
        return fsdp_axes_of(self.mesh) if self.fsdp else None

    def spec_for(self, path: str, shape: Tuple[int, ...]) -> P:
        fs = self._fsdp()
        mdl = "model"
        n = len(shape)

        def ok(dim_size, axes) -> bool:
            return dim_size % _axsize(self.mesh, axes) == 0

        def guarded(*spec):
            """Drop axis assignments that do not divide; vocab-ish dims are
            allowed to stay uneven (GSPMD pads)."""
            out = []
            for dim, axes in enumerate(spec):
                if axes is None:
                    out.append(None)
                    continue
                if ok(shape[dim], axes):
                    out.append(axes)
                elif any(k in path for k in self.allow_uneven):
                    out.append(axes)      # intentional uneven shard
                else:
                    out.append(None)
            return P(*out)

        # ---- embeddings / head ----
        # vocab over model ONLY: putting d on the data axis (FSDP) collides
        # with the batch's data sharding in the logits contraction and made
        # GSPMD materialize full-V (B,S,V) fp32 tensors (26 GB/dev measured
        # on stablelm train). Embeddings are ~2% of params; TP-only is fine.
        if path.endswith("embed"):                       # (V, d)
            return guarded(mdl, None)
        if path.endswith("unembed"):                     # (d, V)
            return guarded(None, mdl)

        # ---- scanned stacks have a leading layer dim; strip it ----
        lead: Tuple = ()
        core = shape
        m = re.search(r"(blocks|encoder|tail|hybrid)", path)
        if m and n >= 3:
            # layer-stacked: 1 leading dim; a hybrid run's plain Mamba
            # layers 2 (units, layers); the shared blocks none
            n_lead = 1
            if "hybrid" in path:
                n_lead = (2 if ".plain." in path
                          else 0 if ".shared." in path else 1)
            lead = (None,) * n_lead
            core = shape[n_lead:]

        def lp(*spec):
            return guarded(*(lead + spec))

        # ---- MoE experts: (E, d, f) / (E, f, d): EP over model ----
        if "w_gate" in path or "w_up" in path:
            if len(core) == 3:                           # moe experts
                return lp(mdl, None, fs)
            return lp(fs, mdl)                           # dense swiglu (d,f)
        if "w_down" in path:
            if len(core) == 3:
                return lp(mdl, fs, None)
            return lp(mdl, fs)                           # dense (f,d)
        if "router" in path:
            return lp(fs, None)

        # ---- attention ----
        if re.search(r"w_[qkv]$", path):                 # (d, H*hd)
            return lp(fs, mdl)
        if path.endswith("w_o"):                         # (H*hd, d)
            return lp(mdl, fs)

        # ---- mamba ----
        if path.endswith("w_in"):                        # (d, d_proj)
            return lp(fs, mdl)
        if path.endswith("w_out"):                       # (d_in, d)
            return lp(mdl, fs)
        if "conv_w" in path:                             # (width, ch)
            return lp(None, mdl)

        # ---- fallback: replicate small, shard biggest dim of big ----
        if len(core) >= 2 and min(core) >= 8:
            big = int(np.argmax(core))
            spec: list = [None] * len(core)
            spec[big] = mdl
            return lp(*spec)
        return P(*((None,) * n))

    def tree(self, params: Any) -> Any:
        """PartitionSpec pytree matching params."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        specs = []
        for path, leaf in flat:
            name = _dotted(path)
            specs.append(self.spec_for(name, leaf.shape))
        return jax.tree_util.tree_unflatten(treedef, specs)

    def shardings(self, params: Any) -> Any:
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), self.tree(params),
            is_leaf=lambda x: isinstance(x, P))


def param_shardings(mesh: Mesh, params: Any, fsdp: bool = True) -> Any:
    return ShardingRules(mesh, fsdp=fsdp).shardings(params)


def batch_shardings(mesh: Mesh, batch: Any, global_batch: int) -> Any:
    """Shard whichever dim equals global_batch over the data axes; shard KV
    head dims of caches over "model" when divisible."""
    dp = fsdp_axes_of(mesh)
    dp_size = _axsize(mesh, dp)
    mdl_size = mesh.shape["model"]

    def spec(path, leaf):
        name = _dotted(path)
        shape = leaf.shape
        out = [None] * len(shape)
        for i, s in enumerate(shape):
            if s == global_batch and s % dp_size == 0:
                out[i] = dp
                break
        # cache KV heads over model: (..., Smax, Hkv, hd)
        if re.search(r"\bk\b|\bv\b|cross_k|cross_v", name) and len(shape) >= 4:
            if shape[-2] % mdl_size == 0:
                out[-2] = "model"
        # ssm decode state (..., nh, hd, N)
        if "ssm" in name and len(shape) >= 3 and shape[-3] % mdl_size == 0:
            out[-3] = "model"
        return NamedSharding(mesh, P(*out))

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat])
