"""Public model API: build any assigned architecture from its config.

``Model`` wraps init / loss / train-shape forward / prefill / decode_step /
input_specs behind one interface so the launcher, dry-run, triples packing
and tests treat all ten architectures uniformly. It also owns the decode
cache's format, which a server pools at batch = lanes.

Batch layouts (all int32 unless noted):
  train   LM      {"tokens": (B,S), "labels": (B,S)}
          vlm     {"embeds": (B,S,d) compute_dtype, "mrope_pos": (3,B,S),
                   "labels": (B,S)}
          encdec  {"enc_embeds": (B,Se,d) compute_dtype, "tokens": (B,S),
                   "labels": (B,S)}
  prefill         same minus labels
  decode  LM/moe  {"tokens": (B,1), "pos": (B,)}
          vlm     + {"mrope_pos": (3,B,1)}
          encdec  {"tokens": (B,1), "pos": (B,)} (cross-KV cached)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ShapeSpec
from repro.kernels.decode_attention import position_block
from repro.models import attention, layers, ssm, transformer
from repro.models.transformer import ParallelCtx

#: the leaves of a decode cache that hold recurrent (SSM) state
STATE_LEAVES = ("conv", "ssm")


def _dt(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


class Model:
    def __init__(self, cfg: ModelConfig, pctx: Optional[ParallelCtx] = None,
                 window: Optional[int] = None):
        self.cfg = cfg
        self.pctx = pctx or ParallelCtx()
        # sliding window override (e.g. zamba2 long_500k uses 4096)
        self.window = cfg.sliding_window if window is None else window
        self.pdt = _dt(cfg.param_dtype)
        self.cdt = _dt(cfg.compute_dtype)

    # ------------------------------------------------------------------ init
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        ks = jax.random.split(key, 6)
        V = cfg.padded_vocab     # padded for TP divisibility (MaxText-style)
        p: Dict[str, Any] = {
            "embed": layers.embed_init(ks[0], V, cfg.d_model, self.pdt),
            "final_ln": jnp.ones((cfg.d_model,), self.pdt),
        }
        if not cfg.tie_embeddings:
            p["unembed"] = layers.dense_init(
                ks[1], cfg.d_model, V, self.pdt)
        fam = cfg.family
        if fam in ("dense", "vlm", "audio"):
            p["blocks"] = transformer.init_stack(
                ks[2], cfg, "dense", cfg.num_layers, self.pdt)
        elif fam == "moe":
            p["blocks"] = transformer.init_stack(
                ks[2], cfg, "moe", cfg.num_layers, self.pdt)
        elif fam == "ssm":
            p["blocks"] = transformer.init_stack(
                ks[2], cfg, "ssm", cfg.num_layers, self.pdt)
        elif fam == "hybrid":
            p["hybrid"] = transformer.init_hybrid(ks[2], cfg, self.pdt)
        elif fam == "encdec":
            p["encoder"] = transformer.init_stack(
                ks[2], cfg, "dense", cfg.num_encoder_layers, self.pdt)
            p["enc_ln"] = jnp.ones((cfg.d_model,), self.pdt)
            p["blocks"] = transformer.init_stack(
                ks[3], cfg, "cross", cfg.num_layers, self.pdt)
        else:
            raise ValueError(fam)
        return p

    # ------------------------------------------------------------- backbone
    def _kind(self) -> str:
        return {"dense": "dense", "vlm": "dense", "audio": "dense",
                "moe": "moe", "ssm": "ssm", "encdec": "cross"}[self.cfg.family]

    def _backbone(self, params, h, positions, *, mrope_positions=None,
                  caches=None, enc_memory=None):
        cfg = self.cfg
        if cfg.family == "hybrid":
            return transformer.run_hybrid(
                params["hybrid"], h, cfg, positions=positions,
                window=self.window, caches=caches, pctx=self.pctx)
        return transformer.run_stack(
            params["blocks"], h, cfg, self._kind(), positions=positions,
            mrope_positions=mrope_positions, window=self.window, causal=True,
            caches=caches, enc_memory=enc_memory, pctx=self.pctx)

    def _encode(self, params, enc_embeds):
        """Bidirectional encoder over precomputed frame embeddings."""
        cfg = self.cfg
        B, Se, _ = enc_embeds.shape
        pos = jnp.broadcast_to(jnp.arange(Se), (B, Se))
        h, _, _ = transformer.run_stack(
            params["encoder"], enc_embeds.astype(self.cdt), cfg, "dense",
            positions=pos, causal=False, pctx=self.pctx)
        return layers.rms_norm(h, params["enc_ln"], cfg.norm_eps)

    def _embed_in(self, params, batch) -> Tuple[jax.Array, jax.Array, Any]:
        """Returns (h, positions, mrope_positions)."""
        cfg = self.cfg
        if "embeds" in batch:  # vlm stub frontend
            h = batch["embeds"].astype(self.cdt)
            B, S, _ = h.shape
        else:
            tok = batch["tokens"]
            B, S = tok.shape
            h = params["embed"][tok].astype(self.cdt)
        if "pos" in batch:
            positions = batch["pos"][:, None] + jnp.arange(S)[None, :]
        else:
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        return h, positions, batch.get("mrope_pos")

    def _head(self, params, h) -> jax.Array:
        h = layers.rms_norm(h, params["final_ln"], self.cfg.norm_eps)
        w = (params["embed"].T if self.cfg.tie_embeddings
             else params["unembed"]).astype(self.cdt)
        mesh = self.pctx.mesh
        if mesh is not None:
            # deterministic TP head: GSPMD's dot partitioner materialized
            # full-vocab (B,S,V) fp32 tensors (26 GB/dev on stablelm train)
            # for the jvp/transpose of this dot no matter the constraints;
            # a shard_map leaves it no choice. bwd: dW stays local,
            # dh gets the automatic psum over "model".
            from jax.sharding import PartitionSpec as P
            dp = self.pctx.batch_axes()
            dp_size = int(np.prod([mesh.shape[a] for a in dp]))
            batch_spec = dp if h.shape[0] % dp_size == 0 else None
            fn = jax.shard_map(
                lambda hl, wl: hl @ wl, mesh=mesh,
                in_specs=(P(batch_spec, None, None), P(None, "model")),
                out_specs=P(batch_spec, None, "model"), check_vma=False)
            return fn(h, w).astype(jnp.float32)
        return (h @ w).astype(jnp.float32)

    # ----------------------------------------------------------------- loss
    def logits(self, params, batch) -> Tuple[jax.Array, jax.Array]:
        """Full-sequence forward with no cache: (logits (B,S,V) fp32,
        router aux loss). The reference served tokens are checked
        against."""
        cfg = self.cfg
        h, positions, mrope = self._embed_in(params, batch)
        enc_memory = None
        if cfg.is_encdec:
            enc_memory = self._encode(params, batch["enc_embeds"])
        h, _, aux = self._backbone(params, h, positions,
                                   mrope_positions=mrope,
                                   enc_memory=enc_memory)
        return self._head(params, h), aux

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        logits, aux = self.logits(params, batch)
        ce = layers.cross_entropy_loss(logits, batch["labels"])
        coef = cfg.moe.router_aux_coef if cfg.moe else 0.0
        total = ce + coef * aux
        return total, {"loss": total, "ce": ce, "aux": aux}

    # ------------------------------------------------------------- serving
    # The decode pool's format is the model's: ``make_cache`` builds it,
    # and the methods below answer what a server needs to know of it (a
    # leaf's lane axis, writing a lane, its bytes, the attention's reads).

    def kv_len(self, max_len: int) -> int:
        """Positions in each attention layer's K/V cache: ``max_len``, or
        the window where that is shorter; 0 for a model with no
        attention."""
        if self.cfg.is_attention_free:
            return 0
        return min(max_len, self.window) if self.window else max_len

    def make_cache(self, batch_size: int, max_len: int) -> Any:
        """Decode cache pytree, stacked per layer (layer-major: K/V
        ``(L, B, kv_len, Hkv*D)``, the layout the layer scan carries). A
        hybrid's holds each Mamba layer's recurrent state (rewritten whole
        by every step) beside the shared blocks' K/V (a row a step)."""
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        attn_len = self.kv_len(max_len)

        def kv_stack(n):
            one = lambda: attention.init_kv_cache(
                batch_size, attn_len, cfg.num_kv_heads, hd, self.cdt)
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (n, *x.shape)).copy(), one())

        def ssm_stack(shape_prefix):
            one = ssm.init_decode_state(batch_size, cfg.d_model, cfg.ssm, self.cdt)
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (*shape_prefix, *x.shape)).copy(), one)

        fam = cfg.family
        if fam in ("dense", "vlm", "audio", "moe"):
            return kv_stack(cfg.num_layers)
        if fam == "ssm":
            return ssm_stack((cfg.num_layers,))
        if fam == "hybrid":
            runs, n_tail = transformer.hybrid_layout(cfg)

            def segment(count, n_plain):
                c = {"layer": ssm_stack((count,)), "kv": kv_stack(count)}
                if n_plain:
                    c["plain"] = ssm_stack((count, n_plain))
                return c
            c = {"runs": [[segment(count, n) for n in plain]
                          for count, plain in runs]}
            if n_tail:
                c["tail"] = ssm_stack((n_tail,))
            return c
        if fam == "encdec":
            self_c = kv_stack(cfg.num_layers)
            L, B = cfg.num_layers, batch_size
            return {
                "self": self_c,
                "cross_k": jnp.zeros((L, B, max_len, cfg.num_kv_heads, hd), self.cdt),
                "cross_v": jnp.zeros((L, B, max_len, cfg.num_kv_heads, hd), self.cdt),
            }
        raise ValueError(fam)

    def lane_axes(self) -> Any:
        """The batch axis of each leaf of the decode cache (the axis whose
        size follows the batch), as a pytree of ints."""
        one, two = (jax.eval_shape(lambda b=b: self.make_cache(b, 1))
                    for b in (1, 2))
        return jax.tree_util.tree_map(
            lambda a, b: next(i for i, (m, n) in enumerate(zip(a.shape, b.shape))
                              if m != n), one, two)

    def attach_lane(self, pool, lane_cache, lane):
        """``pool`` with the batch-1 ``lane_cache`` written into lane
        ``lane`` (traced) of every leaf, on its batch axis."""
        return jax.tree_util.tree_map(
            lambda p, x, ax: jax.lax.dynamic_update_slice_in_dim(
                p, x.astype(p.dtype), lane, axis=ax),
            pool, lane_cache, self.lane_axes())

    def pool_bytes(self, batch_size: int, max_len: int) -> Tuple[int, int]:
        """(all bytes, recurrent-state bytes) of the decode cache at
        ``batch_size``: the state is the Mamba layers' leaves, rewritten
        whole by every step, beside K/V written a row a step."""
        shapes = jax.eval_shape(lambda: self.make_cache(batch_size, max_len))
        total = state = 0
        for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]:
            n = x.size * x.dtype.itemsize
            total += n
            if getattr(path[-1], "key", None) in STATE_LEAVES:
                state += n
        return total, state

    def decode_reads(self, positions: np.ndarray,
                     max_len: int) -> Dict[str, int]:
        """What one decode step's attention reads for lanes at
        ``positions`` (host ints) in caches made for ``max_len``:
        ``kv_positions``, the sum of the positions each lane attends;
        ``kv_blocks``, the decode kernel's position blocks over those
        lanes' caches; ``kv_blocks_read``, the blocks up to the one
        holding each lane's position, which are all the kernel reads.
        All 0 for a model with no attention."""
        n = self.kv_len(max_len)
        if not n:
            return {"kv_positions": 0, "kv_blocks": 0, "kv_blocks_read": 0}
        bs = position_block(n, self.cfg.num_kv_heads,
                            self.cfg.resolved_head_dim)
        seen = np.minimum(positions + 1, n)
        return {"kv_positions": int(seen.sum()),
                "kv_blocks": len(positions) * (n // bs),
                "kv_blocks_read": int((-(-seen // bs)).sum())}

    def prefill(self, params, batch, max_len: int):
        """Full-sequence forward filling a fresh cache. Returns
        (last_logits (B,V), cache)."""
        cfg = self.cfg
        h, positions, mrope = self._embed_in(params, batch)
        B = h.shape[0]
        cache = self.make_cache(B, max_len)
        enc_memory = None
        if cfg.is_encdec:
            enc_memory = self._encode(params, batch["enc_embeds"])
        h, cache, _ = self._backbone(params, h, positions,
                                     mrope_positions=mrope, caches=cache,
                                     enc_memory=enc_memory)
        logits = self._head(params, h[:, -1:])
        return logits[:, 0], cache

    def decode_step(self, params, batch, cache):
        """One-token serve step. Returns (logits (B,V), new_cache)."""
        cfg = self.cfg
        tok = batch["tokens"]                             # (B,1)
        h = params["embed"][tok].astype(self.cdt)
        positions = batch["pos"][:, None]                 # (B,1)
        mrope = batch.get("mrope_pos")
        h, cache, _ = self._backbone(params, h, positions,
                                     mrope_positions=mrope, caches=cache)
        logits = self._head(params, h)
        return logits[:, 0], cache

    # --------------------------------------------------------- input specs
    def input_specs(self, shape: ShapeSpec) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for the batch of a given shape cell.
        For decode shapes, also includes the cache specs under "_cache"."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        d = cfg.d_model
        cdt = self.cdt

        def lm_train():
            b = {"tokens": sds((B, S), i32), "labels": sds((B, S), i32)}
            if cfg.family == "vlm":
                b = {"embeds": sds((B, S, d), cdt),
                     "mrope_pos": sds((3, B, S), i32),
                     "labels": sds((B, S), i32)}
            if cfg.is_encdec:
                b = {"enc_embeds": sds((B, S, d), cdt),
                     "tokens": sds((B, S), i32), "labels": sds((B, S), i32)}
            return b

        if shape.kind == "train":
            return lm_train()
        if shape.kind == "prefill":
            b = lm_train()
            b.pop("labels")
            return b
        # decode: one token + pre-filled cache
        b = {"tokens": sds((B, 1), i32), "pos": sds((B,), i32)}
        if cfg.family == "vlm":
            b["mrope_pos"] = sds((3, B, 1), i32)
        cache_spec = jax.eval_shape(lambda: self.make_cache(B, S))
        b["_cache"] = cache_spec
        return b


def build_model(cfg: ModelConfig, pctx: Optional[ParallelCtx] = None,
                window: Optional[int] = None) -> Model:
    return Model(cfg, pctx=pctx, window=window)
