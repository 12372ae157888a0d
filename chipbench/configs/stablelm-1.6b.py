"""Plain reference of stablelm-1.6b as the program runs it, and the work
of its prefill and decode.

The block, pre-norm with a sequential residual:

    x = x + attn(rmsnorm(x) * ln1)       q, k, v = h @ w_q, w_k, w_v (no bias)
                                          rotary over the whole head,
                                          causal softmax(q k^T / sqrt(64)) v,
                                          @ w_o
    x = x + (silu(h @ w_gate) * (h @ w_up)) @ w_down,   h = rmsnorm(x) * ln2

and the head is ``rmsnorm(x) * final_ln @ unembed``. Computed here over
the whole sequence at once, in float32 at the highest matmul precision,
with no cache and no kernel. The departures of this block from the
published StableLM-2 block are listed in the configuration file.
"""
from __future__ import annotations

import json
import math
from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dims(m: dict) -> dict:
    hd = m["head_dim"] or m["d_model"] // m["num_heads"]
    return dict(d=m["d_model"], h=m["num_heads"], kv=m["num_kv_heads"],
                hd=hd, ff=m["d_ff"], layers=m["num_layers"],
                vocab=m["vocab_size"],
                vocab_rows=-(-m["vocab_size"] // m["vocab_pad_to"])
                * m["vocab_pad_to"])


def _dense(key, n_in, n_out):
    return jax.random.normal(key, (n_in, n_out), F32) * (1.0 / math.sqrt(n_in))


def init(key, m: dict) -> Dict:
    """fp32 weights in the program's layout, drawn exactly as the
    program's init draws them from ``key``."""
    z = dims(m)
    ks = jax.random.split(key, 6)

    def layer(k):
        ka, km = jax.random.split(k, 4)[:2]
        a = jax.random.split(ka, 4)
        f = jax.random.split(km, 3)
        return {"ln1": jnp.ones((z["d"],), F32),
                "ln2": jnp.ones((z["d"],), F32),
                "attn": {"w_q": _dense(a[0], z["d"], z["h"] * z["hd"]),
                         "w_k": _dense(a[1], z["d"], z["kv"] * z["hd"]),
                         "w_v": _dense(a[2], z["d"], z["kv"] * z["hd"]),
                         "w_o": _dense(a[3], z["h"] * z["hd"], z["d"])},
                "mlp": {"w_gate": _dense(f[0], z["d"], z["ff"]),
                        "w_up": _dense(f[1], z["d"], z["ff"]),
                        "w_down": _dense(f[2], z["ff"], z["d"])}}

    return {"embed": jax.random.normal(ks[0], (z["vocab_rows"], z["d"]), F32)
            * 0.02,
            "final_ln": jnp.ones((z["d"],), F32),
            "unembed": _dense(ks[1], z["d"], z["vocab_rows"]),
            "blocks": jax.vmap(layer)(jax.random.split(ks[2], z["layers"]))}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (B, S, H, D), positions 0..S-1; pairs are (i, i + D/2)."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _fp8(x, axes):
    """Scaled float8 (e4m3) values, one scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(spec, a, b, control):
    """``einsum(spec, a, b)``; for the control both operands are first
    rounded to float8, the precision below the bfloat16 the configuration
    computes in (scaled per row of ``a`` and per matrix of ``b``)."""
    if control:
        a = _fp8(a, -1)
        b = _fp8(b, tuple(range(b.ndim))[-2:])
    return jnp.einsum(spec, a, b)


def logits(params, tokens, m: dict, control: bool = False):
    """(B, S) ids -> (B, S, vocab rows) float32 logits, causal."""
    z = dims(m)
    b, s = tokens.shape
    x = params["embed"][tokens]
    causal = jnp.tril(jnp.ones((s, s), bool))
    mm = lambda a, w: _mm("bsd,de->bse", a, w, control)

    def body(x, p):
        h = _rms(x, p["ln1"], m["norm_eps"])
        a = p["attn"]
        q = _rope(mm(h, a["w_q"]).reshape(b, s, z["h"], z["hd"]),
                  m["rope_theta"])
        k = _rope(mm(h, a["w_k"]).reshape(b, s, z["kv"], z["hd"]),
                  m["rope_theta"])
        v = mm(h, a["w_v"]).reshape(b, s, z["kv"], z["hd"])
        rep = z["h"] // z["kv"]
        k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
        sc = _mm("bihd,bjhd->bhij", q, k, control) / math.sqrt(z["hd"])
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        o = _mm("bhij,bjhd->bihd", pr, v, control).reshape(b, s, -1)
        x = x + mm(o, a["w_o"])
        h = _rms(x, p["ln2"], m["norm_eps"])
        f = p["mlp"]
        x = x + mm(jax.nn.silu(mm(h, f["w_gate"])) * mm(h, f["w_up"]),
                   f["w_down"])
        return x, None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return mm(_rms(x, params["final_ln"], m["norm_eps"]), params["unembed"])


_GAP = {}


def served_gap(params, tokens, served, m: dict, variant: str):
    """Per position of ``tokens`` (1, S): how far below the reference's
    best logit lies the token chosen there: the served token
    (``reference``), or the control's first choice (``control``)."""
    key = (json.dumps(m, sort_keys=True), variant)
    if key not in _GAP:
        def gap(params, tokens, served):
            ref = logits(params, tokens, m)[0]
            pick = served if variant == "reference" else jnp.argmax(
                logits(params, tokens, m, control=True)[0], -1)
            return jnp.max(ref, -1) - jnp.take_along_axis(
                ref, pick[:, None], -1)[:, 0]
        _GAP[key] = jax.jit(gap)
    with jax.default_matmul_precision("highest"):
        return _GAP[key](params, tokens, served)


# ------------------------------------------------------------------- work
def _matmul_macs(m: dict) -> float:
    """Multiply-adds of one token through the layers' projections."""
    z = dims(m)
    attn = z["d"] * (z["h"] + 2 * z["kv"]) * z["hd"] + z["h"] * z["hd"] * z["d"]
    return z["layers"] * (attn + 3 * z["d"] * z["ff"])


def prefill_flops(m: dict, s: int) -> float:
    """FLOPs of one prefill of ``s`` tokens: every token through the
    layers, causal attention over the pairs (i >= j), and the head at the
    last position only (over the published vocabulary)."""
    z = dims(m)
    attn = 4.0 * z["layers"] * z["h"] * z["hd"] * s * (s + 1) / 2
    return 2.0 * _matmul_macs(m) * s + attn + 2.0 * z["d"] * z["vocab"]


def decode_flops(m: dict, ctx: int) -> float:
    """FLOPs of one decoded token that attends over ``ctx`` positions."""
    z = dims(m)
    attn = 4.0 * z["layers"] * z["h"] * z["hd"] * ctx
    return 2.0 * (_matmul_macs(m) + z["d"] * z["vocab"]) + attn


def weight_bytes(m: dict) -> float:
    """Bytes of the weights as served (float32)."""
    z = dims(m)
    return 4.0 * (_matmul_macs(m) + 2 * z["layers"] * z["d"] + z["d"]
                  + 2 * z["vocab_rows"] * z["d"])


def prefill_bytes(m: dict, s: int) -> float:
    """Least HBM traffic of one prefill: the weights read once (the
    embedding table only for the ``s`` rows used) and the bf16 K and V
    written to the cache."""
    z = dims(m)
    table = 4.0 * z["vocab_rows"] * z["d"]
    return weight_bytes(m) - table + 4.0 * s * z["d"] \
        + 2.0 * 2 * z["layers"] * s * z["kv"] * z["hd"]


def decode_bytes(m: dict, ctx: int, lanes: int) -> float:
    """Least HBM traffic of one decode step over ``lanes`` requests that
    each attend over ``ctx`` positions: the weights once (one embedding
    row per lane) and each lane's bf16 K and V."""
    z = dims(m)
    table = 4.0 * z["vocab_rows"] * z["d"]
    return weight_bytes(m) - table + 4.0 * lanes * z["d"] \
        + lanes * 2.0 * 2 * z["layers"] * ctx * z["kv"] * z["hd"]
