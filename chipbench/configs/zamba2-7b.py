"""Plain reference of zamba2-7b as the configuration runs it, and the work
of its prefill and decode.

The published Zamba2 block (hf:Zyphra/Zamba2-7B-Instruct, config.json;
the equations of transformers' ``modeling_zamba2``). With ``e`` the token
embedding and ``x`` the running state, every layer is a Mamba-2 layer

    x = x + mamba(rmsnorm(x_in) * ln),    x_in = x + t at a hybrid layer,
                                          x elsewhere (the residual is x)

and at the i-th hybrid layer shared block b = i mod 2 gives

    u = rmsnorm(concat(x, e)) * ln_in                       (2d wide)
    a = attn_b(u) @ w_o     q, k, v = u @ w_q, w_k, w_v; rotary over the
                            whole 224-wide head (pairs i, i + 112);
                            causal softmax(q k^T * (224/2)^-0.5) v
    g, v = h @ w_gate + (h @ A_i) @ Bg_i,  h @ w_up + (h @ A_i) @ Bu_i,
                                          h = rmsnorm(a) * ln_ff
    t = ((gelu(g) * v) @ w_down) @ linear_i     (exact, erf gelu)

mamba(h): [z, xBC, dt] = h @ w_in; xBC = silu(causal depthwise conv(xBC)
+ conv_b) split into x (112 heads of 64), B and C (2 groups of 64, head
i reads group i // 56); dt = softplus(dt + dt_bias), A = -exp(A_log);
the selective scan s_t = exp(dt A) s_{t-1} + dt x_t B_t^T, y_t = s_t C_t
+ D x_t; then rmsnorm over each group of 3584 channels of y * silu(z),
times norm_w, @ w_out. The head is ``rmsnorm(x) * final_ln @ embed^T``.

Computed here over the whole sequence in float32 at the highest matmul
precision, with no cache and no kernel: the scan as its recurrence, one
position at a time; attention as a plain causal softmax, in blocks of
queries so that it fits beside the weights. Nothing of the program is
imported; ``init`` draws weights in the program's layout, which is:

    embed, final_ln; hybrid: shared [block 0, block 1]; runs [[segment]];
    tail

where the hybrid ids cut the layers into segments (plain Mamba layers,
then a hybrid layer), ``num_mem_blocks`` consecutive segments make a unit
(blocks 0, 1, ...), and consecutive units with the same plain counts a
run, each segment's leaves stacked over the run's units (``_layout``).
"""
from __future__ import annotations

import json
import math
from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
#: queries per block of the reference's attention
Q_BLOCK = 512


def dims(m: dict) -> dict:
    s = m["ssm"]
    d_in = s["expand"] * m["d_model"]
    nh = s["num_heads"] or d_in // s["head_dim"]
    gn = s["ngroups"] * s["state_dim"]
    return dict(d=m["d_model"], h=m["num_heads"], kv=m["num_kv_heads"],
                hd=m["head_dim"], ff=m["d_ff"], layers=m["num_layers"],
                vocab=m["vocab_size"],
                vocab_rows=-(-m["vocab_size"] // m["vocab_pad_to"])
                * m["vocab_pad_to"],
                d_in=d_in, nh=nh, p=s["head_dim"], n=s["state_dim"],
                g=s["ngroups"], ch=d_in + 2 * gn, d_proj=2 * d_in + 2 * gn + nh,
                w=s["conv_width"], apps=len(m["hybrid_layer_ids"]),
                blocks=m["num_mem_blocks"], r=m["adapter_rank"])


def _layout(m: dict):
    """(runs, n_tail): runs of (count, plain counts of a unit's segments),
    as the program lays out its parameters and caches."""
    ids = list(m["hybrid_layer_ids"])
    blocks = m["num_mem_blocks"]
    plain = [i - p - 1 for p, i in zip([-1] + ids[:-1], ids)]
    runs = []
    for k in range(0, len(plain), blocks):
        unit = tuple(plain[k:k + blocks])
        if runs and runs[-1][1] == unit:
            runs[-1] = (runs[-1][0] + 1, unit)
        else:
            runs.append((1, unit))
    return runs, m["num_layers"] - 1 - ids[-1]


def _dtype(m, key="param_dtype"):
    return {"float32": F32, "bfloat16": jnp.bfloat16}[m[key]]


def _cache_item(m) -> int:
    """Bytes of a K/V or conv-state element: the compute type."""
    return jnp.dtype(_dtype(m, "compute_dtype")).itemsize


def _dense(key, n_in, n_out, lead=()):
    return jax.random.normal(key, (*lead, n_in, n_out), F32) / math.sqrt(n_in)


def init(key, m: dict) -> Dict:
    """Weights in the program's layout and types (``param_dtype``; A_log,
    D and dt_bias float32), drawn from ``key``."""
    z = dims(m)
    dt = _dtype(m)
    runs, n_tail = _layout(m)
    keys = iter(jax.random.split(key, 4096))

    def mamba(lead):
        u = jax.random.uniform(next(keys), (*lead, z["nh"]), F32)
        dtv = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        return {"ln": jnp.ones((*lead, z["d"]), dt),
                "mamba": {
                    "w_in": _dense(next(keys), z["d"], z["d_proj"], lead).astype(dt),
                    "conv_w": (jax.random.normal(next(keys), (*lead, z["w"], z["ch"]), F32)
                               / math.sqrt(z["w"])).astype(dt),
                    "conv_b": jnp.zeros((*lead, z["ch"]), dt),
                    "dt_bias": dtv + jnp.log(-jnp.expm1(-dtv)),
                    "A_log": jnp.log(jax.random.uniform(
                        next(keys), (*lead, z["nh"]), F32, 1.0, 16.0)),
                    "D": jnp.ones((*lead, z["nh"]), F32),
                    "norm_w": jnp.ones((*lead, z["d_in"]), dt),
                    "w_out": _dense(next(keys), z["d_in"], z["d"], lead).astype(dt)}}

    def block():
        d, hw, kvw = z["d"], z["h"] * z["hd"], z["kv"] * z["hd"]
        return {"ln_in": jnp.ones((2 * d,), dt),
                "attn": {"w_q": _dense(next(keys), 2 * d, hw).astype(dt),
                         "w_k": _dense(next(keys), 2 * d, kvw).astype(dt),
                         "w_v": _dense(next(keys), 2 * d, kvw).astype(dt),
                         "w_o": _dense(next(keys), hw, d).astype(dt)},
                "ln_ff": jnp.ones((d,), dt),
                "mlp": {"w_gate": _dense(next(keys), d, z["ff"]).astype(dt),
                        "w_up": _dense(next(keys), d, z["ff"]).astype(dt),
                        "w_down": _dense(next(keys), z["ff"], d).astype(dt)}}

    def segment(count, n_plain):
        seg = {"layer": mamba((count,)),
               "adapter": {
                   "a": _dense(next(keys), z["d"], z["r"], (count,)).astype(dt),
                   "b_gate": _dense(next(keys), z["r"], z["ff"], (count,)).astype(dt),
                   "b_up": _dense(next(keys), z["r"], z["ff"], (count,)).astype(dt)},
               "linear": _dense(next(keys), z["d"], z["d"], (count,)).astype(dt)}
        if n_plain:
            seg["plain"] = mamba((count, n_plain))
        return seg

    hybrid = {"shared": [block() for _ in range(z["blocks"])],
              "runs": [[segment(count, n) for n in plain]
                       for count, plain in runs]}
    if n_tail:
        hybrid["tail"] = mamba((n_tail,))
    return {"embed": (jax.random.normal(next(keys), (z["vocab_rows"], z["d"]), F32)
                      * 0.02).astype(dt),
            "final_ln": jnp.ones((z["d"],), dt),
            "hybrid": hybrid}


# ----------------------------------------------------------------- forward
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


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


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def _at(tree, *idx):
    return jax.tree_util.tree_map(lambda a: a[idx], tree)


def _rope(x, theta):
    """x (B, S, H, D), positions 0..S-1; pairs are (i, i + D/2)."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _scan(x, dt, A, B, C):
    """The selective scan as its recurrence. x (b,S,nh,P); dt (b,S,nh);
    A (nh,); B, C (b,S,G,N). Returns y (b,S,nh,P)."""
    b, S, nh, P = x.shape
    G, N = B.shape[2:]
    x = x.reshape(b, S, G, nh // G, P)
    dt = dt.reshape(b, S, G, nh // G)
    A = A.reshape(G, nh // G)

    def step(s, inp):
        x_t, dt_t, B_t, C_t = inp
        s = (s * jnp.exp(dt_t * A)[..., None, None]
             + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, None, :])
        return s, jnp.einsum("bghpn,bgn->bghp", s, C_t)

    s0 = jnp.zeros((b, G, nh // G, P, N), F32)
    _, y = jax.lax.scan(step, s0, tuple(jnp.moveaxis(a, 1, 0)
                                        for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1).reshape(b, S, nh, P)


def _mamba(p, x, z, m, control, inject=None):
    mm = lambda a, w: _mm("bsd,de->bse", a, w, control)
    b, S, _ = x.shape
    h = _rms(x if inject is None else x + inject, p["ln"], m["norm_eps"])
    q = p["mamba"]
    proj = mm(h, q["w_in"])
    zg, xBC, dt = jnp.split(proj, [z["d_in"], z["d_in"] + z["ch"]], -1)
    pad = jnp.pad(xBC, ((0, 0), (z["w"] - 1, 0), (0, 0)))
    xBC = jax.nn.silu(sum(pad[:, i:i + S] * q["conv_w"][i]
                          for i in range(z["w"])) + q["conv_b"])
    gn = z["g"] * z["n"]
    xs, B, C = jnp.split(xBC, [z["d_in"], z["d_in"] + gn], -1)
    xs = xs.reshape(b, S, z["nh"], z["p"])
    dt = jax.nn.softplus(dt + q["dt_bias"])
    y = _scan(xs, dt, -jnp.exp(q["A_log"]),
              B.reshape(b, S, z["g"], z["n"]), C.reshape(b, S, z["g"], z["n"]))
    y = (y + q["D"][:, None] * xs).reshape(b, S, z["g"], -1)
    y = y * jax.nn.silu(zg).reshape(y.shape)
    y = _rms(y, 1.0, 1e-5).reshape(b, S, z["d_in"]) * q["norm_w"]
    return x + mm(y, q["w_out"])


def _shared(blk, ad, x, emb, z, m, control):
    mm = lambda a, w: _mm("bsd,de->bse", a, w, control)
    b, S, _ = x.shape
    u = _rms(jnp.concatenate([x, emb], -1), blk["ln_in"], m["norm_eps"])
    at = blk["attn"]
    q = _rope(mm(u, at["w_q"]).reshape(b, S, z["h"], z["hd"]), m["rope_theta"])
    k = _rope(mm(u, at["w_k"]).reshape(b, S, z["kv"], z["hd"]), m["rope_theta"])
    v = mm(u, at["w_v"]).reshape(b, S, z["kv"], z["hd"])
    rep = z["h"] // z["kv"]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    scale = (z["hd"] / 2) ** -0.5
    nq = min(Q_BLOCK, S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * nq, nq, 1)
        sc = _mm("bihd,bjhd->bhij", qb, k, control) * scale
        causal = (i * nq + jnp.arange(nq))[:, None] >= jnp.arange(S)[None]
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        return _mm("bhij,bjhd->bihd", pr, v, control)

    o = jax.lax.map(block, jnp.arange(S // nq))           # (S/nq, b, nq, h, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(b, S, -1)
    a = _rms(mm(o, at["w_o"]), blk["ln_ff"], m["norm_eps"])
    f = blk["mlp"]
    r = mm(a, ad["a"])
    g = mm(a, f["w_gate"]) + mm(r, ad["b_gate"])
    up = mm(a, f["w_up"]) + mm(r, ad["b_up"])
    return mm(jax.nn.gelu(g, approximate=False) * up, f["w_down"])


def logits(params, tokens, m: dict, control: bool = False):
    """(B, S) ids -> (B, S, vocab rows) float32 logits, causal."""
    z = dims(m)
    p = _f32(params)
    hy = p["hybrid"]
    runs, n_tail = _layout(m)
    x = p["embed"][tokens]
    emb = x
    for r, (count, plain) in enumerate(runs):
        for j in range(count):
            for s, n in enumerate(plain):
                seg = hy["runs"][r][s]
                for layer in range(n):
                    x = _mamba(_at(seg["plain"], j, layer), x, z, m, control)
                t = _shared(hy["shared"][s], _at(seg["adapter"], j), x, emb,
                            z, m, control)
                t = _mm("bsd,de->bse", t, seg["linear"][j], control)
                x = _mamba(_at(seg["layer"], j), x, z, m, control, inject=t)
    for layer in range(n_tail):
        x = _mamba(_at(hy["tail"], layer), x, z, m, control)
    h = _rms(x, p["final_ln"], m["norm_eps"])
    return _mm("bsd,ed->bse", h, p["embed"], control)


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
    """Multiply-adds of one token through the projections: every Mamba
    layer's in and out projections, and per shared application the
    attention's q/k/v from 2d and o back to d, the gated MLP, the
    adapter and the linear."""
    z = dims(m)
    mamba = z["d"] * z["d_proj"] + z["d_in"] * z["d"]
    app = (2 * z["d"] * (z["h"] + 2 * z["kv"]) * z["hd"]
           + z["h"] * z["hd"] * z["d"] + 3 * z["d"] * z["ff"]
           + z["r"] * (z["d"] + 2 * z["ff"]) + z["d"] * z["d"])
    return z["layers"] * mamba + z["apps"] * app


def _scan_flops(m: dict) -> float:
    """FLOPs of one token through every layer's conv and scan, the scan in
    its linear (recurrent) form: the state update s = a s + (dt x) B^T, 3
    FLOPs a state element, and the read-out y = s C, 2 a state element;
    the conv 2 a tap and channel."""
    z = dims(m)
    return z["layers"] * (5.0 * z["nh"] * z["p"] * z["n"]
                          + 2.0 * z["w"] * z["ch"])


def prefill_flops(m: dict, s: int) -> float:
    """FLOPs of one prefill of ``s`` tokens: every token through the
    projections and the scans, causal attention over the pairs (i >= j)
    at each shared application, and the head at the last position only
    (over the published vocabulary)."""
    z = dims(m)
    attn = 4.0 * z["apps"] * z["h"] * z["hd"] * s * (s + 1) / 2
    return (2.0 * _matmul_macs(m) + _scan_flops(m)) * s + attn \
        + 2.0 * z["d"] * z["vocab"]


def decode_flops(m: dict, ctx: int) -> float:
    """FLOPs of one decoded token that attends over ``ctx`` positions."""
    z = dims(m)
    attn = 4.0 * z["apps"] * z["h"] * z["hd"] * ctx
    return 2.0 * (_matmul_macs(m) + z["d"] * z["vocab"]) + _scan_flops(m) \
        + attn


def weight_bytes(m: dict) -> float:
    """Bytes of the weights as served: ``param_dtype``, with A_log, D and
    dt_bias in float32."""
    z = dims(m)
    item = jnp.dtype(_dtype(m)).itemsize
    per_layer = (z["d"] * z["d_proj"] + z["d_in"] * z["d"]
                 + (z["w"] + 1) * z["ch"] + z["d_in"] + z["d"])
    block = (2 * z["d"] * (z["h"] + 2 * z["kv"]) * z["hd"]
             + z["h"] * z["hd"] * z["d"] + 3 * z["d"] * z["ff"] + 3 * z["d"])
    app = z["r"] * (z["d"] + 2 * z["ff"]) + z["d"] * z["d"]
    return item * (z["layers"] * per_layer + z["blocks"] * block
                   + z["apps"] * app + z["vocab_rows"] * z["d"] + z["d"]) \
        + 4.0 * 3 * z["nh"] * z["layers"]


def state_bytes(m: dict) -> float:
    """Bytes of one sequence's recurrent state: every layer's float32
    scan state and its conv tail in the compute type."""
    z = dims(m)
    return z["layers"] * (4.0 * z["nh"] * z["p"] * z["n"]
                          + _cache_item(m) * (z["w"] - 1) * z["ch"])


def _kv_bytes(m: dict, positions: float) -> float:
    """K and V (in the compute type) of ``positions`` at every shared
    application."""
    z = dims(m)
    return _cache_item(m) * 2 * z["apps"] * positions * z["kv"] * z["hd"]


def prefill_bytes(m: dict, s: int) -> float:
    """Least HBM traffic of one prefill: the weights read once (the
    embedding table only for the ``s`` rows used), the K and V written to
    the cache and the final recurrent state handed to it."""
    z = dims(m)
    item = jnp.dtype(_dtype(m)).itemsize
    table = item * z["vocab_rows"] * z["d"]
    return weight_bytes(m) - table + item * s * z["d"] + _kv_bytes(m, s) \
        + state_bytes(m)


def decode_bytes(m: dict, ctx: int, lanes: int) -> float:
    """Least HBM traffic of one decode step over ``lanes`` requests that
    each attend over ``ctx`` positions: the weights once (the head reads
    the whole tied table; one embedding row per lane), each lane's K and
    V, and each lane's recurrent state read and written."""
    z = dims(m)
    item = jnp.dtype(_dtype(m)).itemsize
    return weight_bytes(m) + lanes * (item * z["d"] + _kv_bytes(m, ctx)
                                      + 2.0 * state_bytes(m))
