"""Plain reference of mamba2-130m as the program runs it, and the work
its train step needs.

The block (arXiv:2405.21060, the Mamba-2 block with one B/C group):

    h      = rmsnorm(x) * ln
    z, xBC, dt_raw = h @ w_in                    (d_in, d_in + 2N, nh wide)
    xBC    = silu(causal_conv(xBC, conv_w) + conv_b)   -> x, B, C
    dt     = softplus(dt_raw + dt_bias);  A = -exp(A_log)
    y_i    = sum_{j<=i} (C_i . B_j) exp(sum_{k=j+1..i} dt_k A) dt_j x_j + D x_i
    out    = x + (rmsnorm(y * silu(z)) * norm_w) @ w_out

and the head is ``rmsnorm(h) * final_ln @ embed.T`` over the program's
padded table. The scan is computed here in its quadratic (dual) form over
the whole sequence, with the stable segment sum of the SSD paper's
minimal listing: no chunks, no kernel, float32 at the highest matmul
precision. Weights are re-derived from the seed by the program's own
init scheme (the same draws and layout), so the reference starts where
the program starts and takes nothing the program made.
"""
from __future__ import annotations

import json
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(m: dict) -> dict:
    d, s = m["d_model"], m["ssm"]
    d_in = s["expand"] * d
    nh = s["num_heads"] or d_in // s["head_dim"]
    n = s["state_dim"]
    pad = m["vocab_pad_to"]
    return dict(d=d, d_in=d_in, nh=nh, hd=s["head_dim"], n=n,
                ch=d_in + 2 * n, w=s["conv_width"], q=s["chunk_size"],
                layers=m["num_layers"], vocab=m["vocab_size"],
                vocab_rows=-(-m["vocab_size"] // pad) * pad)


# ---------------------------------------------------------------- weights
def _dense(key, n_in, n_out):
    return jax.random.normal(key, (n_in, n_out), F32) * (1.0 / math.sqrt(n_in))


def init(key, m: dict) -> Dict:
    """fp32 weights in the program's layout (``blocks`` stacked over
    layers), drawn exactly as the program draws them from ``key``."""
    z = dims(m)
    ks = jax.random.split(key, 6)

    def layer(k):
        km = jax.random.split(jax.random.split(k, 4)[0], 6)
        dt = jnp.exp(jax.random.uniform(km[2], (z["nh"],), F32,
                                        math.log(1e-3), math.log(1e-1)))
        return {"ln": jnp.ones((z["d"],), F32), "mamba": {
            "w_in": _dense(km[0], z["d"], 2 * z["d_in"] + 2 * z["n"] + z["nh"]),
            "conv_w": jax.random.normal(km[1], (z["w"], z["ch"]), F32)
            / math.sqrt(z["w"]),
            "conv_b": jnp.zeros((z["ch"],), F32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(km[3], (z["nh"],), F32,
                                                1.0, 16.0)),
            "D": jnp.ones((z["nh"],), F32),
            "norm_w": jnp.ones((z["d_in"],), F32),
            "w_out": _dense(km[4], z["d_in"], z["d"]),
        }}

    return {"embed": jax.random.normal(ks[0], (z["vocab_rows"], z["d"]), F32)
            * 0.02,
            "final_ln": jnp.ones((z["d"],), F32),
            "blocks": jax.vmap(layer)(jax.random.split(ks[2], z["layers"]))}


# ---------------------------------------------------------------- forward
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _segsum(a):
    """(..., S) -> (..., S, S): entry [i, j] = sum_{k=j+1..i} a_k for
    i >= j, -inf above the diagonal."""
    s = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (s,))      # [i, j] = a_i
    x = jnp.where(jnp.tril(jnp.ones((s, s), bool), -1), x, 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((s, s), bool)), x, -jnp.inf)


def _block(p, x, z, eps):
    b, s, _ = x.shape
    mp = p["mamba"]
    h = _rms(x, p["ln"], eps)
    proj = h @ mp["w_in"]
    zg = proj[..., :z["d_in"]]
    xbc = proj[..., z["d_in"]:z["d_in"] + z["ch"]]
    dt_raw = proj[..., z["d_in"] + z["ch"]:]
    pad = jnp.pad(xbc, ((0, 0), (z["w"] - 1, 0), (0, 0)))
    xbc = sum(pad[:, i:i + s] * mp["conv_w"][i] for i in range(z["w"]))
    xbc = jax.nn.silu(xbc + mp["conv_b"])
    xs = xbc[..., :z["d_in"]].reshape(b, s, z["nh"], z["hd"])
    bm = xbc[..., z["d_in"]:z["d_in"] + z["n"]]
    cm = xbc[..., z["d_in"] + z["n"]:]
    dt = jax.nn.softplus(dt_raw + mp["dt_bias"])               # (b,s,nh)
    decay = jnp.exp(_segsum(jnp.moveaxis(dt * -jnp.exp(mp["A_log"]), 1, 2)))
    w = (jnp.einsum("bin,bjn->bij", cm, bm)[:, None] * decay
         * jnp.moveaxis(dt, 1, 2)[:, :, None, :])              # (b,nh,i,j)
    y = jnp.einsum("bhij,bjhp->bihp", w, xs)
    y = y + mp["D"][None, None, :, None] * xs
    y = _rms(y.reshape(b, s, z["d_in"]) * jax.nn.silu(zg), mp["norm_w"], 1e-5)
    return x + y @ mp["w_out"]


def loss(params, batch, m: dict):
    """Mean next-token cross-entropy over every position, in float32."""
    z = dims(m)
    x = params["embed"][batch["tokens"]]

    def body(h, p):
        return jax.checkpoint(lambda h, p: _block(p, h, z, m["norm_eps"]))(
            h, p), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    logits = _rms(x, params["final_ln"], m["norm_eps"]) @ params["embed"].T
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


# --------------------------------------------------------- three steps
def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(k)
            for k, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def _make_step(m: dict, o: dict, variant: str):
    """One AdamW step of the reference. ``variant``: ``reference``
    (float32 state); ``control`` (parameters and moments stored in
    bfloat16, the precision below the configuration's float32 state);
    ``half_batch`` (the loss over the first half of the rows only)."""
    store = jnp.bfloat16 if variant == "control" else F32

    def step(p, mu, nu, count, batch, lr):
        if variant == "half_batch":
            half = batch["tokens"].shape[0] // 2
            batch = {k: v[:half] for k, v in batch.items()}
        p32 = jax.tree_util.tree_map(lambda x: x.astype(F32), p)
        value, g = jax.value_and_grad(loss)(p32, batch, m)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, o["grad_clip"]
                                      / jnp.maximum(gnorm, 1e-9)), g)
        count = count + 1
        c1 = 1 - o["b1"] ** count
        c2 = 1 - o["b2"] ** count

        def upd(x, gi, mi, ni):
            mi = o["b1"] * mi.astype(F32) + (1 - o["b1"]) * gi
            ni = o["b2"] * ni.astype(F32) + (1 - o["b2"]) * gi * gi
            x = x - lr * ((mi / c1) / (jnp.sqrt(ni / c2) + o["eps"])
                          + o["weight_decay"] * x)
            return x.astype(store), mi.astype(store), ni.astype(store)

        out = jax.tree_util.tree_map(upd, p32, g, mu, nu)
        pick = lambda i: jax.tree_util.tree_map(
            lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2), count, value, leaf_norms(g)

    return jax.jit(step)


_JIT = {}


def train_steps(m: dict, o: dict, seed: int, lr: float, batches: list,
                variant: str = "reference") -> dict:
    """Reference steps of one task from its seed, one per batch: each
    step's loss, the leaf norms of the first (clipped) gradient, and the
    leaf norms of the parameters' change after the last step."""
    key = (json.dumps(m, sort_keys=True), json.dumps(o, sort_keys=True),
           variant)
    if key not in _JIT:
        _JIT[key] = (_make_step(m, o, variant),
                     jax.jit(lambda k: init(k, m)))
    step, init_fn = _JIT[key]
    with jax.default_matmul_precision("highest"):
        store = jnp.bfloat16 if variant == "control" else F32
        p0 = jax.tree_util.tree_map(lambda x: x.astype(store),
                                    init_fn(jax.random.PRNGKey(seed)))
        zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
        p, mu, nu, count = p0, zeros, zeros, jnp.zeros((), F32)
        losses, grad = [], None
        for b in batches:
            p, mu, nu, count, value, gn = step(
                p, mu, nu, count, {k: jnp.asarray(v) for k, v in b.items()},
                jnp.float32(lr))
            losses.append(float(value))
            grad = gn if grad is None else grad
        delta = leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(F32) - b.astype(F32), p, p0))
        return {"losses": losses, "grad": np.asarray(grad),
                "delta": np.asarray(delta), "leaves": leaf_names(p0)}


# ------------------------------------------------------------------- work
def forward_flops_per_token(m: dict) -> float:
    """FLOPs of one token's forward pass: the projections, the depthwise
    conv, the chunked SSD (intra-chunk C.B and its weighted sum over the
    causal pairs, the chunk states and their read-out) and the tied head
    over the published vocabulary (not the padded table)."""
    z = dims(m)
    proj = z["d"] * (2 * z["d_in"] + 2 * z["n"] + z["nh"]) + z["d_in"] * z["d"]
    conv = z["w"] * z["ch"]
    pairs = (z["q"] + 1) / 2            # causal pairs (i >= j) per token
    ssd = pairs * (z["n"] + z["d_in"]) + 2 * z["d_in"] * z["n"]
    return 2.0 * (z["layers"] * (proj + conv + ssd) + z["d"] * z["vocab"])


def train_flops(m: dict, batch: int, seq: int) -> float:
    """FLOPs one lane's train step needs: forward plus backward (twice
    the forward); recomputation under remat is not counted."""
    return 3.0 * forward_flops_per_token(m) * batch * seq


def param_count(m: dict) -> int:
    z = dims(m)
    layer = (z["d"] * (2 * z["d_in"] + 2 * z["n"] + z["nh"]) + z["w"] * z["ch"]
             + z["ch"] + 3 * z["nh"] + z["d_in"] + z["d_in"] * z["d"] + z["d"])
    return z["layers"] * layer + z["vocab_rows"] * z["d"] + z["d"]


def train_bytes(m: dict) -> float:
    """Least HBM traffic of one lane's AdamW step on float32 state:
    parameters, two moments read and written, and the gradient written
    and read (activations not counted)."""
    return 4.0 * param_count(m) * (2 + 4 + 2)
