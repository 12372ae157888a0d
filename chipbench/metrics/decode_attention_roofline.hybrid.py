"""Roofline share of the decode-attention kernel: the bytes it has to
move over the device time of its calls, the Pallas ``tpu_custom_call``
ops of ``jit_serve_step`` (one call per attention layer a step).

Bytes of one call: the K and V of the positions the live lanes attend
(the mean ``kv_positions`` of the window's ``serve.decode`` spans) and
each lane's query read, in the configuration's compute type (as the
cache holds them), and its float32 output written. Positions the kernel
reads past a lane's last one, and its block-diagonal query's zeros, are
no part of the least bytes, so they show as lost share. Where the
program reports no ``kv_positions``, the reader finds nothing to read.
"""
import jax.numpy as jnp

import peaks
import spans

MODULE = "jit_serve_step"


def read(obs, cell, device):
    if obs.trace is None:
        return None
    calls = [op for op in obs.trace.ops if op.custom and op.module == MODULE]
    steps = [s.counts for s in spans.in_window(obs, "serve.decode")
             if "kv_positions" in s.counts and s.counts.get("lanes")]
    if not calls or not steps:
        return None
    m = cell.config["model"]
    z = cell.reference.dims(m)
    item = jnp.dtype(m["compute_dtype"]).itemsize
    kv = sum(c["kv_positions"] for c in steps) / len(steps)
    lanes = sum(c["lanes"] for c in steps) / len(steps)
    per_call = (2.0 * item * kv * z["kv"] * z["hd"]
                + lanes * z["h"] * z["hd"] * (item + 4))
    least = per_call * len(calls) / peaks.peaks(device["kind"])["hbm_bw"]
    return 100.0 * least / (sum(op.dur_ns for op in calls) * 1e-9)
