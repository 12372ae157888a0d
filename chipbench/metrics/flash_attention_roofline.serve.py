"""Roofline share of the flash-attention kernel in the prefill: the least
time the chip could take for the kernel's work (the larger of its FLOPs
over the peak and its bytes over the HBM bandwidth) over the kernel's
device time. The kernel is the Pallas ``tpu_custom_call`` of the
``jit_prefill`` module, one call per layer.

Work of one call at batch b, heads h, head size d and s positions,
causal: 2 matmuls of 2*d FLOPs per (query, key) pair over s(s+1)/2 pairs
per head; bytes are q, k and v read and o written once, in bfloat16.
"""
import peaks

MODULE = "jit_prefill"


def flops(b, h, s, d):
    return 4.0 * b * h * d * s * (s + 1) / 2


def bytes_moved(b, h, s, d, itemsize=2):
    return 4.0 * b * h * s * d * itemsize


def read(obs, cell, device):
    if obs.trace is None:
        return None
    calls = [op for op in obs.trace.ops if op.custom and op.module == MODULE]
    if not calls:
        return None
    z = cell.reference.dims(cell.config["model"])
    s = obs.counters["prompt_len"]
    p = peaks.peaks(device["kind"])
    least = max(flops(1, z["h"], s, z["hd"]) / p["flops"],
                bytes_moved(1, z["h"], s, z["hd"]) / p["hbm_bw"])
    return 100.0 * least * len(calls) / (sum(op.dur_ns for op in calls) * 1e-9)
