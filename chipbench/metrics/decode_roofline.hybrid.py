"""Roofline share of the whole decode step: the least time one step could
take on the chip's HBM over the mean device time of ``jit_serve_step``.

The least time is the step's bytes over the HBM bandwidth, by the
configuration's ``decode_bytes``: the weights as served, the K/V of the
positions the live lanes attend (the ``kv_positions`` of each window's
``serve.decode`` span over its ``lanes``), and, for a model with
recurrent state, each live lane's state read and written (the reference's
``state_bytes``: the per-lane share of the ``state_bytes`` the program's
``serve.pool`` span counts). Where the program reports no ``kv_positions``, the
reader finds nothing to read.
"""
import peaks
import spans

MODULE = "jit_serve_step"


def read(obs, cell, device):
    if obs.trace is None:
        return None
    mean = obs.trace.module_mean_s(MODULE)
    steps = [s.counts for s in spans.in_window(obs, "serve.decode")
             if "kv_positions" in s.counts and s.counts.get("lanes")]
    if mean is None or not steps:
        return None
    m, ref = cell.config["model"], cell.reference
    least = sum(ref.decode_bytes(m, c["kv_positions"] / c["lanes"],
                                 c["lanes"]) for c in steps) / len(steps)
    return 100.0 * least / (peaks.peaks(device["kind"])["hbm_bw"] * mean)
