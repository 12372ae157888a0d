"""Model FLOPs of the lane-steps completed in the traced window, over the
window times the chip's peak: the whole train step's share of the peak.
FLOPs come from the configuration's reference module (forward plus
backward, published vocabulary, no recomputation)."""
import peaks


def read(obs, cell, device):
    if obs.trace is None or "lane_steps" not in obs.counters:
        return None
    flops = obs.counters["lane_steps"] * obs.counters["train_flops_per_lane_step"]
    peak = peaks.peaks(device["kind"])["flops"] * obs.trace.chips
    return 100.0 * flops / (obs.trace.window_s * peak)
