"""Model FLOPs of the tokens prefilled and decoded in the traced window,
over the window times the chip's peak: the serving loop's share of the
peak. FLOPs come from the configuration's reference module."""
import peaks


def read(obs, cell, device):
    if obs.trace is None or "served_flops" not in obs.counters:
        return None
    peak = peaks.peaks(device["kind"])["flops"] * obs.trace.chips
    return 100.0 * obs.counters["served_flops"] / (obs.trace.window_s * peak)
