"""Model FLOPs of one prefill over its mean device time times the chip's
peak: the prefill step's share of the peak, which bounds the flash
kernel's roofline share from above in what it can move."""
import peaks

MODULE = "jit_prefill"


def read(obs, cell, device):
    if obs.trace is None:
        return None
    mean = obs.trace.module_mean_s(MODULE)
    if mean is None:
        return None
    flops = cell.reference.prefill_flops(cell.config["model"],
                                         obs.counters["prompt_len"])
    return 100.0 * flops / (mean * peaks.peaks(device["kind"])["flops"])
