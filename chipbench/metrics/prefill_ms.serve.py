"""Mean device time of one batch-1 prefill (``jit_prefill``)."""

MODULE = "jit_prefill"


def read(obs, cell, device):
    if obs.trace is None:
        return None
    mean = obs.trace.module_mean_s(MODULE)
    return None if mean is None else mean * 1e3
