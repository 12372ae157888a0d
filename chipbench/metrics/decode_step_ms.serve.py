"""Mean device time of one vmapped decode step (``jit_serve_step``)."""

MODULE = "jit_serve_step"


def read(obs, cell, device):
    if obs.trace is None:
        return None
    mean = obs.trace.module_mean_s(MODULE)
    return None if mean is None else mean * 1e3
