"""Mean device time of one run of the lane pool's masked step (the
``jit_step`` module that ``packing.masked_pool_step`` compiles)."""

MODULE = "jit_step"


def read(obs, cell, device):
    if obs.trace is None:
        return None
    mean = obs.trace.module_mean_s(MODULE)
    return None if mean is None else mean * 1e3
