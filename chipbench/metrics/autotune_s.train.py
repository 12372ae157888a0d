"""Set-up spent choosing the pack factor: the total of the sweep's
``sweep.autotune`` spans (``auto_nppn``'s probe compiles against the free
HBM, and the admission probe where there is one)."""
import spans


def read(obs, cell, device):
    return spans.total_s("sweep.autotune")
