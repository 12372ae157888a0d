"""Time a request takes to join the decode pool: the median of the
window's ``serve.join`` spans (its batch-1 prefill, the swap of its cache
into the pool, and the read of its first token). Every lane waits
through it."""
import spans


def read(obs, cell, device):
    return spans.median_ms(obs, "serve.join")
