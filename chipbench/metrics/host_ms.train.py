"""Host time per lane-pool iteration: the median, over the window's
``lanepool.iteration`` spans, of each one's duration less its
``lanepool.wait`` (the host waiting for the masked step on the device).
What is left is refill, batch building, the step's dispatch and the
per-lane read-back: the host work that keeps the device idle."""
import spans


def read(obs, cell, device):
    return spans.self_ms(obs, "lanepool.iteration", ("lanepool.wait",))
