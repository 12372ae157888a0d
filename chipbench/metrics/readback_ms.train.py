"""Per-lane read-back time per lane-pool iteration: the median, over the
window's ``lanepool.iteration`` spans, of their ``lanepool.read`` child
(each lane's metrics sliced and copied to the host, the sweep's loss
read, detach and ``on_finish``)."""
import spans


def read(obs, cell, device):
    return spans.child_ms(obs, "lanepool.iteration", "lanepool.read")
