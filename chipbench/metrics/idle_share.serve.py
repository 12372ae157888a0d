"""Share of the traced window in which the device ran no op."""
import xplane


def read(obs, cell, device):
    return xplane.idle_share(obs)
