"""Mean live lanes per pool step in the window, counted by the harness's
``batch_fn`` (called once per live lane before each step)."""


def read(obs, cell, device):
    return obs.counters.get("lanes_per_step")
