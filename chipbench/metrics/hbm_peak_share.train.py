"""Peak device memory in use over the allocator's limit, read after the
window: how close packing came to the HBM it packs against."""


def read(obs, cell, device):
    if not obs.peak_bytes or not obs.bytes_limit:
        return None
    return 100.0 * obs.peak_bytes / obs.bytes_limit
