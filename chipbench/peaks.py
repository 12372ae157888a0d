"""Peak rates of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud TPU documentation, system architecture pages for
each generation ("TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM
at 819 GB/s). Copied from the repository's roofline presets so that the
yardstick stays fixed when the program's copy changes. A kind that is not
in the table is an error, never a default.
"""
from __future__ import annotations

#: device_kind -> bf16 FLOP/s, HBM bytes/s, HBM bytes
PEAKS = {
    "TPU v4": {"flops": 275e12, "hbm_bw": 1228e9, "hbm_bytes": 32e9},
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "hbm_bytes": 16e9},
    "TPU v5": {"flops": 459e12, "hbm_bw": 2765e9, "hbm_bytes": 95e9},
    "TPU v6 lite": {"flops": 918e12, "hbm_bw": 1640e9, "hbm_bytes": 32e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak rates for device kind {device_kind!r}; "
                         f"known kinds: {sorted(PEAKS)}") from None
