"""Run one benchmark cell once and print its result as the last line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's parts are found by name from ``BENCHMARK.json`` (see
``chipbench/bench.py``). The run needs as many TPU chips as the cell asks
for: without them it exits with code 3 and prints no result. Set-up
(building weights, compiling, warming up every shape the window uses) is
timed as ``setup_s``; then the window runs for ``--seconds``. With
``--trace 1`` the window is traced and the line carries the per-layer
metrics instead of the end-to-end ones. ``--control 1`` puts the check's
control (the plain reference at the precision below the configuration's)
in the program's place in the comparison, so the run has to come out not
correct; the benchmark's own runs leave it off.

The compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else the
fixed ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench  # noqa: E402


@dataclasses.dataclass
class Run:
    """Everything a driver is given."""
    cell: bench.Cell
    seed: int
    seconds: float
    trace: bool
    control: bool
    t_start: float
    clock: Any
    hbm_budget: Optional[float] = None   # the device's free HBM when None


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, control: bool = False, t_start: float = None,
             hbm_budget: Optional[float] = None):
    """Run the cell in this process. Returns (obs, result). Off a TPU the
    result carries no metric: a CPU run measures no device."""
    import jax
    from clock import CompileClock
    cell = bench.load_cell(root, workload)
    with CompileClock() as clock:
        run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  control=control, clock=clock, hbm_budget=hbm_budget,
                  t_start=T_START if t_start is None else t_start)
        obs = cell.driver().run(run)
    devices = jax.devices()[:cell.chips]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": obs.peak_bytes}
    if trace and obs.trace is not None:
        device["busy_s"] = obs.trace.busy_s
        device["window_s"] = obs.trace.window_s
    res = bench.result(cell, obs, device, traced=trace)
    if device["platform"] != "tpu":
        res["metrics"] = {}
        res.pop("breakdown", None)
    return obs, res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the TPU runtime logs to the fixed /tmp/tpu_logs unless told otherwise,
    # and a run writes only inside its checkout and its TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = bench.load_cell(ROOT, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    obs, res = run_cell(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace), bool(args.control))
    sys.stdout.flush()
    print(f"chipbench: window {obs.window_s!r} s, end-to-end "
          f"{json.dumps(obs.end_to_end)}, counters {json.dumps(obs.counters)}",
          file=sys.stderr)
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
