"""Benchmark driver — one module per paper table/figure. Emits
``name,us_per_call,derived`` CSV rows (benchmarks.common.emit)."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (bench_mnist_sharing, bench_imagenet_sharing,
                            bench_lane_refill, bench_multitenant,
                            bench_preemption, bench_repack, bench_spatial,
                            bench_scheduler_overhead, bench_trace_replay,
                            bench_oom_guard, roofline_table, bench_kernels)
    failures = []
    for mod in (bench_scheduler_overhead, bench_multitenant,
                bench_preemption, bench_lane_refill, bench_repack,
                bench_spatial, bench_trace_replay, bench_oom_guard,
                bench_mnist_sharing, bench_imagenet_sharing,
                bench_kernels, roofline_table):
        name = mod.__name__.split(".")[-1]
        print(f"# --- {name} ---", flush=True)
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001 — report, keep benching
            failures.append(name)
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"# failed benches: {failures}", flush=True)
        sys.exit(1)
    print("# all benches complete", flush=True)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
