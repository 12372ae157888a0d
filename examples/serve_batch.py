"""Batched serving example: prefill + greedy decode over a lane pool —
the inference-side counterpart of job packing (multiple requests share
the accelerator as decode lanes).

    PYTHONPATH=src python examples/serve_batch.py
"""
import time

import jax
import numpy as np

from repro import configs
from repro.launch.serve import BatchServer, Request
from repro.models import ParallelCtx, build_model


def main():
    cfg = configs.get("stablelm-1.6b").reduced()
    model = build_model(cfg, ParallelCtx(moe_oracle=True))
    params = model.init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    reqs = [Request(id=i,
                    prompt=rng.integers(1, cfg.vocab_size, size=5 + i % 4).astype(np.int32),
                    max_new=8)
            for i in range(6)]

    srv = BatchServer(model, params, batch_lanes=3, max_len=32)
    t0 = time.perf_counter()
    out = srv.run(reqs)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(v) for v in out.values())
    print(f"served {len(reqs)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s on CPU)")
    for rid in sorted(out):
        print(f"  req{rid}: {out[rid]}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
