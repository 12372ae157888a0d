"""Parametric study with GPU sharing — the paper's headline use case:
sweep learning rates of a small LM, packed onto shared accelerators with
auto-NPPN, checkpointing, and straggler monitoring.

    PYTHONPATH=src python examples/parametric_sweep.py [--tasks 6] [--steps 20]
"""
import argparse

from repro import configs
from repro.data import SyntheticLM
from repro.launch.sweep import SweepTask, run_sweep
from repro.models import ParallelCtx, build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", type=int, default=6)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    cfg = configs.get("stablelm-1.6b").reduced()
    model = build_model(cfg, ParallelCtx(moe_oracle=True))

    def batch_fn(seed, step):
        ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=64,
                         batch_size=8, seed=seed)
        return ds.batch(step)

    lrs = [1e-3 * (2 ** i) for i in range(args.tasks)]
    tasks = [SweepTask(id=i, lr=lr, seed=i) for i, lr in enumerate(lrs)]
    res = run_sweep(model, tasks, batch_fn=batch_fn, steps=args.steps,
                    max_pack=args.tasks, checkpoint_dir=args.ckpt)
    print(f"\nsweep done in {res.wall_s:.1f}s at pack factor "
          f"{res.pack_factor} (backoffs: {res.backoffs})")
    for t in tasks:
        ls = res.losses[t.id]
        print(f"  lr={t.lr:<8.4g} first={ls[0]:.3f} last={ls[-1]:.3f}")
    best = min(tasks, key=lambda t: res.losses[t.id][-1])
    print(f"best lr: {best.lr:g}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
