"""End-to-end LM training driver: data pipeline -> sharded model ->
AdamW -> checkpoints -> monitoring. Defaults train a ~5M-param model for
200 steps on CPU; --preset 100m is the real-hardware configuration.

    PYTHONPATH=src python examples/train_lm.py --steps 200
"""
import argparse
import dataclasses

import jax

from repro import configs, optim
from repro.data import SyntheticLM
from repro.launch.train import Trainer
from repro.models import ParallelCtx, build_model
from repro.optim import schedule


PRESETS = {
    # ~5M params: runnable on this CPU container in minutes
    "5m": dict(num_layers=4, d_model=128, num_heads=4, num_kv_heads=4,
               head_dim=32, d_ff=512, vocab_size=8192, remat=False,
               param_dtype="float32", compute_dtype="float32"),
    # ~100M params: the few-hundred-step run for a real accelerator
    "100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=12,
                 head_dim=64, d_ff=3072, vocab_size=32768, remat=False),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="5m", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default="artifacts/train_lm_ckpt")
    args = ap.parse_args()

    cfg = dataclasses.replace(configs.get("stablelm-1.6b"),
                              **PRESETS[args.preset])
    model = build_model(cfg, ParallelCtx())
    print(f"model: {cfg.param_count()/1e6:.1f}M params")

    ds = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                     batch_size=args.batch, seed=0)
    trainer = Trainer(model, optim.adamw(),
                      schedule.linear_warmup_cosine(args.lr, 20, args.steps),
                      checkpoint_dir=args.ckpt, checkpoint_every=50,
                      log_every=10)
    out = trainer.fit(jax.random.PRNGKey(0), iter(ds), steps=args.steps)
    print(f"loss: {out['losses'][0]:.3f} -> {out['losses'][-1]:.3f} "
          f"({out['monitor']['mean_s']*1e3:.0f} ms/step)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    main()
