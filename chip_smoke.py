"""Chip smoke test: the system's main path, end to end, on one TPU chip.

One process, two phases, at published widths, with weights from a
seeded ``model.init`` and data from ``SyntheticLM``:

* sweep — a learning-rate sweep of mamba2-130m (6 tasks, batch 8 x seq
  512), submitted as one gang job through ``ControlPlane.submit``/``run``
  (event log in a temporary directory). The job's task runs
  ``launch.sweep.run_sweep``: ``auto_nppn`` packs lanes against the HBM
  the chip reports free, and ``LanePool``/``RefillExecutor`` train them.
  Reference: each task trained alone by a plainly jitted
  ``make_train_step``; per-task losses must agree within ``LOSS_ATOL``.
* serve — ``launch.serve.BatchServer`` on stablelm-1.6b (3 lanes, 6
  requests of one prompt length, 16 new tokens each). Reference: one
  full forward with no cache over prompt plus served tokens; every
  served token must be the reference's greedy choice, except where the
  reference's top two logits are within ``TOP2_TOL``. On a TPU the
  compiled prefill must hold the Pallas flash kernel.

Usage::

    python chip_smoke.py                  # needs a TPU; exits non-zero without
    python chip_smoke.py --cpu-rehearsal  # reduced configs, any platform

Any failed check raises, so the exit code is non-zero and no result line
is printed. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The compile cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs, optim  # noqa: E402
from repro.core import triples as T  # noqa: E402
from repro.core.controlplane import ControlPlane, register_task  # noqa: E402
from repro.core.monitor import device_hbm_budget  # noqa: E402
from repro.data import SyntheticLM  # noqa: E402
from repro.launch.serve import BatchServer, Request, make_prefill  # noqa: E402
from repro.launch.sweep import SweepTask, run_sweep  # noqa: E402
from repro.launch.train import make_train_step  # noqa: E402
from repro.models import ParallelCtx, build_model  # noqa: E402

#: per-step loss agreement, packed lane vs the task trained alone
LOSS_ATOL = 2e-2
#: a served token may differ from the reference only at a near-tie
TOP2_TOL = 1e-1
#: the HBM budget of the CPU rehearsal, which reports no memory_stats
REHEARSAL_HBM = 64e6
SWEEP_ARCH, SERVE_ARCH = "mamba2-130m", "stablelm-1.6b"


def _sizes(rehearsal: bool) -> dict:
    if rehearsal:
        return dict(reduced=True, tasks=6, batch=2, seq=64, steps=3,
                    lanes=3, requests=6, prompt=8, new=6)
    return dict(reduced=False, tasks=6, batch=8, seq=512, steps=4,
                lanes=3, requests=6, prompt=64, new=16)


def _config(name: str, reduced: bool):
    cfg = configs.get(name)
    return cfg.reduced() if reduced else cfg


def _check(cond: bool, msg: str):
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


class CompileClock:
    """Seconds JAX spent compiling (backend compile, persistent-cache
    reads included) and persistent-cache hits, from JAX's own monitoring
    events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        return False

    def lap(self) -> tuple:
        return self.seconds, self.cache_hits


def _peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


def _lm_batch_fn(vocab: int, seq: int, batch: int):
    def batch_fn(seed, step):
        return SyntheticLM(vocab_size=vocab, seq_len=seq, batch_size=batch,
                           seed=seed).batch(step)
    return batch_fn


@register_task("chip_smoke.sweep")
def sweep_task(ctx, payload):
    """The sweep, as the control plane runs it: rebuilt from its JSON
    payload, packed against the device's free HBM (or the payload's
    budget where the device reports none)."""
    cfg = _config(payload["arch"], payload["reduced"])
    model = build_model(cfg)
    tasks = [SweepTask(id=t["id"], lr=t["lr"], seed=t["seed"])
             for t in payload["tasks"]]
    budget = payload["hbm_budget"] or device_hbm_budget()
    res = run_sweep(model, tasks,
                    batch_fn=_lm_batch_fn(cfg.vocab_size, payload["seq"],
                                          payload["batch"]),
                    steps=payload["steps"], hbm_budget=budget,
                    max_pack=len(tasks))
    return {"losses": {str(k): v for k, v in res.losses.items()},
            "pack_factor": res.pack_factor, "backoffs": res.backoffs,
            "hbm_budget": budget, "global_steps": res.global_steps,
            "refills": res.refills, "n_traces": res.n_traces,
            "wall_s": res.wall_s}


def sweep_phase(sz: dict, seed: int, clock: CompileClock) -> None:
    hbm = REHEARSAL_HBM if jax.devices()[0].memory_stats() is None else 0
    tasks = [{"id": i, "lr": 1e-3 * 2.0 ** (i - 3), "seed": seed + i}
             for i in range(sz["tasks"])]
    payload = {"arch": SWEEP_ARCH, "reduced": sz["reduced"],
               "tasks": tasks, "steps": sz["steps"], "batch": sz["batch"],
               "seq": sz["seq"], "hbm_budget": hbm}
    c0, h0 = clock.lap()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as log_dir:
        cp = ControlPlane(log_dir, n_nodes=1,
                          node_spec=T.NodeSpec(chips_per_node=1)).start()
        job = cp.submit("smoke", "chip_smoke.sweep", job_key="sweep",
                        trip=T.Triples(1, 1, 1), payloads=[payload])
        cp.run()
        cp.close()
    _check(job.result is not None and not job.result.failed,
           f"sweep job did not complete: {job.state}")
    out = job.result.results[0]
    wall = time.perf_counter() - t0
    c1, h1 = clock.lap()
    print(f"sweep: {SWEEP_ARCH}{' (reduced)' if sz['reduced'] else ''}"
          f" {len(tasks)} tasks x {sz['steps']} steps, batch "
          f"{sz['batch']}x{sz['seq']}; hbm_budget={out['hbm_budget']} B "
          f"pack_factor={out['pack_factor']} backoffs={out['backoffs']} "
          f"global_steps={out['global_steps']} refills={out['refills']} "
          f"n_traces={out['n_traces']}")
    print(f"sweep: wall {wall:.1f} s, compile {c1 - c0:.1f} s "
          f"(persistent-cache hits {h1 - h0}), "
          f"peak_bytes_in_use={_peak_bytes()}")
    _check(out["backoffs"] == 0, f"backoffs={out['backoffs']}")
    _check(out["n_traces"] == 1, f"pool traced {out['n_traces']} times")

    # reference: each task alone, one plainly jitted train step
    cfg = _config(SWEEP_ARCH, sz["reduced"])
    model = build_model(cfg)
    opt = optim.adamw(weight_decay=0.0)
    step = jax.jit(make_train_step(model, opt), donate_argnums=(0, 1))
    batch_fn = _lm_batch_fn(cfg.vocab_size, sz["seq"], sz["batch"])
    worst = 0.0
    for t in tasks:
        params = model.init(jax.random.PRNGKey(t["seed"]))
        opt_state = opt.init(params)
        ref = []
        for s in range(sz["steps"]):
            params, opt_state, m = step(params, opt_state,
                                        batch_fn(t["seed"], s),
                                        jnp.float32(t["lr"]))
            ref.append(float(m["loss"]))
        got = out["losses"][str(t["id"])]
        _check(len(got) == len(ref), f"task {t['id']}: {len(got)} losses, "
               f"expected {len(ref)}")
        _check(bool(np.all(np.isfinite(got))), f"task {t['id']}: {got}")
        diff = float(np.max(np.abs(np.asarray(got) - np.asarray(ref))))
        worst = max(worst, diff)
        print(f"sweep: task {t['id']} lr={t['lr']:.3g} packed "
              f"{[round(v, 5) for v in got]} alone "
              f"{[round(v, 5) for v in ref]} max|diff|={diff:.3g}")
    print(f"sweep: packed vs alone max|loss diff| {worst:.3g} "
          f"(tolerance {LOSS_ATOL})")
    _check(worst <= LOSS_ATOL, f"loss diff {worst} > {LOSS_ATOL}")


def _prefill_has_kernel(model, params, prompt: int, max_len: int) -> tuple:
    """Whether the prefill the server compiles holds the Pallas flash
    kernel: the compiled program's ``tpu_custom_call`` on a TPU, the
    jaxpr's ``pallas_call`` elsewhere (interpret mode compiles none)."""
    prefill = jax.jit(make_prefill(model, max_len))
    toks = {"tokens": jax.ShapeDtypeStruct((1, prompt), jnp.int32)}
    if jax.devices()[0].platform == "tpu":
        text = prefill.lower(params, toks).compile().as_text()
        return "tpu_custom_call" in text, "tpu_custom_call in compiled HLO"
    text = str(jax.make_jaxpr(make_prefill(model, max_len))(params, toks))
    return "pallas_call" in text, "pallas_call in jaxpr"


def serve_phase(sz: dict, seed: int, clock: CompileClock) -> None:
    cfg = _config(SERVE_ARCH, sz["reduced"])
    on_tpu = jax.devices()[0].platform == "tpu"
    # on a TPU the default impl is the Pallas kernel; the rehearsal runs
    # the same kernel in interpret mode
    model = build_model(cfg, None if on_tpu
                        else ParallelCtx(attn_impl="pallas_interpret"))
    ref_model = build_model(cfg, ParallelCtx(attn_impl="xla"))
    c0, h0 = clock.lap()
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    P, new = sz["prompt"], sz["new"]
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, size=(sz["requests"], P),
                           dtype=np.int32)
    reqs = [Request(id=i, prompt=prompts[i], max_new=new)
            for i in range(sz["requests"])]
    srv = BatchServer(model, params, batch_lanes=sz["lanes"],
                      max_len=P + new)
    t0 = time.perf_counter()
    out = srv.run(reqs)
    wall = time.perf_counter() - t0
    has_kernel, evidence = _prefill_has_kernel(model, params, P, P + new)
    c1, h1 = clock.lap()
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"serve: {SERVE_ARCH}{' (reduced)' if sz['reduced'] else ''}"
          f" {n_params} params ({n_params * 4} B fp32), "
          f"{sz['lanes']} lanes, {len(reqs)} requests x {new} tokens, "
          f"prompt {P}; global_steps={srv.stats.global_steps} "
          f"prefills={srv.stats.prefills}")
    print(f"serve: wall {wall:.1f} s, compile {c1 - c0:.1f} s "
          f"(persistent-cache hits {h1 - h0}), "
          f"peak_bytes_in_use={_peak_bytes()}")
    print(f"serve: flash kernel in prefill: {has_kernel} ({evidence})")
    _check(has_kernel or not on_tpu,
           "compiled prefill holds no Pallas flash kernel")
    served = np.asarray([out[r.id] for r in reqs], np.int32)
    _check(served.shape == (len(reqs), new), f"served {served.shape}")

    # reference: one full forward (no cache) over prompt + served tokens;
    # position P-1+t predicts served token t
    toks = np.concatenate([prompts, served], axis=1)

    @jax.jit
    def ref_top2(p, t):
        logits, _ = ref_model.logits(p, {"tokens": t})
        return jax.lax.top_k(logits[:, P - 1:P - 1 + new], 2)

    vals, idx = ref_top2(params, jnp.asarray(toks))
    vals, idx = np.asarray(vals), np.asarray(idx)
    gap = vals[..., 0] - vals[..., 1]
    equal = served == idx[..., 0]
    excused = ~equal & (gap < TOP2_TOL)
    strict = gap >= TOP2_TOL
    worst_gap = float(gap[~equal].max()) if (~equal).any() else 0.0
    print(f"serve: tokens equal to the full-forward reference "
          f"{int(equal.sum())}/{equal.size}, near-ties excused "
          f"{int(excused.sum())} (top-2 gap < {TOP2_TOL}), strictly "
          f"compared {int(strict.sum())}, largest top-2 gap at a "
          f"mismatch {worst_gap:.4g}")
    _check(bool(np.all(equal | excused)),
           f"{int((~equal & ~excused).sum())} served tokens differ from "
           f"the reference beyond a near-tie")
    _check(strict.sum() * 2 >= strict.size,
           "the reference's logits are too flat to compare tokens")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run the reduced configs on whatever platform "
                         "JAX finds (for CPU tests)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}; platform={dev.platform} "
          f"device_kind={dev.device_kind!r} device_count={len(devices)}",
          flush=True)
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print("chip_smoke: no TPU found; --cpu-rehearsal runs the reduced "
              "configs elsewhere", file=sys.stderr)
        return 2
    sz = _sizes(args.cpu_rehearsal)
    with CompileClock() as clock:
        sweep_phase(sz, args.seed, clock)
        gc.collect()
        serve_phase(sz, args.seed, clock)
        total, hits = clock.lap()
    print(f"compile total {total:.1f} s, persistent-cache hits {hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
