"""Sweep driver: a queue of training tasks through ``launch.sweep.run_sweep``.

The harness sees the sweep only through the callbacks ``run_sweep``
takes. Its ``batch_fn`` is called once per live lane before every pool
step, and its ``early_stop`` once per live lane after it (with the lane's
loss, which the sweep reads back from the device), so a run of
``batch_fn`` calls followed by a run of ``early_stop`` calls is one pool
step: its lane count, and its completion time on the host clock.

Set-up is everything up to the end of pool step ``WARMUP_STEPS - 1``
(a traced run starts its profiler one step earlier):
weights and packing (``auto_nppn`` probes against the HBM the chip reports
free), compiling the pool, and the first steps, during which the harness
reads the state of every lane that started in the pool: its optimizer's
first moment after one step (the first gradient as the optimizer got it)
and its parameters after ``CHECK_STEPS`` steps. The window then runs until
the first pool step that completes ``--seconds`` later; ``early_stop``
ends the sweep there by raising, so nothing after the window grows with
the queue and no task is checkpointed.

After the window the program's state is freed and the plain reference
trains each checked task for ``CHECK_STEPS`` steps from the same seed on
the same batches. The compared numbers are the worst over those tasks:
the gap of each step's loss; and, leaf by leaf, the gap between the
program's and the reference's norms of the first gradient and of the
parameters' change, over the larger of the reference's norm of that leaf
and of the median leaf. Leaves whose reference gradient is under a
thousandth of the median leaf's move by round-off alone and are left out.
With ``--control 1`` the reference's control (its state stored in
bfloat16) takes the program's place in that comparison.

The pool is found on the callers' stack (``_find_pool``), as ``run_sweep``
passes it to no callback. Where it is not found by the first pool step,
the run stops with an error that says so, rather than train on with no
window.
"""
from __future__ import annotations

import gc
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

import bench
import generator
import xplane


#: pool steps of set-up; the window opens as the last of them completes
WARMUP_STEPS = 4
#: the steps the check compares (all of them in set-up)
CHECK_STEPS = 3


class WindowClosed(Exception):
    pass


def _find_pool(pool_type):
    """The lane pool of the sweep that is calling back (a local of a
    caller's frame, or its ``pool`` attribute); None when no pool is on
    the stack, as when ``auto_nppn`` asks for a batch to take its shapes."""
    f = sys._getframe(1)
    while f is not None:
        for v in list(f.f_locals.values()):
            if isinstance(v, pool_type):
                return v
            if isinstance(getattr(v, "pool", None), pool_type):
                return v.pool
        f = f.f_back
    return None


@jax.jit
def _lane_norms(tree, lane):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x[lane].astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


@jax.jit
def _lane(tree, lane):
    return jax.tree_util.tree_map(lambda x: x[lane], tree)


@jax.jit
def _diff_norms(a, b):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x - y)))
                      for x, y in zip(jax.tree_util.tree_leaves(a),
                                      jax.tree_util.tree_leaves(b))])


def moving_leaves(ref_grad) -> np.ndarray:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's; the others move by round-off alone."""
    g = np.asarray(ref_grad, float)
    return g >= 1e-3 * np.median(g)


def step_loss_gap(prog, ref) -> float:
    """Worst |prog - ref| over the compared steps' losses (inf where a
    step's loss is missing or not finite)."""
    if len(prog) != len(ref) or not np.all(np.isfinite(prog)):
        return float("inf")
    return float(np.max(np.abs(np.asarray(prog) - np.asarray(ref))))


def leaf_gap(prog, ref, keep) -> float:
    """Worst kept leaf's |prog - ref| over the larger of its reference
    norm and the median kept leaf's."""
    prog, ref = np.asarray(prog, float)[keep], np.asarray(ref, float)[keep]
    return float(np.max(np.abs(prog - ref)
                        / np.maximum(ref, np.median(ref))))


class Recorder:
    """Pool steps, losses and lane state, from the sweep's callbacks."""

    def __init__(self, run, tasks, pool_type, b1, vocab):
        self.run = run
        self.vocab = vocab
        self.mix = run.cell.traffic
        self.by_seed = {t["seed"]: t for t in tasks}
        self.pool_type = pool_type
        self.b1 = b1
        self.steps = []                 # [lanes, t_done]
        self.phase = "e"
        self.losses = {}                # task id -> [loss per step]
        self.grad = {}                  # task id -> leaf norms, step 1
        self.params = {}                # task id -> host params after check
        self.checked = None             # ids of the tasks first in the pool
        self.t_open = self.t_close = None
        self.open_step = None
        self.lap_open = None
        self.tracer = None
        self.failed = 0                 # window lane-steps with no finite loss

    # --------------------------------------------------------- callbacks
    def batch_fn(self, seed, step):
        batch = generator.lm_batch(self.vocab, self.mix["seq"],
                                   self.mix["batch"], seed, step)
        if self.t_open is None:
            pool = _find_pool(self.pool_type)
            if pool is None:
                return batch
        if self.phase == "e":
            self.steps.append([0, None])
            self.phase = "b"
        self.steps[-1][0] += 1
        task = self.by_seed[seed]
        if step in (1, CHECK_STEPS) and self.t_open is None:
            if self.checked is None:
                self.checked = sorted(o for o in pool.owner if o is not None)
            if task["id"] in self.checked:
                lane = pool.owner.index(task["id"])
                if step == 1:
                    self.grad[task["id"]] = np.asarray(_lane_norms(
                        pool.opt_state["mu"], lane)) / (1 - self.b1)
                if step == CHECK_STEPS:
                    self.params[task["id"]] = jax.device_get(
                        _lane(pool.params, lane))
        return batch

    def early_stop(self, task, step, loss):
        now = time.perf_counter()
        if not self.steps:
            raise RuntimeError(
                "a pool step completed, but _find_pool found no "
                f"{self.pool_type.__name__} on the stack of its batch_fn "
                "calls: the sweep no longer holds its pool as a local or "
                "as a .pool attribute, and the window cannot open")
        if self.phase == "b":
            self.phase = "e"
            self.steps[-1][1] = now
            k = len(self.steps) - 1
            if self.run.trace and k == WARMUP_STEPS - 2:
                self.tracer = xplane.Tracer()
            if self.t_open is None and k == WARMUP_STEPS - 1:
                self._open()
            elif self.t_open is not None and now >= self.deadline:
                self.t_close = now
                self.lap_close = self.run.clock.lap()
                if self.tracer is not None:
                    self.tracer.close()
                raise WindowClosed()
        if step < CHECK_STEPS:
            self.losses.setdefault(task.id, []).append(loss)
        if self.t_open is not None and not np.isfinite(loss):
            self.failed += 1
        return False

    def _open(self):
        if self.tracer is not None:
            self.tracer.open()
        self.t_open = time.perf_counter()
        self.open_step = len(self.steps)
        self.deadline = self.t_open + self.run.seconds
        self.lap_open = self.run.clock.lap()


def run(run) -> bench.Obs:
    from repro import optim
    from repro.core.lanepool import LanePool
    from repro.core.monitor import device_hbm_budget
    from repro.launch.sweep import SweepTask, run_sweep
    from repro.models import build_model

    cell, mix = run.cell, run.cell.traffic
    m, o = cell.config["model"], cell.config["optimizer"]
    tasks = generator.sweep_tasks(mix, run.seed)
    rec = Recorder(run, tasks, LanePool, o["b1"], m["vocab_size"])
    opt = optim.adamw(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                      weight_decay=o["weight_decay"],
                      grad_clip=o["grad_clip"])
    model = build_model(cell.model_config())
    budget = run.hbm_budget if run.hbm_budget is not None \
        else device_hbm_budget()
    try:
        run_sweep(model, [SweepTask(id=t["id"], lr=t["lr"], seed=t["seed"],
                                    steps=t["steps"]) for t in tasks],
                  batch_fn=rec.batch_fn,
                  steps=max(t["steps"] for t in tasks), hbm_budget=budget,
                  max_pack=mix["max_pack"], opt=opt,
                  early_stop=rec.early_stop)
    except WindowClosed:
        pass
    if rec.t_open is None:
        raise RuntimeError(f"the sweep ended after {len(rec.steps)} pool "
                           "steps, before its window opened")
    obs = bench.Obs()
    if rec.t_close is None:             # the queue drained first
        rec.t_close = rec.steps[-1][1]
        rec.lap_close = run.clock.lap()
    window = rec.t_close - rec.t_open
    in_window = rec.steps[rec.open_step:]
    lane_steps = sum(n for n, _ in in_window)
    tokens = lane_steps * mix["batch"] * mix["seq"]
    obs.window_s = window
    obs.end_to_end = {"setup_s": rec.t_open - run.t_start,
                      "train_tokens_per_s": tokens / window}
    obs.attempted = lane_steps
    obs.failed = rec.failed
    obs.programs_in_window = rec.lap_close[1] - rec.lap_open[1]
    done = [rec.t_open] + [t for _, t in in_window]
    obs.counters = {"lane_steps": lane_steps, "pool_steps": len(in_window),
                    "step_ms": [round((b - a) * 1e3, 1)
                                for a, b in zip(done, done[1:])],
                    "lanes_per_step": lane_steps / max(1, len(in_window)),
                    "train_flops_per_lane_step": cell.reference.train_flops(
                        m, mix["batch"], mix["seq"]),
                    **bench.setup_compiles(rec.lap_open)}
    obs.read_memory(cell.chips)
    del model
    gc.collect()
    if rec.tracer is not None:
        obs.trace = rec.tracer.finish(window)
    _check(run, rec, tasks, obs)
    return obs


def _check(run, rec, tasks, obs):
    cell, mix = run.cell, run.cell.traffic
    m, o = cell.config["model"], cell.config["optimizer"]
    ref = cell.reference
    by_id = {t["id"]: t for t in tasks}
    n = CHECK_STEPS
    loss_gap = grad_gap = update_gap = 0.0
    faults = [0.0, 0.0, 0.0]
    init = jax.jit(lambda k: ref.init(k, m))
    for tid in rec.checked:
        t = by_id[tid]
        batches = [generator.lm_batch(m["vocab_size"], mix["seq"],
                                      mix["batch"], t["seed"], s)
                   for s in range(n)]
        r = ref.train_steps(m, o, t["seed"], t["lr"], batches)
        keep = moving_leaves(r["grad"])
        obs.counters["leaves_left_out"] = sorted(set(
            obs.counters.get("leaves_left_out", []))
            | {name for name, k in zip(r["leaves"], keep) if not k})
        if run.control:
            v = ref.train_steps(m, o, t["seed"], t["lr"], batches, "control")
            got, grad, delta = v["losses"], v["grad"], v["delta"]
            h = ref.train_steps(m, o, t["seed"], t["lr"], batches,
                                "half_batch")
            faults = [max(faults[0], step_loss_gap(h["losses"], r["losses"])),
                      max(faults[1], leaf_gap(h["grad"], r["grad"], keep)),
                      max(faults[2], leaf_gap(h["delta"], r["delta"], keep))]
        else:
            got, grad = rec.losses.get(tid, []), rec.grad[tid]
            p0 = init(jax.random.PRNGKey(t["seed"]))
            delta = np.asarray(_diff_norms(jax.device_put(rec.params[tid]),
                                           p0))
            del p0
        loss_gap = max(loss_gap, step_loss_gap(got, r["losses"]))
        grad_gap = max(grad_gap, leaf_gap(grad, r["grad"], keep))
        update_gap = max(update_gap, leaf_gap(delta, r["delta"], keep))
    lim = cell.limits
    # the first gradient's gap is read but not compared: neither the
    # control nor a planted fault reads ten times what sound runs read
    obs.counters["grad_gap"] = grad_gap
    obs.checks = [bench.Check("loss_gap", loss_gap, lim["loss_gap"]),
                  bench.Check("update_gap", update_gap, lim["update_gap"]),
                  bench.Check("programs_in_window",
                              float(obs.programs_in_window), 0.0)]
    if run.control:
        # a fault planted in the reference, read beside the control
        obs.control = {"half_batch.loss_gap": faults[0],
                       "half_batch.grad_gap": faults[1],
                       "half_batch.update_gap": faults[2]}
