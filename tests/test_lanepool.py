"""Lane-pool executor: lifecycle equivalence, compile-once, refill safety."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import packing
from repro.core.lanepool import LanePool, LaneTask, RefillExecutor, run_waves
from tests.prop import given_cases


def _tiny_model():
    def init(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (8, 16)) * 0.1,
                "w2": jax.random.normal(k2, (16, 4)) * 0.1}

    def loss(params, batch):
        h = jnp.tanh(batch["x"] @ params["w1"])
        return jnp.mean((h @ params["w2"] - batch["y"]) ** 2)

    return init, loss


def _batch(seed, step, n=16):
    rng = np.random.Generator(np.random.Philox(key=seed,
                                               counter=[step, 0, 0, 0]))
    x = rng.standard_normal((n, 8)).astype(np.float32)
    return {"x": x, "y": (x[:, :4] * 0.5).astype(np.float32)}


def _step_fn(loss, opt):
    def step(params, opt_state, batch, lr):
        l, g = jax.value_and_grad(loss)(params, batch)
        upd, opt_state = opt.update(g, opt_state, params, lr)
        return optim.apply_updates(params, upd), opt_state, {"loss": l}
    return step


def _setup():
    init, loss = _tiny_model()
    opt = optim.sgd()
    step = _step_fn(loss, opt)
    return init, opt, step


def _pool(step, init, opt, capacity):
    tmpl = init(jax.random.PRNGKey(0))
    return LanePool(capacity, step, template_params=tmpl,
                    template_opt=opt.init(tmpl),
                    template_hparams=jnp.float32(0.0))


def _lane_task(init, opt, i, steps, lr=1e-2):
    return LaneTask(
        id=i, hparams=jnp.float32(lr),
        init_fn=lambda: (lambda p: (p, opt.init(p)))(
            init(jax.random.PRNGKey(i))),
        batch_fn=lambda s, i=i: _batch(i, s),
        steps=steps)


def _run_collect(executor_tasks, pool):
    losses = {}
    ex = RefillExecutor(pool, on_metrics=lambda t, s, m: losses.setdefault(
        t.id, []).append(float(np.asarray(m["loss"]))) and False)
    stats = ex.run(executor_tasks)
    return losses, stats, ex


# ---------------------------------------------------------------------------
# masked-step semantics
# ---------------------------------------------------------------------------

def test_masked_step_freezes_inactive_lanes_bit_identical():
    init, opt, step = _setup()
    K = 3
    keys = jnp.stack([jax.random.PRNGKey(s) for s in range(K)])
    params = packing.pack_init(init, keys)
    opt_state = jax.vmap(opt.init)(params)
    lrs = jnp.full((K,), 1e-2, jnp.float32)
    batch = packing.stack_trees([_batch(i, 0) for i in range(K)])
    masked = packing.packed_masked_step(step, donate=False)
    mask = jnp.asarray([True, False, True])
    new_p, new_o, _ = masked(params, opt_state, batch, lrs, mask)
    # inactive lane 1 passes through untouched, bit for bit
    for leaf_new, leaf_old in zip(jax.tree_util.tree_leaves(new_p),
                                  jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(leaf_new[1]),
                                      np.asarray(leaf_old[1]))
    # active lanes match the unmasked lockstep step exactly
    lock = packing.packed_step(step, donate=False)
    ref_p, _, _ = lock(params, opt_state, batch, lrs)
    for leaf_new, leaf_ref in zip(jax.tree_util.tree_leaves(new_p),
                                  jax.tree_util.tree_leaves(ref_p)):
        np.testing.assert_array_equal(np.asarray(leaf_new[0]),
                                      np.asarray(leaf_ref[0]))
        np.testing.assert_array_equal(np.asarray(leaf_new[2]),
                                      np.asarray(leaf_ref[2]))


def test_tree_lane_swap_roundtrip():
    trees = [{"a": jnp.arange(3) + i, "b": jnp.ones((2, 2)) * i}
             for i in range(4)]
    stacked = packing.stack_trees(trees)
    lane2 = packing.tree_get_lane(stacked, 2)
    swapped = packing.tree_set_lane(stacked, 0, lane2)
    back = packing.tree_get_lane(swapped, 0)
    assert jnp.array_equal(back["a"], trees[2]["a"])
    assert jnp.array_equal(back["b"], trees[2]["b"])
    # other lanes untouched
    assert jnp.array_equal(packing.tree_get_lane(swapped, 1)["a"],
                           trees[1]["a"])


# ---------------------------------------------------------------------------
# lifecycle: detach/re-attach equivalence
# ---------------------------------------------------------------------------

def test_detach_reattach_on_other_lane_bit_identical():
    """A task migrated mid-run to a different lane (with different
    co-residents) must produce bit-identical losses to an uninterrupted
    run of the same task."""
    init, opt, step = _setup()
    STEPS = 6

    # uninterrupted reference: task 7 runs lane 0 of a pool, start to end
    pool = _pool(step, init, opt, 2)
    ref_losses, _, _ = _run_collect(
        [_lane_task(init, opt, 7, STEPS),
         _lane_task(init, opt, 8, STEPS)], pool)

    # migrated: run task 7 three steps on lane 0, detach, re-attach on
    # lane 1 next to a different neighbour, run the remaining three
    pool2 = _pool(step, init, opt, 2)
    t7 = _lane_task(init, opt, 7, STEPS)
    params, opt_state = t7.init_fn()
    pool2.attach(0, 7, params, opt_state, t7.hparams)
    pool2.attach(1, 9, *_lane_task(init, opt, 9, STEPS).init_fn(),
                 jnp.float32(1e-2))
    got = []
    for s in range(3):
        batch = packing.stack_trees([
            jax.tree_util.tree_map(jnp.asarray, _batch(7, s)),
            jax.tree_util.tree_map(jnp.asarray, _batch(9, s))])
        m = pool2.step(batch)
        got.append(float(np.asarray(m["loss"][0])))
    mid_state = pool2.detach(0)
    pool2.attach(1 - 1, 5, *_lane_task(init, opt, 5, STEPS).init_fn(),
                 jnp.float32(3e-2))    # a NEW neighbour takes lane 0
    pool2.detach(1)
    pool2.attach(1, 7, *mid_state, t7.hparams)   # task 7 now on lane 1
    for s in range(3, STEPS):
        batch = packing.stack_trees([
            jax.tree_util.tree_map(jnp.asarray, _batch(5, s)),
            jax.tree_util.tree_map(jnp.asarray, _batch(7, s))])
        m = pool2.step(batch)
        got.append(float(np.asarray(m["loss"][1])))

    np.testing.assert_array_equal(np.float32(ref_losses[7]),
                                  np.float32(got))
    assert pool2.n_traces == 1


# ---------------------------------------------------------------------------
# compile-once guarantee (acceptance criterion)
# ---------------------------------------------------------------------------

def test_skewed_sweep_3x_capacity_traces_once():
    """3× pool-capacity tasks with skewed durations: exactly ONE jit trace
    of the packed step over the whole run."""
    init, opt, step = _setup()
    CAP = 3
    tasks = [_lane_task(init, opt, i, steps=2 + (5 * i) % 7)
             for i in range(3 * CAP)]
    pool = _pool(step, init, opt, CAP)
    losses, stats, _ = _run_collect(tasks, pool)
    assert stats.n_traces == 1, (
        f"expected exactly one trace, got {stats.n_traces}")
    assert stats.attaches == 3 * CAP
    for i in range(3 * CAP):
        assert len(losses[i]) == 2 + (5 * i) % 7


def test_refill_beats_waves_on_skewed_budgets():
    init, opt, step = _setup()
    CAP = 3
    mk = lambda: [_lane_task(init, opt, i, steps=1 + (4 * i) % 9)
                  for i in range(9)]
    wave = run_waves(lambda: _pool(step, init, opt, CAP), mk())
    pool = _pool(step, init, opt, CAP)
    refill = RefillExecutor(pool).run(mk())
    assert wave.lane_steps == refill.lane_steps      # same useful work
    assert refill.global_steps < wave.global_steps   # fewer pool steps
    assert refill.occupancy > wave.occupancy


# ---------------------------------------------------------------------------
# property: refill never double-books a lane
# ---------------------------------------------------------------------------

@given_cases(n=15, seed=3)
def test_refill_never_runs_two_tasks_on_one_lane(rng):
    init, opt, step = _setup()
    cap = int(rng.integers(1, 4))
    n_tasks = int(rng.integers(1, 9))
    tasks = [_lane_task(init, opt, i, steps=int(rng.integers(1, 6)))
             for i in range(n_tasks)]
    budgets = {t.id: t.steps for t in tasks}
    pool = _pool(step, init, opt, cap)
    ex = RefillExecutor(pool, record_history=True)
    stats = ex.run(tasks)
    seen = {}
    per_task = {}
    for g, lane, tid in ex.history:
        key = (g, lane)
        assert key not in seen, \
            f"lane {lane} ran tasks {seen[key]} and {tid} at step {g}"
        seen[key] = tid
        per_task[tid] = per_task.get(tid, 0) + 1
    # every task ran exactly its budget, nothing more
    assert per_task == budgets
    assert stats.lane_steps == sum(budgets.values())


def test_pool_step_failure_raises_poolsteperror_but_callbacks_raw():
    from repro.core.lanepool import PoolStepError
    init, opt, step = _setup()
    pool = _pool(step, init, opt, 2)
    t = _lane_task(init, opt, 0, 2)
    pool.attach(0, 0, *t.init_fn(), t.hparams)
    bad = {"x": jnp.zeros((2, 16, 5)), "y": jnp.zeros((2, 16, 4))}
    with pytest.raises(PoolStepError):  # contraction mismatch: pool-wide
        pool.step(bad)
    # a bug in a user callback must propagate RAW (no OOM misdiagnosis)
    pool2 = _pool(step, init, opt, 2)
    ex = RefillExecutor(pool2, on_metrics=lambda t, s, m: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        ex.run([_lane_task(init, opt, 0, 2)])


def test_refill_periodic_checkpoint_hook():
    init, opt, step = _setup()
    pool = _pool(step, init, opt, 2)
    saved = []
    ex = RefillExecutor(pool, checkpoint_every=2,
                        on_checkpoint=lambda t, p, o: saved.append(
                            (t.id, t.step_done)))
    ex.run([_lane_task(init, opt, 0, 5), _lane_task(init, opt, 1, 2)])
    # task 0 checkpoints at steps 2 and 4 (not 5: detach saves via
    # on_finish); task 1 finishes exactly at its would-be checkpoint
    assert saved == [(0, 2), (0, 4)]


def test_attach_occupied_lane_raises():
    init, opt, step = _setup()
    pool = _pool(step, init, opt, 2)
    t = _lane_task(init, opt, 0, 2)
    pool.attach(0, 0, *t.init_fn(), t.hparams)
    with pytest.raises(RuntimeError, match="already occupied"):
        pool.attach(0, 1, *t.init_fn(), t.hparams)
    with pytest.raises(RuntimeError, match="not occupied"):
        pool.detach(1)


# ---------------------------------------------------------------------------
# masked execution modes (PR 7): where / compact / kernel equivalence
# ---------------------------------------------------------------------------

def _pool_mode(step, init, opt, capacity, mode):
    tmpl = init(jax.random.PRNGKey(0))
    return LanePool(capacity, step, template_params=tmpl,
                    template_opt=opt.init(tmpl),
                    template_hparams=jnp.float32(0.0), exec_mode=mode)


def test_compact_mode_bit_identical_through_refill():
    """The full executor lifecycle (skewed budgets, attach/detach churn)
    produces bit-identical per-task losses in "where" and "compact"
    modes, and compact compiles at most log2(capacity)+1 programs."""
    init, opt, step = _setup()
    CAP = 4
    mk = lambda: [_lane_task(init, opt, i, steps=1 + (5 * i) % 7)
                  for i in range(3 * CAP)]
    ref_losses, ref_stats, _ = _run_collect(
        mk(), _pool_mode(step, init, opt, CAP, "where"))
    got_losses, got_stats, _ = _run_collect(
        mk(), _pool_mode(step, init, opt, CAP, "compact"))
    assert set(got_losses) == set(ref_losses)
    for tid in ref_losses:
        np.testing.assert_array_equal(np.float32(ref_losses[tid]),
                                      np.float32(got_losses[tid]))
    assert ref_stats.lane_steps == got_stats.lane_steps
    assert got_stats.n_traces <= 3   # buckets {1, 2, 4} at capacity 4


def test_compact_mode_traces_once_per_occupancy_bucket():
    init, opt, step = _setup()
    pool = _pool_mode(step, init, opt, 4, "compact")
    tasks = [_lane_task(init, opt, i, 99) for i in range(4)]

    def step_pool(n_att):
        batch = packing.stack_trees(
            [jax.tree_util.tree_map(jnp.asarray, _batch(i, 0))
             for i in range(4)])
        pool.step(batch)

    for n, want in ((1, 1), (2, 2), (3, 3), (4, 3)):  # buckets 1,2,4,4
        for lane in range(n - 1 if n > 1 else 0, n):
            if lane not in pool.active_lanes():
                pool.attach(lane, n * 10 + lane, *tasks[lane].init_fn(),
                            tasks[lane].hparams)
        step_pool(n)
        assert pool.n_traces == want, (n, pool.n_traces)
    # repeat steps at seen occupancies: no new traces
    pool.detach(3)
    step_pool(3)
    pool.detach(2)
    step_pool(2)
    assert pool.n_traces == 3


def test_kernel_mode_pool_freezes_inactive_lanes():
    """exec_mode="kernel" takes a POOL-LEVEL mask-aware step; inactive
    lane state must pass through bit-identically and active lanes match
    the same step run dense."""
    from repro.kernels import ops as kops

    def pool_step(params, opt_state, batch, hp, active):
        pred = kops.packed_matmul(batch["x"], params["w"], active=active,
                                  impl="pallas_interpret")
        err = pred - batch["y"]
        xt = jnp.swapaxes(batch["x"], -1, -2)
        grad = kops.packed_matmul(xt, err, active=active,
                                  impl="pallas_interpret") / batch["x"].shape[-2]
        loss = jnp.mean(err * err, axis=(-1, -2))
        return ({"w": params["w"] - hp.reshape(-1, 1, 1) * grad},
                {"m": opt_state["m"] * 0.9 + loss * 0.1}, {"loss": loss})

    J, nb, d = 3, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    tmpl_p = {"w": jax.random.normal(ks[0], (d, d)) * 0.1}
    pool = LanePool(J, pool_step, template_params=tmpl_p,
                    template_opt={"m": jnp.float32(0.0)},
                    template_hparams=jnp.float32(0.0), exec_mode="kernel")
    lane_p = {"w": jax.random.normal(ks[1], (d, d)) * 0.1}
    pool.attach(0, 0, lane_p, {"m": jnp.float32(0.0)}, jnp.float32(1e-2))
    pool.attach(2, 2, jax.tree_util.tree_map(lambda a: a + 0.5, lane_p),
                {"m": jnp.float32(0.0)}, jnp.float32(1e-2))
    before_lane1 = jax.tree_util.tree_map(np.asarray, pool.params)
    batch = {"x": jax.random.normal(ks[2], (J, nb, d)),
             "y": jnp.zeros((J, nb, d))}
    pool.step(batch)
    # lane 1 (never attached) untouched bit-for-bit
    np.testing.assert_array_equal(np.asarray(pool.params["w"][1]),
                                  before_lane1["w"][1])
    # active lanes match a dense run through the SAME compiled wrapper
    dense_step = packing.packed_kernel_step(pool_step, donate=False)
    dense_p, _, _ = dense_step(
        {"w": jnp.asarray(before_lane1["w"])},
        {"m": jnp.zeros((J,), jnp.float32)}, batch,
        jnp.full((J,), 1e-2, jnp.float32), jnp.ones((J,), jnp.int32))
    for lane in (0, 2):
        np.testing.assert_array_equal(np.asarray(pool.params["w"][lane]),
                                      np.asarray(dense_p["w"][lane]))
    assert pool.n_traces == 1


@given_cases(n=10, seed=11)
def test_exec_modes_agree_random_lifecycle(rng):
    """Property: a random attach/detach/step schedule gives bit-identical
    per-task losses and final states in "where" and "compact" modes."""
    init, opt, step = _setup()
    cap = int(rng.integers(2, 5))
    n_tasks = int(rng.integers(cap, 2 * cap + 1))
    steps = [int(rng.integers(1, 5)) for _ in range(n_tasks)]
    mk = lambda: [_lane_task(init, opt, i, steps=steps[i])
                  for i in range(n_tasks)]
    a, _, _ = _run_collect(mk(), _pool_mode(step, init, opt, cap, "where"))
    b, _, _ = _run_collect(mk(), _pool_mode(step, init, opt, cap, "compact"))
    assert set(a) == set(b)
    for tid in a:
        np.testing.assert_array_equal(np.float32(a[tid]),
                                      np.float32(b[tid]))


# ---------------------------------------------------------------------------
# per-gang lane-occupancy gauge
# ---------------------------------------------------------------------------

def test_gang_lane_gauge_decays_per_gang():
    from repro.core.monitor import TenantGauges
    g = TenantGauges(occupancy_decay=0.5)
    # gang A holds steady at 100%; gang B churns 100% -> 0%
    for _ in range(8):
        g.on_lane_sample("u", "gang:A", 4, 4)
    for frac in (4, 4, 0, 0):
        g.on_lane_sample("u", "gang:B", frac, 4)
    a, b = g.gang_gauge("gang:A"), g.gang_gauge("gang:B")
    assert a.occupancy == pytest.approx(1.0)       # B's churn can't leak in
    assert 0.0 < b.occupancy < 1.0
    assert b.last == 0.0
    table = g.gang_table()
    assert "gang:A" in table and "gang:B" in table
    g.on_gang_done("gang:B")
    assert "gang:B" not in g.gang_table()
