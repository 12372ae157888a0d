"""LLload analogue + auto_nppn memory guard."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core.monitor import RunMonitor, StaticProfile, profile_fn


def test_profile_fn_counts_memory_and_flops():
    def f(x, w):
        return jnp.tanh(x @ w).sum()
    p = profile_fn(f, jnp.ones((128, 256)), jnp.ones((256, 512)))
    assert p.argument_bytes == (128 * 256 + 256 * 512) * 4
    assert p.flops > 2 * 128 * 256 * 512 * 0.9
    assert p.resident_bytes > 0


def test_fits():
    p = StaticProfile(argument_bytes=10 ** 9, temp_bytes=10 ** 9,
                      output_bytes=0, flops=1e12, bytes_accessed=0)
    assert p.fits(hbm_budget=16e9)
    assert not p.fits(hbm_budget=2e9)


def test_straggler_detection():
    mon = RunMonitor(straggler_ratio=1.5)
    for step in range(5):
        mon.start_step()
        lane_times = np.array([0.1, 0.1, 0.1, 0.5])   # lane 3 lags
        mon.end_step(step, lane_times)
    assert mon.stragglers() == [3]
    assert mon.summary()["steps"] == 5


def test_auto_nppn_with_real_jit():
    """Packing factor search against a real compiled vmapped step."""
    def step(params, x):
        return params @ x

    def make_packed(k):
        return jax.vmap(step)

    def example_args(k):
        return (jnp.ones((k, 256, 256)), jnp.ones((k, 256, 64)))

    one = autotune.measure_packed(make_packed, 1, example_args)
    per_lane = one.resident_bytes
    budget = per_lane * 4.5
    d = autotune.auto_nppn(make_packed, example_args, budget, max_factor=16,
                           headroom=1.0)
    assert 3 <= d.nppn_per_chip <= 5        # ~4 lanes fit
    assert d.profile.fits(budget, headroom=1.0)

    with pytest.raises(MemoryError):
        autotune.auto_nppn(make_packed, example_args, per_lane * 0.5,
                           max_factor=4, headroom=1.0)


def _fake_measure(per_lane: int):
    """Synthetic probe: a k-lane packed step is exactly k × per_lane bytes
    (memory_analysis is monotone in the packing factor), counting calls."""
    calls = []

    def measure(make_packed, k, example_args_fn):
        calls.append(k)
        return StaticProfile(argument_bytes=per_lane * k, temp_bytes=0,
                             output_bytes=0, flops=0, bytes_accessed=0)

    return measure, calls


@pytest.mark.parametrize("max_factor", [3, 5, 6, 7, 12])
@pytest.mark.parametrize("frontier", [2, 3, 5, 6, 9, 100])
def test_auto_nppn_non_power_of_two_frontier(monkeypatch, max_factor,
                                             frontier):
    """Regression for the packing-frontier gap: the exponential probe never
    tested factors in (2^m, max_factor], so an admission-derived
    non-power-of-two cap (e.g. 6) silently packed at 4. Lock the selected
    factor to the brute-force frontier for every (max_factor, budget)."""
    per_lane = 10 ** 6
    budget = per_lane * frontier        # k fits iff k <= frontier
    measure, calls = _fake_measure(per_lane)
    monkeypatch.setattr(autotune, "measure_packed", measure)
    d = autotune.auto_nppn(None, None, budget, max_factor=max_factor,
                           headroom=1.0)
    brute = max(k for k in range(1, max_factor + 1) if k * per_lane <= budget)
    assert d.nppn_per_chip == brute, (
        f"frontier gap: selected {d.nppn_per_chip}, brute force says {brute}")
    assert max(calls) <= max_factor     # never probes past the cap
    if d.rejected is not None:
        assert d.rejected == brute + 1 or d.rejected > brute


def test_auto_nppn_max_factor_6_selects_6_when_it_fits(monkeypatch):
    """The live utilization loss from ISSUE: admission caps max_pack at 6,
    6 fits, but the old probe returned 4."""
    per_lane = 10 ** 6
    measure, calls = _fake_measure(per_lane)
    monkeypatch.setattr(autotune, "measure_packed", measure)
    d = autotune.auto_nppn(None, None, per_lane * 64, max_factor=6,
                           headroom=1.0)
    assert d.nppn_per_chip == 6
    assert sorted(set(calls)) == [1, 2, 4, 6]   # O(log) probes, cap included


def test_predict_oom_guards_the_48_job_case():
    p = StaticProfile(argument_bytes=48 * 4 * 10 ** 9, temp_bytes=0,
                      output_bytes=0, flops=0, bytes_accessed=0)
    # 48 jobs × 4GB > 64GB of two V100s -> guard fires BEFORE launch
    assert autotune.predict_oom(p, hbm_budget=64e9)


def test_device_hbm_budget_reads_memory_stats():
    """The sweep's budget is the device's free HBM; a backend that
    reports no memory statistics (the CPU) is an error, not a guess."""
    from repro.core.monitor import device_hbm_budget

    class _Dev:
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_limit": 16_000, "bytes_in_use": 1_500}
    assert device_hbm_budget(_Dev()) == 14_500
    with pytest.raises(RuntimeError, match="memory_stats"):
        device_hbm_budget()


def test_sweep_probe_args_are_shapes_only(monkeypatch):
    """auto_nppn's probes compile from shapes: building the k-lane
    arguments allocates no parameters or optimizer state."""
    from repro import configs
    from repro.data import SyntheticLM
    from repro.launch.sweep import SweepTask, run_sweep
    from repro.models import build_model
    model = build_model(configs.get("mamba2-130m").reduced())
    seen = {}
    real = autotune.auto_nppn

    def spy(make_packed, example_args_fn, *a, **kw):
        seen["args"] = example_args_fn(4)
        return real(make_packed, example_args_fn, *a, **kw)
    monkeypatch.setattr(autotune, "auto_nppn", spy)
    res = run_sweep(
        model, [SweepTask(id=i, lr=1e-3, seed=i) for i in range(2)],
        batch_fn=lambda seed, step: SyntheticLM(
            vocab_size=model.cfg.vocab_size, seq_len=32, batch_size=2,
            seed=seed).batch(step),
        steps=1, hbm_budget=1e9)
    leaves = jax.tree_util.tree_leaves(seen["args"])
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct)
                          for x in leaves)
    assert all(x.shape[0] == 4 for x in leaves)
    assert res.pack_factor == 2 and len(res.losses[1]) == 1
