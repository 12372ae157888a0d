"""Roofline-fed mode planning (PR 7): HW presets, IntensityProfile,
record-at-first-dispatch plumbing, and the planner-facing override.

The acceptance criterion tested at the bottom: enabling the roofline
signal changes ModePlanner decisions on the canonical mixed trace, while
disabling it reproduces the default planner's report exactly.
"""
import numpy as np
import pytest

from repro.core import simulate as S
from repro.core import spatial as sp
from repro.core import tenancy as ten
from repro.core import traces as TR
from repro.roofline.analysis import HW, IntensityProfile


# ---------------------------------------------------------------------------
# HW presets
# ---------------------------------------------------------------------------

def test_hw_for_arch_presets():
    class _Dev:                           # a v5e as jax reports it
        platform, device_kind = "tpu", "TPU v5 lite"
    assert HW.for_device(_Dev()) == HW.for_arch("v5e")
    with pytest.raises(ValueError, match="no roofline peaks"):
        HW.for_device()                   # the CPU has no preset
    for arch in ("v4", "v5e", "v5p", "v6e"):
        hw = HW.for_arch(arch)
        assert hw.peak_flops > 0 and hw.hbm_bw > 0
        assert hw.ici_bw > 0 and hw.hbm_bytes > 0
    assert HW.for_arch("v5p").peak_flops > HW.for_arch("v5e").peak_flops


def test_hw_for_arch_unknown_raises():
    with pytest.raises(ValueError, match="v5e"):
        HW.for_arch("h100")


# ---------------------------------------------------------------------------
# IntensityProfile
# ---------------------------------------------------------------------------

def test_intensity_profile_interference_clamps():
    p = IntensityProfile(arithmetic_intensity=2.0, memory_bound_frac=0.7,
                         bottleneck="memory")
    assert p.interference == pytest.approx(0.7)
    hi = IntensityProfile(arithmetic_intensity=0.1, memory_bound_frac=1.7,
                          bottleneck="memory")
    lo = IntensityProfile(arithmetic_intensity=9.0, memory_bound_frac=-0.2,
                          bottleneck="compute")
    assert hi.interference == 1.0
    assert lo.interference == 0.0


def test_intensity_profile_from_compiled_decode_vs_train_ordering():
    """A bandwidth-bound program must score a larger memory_bound_frac
    than a compute-bound one (the signal the planner consumes)."""
    import jax
    import jax.numpy as jnp
    # matmul: high arithmetic intensity -> compute-leaning
    mm = jax.jit(lambda a, b: a @ b).lower(
        jnp.zeros((512, 512)), jnp.zeros((512, 512))).compile()
    # elementwise: one flop per operand byte -> memory-bound
    ew = jax.jit(lambda a, b: a + b).lower(
        jnp.zeros((512, 512)), jnp.zeros((512, 512))).compile()
    hw = HW.for_arch("v5e")
    p_mm = IntensityProfile.from_compiled(mm, hw)
    p_ew = IntensityProfile.from_compiled(ew, hw)
    assert p_ew.memory_bound_frac > p_mm.memory_bound_frac
    assert p_mm.arithmetic_intensity > p_ew.arithmetic_intensity


# ---------------------------------------------------------------------------
# MemoryAdmission.record_intensity
# ---------------------------------------------------------------------------

def test_record_intensity_replace_semantics_and_clamp():
    adm = ten.MemoryAdmission()
    assert adm.measured_intensity("kind:serve") is None
    adm.record_intensity("kind:serve", 0.4)
    adm.record_intensity("kind:serve", 0.9)      # newest replaces
    assert adm.measured_intensity("kind:serve") == pytest.approx(0.9)
    adm.record_intensity("kind:serve", 0.2)      # ...in both directions
    assert adm.measured_intensity("kind:serve") == pytest.approx(0.2)
    adm.record_intensity("kind:serve", 1.8)
    assert adm.measured_intensity("kind:serve") == 1.0
    adm.record_intensity("", 0.5)                # ignored
    adm.record_intensity("u", -0.1)              # ignored
    assert adm.measured_intensity("") is None
    assert adm.measured_intensity("u") is None


# ---------------------------------------------------------------------------
# measured_interference: override + exact fallback
# ---------------------------------------------------------------------------

def _prof(user="alice", kind="serve", intensity=0.1):
    return sp.JobProfile(job_id=1, user=user, intensity=intensity,
                         want_lanes=1, kind=kind)


def test_measured_interference_fallback_is_exactly_default():
    """No measurement recorded -> identical scores to the default
    sources (declared-only, and ewma_interference when gauges exist)."""
    adm = ten.MemoryAdmission()
    p = _prof(intensity=0.37)
    assert sp.measured_interference(adm)(p) == p.intensity

    class FakeGauges:
        def user_occupancy(self, user):
            return 0.81
    g = FakeGauges()
    assert (sp.measured_interference(adm, gauges=g)(p)
            == sp.ewma_interference(g)(p))


def test_measured_interference_override_and_priority():
    adm = ten.MemoryAdmission()
    adm.record_intensity("kind:serve", 0.9)
    adm.record_intensity("alice", 0.3)
    # kind key wins over user key
    assert sp.measured_interference(adm)(_prof()) == pytest.approx(0.9)
    # no kind measurement -> user key
    assert sp.measured_interference(adm)(
        _prof(kind="train")) == pytest.approx(0.3)
    # measurement REPLACES the occupancy proxy (busy compute-bound
    # tenant is no longer priced as thrashy)

    class FakeGauges:
        def user_occupancy(self, user):
            return 1.0
    score = sp.measured_interference(adm, gauges=FakeGauges())(
        _prof(kind="train", intensity=0.05))
    assert score == pytest.approx(0.3)
    # declared intensity and floor still lower-bound
    assert sp.measured_interference(adm)(
        _prof(kind="train", intensity=0.6)) == pytest.approx(0.6)
    assert sp.measured_interference(adm, floor=0.5)(
        _prof(kind="train", intensity=0.0)) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# acceptance: the signal changes planner decisions; off == default exactly
# ---------------------------------------------------------------------------

def test_roofline_signal_flips_planner_decisions_and_off_is_default():
    import dataclasses
    spec = TR.CANONICAL["roofline_mix"]
    base = TR.REPLAY["roofline_mix"]
    jobs = TR.generate(spec)

    on = S.compare_modes(jobs, base.n_nodes,
                         **TR.replay_kwargs(base))          # roofline=True
    off_cfg = dataclasses.replace(base, roofline=False)
    off = S.compare_modes(jobs, base.n_nodes, **TR.replay_kwargs(off_cfg))
    # today's planner, constructed by hand — the "disable" baseline
    kw = TR.replay_kwargs(off_cfg)
    kw["spatial"] = sp.ModePlanner()
    manual = S.compare_modes(jobs, base.n_nodes, **kw)

    assert base.roofline, "canonical roofline_mix replay must enable it"
    key = "shared+spatial"
    # off == default, metric for metric
    for a, b in ((off[key], manual[key]),
                 (off["shared+full"], manual["shared+full"])):
        assert (a.makespan, a.node_util, a.spatial_placements,
                a.preemptions, a.repacks) == \
               (b.makespan, b.node_util, b.spatial_placements,
                b.preemptions, b.repacks)
    # on != off: the measured intensity changed real placement decisions
    assert on[key].spatial_placements != off[key].spatial_placements
    assert (on[key].makespan, on[key].spatial_placements) != \
           (off[key].makespan, off[key].spatial_placements)
