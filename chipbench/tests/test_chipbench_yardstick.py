"""The yardstick's parts at CPU size: the peak table, the work functions
against hand counts, the traffic generator, the trace reduction on a
trace recorded on a TPU v5e, and the check's leaf rule."""
import json
import math
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BASE = os.path.dirname(HERE)
sys.path[:0] = [BASE, os.path.join(os.path.dirname(BASE), "src")]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import bench  # noqa: E402
import generator  # noqa: E402
import peaks  # noqa: E402
import xplane  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures", "small.xplane.pb")


def _reference(config):
    return bench.load_module(os.path.join(BASE, "configs", config + ".py"))


def test_peak_table_names_v5e_and_refuses_unknown_kinds():
    assert peaks.peaks("TPU v5 lite") == {"flops": 197e12, "hbm_bw": 819e9,
                                          "hbm_bytes": 16e9}
    with pytest.raises(ValueError, match="cpu"):
        peaks.peaks("cpu")


def test_mamba2_train_flops_match_a_hand_count():
    ref = _reference("mamba2-130m")
    m = {"d_model": 8, "num_layers": 2, "vocab_size": 10, "vocab_pad_to": 4,
         "ssm": {"expand": 2, "head_dim": 4, "num_heads": 0, "state_dim": 2,
                 "conv_width": 3, "chunk_size": 3}}
    # d_in 16, 4 heads, N 2, conv channels 20, chunk 3 (2 causal pairs/token)
    proj = 8 * (32 + 4 + 4) + 16 * 8
    ssd = 2 * (2 + 16) + 2 * 16 * 2
    per_token = 2 * (2 * (proj + 3 * 20 + ssd) + 8 * 10)
    assert ref.forward_flops_per_token(m) == per_token
    assert ref.train_flops(m, batch=3, seq=5) == 3 * per_token * 15


def test_mamba2_published_size_counts():
    ref = _reference("mamba2-130m")
    with open(os.path.join(BASE, "configs", "mamba2-130m.json")) as f:
        m = json.load(f)["model"]
    # 24 layers of d 768 and the 50,432-row tied table: about 129M
    assert 125e6 < ref.param_count(m) < 135e6
    # fp32 parameters and two moments, each read and written, and the
    # gradient written and read: 8 float32 passes over the parameters
    assert ref.train_bytes(m) == 32 * ref.param_count(m)
    assert ref.forward_flops_per_token(m) == pytest.approx(281.8e6, rel=1e-3)


def test_stablelm_prefill_and_decode_match_a_hand_count():
    ref = _reference("stablelm-1.6b")
    m = {"d_model": 8, "num_heads": 2, "num_kv_heads": 2, "head_dim": 0,
         "d_ff": 12, "num_layers": 3, "vocab_size": 20, "vocab_pad_to": 4}
    macs = 3 * (4 * 8 * 8 + 3 * 8 * 12)
    assert ref.prefill_flops(m, 4) == 2 * macs * 4 + 4 * 3 * 8 * 10 + 2 * 8 * 20
    assert ref.decode_flops(m, 5) == 2 * (macs + 8 * 20) + 4 * 3 * 8 * 5
    weights = 4 * (macs + 2 * 3 * 8 + 8 + 2 * 20 * 8)
    assert ref.weight_bytes(m) == weights
    kv = 2 * 2 * 3 * 8                  # bf16 K and V per position
    assert ref.decode_bytes(m, 5, 2) == weights - 4 * 20 * 8 + 4 * 2 * 8 \
        + 2 * kv * 5
    assert ref.prefill_bytes(m, 4) == weights - 4 * 20 * 8 + 4 * 4 * 8 + kv * 4


def test_flash_kernel_work():
    flash = bench.load_module(os.path.join(
        BASE, "metrics", "flash_attention_roofline.serve.py"))
    # 3 positions, causal: 6 pairs, two matmuls of 2*d FLOPs per pair
    assert flash.flops(1, 2, 3, 4) == 2 * 6 * 2 * 2 * 4
    assert flash.bytes_moved(1, 2, 3, 4) == 4 * 2 * 3 * 4 * 2


def test_stratified_draws_give_every_seed_the_same_sizes():
    dist = {"kind": "lognormal", "median": 128, "sigma": 0.8, "low": 16,
            "high": 512, "integer": True}
    a = generator.draw(dist, 32, seed=1, stream=4)
    b = generator.draw(dist, 32, seed=2**31 + 11, stream=4)
    assert a != b and sorted(a[:16]) == sorted(b[:16]) \
        and sorted(a[16:]) == sorted(b[16:])
    assert 16 <= min(a) and max(a) <= 512
    lr = generator.draw({"kind": "loguniform", "low": 1.25e-4, "high": 4e-3},
                        16, seed=3, stream=1)
    assert sorted(lr) == pytest.approx(
        [1.25e-4 * 32 ** ((j + 0.5) / 16) for j in range(16)])
    lrs = generator.draw({"kind": "geometric", "base": 1e-3, "ratio": 2.0,
                          "offset": -3}, 6, seed=0, stream=1)
    assert lrs == pytest.approx([1.25e-4, 2.5e-4, 5e-4, 1e-3, 2e-3, 4e-3])


def test_lm_batch_is_the_programs_synthetic_stream():
    from repro.data import SyntheticLM
    got = generator.lm_batch(50280, 64, 3, 2**31 + 5, 7)
    want = SyntheticLM(vocab_size=50280, seq_len=64, batch_size=3,
                       seed=2**31 + 5).batch(7)
    for k in ("tokens", "labels"):
        np.testing.assert_array_equal(got[k], want[k])


def test_trace_reduction_of_a_recorded_trace():
    """The fixture: three rounds of a 256-token flash-attention kernel
    call and a 512x512 matmul, traced on one TPU v5e."""
    r = xplane.reduce(FIXTURE, window_s=0.3)
    assert r.chips == 1
    assert sorted(r.modules) == ["jit__lambda"]
    assert len(r.modules["jit__lambda"]) == 6
    kernel = [op for op in r.ops if op.custom]
    assert len(kernel) == 3 and all(op.module == "jit__lambda"
                                    for op in kernel)
    assert sum(op.dur_ns for op in kernel) == pytest.approx(10550, abs=2)
    # the busy union is no longer than the ops' sum, and inside the window
    assert 0 < r.busy_s <= sum(op.dur_ns for op in r.ops) * 1e-9 + 1e-12
    assert xplane.idle_share(bench.Obs(trace=r)) == pytest.approx(
        100 * (1 - r.busy_s / 0.3))
    assert r.top_ops(1)[0][0] == "jit__lambda/_lambda_.1"
    assert len(r.gaps) == 10 and all(s >= 0 for _, s in r.gaps)
    assert r.gaps[0][1] >= r.gaps[-1][1]


def test_trace_reduction_of_a_trace_with_no_device_is_none(tmp_path):
    import jax
    f = jax.jit(lambda x: x * 2)
    f(np.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(np.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    assert xplane.reduce(str(tmp_path), 0.1) is None


def test_leaf_gap_leaves_out_leaves_that_move_by_round_off():
    sweep = bench.load_module(os.path.join(BASE, "drivers", "sweep.py"))
    ref = np.array([1.0, 2.0, 3.0, 1e-6])
    keep = sweep.moving_leaves(ref)
    assert keep.tolist() == [True, True, True, False]
    assert sweep.leaf_gap(ref, ref, keep) == 0.0
    # measured against the larger of its own norm and the median (2.0)
    assert sweep.leaf_gap([2.0, 2.0, 3.0, 1e-6], ref, keep) == 0.5
    assert sweep.leaf_gap([1.0, 4.0, 3.0, 1e-6], ref, keep) == 1.0
    assert sweep.leaf_gap([1.0, 2.0, 3.0, 5.0], ref, keep) == 0.0
    assert math.isclose(sweep.leaf_gap([1.0, 2.0, 3.3, 1e-6], ref, keep), 0.1)
