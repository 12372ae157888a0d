"""The zamba2 serving cell at CPU size, and the readers of its new metrics.

A tiny zamba2 cell (the configuration's hybrid block at smoke widths, the
serve driver, the full-size cell's limit) is added to a benchmark root
built as ``tiny.make_root`` builds its own, by adding files only. It runs
once through the runner the chip runs use. The two new readers, and the
flash share that this cell reads too, are then given a trace made here
(no device runs off the chip) beside the run's own span log, and checked
against a count by hand."""
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BASE = os.path.dirname(HERE)
sys.path[:0] = [HERE, BASE, os.path.join(os.path.dirname(BASE), "src")]

import pytest  # noqa: E402

import bench  # noqa: E402
import run  # noqa: E402
import spans  # noqa: E402
import tiny  # noqa: E402
import xplane  # noqa: E402

SEED = 2**31 + 13
CELL, FULL = "zamba2-tiny.serve-tiny", "zamba2-7b.serve-doc"
NEW = ("decode_roofline.hybrid", "decode_attention_roofline.hybrid")
#: the metrics this cell reads: the new ones and the prefill's flash share
READ = NEW + ("flash_attention_roofline.serve",)
SMALL = {"num_layers": 6, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "head_dim": 32, "d_ff": 96, "vocab_size": 256,
         "hybrid_layer_ids": [2, 5], "adapter_rank": 8,
         "ssm": {"state_dim": 16, "head_dim": 16, "num_heads": 8, "expand": 2,
                 "conv_width": 4, "chunk_size": 8, "ngroups": 2},
         "param_dtype": "float32", "compute_dtype": "float32",
         "remat": False}
V5E = {"kind": "TPU v5 lite"}


def _add_zamba2(root: str):
    base = os.path.join(root, "chipbench")
    with open(os.path.join(base, "configs", "zamba2-7b.json")) as f:
        cfg = json.load(f)
    cfg["model"].update(SMALL, name="zamba2-tiny")
    with open(os.path.join(base, "configs", "zamba2-tiny.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(base, "configs", "zamba2-7b.py"),
                os.path.join(base, "configs", "zamba2-tiny.py"))
    shutil.copy(os.path.join(base, "limits", FULL + ".json"),
                os.path.join(base, "limits", CELL + ".json"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "zamba2-tiny", "source": "test",
                         "file": "chipbench/configs/zamba2-tiny.json",
                         "reduced": sorted(SMALL), "why": "test"})
    b["workloads"].append({"name": CELL, "config": "zamba2-tiny",
                           "traffic": "serve-tiny", "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if FULL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f, indent=1)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench")))
    _add_zamba2(root)
    return root


@pytest.fixture(scope="module")
def served(root):
    """(cell, obs, result, the window's serve.decode counts, the pool's
    serve.pool counts) of one untraced run."""
    obs, res = run.run_cell(root, CELL, SEED, 1.0, False,
                            t_start=run.time.perf_counter())
    cell = bench.load_cell(root, CELL)
    decodes = [s.counts for s in spans.in_window(obs, "serve.decode")]
    pool = [s.counts for s in spans.log() if s.name == "serve.pool"][-1]
    return cell, obs, res, decodes, pool


def test_the_cell_runs_correct(served):
    cell, obs, res, _, _ = served
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert obs.counters["compared_tokens"] > 0
    # the new per-layer metrics and the flash share reach the cell by
    # their workloads lists, the serve metrics with no list by the
    # end-to-end metric they move
    names = [m["name"] for m in cell.per_layer]
    assert set(READ) <= set(names) and "prefill_ms.serve" in names


def test_the_control_reads_far_above_the_sound_run(root, served):
    """At smoke widths the tied table (rows drawn at 0.02) gives logits
    of a tenth of the full size's, so no gap reaches the full-size limit;
    the control (float8 operands) still reads ten times the sound run."""
    _, res = run.run_cell(root, CELL, SEED, 1.0, False, control=True,
                          t_start=run.time.perf_counter())
    sound = served[2]["checks"]["logit_gap"]["value"]
    assert res["checks"]["logit_gap"]["value"] > 10 * sound, \
        (res["checks"], sound)


def test_the_pool_counts_its_recurrent_state(served):
    """``state_bytes`` is the lanes' recurrent state, as the reference
    reckons one sequence's; the rest of ``cache_bytes`` is K/V and its
    bookkeeping."""
    cell, _, _, _, pool = served
    m, ref = cell.config["model"], cell.reference
    assert pool["state_bytes"] == pool["lanes"] * ref.state_bytes(m)
    z = ref.dims(m)
    max_len = cell.traffic["max_len"]
    kv = 2 * 4 * z["apps"] * max_len * z["kv"] * z["hd"]   # fp32 cache here
    book = 4 * z["apps"] * (1 + max_len)                    # len, pos
    assert pool["cache_bytes"] - pool["state_bytes"] == \
        pool["lanes"] * (kv + book)


def test_kv_positions_count_the_live_lanes_positions(served):
    cell, _, _, decodes, _ = served
    prompt = cell.traffic["prompt_len"]["value"]
    assert decodes and all("kv_positions" in c for c in decodes)
    for c in decodes:
        # each live lane attends its prompt, its tokens so far, the new one
        assert c["lanes"] * (prompt + 1) <= c["kv_positions"] \
            <= c["lanes"] * cell.traffic["max_len"]


def _trace(decode_ms, kernel_ms, flash_ms, steps):
    """A reduced trace of ``steps`` decode steps, each with two kernel
    calls, and one prefill with two flash calls."""
    ops = [xplane.Op("decode_attention", "jit_serve_step", 0, kernel_ms * 1e6,
                     True) for _ in range(2 * steps)]
    ops += [xplane.Op("fusion.1", "jit_serve_step", 0, 1e6, False),
            xplane.Op("flash", "jit_prefill", 0, flash_ms * 1e6, True),
            xplane.Op("flash", "jit_prefill", 0, flash_ms * 1e6, True)]
    return xplane.Reduced(window_s=1.0, chips=1, busy_s=1.0,
                          modules={"jit_serve_step": [decode_ms * 1e-3] * steps,
                                   "jit_prefill": [0.1]},
                          ops=ops, gaps=[])


def test_new_readers_against_a_count_by_hand(served):
    import peaks
    cell, obs, _, _, _ = served
    m, ref = cell.config["model"], cell.reference
    z = ref.dims(m)
    # the window as the readers see it now: the log's last entry closes it
    decodes = [s.counts for s in spans.in_window(obs, "serve.decode")]
    bw, peak = peaks.PEAKS[V5E["kind"]]["hbm_bw"], \
        peaks.PEAKS[V5E["kind"]]["flops"]
    obs.trace = _trace(decode_ms=2.0, kernel_ms=0.1, flash_ms=0.05, steps=4)
    try:
        read = {n: cell.reader(n).read(obs, cell, V5E) for n in READ}
    finally:
        obs.trace = None
    kv = sum(c["kv_positions"] for c in decodes) / len(decodes)
    lanes = sum(c["lanes"] for c in decodes) / len(decodes)
    step = sum(ref.decode_bytes(m, c["kv_positions"] / c["lanes"], c["lanes"])
               for c in decodes) / len(decodes)
    assert read["decode_roofline.hybrid"] == pytest.approx(
        100 * step / bw / 2e-3)
    # the tiny cell computes in float32: K, V and q of 4 bytes an element
    call = 8 * kv * z["kv"] * z["hd"] + 8 * lanes * z["h"] * z["hd"]
    assert read["decode_attention_roofline.hybrid"] == pytest.approx(
        100 * call / bw / 1e-4)
    s = obs.counters["prompt_len"]
    least = max(4 * z["h"] * z["hd"] * s * (s + 1) / 2 / peak,
                4 * z["h"] * s * z["hd"] * 2 / bw)
    assert read["flash_attention_roofline.serve"] == pytest.approx(
        100 * least / 5e-5)


def test_new_readers_read_nothing_without_the_counters(served, monkeypatch):
    """A program that counts no ``kv_positions`` (as before they were
    added) gives the decode readers nothing to read, and no error."""
    cell, obs, _, _, _ = served
    bare = [s._replace(counts={}) if s.name == "serve.decode" else s
            for s in spans.log()]
    monkeypatch.setattr(spans, "log", lambda: bare)
    obs.trace = _trace(decode_ms=2.0, kernel_ms=0.1, flash_ms=0.05, steps=4)
    try:
        for n in NEW:
            assert cell.reader(n).read(obs, cell, V5E) is None
    finally:
        obs.trace = None
