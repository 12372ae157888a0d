"""Every cell's control flow at CPU size, in this process, through the
same runner the chip runs use; and the runner's refusals.

The tiny cells live in a benchmark root built by adding files only
(``tiny.make_root``), so their runs also show that a new configuration,
mix, limits file and per-layer metric are found by name."""
import json
import os
import shutil
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BASE = os.path.dirname(HERE)
REPO = os.path.dirname(BASE)
sys.path[:0] = [HERE, BASE, os.path.join(REPO, "src")]

import pytest  # noqa: E402

import run  # noqa: E402
import tiny  # noqa: E402

SEED = 2**31 + 7


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _run(root, cell, trace=False, control=False):
    return run.run_cell(root, cell, SEED, 1.0, trace, control=control,
                        t_start=run.time.perf_counter(), hbm_budget=64e6)


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct_with_no_device_metric_off_the_chip(root, cell,
                                                              trace):
    obs, res = _run(root, cell, trace)
    assert res["correct"], res["checks"]
    assert res["device"]["platform"] == "cpu" and res["metrics"] == {}
    assert "breakdown" not in res and obs.trace is None
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert obs.end_to_end["setup_s"] > 0 and obs.window_s >= 1.0
    assert obs.counters["setup_programs"] > 0
    assert obs.counters["setup_compile_s"] > 0
    json.dumps(res)


def test_a_metric_added_by_file_is_read(root):
    obs, _ = _run(root, "mamba2-tiny.sweep-tiny")
    import bench
    cell = bench.load_cell(root, "mamba2-tiny.sweep-tiny")
    assert "pool_steps" in [m["name"] for m in cell.per_layer]
    assert cell.reader("pool_steps").read(obs, cell, {}) \
        == obs.counters["pool_steps"] > 0
    assert cell.reader("lanes_per_step.train").read(obs, cell, {}) == 2.0
    # a per-layer metric with no workloads key reaches the new cell by the
    # end-to-end metric it moves, and a serve metric does not
    names = [m["name"] for m in cell.per_layer]
    assert "pool_step_ms.train" in names and "mfu.serve" not in names
    # device readers find nothing to read off the chip, and say so
    for name in ("pool_step_ms.train", "idle_share.train", "mfu.train",
                 "hbm_peak_share.train"):
        assert cell.reader(name).read(obs, cell, {"kind": "cpu"}) is None


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_the_control_fails_the_full_size_limits(root, cell):
    """With the control in the program's place, each cell comes out not
    correct against the limits of its full-size cell (which the tiny
    cells keep), through a number the check compares."""
    _, res = _run(root, cell, control=True)
    assert not res["correct"]
    failed = [k for k, c in res["checks"].items()
              if not c["value"] <= c["limit"]]
    assert failed and "programs_in_window" not in failed, res["checks"]
    assert list(res)[-1] == "checks"


def test_a_pool_out_of_reach_stops_the_sweep_with_its_cause(root,
                                                          monkeypatch):
    """The sweep driver finds the lane pool on its callers' stack. Where the
    program no longer holds one there that the driver can see (here: the
    driver looks for a type the program never makes), the run stops at the
    first pool step with an error that names the lookup, rather than train
    on with no window."""
    import repro.launch.sweep  # noqa: F401  the program keeps the real type
    from repro.core import lanepool
    monkeypatch.setattr(lanepool, "LanePool", type("LanePool", (), {}))
    with pytest.raises(RuntimeError, match="_find_pool found no LanePool"):
        _run(root, "mamba2-tiny.sweep-tiny")


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "mamba2-130m.sweep-long", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_no_tpu_exits_nonzero_with_no_result():
    p = _cli(REPO)
    assert p.returncode == 3 and p.stdout == ""
    assert "needs 1 TPU chip" in p.stderr


def test_benchmark_files_alone_exit_nonzero_with_no_result(tmp_path):
    shutil.copytree(BASE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "mamba2-130m.sweep-long", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
