"""The per-layer metrics that read the program's own spans, at CPU size.

Each tiny cell runs once, through the same runner the chip runs use, and
its readers are called right after it: the span log is the process's, and
the window rule takes the log's last entry as the window's close."""
import os
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

SEED = 2**31 + 11
SWEEP, SERVE = "mamba2-tiny.sweep-tiny", "stablelm-tiny.serve-tiny"
#: the readers of each tiny cell's spans
READERS = {SWEEP: ("host_ms.train", "readback_ms.train", "autotune_s.train"),
           SERVE: ("join_ms.serve", "host_ms.serve")}


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """cell -> (obs, what each of its readers read, the window's spans)."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("bench")))
    out = {}
    for name, readers in READERS.items():
        obs, _ = run.run_cell(root, name, SEED, 1.0, False,
                              t_start=run.time.perf_counter(),
                              hbm_budget=64e6)
        cell = bench.load_cell(root, name)
        read = {m: cell.reader(m).read(obs, cell, {"kind": "cpu"})
                for m in readers}
        step = "lanepool.iteration" if name == SWEEP else "serve.step"
        out[name] = (obs, read, spans.in_window(obs, step))
    return out


@pytest.mark.parametrize("cell,metric", [(c, m) for c, ms in READERS.items()
                                         for m in ms])
def test_each_span_reader_reads_a_positive_number(runs, cell, metric):
    value = runs[cell][1][metric]
    assert value is not None and value > 0, (metric, value)


def test_the_window_holds_the_harness_pool_steps(runs):
    """The window rule picks the pool steps the harness counted, to
    within one ``lanepool.iteration``."""
    obs, _, iterations = runs[SWEEP]
    assert abs(len(iterations) - obs.counters["pool_steps"]) <= 1
    assert all(s.counts["lanes"] == 2 for s in iterations)


def test_the_window_tokens_add_up_to_the_harness_tokens(runs):
    """The ``tokens`` counts of the window's ``serve.step`` spans add up
    to the tokens the harness stamped, to within one step's lanes (the
    step whose append closes the window counts its lanes at entry, and
    emits none of them)."""
    obs, _, steps = runs[SERVE]
    lanes = tiny.MIXES["serve-tiny"]["lanes"]
    counted = sum(s.counts["tokens"] for s in steps)
    assert 0 <= counted - obs.counters["tokens"] <= lanes


def test_readers_read_nothing_from_a_program_with_no_span_log(runs,
                                                             monkeypatch):
    """A program without the recorder (as before it was added) gives no
    reading, and no error."""
    from repro.core import monitor
    monkeypatch.delattr(monitor, "span_log")
    obs = runs[SWEEP][0]
    for name, readers in READERS.items():
        for m in readers:
            assert bench.load_module(os.path.join(
                BASE, "metrics", m + ".py")).read(obs, None, {}) is None
