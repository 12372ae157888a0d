"""chip_smoke.py in-process on the CPU, at reduced size: both phases
run through their entry points, the reference comparisons bite, and a
failed phase or a missing TPU gives no result line."""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import chip_smoke  # noqa: E402
from repro.core import packing  # noqa: E402
from repro.core.lanepool import PoolStepError  # noqa: E402


def _result_lines(out: str):
    return [l for l in out.splitlines() if l.startswith('{"ok"')]


def test_rehearsal_runs_both_phases(capsys):
    assert chip_smoke.main(["--cpu-rehearsal"]) == 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out.splitlines()[0]
    assert "backoffs=0" in out and "packed vs alone" in out
    assert "tokens equal to the full-forward reference" in out
    assert "flash kernel in prefill: True" in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}


def test_chip_path_never_imports_the_flag_setters():
    """launch/dryrun.py overwrites XLA_FLAGS as it is imported; nothing
    chip_smoke.py or benchmarks/run.py imports may pull it in."""
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys, benchmarks.run as r, chip_smoke\n"
        "import ast, importlib\n"
        "mods = [n.names for n in ast.walk(ast.parse(open(r.__file__).read()))"
        " if isinstance(n, ast.ImportFrom) and n.module == 'benchmarks']\n"
        "for names in mods:\n"
        "    for a in names: importlib.import_module('benchmarks.' + a.name)\n"
        "bad = {'repro.launch.dryrun'}\n"
        "print(sorted(bad & set(sys.modules)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(root, "src"), root]))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == "[]"


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache is the checkout's fixed .jax_cache."""
    import jax
    from repro.launch.cache import CHECKOUT, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = enable_compile_cache()
        assert path == os.path.join(CHECKOUT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert os.path.isfile(os.path.join(CHECKOUT, "chip_smoke.py"))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    captured = capsys.readouterr()
    assert not _result_lines(captured.out)
    assert "no TPU found" in captured.err


def test_non_oom_step_failure_propagates(monkeypatch, capsys):
    """An injected non-OOM failure of the packed step reaches the caller
    (no backoff), so the script exits non-zero with no result line."""
    def failing(step_fn, **kw):
        def step(*args):
            raise ValueError("injected device fault")
        return step
    monkeypatch.setattr(packing, "masked_pool_step", failing)
    with pytest.raises(PoolStepError) as err:
        chip_smoke.main(["--cpu-rehearsal"])
    assert not err.value.oom
    assert not _result_lines(capsys.readouterr().out)


def test_sweep_reference_catches_wrong_losses(monkeypatch):
    real = chip_smoke.run_sweep

    def skewed(*args, **kw):
        res = real(*args, **kw)
        res.losses[0] = [v + 0.5 for v in res.losses[0]]
        return res
    monkeypatch.setattr(chip_smoke, "run_sweep", skewed)
    with chip_smoke.CompileClock() as clock, \
            pytest.raises(RuntimeError, match="loss diff"):
        chip_smoke.sweep_phase(chip_smoke._sizes(True), 0, clock)


def test_serve_reference_catches_wrong_tokens(monkeypatch):
    real = chip_smoke.BatchServer.run

    def shifted(self, requests):
        out = real(self, requests)
        for r in requests:
            out[r.id][:] = list(np.roll(out[r.id], 1))
        return out
    monkeypatch.setattr(chip_smoke.BatchServer, "run", shifted)
    with chip_smoke.CompileClock() as clock, \
            pytest.raises(RuntimeError, match="differ from the reference"):
        chip_smoke.serve_phase(chip_smoke._sizes(True), 0, clock)
