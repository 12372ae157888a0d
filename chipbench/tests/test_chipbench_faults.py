"""A run with its timed path broken underneath comes out not correct.

Each fault is planted in the program (the module the driver calls into),
and the rest of a run is driven at CPU size: one test per fault each cell
can have. The cells run on one chip, so no exchange between chips can be
left out."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BASE = os.path.dirname(HERE)
sys.path[:0] = [HERE, BASE, os.path.join(os.path.dirname(BASE), "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

import run  # noqa: E402
import tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def _correct(root, cell):
    _, res = run.run_cell(root, cell, 12345, 1.0, False,
                          t_start=run.time.perf_counter(), hbm_budget=64e6)
    return res["correct"], res["checks"]


def test_a_step_that_returns_its_state_unchanged(root, monkeypatch):
    from repro.launch import sweep
    real = sweep.make_train_step

    def frozen(model, opt):
        step = real(model, opt)

        def unchanged(params, opt_state, batch, lr):
            _, _, metrics = step(params, opt_state, batch, lr)
            return params, opt_state, metrics
        return unchanged

    monkeypatch.setattr(sweep, "make_train_step", frozen)
    ok, checks = _correct(root, "mamba2-tiny.sweep-tiny")
    assert not ok and checks["update_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(root, monkeypatch):
    from repro.launch import sweep
    real = sweep.make_train_step

    def halved(model, opt):
        step = real(model, opt)

        def half(params, opt_state, batch, lr):
            rows = jax.tree_util.tree_leaves(batch)[0].shape[0] // 2
            return step(params, opt_state,
                        jax.tree_util.tree_map(lambda x: x[:rows], batch), lr)
        return half

    monkeypatch.setattr(sweep, "make_train_step", halved)
    ok, checks = _correct(root, "mamba2-tiny.sweep-tiny")
    assert not ok, checks


def test_a_token_altered_where_it_is_produced(root, monkeypatch):
    from repro.launch import serve
    real = serve.make_serve_step

    def shifted(model):
        step = real(model)

        def off_by_one(params, batch, cache):
            logits, cache = step(params, batch, cache)
            return jnp.roll(logits, 1, axis=-1), cache
        return off_by_one

    monkeypatch.setattr(serve, "make_serve_step", shifted)
    ok, checks = _correct(root, "stablelm-tiny.serve-tiny")
    assert not ok and checks["logit_gap"]["value"] > \
        checks["logit_gap"]["limit"]
