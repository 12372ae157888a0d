"""Sharding rules over real param trees + multi-device subprocess tests
(device count must be fixed before jax init, so SPMD tests run in a child
python with XLA_FLAGS set)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

import numpy as np
import pytest

from repro import configs
from repro.models import ParallelCtx, build_model

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent(code)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


# ---------------------------------------------------------------------------
# sharding rules (no devices needed — specs only)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", list(configs.available()))
def test_sharding_rules_cover_every_param(arch):
    """Every leaf gets a spec whose rank matches and whose sharded dims
    divide evenly on the production mesh (shapes only, no allocation)."""
    import numpy as _np
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import ShardingRules

    cfg = configs.get(arch)
    model = build_model(cfg)
    p_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    rules = ShardingRules(FakeMesh())  # type: ignore[arg-type]
    spec_tree = rules.tree(p_spec)
    flat_p = jax.tree_util.tree_leaves(p_spec)
    flat_s = jax.tree_util.tree_leaves(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    n_sharded = 0
    for leaf, spec in zip(flat_p, flat_s):
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)
        for dim, axes in enumerate(spec):
            if axes is None:
                continue
            size = (_np.prod([FakeMesh.shape[a] for a in axes])
                    if isinstance(axes, tuple) else FakeMesh.shape[axes])
            assert leaf.shape[dim] % size == 0, \
                f"{arch}: {leaf.shape} dim{dim} ! % {size} ({spec})"
            n_sharded += 1
    # the big weights must actually be sharded
    assert n_sharded >= len(flat_p) * 0.4, f"{arch}: too few sharded params"


def test_large_params_are_model_sharded():
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import ShardingRules

    cfg = configs.get("llama3-405b")
    model = build_model(cfg)
    p_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    class FakeMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    spec = ShardingRules(FakeMesh()).tree(p_spec)  # type: ignore[arg-type]
    # attention q weight: (L, d, H*hd) -> (None, fsdp, model)
    s = spec["blocks"]["attn"]["w_q"]
    assert s == P(None, ("data",), "model")
    s = spec["blocks"]["mlp"]["w_down"]
    assert s == P(None, "model", ("data",))
    # embeddings: vocab over model ONLY (FSDP d-dim sharding collides with
    # the batch's data sharding in the logits contraction — see §Perf it1)
    assert spec["embed"] == P("model", None)


# ---------------------------------------------------------------------------
# multi-device SPMD subprocess tests
# ---------------------------------------------------------------------------

def test_ep_moe_matches_oracle_on_8_devices():
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import MoEConfig
        from repro.models import moe
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        m = MoEConfig(num_experts=8, top_k=2, expert_d_ff=16,
                      capacity_factor=0.0)
        p = moe.init_moe(jax.random.PRNGKey(0), 32, m, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
        y_ref, _ = moe.moe_dense_oracle(p, x, m)
        # aux is computed per data shard then pmean'd (standard
        # per-microbatch load-balance loss) — mirror that in the oracle
        a_ref = (moe.moe_dense_oracle(p, x[:32], m)[1]
                 + moe.moe_dense_oracle(p, x[32:], m)[1]) / 2
        def body(router, wg, wu, wd, xt):
            prm = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
            y, aux = moe.moe_routed(prm, xt, m, ep_axis="model")
            return y, jax.lax.pmean(aux, ("data",))
        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                     in_specs=(P(), P("model"), P("model"), P("model"),
                               P(("data",), None)),
                     out_specs=(P(("data",), None), P()), check_vma=False))
        y_ep, a_ep = fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
        err = float(jnp.abs(y_ref - y_ep).max())
        aerr = abs(float(a_ref) - float(a_ep))
        print("ERR", err, aerr)
        assert err < 1e-4 and aerr < 1e-4, (err, aerr)
    """)
    assert "ERR" in out


def test_compressed_psum_on_4_devices():
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import compressed_psum
        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 128))
        def body(gl):
            exact = jax.lax.psum(gl, "data")
            i8 = compressed_psum(gl, "data", "int8")
            b16 = compressed_psum(gl, "data", "bf16")
            return exact, i8, b16
        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                     in_specs=P("data"),
                     out_specs=(P("data"), P("data"), P("data")),
                     check_vma=False))
        exact, i8, b16 = fn(g)
        rel8 = float(jnp.abs(i8 - exact).max() / jnp.abs(exact).max())
        rel16 = float(jnp.abs(b16 - exact).max() / jnp.abs(exact).max())
        print("REL", rel8, rel16)
        assert rel8 < 0.05 and rel16 < 0.02, (rel8, rel16)
    """, devices=4)
    assert "REL" in out


def test_small_multipod_dryrun_cell():
    """End-to-end dry-run machinery on a (2,2,2) multi-pod mesh with a
    reduced arch — proves the pod axis shards (deliverable e, miniature)."""
    out = _run_sub("""
        import jax
        from repro.launch import dryrun
        from repro.launch.mesh import make_mesh
        from repro.roofline.analysis import HW, analyze_compiled
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        with mesh:
            lowered, n_tok, kind, model = dryrun.lower_cell(
                "stablelm-1.6b", "train_4k", mesh,
                overrides=dict(num_layers=2, d_model=128, num_heads=4,
                               num_kv_heads=4, head_dim=32, d_ff=256,
                               vocab_size=512))
            c = lowered.compile()
        rep = analyze_compiled(c, arch="x", shape="train_4k",
                               mesh_name="2x2x2", chips=8,
                               n_params=1e6, n_tokens=n_tok, kind="train",
                               hw=HW.for_arch("v5e"))
        assert rep.flops_per_dev > 0
        assert rep.coll_operand_bytes > 0      # pod axis collectives exist
        ma = c.memory_analysis()
        print("OK", rep.bottleneck, ma.temp_size_in_bytes)
    """)
    assert "OK" in out


def test_distributed_train_step_runs_on_8_devices():
    """Actually EXECUTE (not just compile) a reduced sharded train step."""
    out = _run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch import dryrun
        from repro.launch.mesh import make_mesh
        from repro import configs, optim
        from repro.launch.train import make_train_step
        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh:
            lowered, _, _, model = dryrun.lower_cell(
                "deepseek-moe-16b", "train_4k", mesh,
                overrides=dict(num_layers=2, d_model=64, num_heads=4,
                               num_kv_heads=4, head_dim=16, d_ff=64,
                               vocab_size=512))
            # build REAL values matching the lowered specs and execute
            model.pctx = model.pctx
            params = model.init(jax.random.PRNGKey(0))
            opt = optim.adamw()
            ostate = opt.init(params)
            step = jax.jit(make_train_step(model, opt))
            B, S = 256, 4096
            # reduced batch to keep runtime sane
            batch = {"tokens": jnp.zeros((16, 128), jnp.int32),
                     "labels": jnp.zeros((16, 128), jnp.int32)}
            params, ostate, m = step(params, ostate, batch,
                                     jnp.float32(1e-3))
            loss = float(m["loss"])
            assert np.isfinite(loss)
            print("LOSS", loss)
    """)
    assert "LOSS" in out
