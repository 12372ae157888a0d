"""Compile the main-path kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned blocks, primitives Mosaic has no lowering
for, VMEM overruns. These cases hand real widths to the TPU compiler,
which is installed here and compiles for a ``v5e:2x2`` topology that is
described, not attached. Nothing runs. The topology is described inside
a module-scoped fixture (never at import), so every test worker collects
the same tests and only the worker given this file loads libtpu.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.fused_rmsnorm import packed_rmsnorm
from repro.kernels.packed_gemm import packed_gemm
from repro.kernels.ssd_scan import ssd_scan
from repro.launch.serve import make_prefill
from repro.models import ParallelCtx, build_model


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"      # no compiler logs in /tmp
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler: skip, say why
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _compile(sharding, fn, *shapes):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs;
    returns the compiled program's HLO text."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("masked", [False, True])
def test_flash_attention_compiles_at_stablelm_widths(one_chip, masked):
    B, S, H, D = 2, 512, 32, 64
    qkv = [((B, S, H, D), jnp.bfloat16)] * 3
    if masked:
        text = _compile(one_chip,
                        lambda q, k, v, a: flash_attention_fwd(
                            q, k, v, active=a),
                        *qkv, ((B,), jnp.int32))
    else:
        text = _compile(one_chip, flash_attention_fwd, *qkv)
    assert "tpu_custom_call" in text


def test_packed_gemm_masked_compiles(one_chip):
    J, M, K, N = 8, 512, 768, 768
    text = _compile(one_chip, lambda x, w, a: packed_gemm(x, w, active=a),
                    ((J, M, K), jnp.float32), ((J, K, N), jnp.float32),
                    ((J,), jnp.int32))
    assert "tpu_custom_call" in text


def test_packed_rmsnorm_masked_compiles(one_chip):
    J, rows, d = 8, 512, 2048
    text = _compile(one_chip,
                    lambda x, w, a: packed_rmsnorm(x, w, active=a),
                    ((J, rows, d), jnp.float32), ((J, d), jnp.float32),
                    ((J,), jnp.int32))
    assert "tpu_custom_call" in text


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    b, S, nh, hd, N = 1, 512, 24, 64, 128
    text = _compile(one_chip,
                    lambda x, dt, A, B, C: ssd_scan(x, dt, A, B, C,
                                                    chunk=128),
                    ((b, S, nh, hd), jnp.float32), ((b, S, nh), jnp.float32),
                    ((nh,), jnp.float32), ((b, S, N), jnp.float32),
                    ((b, S, N), jnp.float32))
    assert "tpu_custom_call" in text


def test_stablelm_prefill_holds_flash_kernel(one_chip):
    """The server's prefill at published widths, with the Pallas path
    named explicitly (this host's backend is the CPU, where the default
    impl is XLA), compiles and carries the flash kernel."""
    cfg = configs.get("stablelm-1.6b")
    model = build_model(cfg, ParallelCtx(attn_impl="pallas"))
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32, sharding=one_chip)
    compiled = jax.jit(make_prefill(model, 80)).lower(
        params, {"tokens": tokens}).compile()
    assert "tpu_custom_call" in compiled.as_text()
