"""Compile the main-path kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned blocks, primitives Mosaic has no lowering
for, VMEM overruns. These cases hand real widths to the TPU compiler,
which is installed here and compiles for a ``v5e:2x2`` topology that is
described, not attached. Nothing runs. The topology is described inside
a module-scoped fixture (never at import), so every test worker collects
the same tests and only the worker given this file loads libtpu.
"""
import dataclasses
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.configs.base import ModelConfig, SSMConfig
from repro.kernels.decode_attention import (decode_attention_fwd,
                                            position_block)
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.fused_rmsnorm import packed_rmsnorm
from repro.kernels.packed_gemm import packed_gemm
from repro.kernels.ssd_scan import ssd_scan
from repro.launch.serve import make_prefill, make_serve_step
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


def test_flash_attention_compiles_at_zamba2_widths(one_chip):
    """32 heads of 224 (no multiple of the 128 lanes: one block of the
    full head) over a 2048-token prompt, scores scaled by (224/2)^-0.5."""
    B, S, H, D = 1, 2048, 32, 224
    text = _compile(one_chip,
                    lambda q, k, v: flash_attention_fwd(
                        q, k, v, scale=(D / 2) ** -0.5),
                    *[((B, S, H, D), jnp.bfloat16)] * 3)
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


@pytest.mark.parametrize("Hq,Hkv,D,S", [
    (32, 32, 64, 4096),     # stablelm-1.6b at its published context
    (12, 3, 64, 4096)])     # an odd KV head count: 192-wide rows
def test_decode_attention_compiles(one_chip, Hq, Hkv, D, S):
    """The decode-attention kernel over a 24-layer stack of 8 caches,
    long enough to take several position blocks."""
    L, B = 24, 8
    text = _compile(one_chip,
                    lambda q, k, v, valid, layer: decode_attention_fwd(
                        q, k, v, valid, layer),
                    ((B, Hq, D), jnp.bfloat16),
                    ((L, B, S, Hkv * D), jnp.bfloat16),
                    ((L, B, S, Hkv * D), jnp.bfloat16),
                    ((B, S), jnp.bool_), ((), jnp.int32))
    assert "tpu_custom_call" in text


def test_decode_attention_compiles_at_the_stablelm_cell(one_chip):
    """The kernel over the stablelm cell's pool: 8 lanes of 1024
    positions of 32 heads of 64, one position block, so each lane reads
    the whole cache as one block, as before it read only live blocks."""
    L, B, H, D, S = 24, 8, 32, 64, 1024
    assert position_block(S, H, D) == S
    text = _compile(one_chip,
                    lambda q, k, v, valid, layer: decode_attention_fwd(
                        q, k, v, valid, layer),
                    ((B, H, D), jnp.bfloat16),
                    ((L, B, S, H * D), jnp.bfloat16),
                    ((L, B, S, H * D), jnp.bfloat16),
                    ((B, S), jnp.bool_), ((), jnp.int32))
    assert "tpu_custom_call" in text


def test_decode_attention_compiles_at_zamba2_widths(one_chip):
    """The kernel over the zamba2 cell's pool: 2 shared applications, 32
    lanes, 4096 positions of 32 heads of 224 (7168-wide rows, read in
    896-wide head blocks), scores scaled by (224/2)^-0.5; eight position
    blocks of 512, of which each lane reads its live ones."""
    L, B, H, D, S = 2, 32, 32, 224, 4096
    assert S // position_block(S, H, D) == 8
    text = _compile(one_chip,
                    lambda q, k, v, valid, layer: decode_attention_fwd(
                        q, k, v, valid, layer, scale=(D / 2) ** -0.5),
                    ((B, H, D), jnp.bfloat16),
                    ((L, B, S, H * D), jnp.bfloat16),
                    ((L, B, S, H * D), jnp.bfloat16),
                    ((B, S), jnp.bool_), ((), jnp.int32))
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


@pytest.mark.parametrize("max_len", [1024, 4096])
def test_stablelm_decode_keeps_the_pool_in_place(one_chip, max_len):
    """The server's decode step at published widths, 8 lanes and a pool
    of the benchmark's 1024 positions or of the model's published 4096 in
    the benchmark's types: every op whose output is the size of a K/V pool
    leaf is an in-place row write (a scatter, or the fusion around one),
    and the decode-attention kernel reads the pool. A chip whose compiler
    wants another layout for the pool than the one it stores shows a
    pool-sized copy here."""
    cfg = dataclasses.replace(configs.get("stablelm-1.6b"),
                              param_dtype="float32",
                              compute_dtype="bfloat16", remat=True)
    model = build_model(cfg, ParallelCtx(attn_impl="pallas"))
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    lanes = 8
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = on_chip(jax.eval_shape(lambda: model.make_cache(lanes, max_len)))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((lanes, 1), jnp.int32),
                     "pos": jax.ShapeDtypeStruct((lanes,), jnp.int32)})
    text = jax.jit(make_serve_step(model), donate_argnums=(2,)).lower(
        params, batch, pool).compile().as_text()
    assert "tpu_custom_call" in text
    leaf = pool["k"].size
    others = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(", line)
        if not (m and m.group(1) and math.prod(
                int(d) for d in m.group(1).split(",")) >= leaf):
            continue
        op = m.group(2)
        if op in ("parameter", "get-tuple-element", "scatter") or (
                op == "fusion" and '/scatter"' in line):
            continue
        others.append(line.strip()[:160])
    assert not others, others


def _zamba2_stage():
    """The benchmark's zamba2-7b stage: 12 layers at published widths,
    bf16 weights and compute, on the Pallas path."""
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench", "configs",
            "zamba2-7b.json")) as f:
        m = json.load(f)["model"]
    cfg = ModelConfig(**dict(m, ssm=SSMConfig(**m["ssm"])))
    return build_model(cfg, ParallelCtx(attn_impl="pallas"))


def test_zamba2_prefill_holds_flash_kernel(one_chip):
    """The stage's prefill compiles with the flash kernel at 32 heads of
    224, once for each of its two shared applications."""
    model = _zamba2_stage()
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one_chip)
    text = jax.jit(make_prefill(model, 1024)).lower(
        params, {"tokens": tokens}).compile().as_text()
    assert text.count("tpu_custom_call") >= 2


def test_zamba2_decode_keeps_the_pool_in_place(one_chip):
    """The stage's decode step at the cell's 32 lanes and 4096 positions:
    every op whose output is the size of a K/V pool leaf is an in-place
    row write or a bitcast of the pool, the decode kernel reads it, and no
    float32 copy or transpose as large as one layer's scan state is made
    (the Mamba states are read and rewritten where they lie)."""
    model = _zamba2_stage()
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    lanes, max_len = 32, 4096
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = on_chip(jax.eval_shape(lambda: model.make_cache(lanes, max_len)))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((lanes, 1), jnp.int32),
                     "pos": jax.ShapeDtypeStruct((lanes,), jnp.int32)})
    compiled = jax.jit(make_serve_step(model), donate_argnums=(2,)).lower(
        params, batch, pool).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    kv = pool["runs"][0][0]["kv"]["k"].size           # (1, 32, 4096, 7168)
    state = pool["runs"][0][0]["layer"]["ssm"].size   # (1, 32, 112, 64, 64)
    others = []
    for line in text.splitlines():
        m = re.search(r"= (\w+)\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(", line)
        if not (m and m.group(2)):
            continue
        size, op = math.prod(int(d) for d in m.group(2).split(",")), m.group(3)
        if size >= kv and op not in (
                "parameter", "get-tuple-element", "scatter", "bitcast") and \
                not (op == "fusion" and '/scatter"' in line):
            others.append(line.strip()[:160])
        if m.group(1) == "f32" and size >= state and op in ("copy",
                                                             "transpose"):
            others.append(line.strip()[:160])
    assert not others, others
    # weights 3.52 GB and pool 8.34 GB in place; temporaries stay small
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9
