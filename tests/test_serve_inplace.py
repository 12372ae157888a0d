"""The decode pool stays where it lies: layer-major, updated in place.

``BatchServer`` keeps the model's own decode cache at batch = lanes as its
pool and steps it with the model's batched ``decode_step``; each step
writes one K/V row per layer and lane into the donated pool, and a joiner
is written into its lane by one donated ``attach_lane``. These tests read
the compiled programs (no pool-sized copy, transpose or broadcast; the
pool aliased from input to output), check the decode-attention kernel
against the XLA path, and check that every family serves a request the
same tokens whatever it shares the pool with.
"""
import ast
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core.monitor import span_log
from repro.kernels import decode_attention
from repro.launch import serve
from repro.launch.serve import BatchServer, Request
from repro.models import ParallelCtx, attention, build_model

LANES, MAX_LEN = 8, 1024


def _small_stablelm():
    """stablelm-1.6b at 4 layers and d 512 (8 heads of 64), in the
    benchmark's types: fp32 weights, bf16 compute and cache."""
    cfg = dataclasses.replace(
        configs.get("stablelm-1.6b"), num_layers=4, d_model=512,
        num_heads=8, num_kv_heads=8, d_ff=1024, vocab_size=1024,
        param_dtype="float32", compute_dtype="bfloat16", remat=True)
    return build_model(cfg)


def _outputs(hlo: str, min_elems: int):
    """(opcode, shape) of each instruction whose output has at least
    ``min_elems`` elements."""
    found = []
    for line in hlo.splitlines():
        m = re.search(r"= (\w+)\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(", line)
        if m:
            elems = int(np.prod([int(d) for d in m.group(2).split(",") if d]))
            if elems >= min_elems:
                found.append((m.group(3), m.group(2)))
    return found


def _aliases(hlo: str) -> str:
    """The module header's input/output aliasing (donations that took)."""
    header = hlo.splitlines()[0]
    start = header.find("input_output_alias=")
    return header[start:header.find("entry_computation_layout")] \
        if start >= 0 else ""


@pytest.fixture(scope="module")
def small():
    model = _small_stablelm()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = jax.eval_shape(lambda: model.make_cache(LANES, MAX_LEN))
    return model, params, pool


def test_decode_step_writes_the_pool_in_place(small):
    model, params, pool = small
    batch = {"tokens": jax.ShapeDtypeStruct((LANES, 1), jnp.int32),
             "pos": jax.ShapeDtypeStruct((LANES,), jnp.int32)}
    step = jax.jit(serve.make_serve_step(model), donate_argnums=(2,))
    hlo = step.lower(params, batch, pool).compile().as_text()
    leaf = int(np.prod(pool["k"].shape))
    assert pool["k"].shape[:2] == (4, LANES)          # layer-major
    moved = [o for o in _outputs(hlo, leaf)
             if o[0] in ("copy", "transpose", "broadcast")]
    assert not moved, moved
    # every pool leaf is donated: k, v, len and pos alias into the output
    assert _aliases(hlo).count("may-alias") == 4
    assert "HloModule jit_serve_step" in hlo


def test_attach_lane_donates_the_pool(small):
    model, _, pool = small
    lane_cache = jax.eval_shape(lambda: model.make_cache(1, MAX_LEN))
    attach = jax.jit(model.attach_lane, donate_argnums=(0,))
    hlo = attach.lower(pool, lane_cache,
                       jax.ShapeDtypeStruct((), jnp.int32)).compile().as_text()
    assert _aliases(hlo).count("may-alias") == 4
    assert "HloModule jit_attach_lane" in hlo
    leaf = int(np.prod(pool["k"].shape))
    assert not [o for o in _outputs(hlo, leaf)
                if o[0] in ("copy", "transpose", "broadcast")]


def _hybrid_irregular():
    """The zamba2 block at smoke widths over the published layout's three
    shapes: hybrid layers at 2, 3, 5 and 7 of 9 give a first unit of
    unequal segments (2 plain layers, then none), a unit of (1, 1) and a
    plain tail layer."""
    return dataclasses.replace(configs.get("zamba2-7b").reduced(),
                               num_layers=9, hybrid_layer_ids=(2, 3, 5, 7))


def test_lane_axes_follow_the_batch():
    axes = build_model(_hybrid_irregular()).lane_axes()
    # a run's plain Mamba states are (units, layers, B, ...); the hybrid
    # layer's state and the shared block's K/V (units, B, ...); the tail
    # (n, B, ...)
    first, second = axes["runs"]
    assert first[0]["plain"] == {"conv": 2, "ssm": 2}
    assert "plain" not in first[1]
    assert second[1]["plain"] == {"conv": 2, "ssm": 2}
    for seg in first + second:
        assert seg["layer"] == {"conv": 1, "ssm": 1}
        assert seg["kv"] == {"k": 1, "v": 1, "len": 1, "pos": 1}
    assert axes["tail"] == {"conv": 1, "ssm": 1}


@pytest.mark.parametrize("B,Hq,Hkv,D,S,window,ends", [
    pytest.param(3, 4, 2, 16, 24, 0, None, id="3-4-2-16-24-0"),     # GQA
    pytest.param(2, 4, 4, 16, 40, 8, None, id="2-4-4-16-40-8"),     # windowed
    # several head blocks
    pytest.param(2, 16, 16, 64, 128, 0, None, id="2-16-16-64-128-0"),
    # several position blocks
    pytest.param(1, 2, 1, 128, 8192, 0, None, id="1-2-1-128-8192-0"),
    # ... the first of them all masked
    pytest.param(1, 2, 1, 128, 8192, 1000, None, id="1-2-1-128-8192-1000"),
    # four blocks of 1024: one valid position, the last and the first
    # position of a block, the full cache, and a lane with none
    pytest.param(5, 8, 4, 128, 4096, 0, (0, 1023, 1024, 4095, -1),
                 id="lanes-in-different-blocks"),
    # windowed lanes whose leading blocks are all masked
    pytest.param(3, 8, 4, 128, 4096, 1000, (1500, 3000, 4095),
                 id="window-masks-leading-blocks")])
def test_decode_kernel_matches_xla_path(B, Hq, Hkv, D, S, window, ends):
    """The Pallas decode attention (interpret mode) gives the XLA path's
    output and the same in-place cache update, reading layer 1 of a
    3-layer stack. ``ends`` puts each lane's new token at a position of
    its own (-1: a lane with no valid position, whose output is only
    finite). Reading each lane's live position blocks gives every live
    lane exactly what reading every block gives."""
    L, n = 3, (5 if S <= 128 else S - 300)
    ks = jax.random.split(jax.random.PRNGKey(B * S), 5)
    params = attention.init_attention(ks[0], Hq * D, Hq, Hkv, D,
                                      jnp.bfloat16)
    x = jax.random.normal(ks[1], (B, 1, Hq * D)).astype(jnp.bfloat16)
    lens = n + jnp.arange(B) if ends is None else jnp.array(ends)
    live = np.asarray(lens >= 0)
    slots = jnp.arange(S)[None, :]
    pos = jnp.where(slots < lens[:, None], slots, -1).astype(jnp.int32)
    cache = {"k": jax.random.normal(ks[2], (L, B, S, Hkv * D)).astype(
                 jnp.bfloat16),
             "v": jax.random.normal(ks[3], (L, B, S, Hkv * D)).astype(
                 jnp.bfloat16),
             "len": jnp.broadcast_to(lens, (L, B)).astype(jnp.int32),
             "pos": jnp.broadcast_to(pos, (L, B, S))}
    out = {}
    for impl in ("xla", "pallas_interpret"):
        out[impl] = attention.attention_block(
            params, x, num_heads=Hq, num_kv_heads=Hkv, head_dim=D,
            positions=lens[:, None].astype(jnp.int32), rope_theta=1e4,
            window=window, kv_cache=cache, layer=jnp.int32(1), impl=impl)
    np.testing.assert_allclose(
        np.asarray(out["xla"][0], np.float32)[live],
        np.asarray(out["pallas_interpret"][0], np.float32)[live], atol=1e-2)
    assert np.isfinite(np.asarray(out["pallas_interpret"][0],
                                  np.float32)).all()
    for a, b in zip(jax.tree_util.tree_leaves(out["xla"][1]),
                    jax.tree_util.tree_leaves(out["pallas_interpret"][1])):
        assert jnp.array_equal(a, b)
    new = out["xla"][1]
    # only layer 1 moved, by one row per sequence at its ring slot
    assert jnp.array_equal(new["len"][1], lens + 1)
    assert jnp.array_equal(new["len"][0], cache["len"][0])
    changed = np.asarray(new["pos"] != cache["pos"])
    assert changed.sum() == live.sum() and changed[1].sum() == live.sum()
    # the live blocks alone against every block, on the updated cache
    p, cur = new["pos"][1], lens[:, None]
    valid = (p >= 0) & (p <= cur)
    if window:
        valid = valid & (p > cur - window)
    q = jax.random.normal(ks[4], (B, Hq, D)).astype(jnp.bfloat16)
    nblk = S // decode_attention.position_block(S, Hkv, D)
    read = decode_attention.decode_attention_fwd(
        q, new["k"], new["v"], valid, jnp.int32(1), interpret=True)
    every = decode_attention._decode_attention(
        q, new["k"], new["v"], valid, jnp.int32(1),
        jnp.zeros((B,), jnp.int32), jnp.full((B,), nblk - 1, jnp.int32),
        scale=None, interpret=True)
    assert np.isfinite(np.asarray(read)).all()
    assert jnp.array_equal(read[live], every[live])


@pytest.mark.parametrize("Hkv,D,S,hb,bs", [
    (32, 64, 1024, 8, 1024),    # stablelm-1.6b: 512 lanes, one block
    (32, 64, 4096, 8, 1024),    # ... at its published context
    (3, 64, 4096, 3, 2048),     # odd head count: the full 192-wide row
    (2, 16, 24, 2, 24),         # narrower than a tile: the full row
    (32, 224, 4096, 4, 512),    # zamba2-7b: the narrowest legal, 896 wide
    (8, 128, 32768, 4, 1024)])
def test_decode_kernel_blocks(Hkv, D, S, hb, bs):
    """Head blocks are a multiple of 128 lanes wide or the full row, and
    a K/V block holds at most ``BLOCK_ELEMS`` elements."""
    assert decode_attention.head_block(Hkv, D) == hb
    assert decode_attention.seq_block(S, hb * D) == bs
    assert bs * hb * D <= decode_attention.BLOCK_ELEMS or bs == S


@pytest.mark.parametrize("max_len,blocks,P", [(2048, 2, 1020),
                                              (1024, 1, 1012)])
def test_decode_spans_count_the_blocks_the_kernel_reads(max_len, blocks, P):
    """Every ``serve.decode`` span counts the kernel's position blocks
    over its live lanes and those it reads (``Model.decode_reads``): at
    step t each live lane is at position P + t and reads the blocks up to
    the one holding it."""
    model = _small_stablelm()
    params = model.init(jax.random.PRNGKey(2))
    bs = decode_attention.position_block(max_len, 8, 64)
    assert model.decode_reads(np.array([P, bs - 1]), max_len) == {
        "kv_positions": min(P + 1, max_len) + bs,
        "kv_blocks": 2 * blocks, "kv_blocks_read": -(-(P + 1) // bs) + 1}
    assert max_len // bs == blocks
    max_news = (3, 6, 9)
    rng = np.random.default_rng(0)
    reqs = [Request(id=i, prompt=rng.integers(1, 1024, P).astype(np.int32),
                    max_new=m) for i, m in enumerate(max_news)]
    before = {s.index for s in span_log()}
    BatchServer(model, params, batch_lanes=3, max_len=max_len).run(reqs)
    decodes = [s.counts for s in span_log()
               if s.index not in before and s.name == "serve.decode"]
    assert len(decodes) == max(max_news) - 1
    for t, c in enumerate(decodes):
        live = sum(m - 1 > t for m in max_news)
        assert c["lanes"] == live
        assert c["kv_blocks"] == live * blocks
        assert c["kv_blocks_read"] == live * -(-(P + t + 1) // bs)
    shares = [c["kv_blocks_read"] / c["kv_blocks"] for c in decodes]
    assert shares[0] == 1 / blocks and shares[-1] == 1.0


def test_serving_loop_leaves_the_pool_format_to_the_model():
    """``launch/serve.py`` moves requests through lanes and nothing more:
    it imports no kernel, names no cache leaf and reads neither the
    attention window nor whether the model has attention; the model
    answers all of that."""
    with open(serve.__file__) as f:
        tree = ast.parse(f.read())
    imported = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names]
    imported += [n.module or "" for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom)]
    assert not [m for m in imported if m.startswith("repro.kernels")]
    leaves = {"conv", "ssm", "k", "v"}
    assert not [n.value for n in ast.walk(tree)
                if isinstance(n, ast.Constant) and n.value in leaves]
    assert not [n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)
                and n.attr in ("window", "is_attention_free")]


def test_decode_kernel_refuses_an_undividable_cache():
    with pytest.raises(ValueError, match="no block"):
        decode_attention.seq_block(1000, 1024)


def _family(name):
    cfg = configs.get(name).reduced()
    if cfg.family == "hybrid":
        cfg = _hybrid_irregular()
    # a routed MoE (not the dense oracle) so that capacity is exercised
    return build_model(cfg, ParallelCtx(moe_oracle=cfg.family != "moe"))


@pytest.mark.parametrize("name,lanes", [
    ("stablelm-1.6b", 3), ("zamba2-7b", 3), ("mamba2-130m", 3),
    ("deepseek-moe-16b", 12)])
def test_tokens_equal_served_alone(name, lanes):
    """Each request's greedy tokens equal those of the same request
    served alone: joins mid-decode, lanes left empty as the queue drains,
    and, for the routed MoE, more tokens a step than its default expert
    capacity."""
    model = _family(name)
    params = model.init(jax.random.PRNGKey(1))
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(0)
    n_req = lanes + 3
    prompts = [rng.integers(1, vocab, 5).astype(np.int32)
               for _ in range(n_req)]
    max_news = [int(m) for m in rng.integers(2, 9, n_req)]
    mk = lambda: [Request(id=i, prompt=p, max_new=m)
                  for i, (p, m) in enumerate(zip(prompts, max_news))]
    out = BatchServer(model, params, batch_lanes=lanes, max_len=24).run(mk())
    solo = BatchServer(model, params, batch_lanes=1, max_len=24)
    for r in mk():
        assert out[r.id] == solo.run([r])[r.id], r.id
