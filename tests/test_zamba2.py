"""zamba2-7b: the published hybrid block, its plain reference, and the
program against both.

The plain reference is the benchmark's (``chipbench/configs/zamba2-7b.py``,
float32, the scan as its recurrence). It is checked against transformers'
``Zamba2ForCausalLM`` where torch is installed, and the program is checked
against it: its full forward, and the logits it serves through the cache
(prefill, then one decode step at a time) and through ``BatchServer``.
"""
import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ModelConfig, SSMConfig
from repro.launch.serve import BatchServer, Request
from repro.models import build_model, ssm
from repro.models.transformer import hybrid_layout

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "zamba2_reference", os.path.join(REPO, "chipbench", "configs",
                                     "zamba2-7b.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

#: the published block at smoke widths: 12 layers, hybrid at 5 and 11
#: (two units of one run), 2 shared blocks, 2 B/C groups, 4 heads of 32
#: over a 128-wide concat, MLP adapters of rank 8
SMALL = {"name": "zamba2-small", "family": "hybrid", "num_layers": 12,
         "d_model": 64, "num_heads": 4, "num_kv_heads": 4, "head_dim": 32,
         "d_ff": 96, "vocab_size": 256,
         "ssm": {"state_dim": 16, "head_dim": 16, "num_heads": 8,
                 "expand": 2, "conv_width": 4, "chunk_size": 4,
                 "ngroups": 2},
         "hybrid_layer_ids": [5, 11], "num_mem_blocks": 2,
         "adapter_rank": 8, "rope_theta": 10000.0, "mlp_type": "geglu",
         "norm_eps": 1e-05, "tie_embeddings": True,
         "param_dtype": "float32", "compute_dtype": "float32",
         "vocab_pad_to": 256, "remat": False}


def _model(m=SMALL):
    kw = dict(m, ssm=SSMConfig(**m["ssm"]))
    return build_model(ModelConfig(**kw))


def _ref_logits(params, tokens, m=SMALL):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(params, tokens, m))


def test_registry_holds_the_published_block():
    cfg = configs.get("zamba2-7b")
    assert (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == \
        (81, 3584, 14336, 32000)
    assert cfg.hybrid_layer_ids == (6, 11, 17, 23, 29, 35, 41, 47, 53, 59,
                                    65, 71, 77)
    assert (cfg.num_mem_blocks, cfg.adapter_rank) == (2, 128)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 32, 224)
    assert cfg.num_heads * cfg.head_dim == 2 * cfg.d_model
    s = cfg.ssm
    assert (s.num_heads, s.head_dim, s.state_dim, s.ngroups, s.conv_width,
            s.chunk_size) == (112, 64, 64, 2, 4, 256)
    assert cfg.mlp_type == "geglu" and cfg.tie_embeddings
    assert "unverified" not in cfg.source
    # 81 x 78.4M Mamba layers, 2 x 334M shared blocks, 13 x 17.0M adapters
    # and linears, 114.7M embedding
    assert cfg.param_count() == 7_356_749_648
    # the irregular start (6, 11), eleven regular superblocks, the tail
    assert hybrid_layout(cfg) == (((1, (6, 4)), (5, (5, 5)), (1, (5,))), 3)


def test_the_benchmark_stage_holds_1_76b_parameters():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "zamba2-7b.json")) as f:
        m = json.load(f)["model"]
    model = _model(m)
    shapes = jax.eval_shape(lambda k: ref.init(k, m), jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert n == model.cfg.param_count() == 1_757_853_120
    assert ref.weight_bytes(m) == sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(shapes))
    assert hybrid_layout(model.cfg) == (((1, (5, 5)),), 0)


@pytest.mark.parametrize("with_state", [False, True])
def test_chunked_ssd_in_two_groups_matches_the_recurrence(with_state):
    """The program's chunked scan with B/C in 2 groups (heads 0-3 read
    group 0, heads 4-7 group 1) against the recurrence, both the
    program's and the reference's; float32, so to 1e-5 of the output's
    scale (the orders of summation differ)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    b, S, nh, hd, G, N = 2, 48, 8, 16, 2, 16
    x = jax.random.normal(ks[0], (b, S, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, nh)) - 2.0)
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    B = jax.random.normal(ks[3], (b, S, G, N))
    C = jax.random.normal(ks[4], (b, S, G, N))
    s0 = jax.random.normal(ks[5], (b, nh, hd, N)) if with_state else None
    y, final = ssm.ssd_chunked(x, dt, A, B, C, chunk=16, init_state=s0)
    if with_state:   # the recurrence from s0, step by step
        state = s0
        ys = []
        for t in range(S):
            o, state = ssm.ssd_decode_step(state, x[:, t], dt[:, t], A,
                                           B[:, t], C[:, t])
            ys.append(o)
        y_rec, f_rec = jnp.stack(ys, 1), state
    else:
        y_rec, f_rec = ssm.ssd_reference_recurrent(x, dt, A, B, C)
        np.testing.assert_allclose(ref._scan(x, dt, A, B, C), y_rec,
                                   atol=1e-5 * float(jnp.abs(y_rec).max()))
    scale = float(jnp.abs(y_rec).max())
    np.testing.assert_allclose(y, y_rec, atol=1e-5 * scale)
    np.testing.assert_allclose(final, f_rec,
                               atol=1e-5 * float(jnp.abs(f_rec).max()))


def test_program_forward_matches_the_reference():
    """The program's full forward (chunked scan, XLA attention) against
    the reference's, both float32 on the reference's weights: agreement to
    1e-4 of the logits' scale (orders of summation only)."""
    model = _model()
    params = ref.init(jax.random.PRNGKey(3), SMALL)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 256)
    want = _ref_logits(params, tokens)
    got, _ = model.logits(params, {"tokens": tokens})
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())


def test_served_logits_match_the_reference():
    """Prefill, then one decode step a token, through the layer-major
    cache (K/V of both shared applications, conv and scan state of the 12
    Mamba layers), gives every position the reference's logits to 1e-4 of
    their scale; and ``BatchServer``, three lanes joining and leaving,
    serves each request the reference's best token at every position
    (within 1e-4 of the best logit: ties at float32 rounding aside)."""
    model = _model()
    params = ref.init(jax.random.PRNGKey(5), SMALL)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 30), 0, 256)
    want = _ref_logits(params, tokens)[0]
    first, cache = model.prefill(params, {"tokens": tokens[:, :20]}, 32)
    got = [first[0]]
    for t in range(20, 29):
        lg, cache = model.decode_step(
            params, {"tokens": tokens[:, t:t + 1],
                     "pos": jnp.array([t], jnp.int32)}, cache)
        got.append(lg[0])
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.stack(got), want[19:29], atol=1e-4 * scale)

    rng = np.random.default_rng(0)
    reqs = [Request(id=i, prompt=rng.integers(0, 256, 12).astype(np.int32),
                    max_new=int(rng.integers(3, 9))) for i in range(5)]
    out = BatchServer(model, params, batch_lanes=3, max_len=24).run(reqs)
    for r in reqs:
        seq = np.concatenate([r.prompt, np.asarray(out[r.id][:-1], np.int32)])
        lg = _ref_logits(params, jnp.asarray(seq[None]))[0][len(r.prompt) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(out[r.id])), out[r.id]]
        assert gap.max() <= 1e-4 * scale, (r.id, gap)


def test_pool_counts_its_recurrent_state():
    model = _model()
    total, state = model.pool_bytes(3, 24)
    assert state == 3 * ref.state_bytes(SMALL)
    assert total - state == 3 * 2 * (2 * 4 * 24 * 128 + 4 + 4 * 24)
    dense = build_model(configs.get("stablelm-1.6b").reduced())
    assert dense.pool_bytes(2, 8)[1] == 0


def _to_torch(params, m, model):
    """Copy the reference's weights (the program's layout) into
    transformers' Zamba2ForCausalLM."""
    import torch
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    runs, n_tail = ref._layout(m)
    hy = params["hybrid"]
    lm = model.model
    lm.embed_tokens.weight.data = t(params["embed"][:m["vocab_size"]])
    model.lm_head.weight.data = t(params["embed"][:m["vocab_size"]])
    lm.final_layernorm.weight.data = t(params["final_ln"])

    def mamba(layer, p):
        q, mx = p["mamba"], layer.mamba
        layer.input_layernorm.weight.data = t(p["ln"])
        mx.in_proj.weight.data = t(q["w_in"].T)
        mx.conv1d.weight.data = t(q["conv_w"].T[:, None, :])
        mx.conv1d.bias.data = t(q["conv_b"])
        mx.dt_bias.data = t(q["dt_bias"])
        mx.A_log.data = t(q["A_log"])
        mx.D.data = t(q["D"])
        mx.norm.weight.data = t(q["norm_w"])
        mx.out_proj.weight.data = t(q["w_out"].T)

    at = lambda tree, *i: jax.tree_util.tree_map(lambda a: a[i], tree)
    i = app = 0
    for r, (count, plain) in enumerate(runs):
        for j in range(count):
            for s, n in enumerate(plain):
                seg = hy["runs"][r][s]
                for k in range(n):
                    mamba(lm.layers[i], at(seg["plain"], j, k))
                    i += 1
                hl, blk = lm.layers[i], hy["shared"][s]
                mamba(hl.mamba_decoder, at(seg["layer"], j))
                hl.linear.weight.data = t(seg["linear"][j].T)
                st = hl.shared_transformer
                assert st.block_id == s
                st.input_layernorm.weight.data = t(blk["ln_in"])
                st.pre_ff_layernorm.weight.data = t(blk["ln_ff"])
                for name in ("q", "k", "v", "o"):
                    getattr(st.self_attn, name + "_proj").weight.data = \
                        t(blk["attn"]["w_" + name].T)
                ff, ad = st.feed_forward, at(seg["adapter"], j)
                ff.gate_up_proj.weight.data = t(np.concatenate(
                    [blk["mlp"]["w_gate"], blk["mlp"]["w_up"]], 1).T)
                ff.down_proj.weight.data = t(blk["mlp"]["w_down"].T)
                ff.gate_up_proj_adapter_list[app][0].weight.data = t(ad["a"].T)
                ff.gate_up_proj_adapter_list[app][1].weight.data = t(
                    np.concatenate([ad["b_gate"], ad["b_up"]], 1).T)
                i += 1
                app += 1
    for k in range(n_tail):
        mamba(lm.layers[i], at(hy["tail"], k))
        i += 1
    assert i == m["num_layers"]


def test_reference_matches_transformers_zamba2():
    """The reference against transformers' Zamba2ForCausalLM at a tiny
    Zamba2Config (12 layers, hybrid at 5 and 11, 2 shared blocks, 2 B/C
    groups, MLP adapters, rotary on), on the same weights: float32 on
    both sides, to 1e-4 of the logits' scale. Two settings keep
    transformers' CPU path (``torch_forward``) on the published equations:
    it clamps dt below at ``time_step_min``, where the configuration's
    ``time_step_limit`` is null and the CUDA path does not, so
    ``time_step_min`` is 1e-30; and where a sequence spans several chunks
    it sums the carried state over the target chunk's axis (transformers
    4.57.6, ``.sum(dim=2)``), not the source's, so its ``chunk_size``
    holds the whole sequence. The chunk size changes how the scan is
    computed, not what."""
    torch = pytest.importorskip("torch")
    from transformers import Zamba2Config, Zamba2ForCausalLM
    m = SMALL
    s = m["ssm"]
    ids = m["hybrid_layer_ids"]
    hf = Zamba2Config(
        vocab_size=m["vocab_size"], hidden_size=m["d_model"],
        num_hidden_layers=m["num_layers"],
        layers_block_type=["hybrid" if i in ids else "mamba"
                           for i in range(m["num_layers"])],
        mamba_d_state=s["state_dim"], mamba_d_conv=s["conv_width"],
        mamba_expand=s["expand"], mamba_ngroups=s["ngroups"],
        n_mamba_heads=s["num_heads"], chunk_size=32,
        intermediate_size=m["d_ff"], hidden_act="gelu",
        num_attention_heads=m["num_heads"],
        num_key_value_heads=m["num_kv_heads"],
        num_mem_blocks=m["num_mem_blocks"], adapter_rank=m["adapter_rank"],
        use_shared_attention_adapter=False, use_mem_rope=True,
        rope_theta=m["rope_theta"], rms_norm_eps=m["norm_eps"],
        time_step_min=1e-30, use_cache=False, attn_implementation="eager")
    assert hf.attention_head_dim == m["head_dim"]
    torch.manual_seed(0)
    model = Zamba2ForCausalLM(hf).eval()
    params = jax.tree_util.tree_map(
        np.asarray, ref.init(jax.random.PRNGKey(8), m))
    _to_torch(params, m, model)
    tokens = np.random.default_rng(1).integers(0, 256, (2, 24))
    with torch.no_grad():
        got = model(torch.tensor(tokens), use_cache=False).logits.numpy()
    want = _ref_logits(params, jnp.asarray(tokens))
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())
