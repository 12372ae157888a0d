"""SSM + attention substrate invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SSMConfig
from repro.models import attention, ssm
from repro.kernels import ref
from tests.prop import given_cases


# ---------------------------------------------------------------------------
# SSD / Mamba2
# ---------------------------------------------------------------------------

@given_cases(n=10, seed=11)
def test_ssd_chunked_matches_recurrence(rng):
    b = int(rng.integers(1, 3))
    nh = int(rng.choice([1, 2, 4]))
    hd = int(rng.choice([4, 8, 16]))
    N = int(rng.choice([8, 16]))
    chunk = int(rng.choice([8, 16, 32]))
    S = chunk * int(rng.integers(1, 5))
    ks = jax.random.split(jax.random.PRNGKey(int(rng.integers(1 << 20))), 5)
    x = jax.random.normal(ks[0], (b, S, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    B = jax.random.normal(ks[3], (b, S, N))
    C = jax.random.normal(ks[4], (b, S, N))
    y1, s1 = ssm.ssd_chunked(x, dt, A, B, C, chunk=chunk)
    y2, s2 = ssm.ssd_reference_recurrent(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=2e-4,
                               atol=2e-4)


def test_ssd_chunked_grads_finite_when_decay_overflows():
    """Within a chunk the log-decay spread can pass f32's exp range
    (dt * |A| summed over Q steps > 88, as at mamba2-130m's init with
    A down to -16 and Q = 128). The masked upper triangle must not turn
    that overflow into NaN gradients."""
    b, S, nh, hd, N, Q = 1, 32, 2, 4, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (b, S, nh, hd))
    dt = jnp.ones((b, S, nh))
    A = jnp.array([-16.0, -1.0])
    B = jax.random.normal(ks[1], (b, S, N))
    C = jax.random.normal(ks[2], (b, S, N))

    def loss(x, dt, B, C):
        y, st = ssm.ssd_chunked(x, dt, A, B, C, chunk=Q)
        return jnp.sum(y ** 2) + jnp.sum(st)
    grads = jax.grad(loss, argnums=(0, 1, 2, 3))(x, dt, B, C)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))


def test_mamba2_prefill_then_decode_continues_exactly():
    """Decode from the prefill state == running the longer sequence."""
    cfg = SSMConfig(state_dim=16, head_dim=8, expand=2, conv_width=4,
                    chunk_size=16)
    d = 32
    p = ssm.init_mamba2(jax.random.PRNGKey(0), d, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 33, d)) * 0.5
    # full pass over 33 tokens
    y_full, _ = ssm.mamba2_block(p, x[:, :32], d, cfg)
    # prefill 32 (chunk-aligned), then decode token 32
    _, state = ssm.mamba2_block(p, x[:, :32], d, cfg)
    z, xBC, dt_raw, (d_in, nh, ch) = ssm._project(p, x[:, :32], d, cfg)
    conv_state = xBC[:, -(cfg.conv_width - 1):]
    y_t, _ = ssm.mamba2_decode_step(
        p, x[:, 32], {"conv": conv_state, "ssm": state}, d, cfg)
    # reference: full 33-token pass, take last step (chunk pad to 33? use
    # recurrent oracle through the block by running block on padded len)
    # Instead compare against block run at chunk=1 semantics via decode chain:
    st = {"conv": jnp.zeros_like(conv_state), "ssm": jnp.zeros_like(state)}
    ys = []
    for t in range(33):
        y_step, st = ssm.mamba2_decode_step(p, x[:, t], st, d, cfg)
        ys.append(y_step)
    np.testing.assert_allclose(np.asarray(y_t), np.asarray(ys[32]),
                               rtol=1e-4, atol=1e-4)
    # and the chunked block matches the decode chain everywhere
    np.testing.assert_allclose(np.asarray(y_full),
                               np.asarray(jnp.stack(ys[:32], 1)),
                               rtol=1e-3, atol=1e-3)


def test_causal_conv_is_causal():
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
    b = jnp.zeros((8,))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 8))
    y1 = ssm.causal_conv1d(x, w, b)
    x2 = x.at[:, 10:].set(99.0)                 # corrupt the future
    y2 = ssm.causal_conv1d(x2, w, b)
    np.testing.assert_array_equal(np.asarray(y1[:, :10]),
                                  np.asarray(y2[:, :10]))


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

@given_cases(n=10, seed=13)
def test_chunked_attention_matches_ref(rng):
    B = int(rng.integers(1, 3))
    Hkv = int(rng.choice([1, 2, 4]))
    G = int(rng.choice([1, 2, 4]))
    D = int(rng.choice([8, 16, 32]))
    S = int(rng.integers(8, 128))
    causal = bool(rng.integers(0, 2))
    window = int(rng.choice([0, 16]))
    ks = jax.random.split(jax.random.PRNGKey(int(rng.integers(1 << 20))), 3)
    q = jax.random.normal(ks[0], (B, S, Hkv * G, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = attention.sdpa_chunked(q, k, v, causal=causal, window=window,
                                 chunk_k=32)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_ring_cache_decode_matches_full_attention_within_window():
    """Windowed ring cache (size == window) must equal full attention with
    the same window mask, across a wrap-around boundary."""
    B, Hq, Hkv, D, W = 1, 2, 2, 8, 8
    total = 20                                   # wraps the 8-slot ring twice
    params = attention.init_attention(jax.random.PRNGKey(0), 16, Hq, Hkv, D,
                                      jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, total, 16)) * 0.5
    # reference: full self-attention with window
    ref_out, _ = attention.attention_block(
        params, x, num_heads=Hq, num_kv_heads=Hkv, head_dim=D,
        positions=jnp.broadcast_to(jnp.arange(total), (B, total)),
        rope_theta=1e4, causal=True, window=W, impl="xla")
    # streaming: decode one token at a time through a ring cache of size W
    cache = attention.init_kv_cache(B, W, Hkv, D, jnp.float32)
    outs = []
    for t in range(total):
        o, cache = attention.attention_block(
            params, x[:, t:t + 1], num_heads=Hq, num_kv_heads=Hkv,
            head_dim=D, positions=jnp.full((B, 1), t, jnp.int32),
            rope_theta=1e4, causal=True, window=W, kv_cache=cache,
            impl="xla")
        outs.append(o)
    stream = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(stream), np.asarray(ref_out),
                               rtol=2e-4, atol=2e-4)


def test_mrope_sections_and_rotation():
    from repro.models import layers
    D = 32
    sizes = layers.mrope_section_sizes(D)
    assert sum(sizes) == D // 2 and len(sizes) == 3
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, D))
    # all-equal position streams == plain rope
    pos = jnp.broadcast_to(jnp.arange(4), (3, 1, 4)).astype(jnp.int32)
    a = layers.apply_mrope(x, pos, 1e4)
    b = layers.apply_rope(x, pos[0], 1e4)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)
    # norm preservation (rotations)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(a)),
                               np.linalg.norm(np.asarray(x)), rtol=1e-5)
