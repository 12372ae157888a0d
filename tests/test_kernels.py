"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.packed_gemm import packed_gemm
from repro.kernels.ssd_scan import ssd_scan
from repro.models import ssm
from tests.prop import given_cases


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,Hq,Hkv,D,causal,window",
    [
        (2, 128, 128, 4, 2, 64, True, 0),       # GQA causal
        (1, 256, 256, 4, 4, 32, False, 0),      # MHA bidir
        (2, 96, 96, 2, 1, 64, True, 32),        # MQA + sliding window
        (1, 200, 200, 4, 2, 128, True, 0),      # non-block-multiple seq
        (1, 64, 192, 8, 8, 64, False, 0),       # cross-length
    ])
def test_flash_attention_vs_ref(B, Sq, Sk, Hq, Hkv, D, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(Sq + Hq + D), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, D), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **TOL[dtype])


@given_cases(n=8, seed=3)
def test_flash_attention_random_shapes(rng):
    B = int(rng.integers(1, 3))
    Hkv = int(rng.choice([1, 2, 4]))
    G = int(rng.choice([1, 2]))
    D = int(rng.choice([32, 64]))
    S = int(rng.integers(2, 24)) * 8
    causal = bool(rng.integers(0, 2))
    ks = jax.random.split(jax.random.PRNGKey(int(rng.integers(0, 1 << 30))), 3)
    q = jax.random.normal(ks[0], (B, S, Hkv * G, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, D))
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=32, block_k=32,
                              interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_chunked():
    """custom_vjp bwd (recompute) == autodiff of the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))

    def f_kernel(q, k, v):
        return ops.flash_attention(q, k, v, True, 0,
                                   "pallas_interpret").sum()

    def f_ref(q, k, v):
        return ref.attention_ref(q, k, v, causal=True).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,S,nh,hd,N,chunk",
                         [(2, 128, 4, 16, 32, 32),
                          (1, 64, 2, 8, 16, 64),
                          (2, 96, 3, 16, 64, 32)])
def test_ssd_kernel_vs_recurrence(b, S, nh, hd, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + N), 5)
    x = jax.random.normal(ks[0], (b, S, nh, hd), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, nh))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    B = jax.random.normal(ks[3], (b, S, N), dtype)
    C = jax.random.normal(ks[4], (b, S, N), dtype)
    y, st = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y2, st2 = ref.ssd_ref(x.astype(jnp.float32), dt.astype(jnp.float32), A,
                          B.astype(jnp.float32), C.astype(jnp.float32))
    tol = TOL[dtype]
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y2),
                               **tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st2),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_kernel_matches_jnp_chunked_exactly():
    """Kernel and the model's XLA path share the same chunked algorithm."""
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    b, S, nh, hd, N = 2, 256, 4, 32, 64
    x = jax.random.normal(ks[0], (b, S, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    B = jax.random.normal(ks[3], (b, S, N))
    C = jax.random.normal(ks[4], (b, S, N))
    y1, s1 = ssd_scan(x, dt, A, B, C, chunk=64, interpret=True)
    y2, s2 = ssm.ssd_chunked(x, dt, A, B, C, chunk=64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# packed multi-job GEMM
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("J,M,K,N,bm", [(4, 64, 64, 64, 32),
                                        (3, 50, 70, 30, 32),
                                        (8, 128, 32, 16, 64),
                                        (1, 16, 16, 16, 16)])
def test_packed_gemm_vs_ref(J, M, K, N, bm, dtype):
    ks = jax.random.split(jax.random.PRNGKey(J * M + N), 2)
    x = jax.random.normal(ks[0], (J, M, K), dtype)
    w = jax.random.normal(ks[1], (J, K, N), dtype)
    out = packed_gemm(x, w, block_m=bm, block_n=bm, block_k=bm,
                      interpret=True)
    expect = ref.packed_gemm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **TOL[dtype])


def test_ops_dispatch_on_cpu_uses_xla():
    """On CPU with no impl named, ops take the jnp path; an explicit
    "pallas" is never turned into XLA."""
    assert ops.resolve_impl(None) == "xla"
    assert ops.resolve_impl("pallas") == "pallas"
    q = jnp.ones((1, 16, 2, 8))
    out = ops.flash_attention(q, q, q, True, 0)
    assert out.shape == q.shape
    jaxpr = str(jax.make_jaxpr(
        lambda q: ops.flash_attention(q, q, q, True, 0, "pallas"))(q))
    assert "pallas_call" in jaxpr
    with pytest.raises(ValueError, match="unknown kernel impl"):
        ops.resolve_impl("triton")


# ---------------------------------------------------------------------------
# lane-masked packed kernels (PR 7): the `active=` predicate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("active", [(1, 0, 1, 0), (0, 0, 0, 1),
                                    (1, 1, 1, 1)])
def test_packed_gemm_masked_vs_dense(active):
    """Masked grid: active lanes bit-identical to the unmasked kernel,
    inactive lanes exactly zero."""
    J, M, K, N = 4, 64, 64, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    x = jax.random.normal(ks[0], (J, M, K), jnp.float32)
    w = jax.random.normal(ks[1], (J, K, N), jnp.float32)
    dense = packed_gemm(x, w, block_m=32, block_n=32, block_k=32,
                        interpret=True)
    masked = packed_gemm(x, w, active=jnp.asarray(active), block_m=32,
                         block_n=32, block_k=32, interpret=True)
    for j, a in enumerate(active):
        if a:
            np.testing.assert_array_equal(np.asarray(masked[j]),
                                          np.asarray(dense[j]))
        else:
            np.testing.assert_array_equal(np.asarray(masked[j]),
                                          np.zeros((M, N), np.float32))


def test_packed_rmsnorm_masked_vs_oracle():
    from repro.kernels.fused_rmsnorm import packed_rmsnorm
    from repro.models.layers import rms_norm
    J, rows, d = 4, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (J, rows, d), jnp.float32)
    w = 1.0 + 0.1 * jax.random.normal(ks[1], (J, d), jnp.float32)
    active = jnp.asarray([1, 0, 1, 1])
    out = packed_rmsnorm(x, w, active=active, block_rows=8, interpret=True)
    dense = packed_rmsnorm(x, w, block_rows=8, interpret=True)
    for j in range(J):
        if int(active[j]):
            np.testing.assert_array_equal(np.asarray(out[j]),
                                          np.asarray(dense[j]))
            np.testing.assert_allclose(np.asarray(out[j]),
                                       np.asarray(rms_norm(x[j], w[j])),
                                       rtol=2e-5, atol=2e-5)
        else:
            np.testing.assert_array_equal(np.asarray(out[j]),
                                          np.zeros((rows, d), np.float32))


@given_cases(n=8, seed=17)
def test_masked_ops_random_occupancy(rng):
    """Property: for random shapes and occupancy patterns, BOTH dispatch
    paths of ops.packed_matmul (Pallas interpret and the XLA where-mask
    fallback) zero inactive lanes and leave active lanes equal to the
    dense run."""
    J = int(rng.choice([2, 4, 8]))
    M = int(rng.choice([16, 32, 48]))
    K = int(rng.choice([16, 32]))
    N = int(rng.choice([16, 32]))
    mask = rng.integers(0, 2, size=J)
    if mask.sum() == 0:
        mask[int(rng.integers(0, J))] = 1
    ks = jax.random.split(jax.random.PRNGKey(int(rng.integers(1 << 30))), 2)
    x = jax.random.normal(ks[0], (J, M, K), jnp.float32)
    w = jax.random.normal(ks[1], (J, K, N), jnp.float32)
    active = jnp.asarray(mask)
    for impl in ("pallas_interpret", "xla"):
        out = ops.packed_matmul(x, w, active=active, impl=impl)
        dense = ops.packed_matmul(x, w, impl=impl)
        act, inact = np.flatnonzero(mask), np.flatnonzero(mask == 0)
        np.testing.assert_array_equal(np.asarray(out[act]),
                                      np.asarray(dense[act]))
        if inact.size:
            np.testing.assert_array_equal(
                np.asarray(out[inact]),
                np.zeros((inact.size, M, N), np.float32))


@pytest.mark.parametrize("active", [(1, 0, 1, 0), (0, 0, 0, 1),
                                    (1, 1, 1, 1)])
def test_flash_attention_masked_lanes(active):
    """ops.flash_attention honors the active= contract (MASK201): the
    batch dim is the lane axis — active lanes bit-identical to the
    unmasked call, inactive lanes exact zeros."""
    B, S, H, D = 4, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    dense = ops.flash_attention(q, k, v, causal=True)
    masked = ops.flash_attention(q, k, v, causal=True,
                                 active=jnp.asarray(active))
    for b, a in enumerate(active):
        if a:
            np.testing.assert_array_equal(np.asarray(masked[b]),
                                          np.asarray(dense[b]))
        else:
            np.testing.assert_array_equal(np.asarray(masked[b]),
                                          np.zeros((S, H, D), np.float32))


def test_flash_attention_masked_grad_zero_on_inactive():
    """The masked path is its own custom_vjp (recompute through the
    masked sdpa): gradients must still flow — active lanes match the
    dense grad, inactive lanes get zero grad."""
    B, S, H, D = 4, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(29), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    active = jnp.asarray([1, 0, 1, 1])

    g_masked = jax.grad(
        lambda q_: ops.flash_attention(q_, k, v, causal=True,
                                       active=active).sum())(q)
    g_dense = jax.grad(
        lambda q_: ops.flash_attention(q_, k, v, causal=True).sum())(q)
    for b in range(B):
        if int(active[b]):
            np.testing.assert_allclose(np.asarray(g_masked[b]),
                                       np.asarray(g_dense[b]),
                                       rtol=1e-6, atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(g_masked[b]),
                                          np.zeros((S, H, D), np.float32))


@pytest.mark.parametrize("active", [(1, 0, 1, 0), (0, 0, 0, 1),
                                    (1, 1, 1, 1)])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0),
                                           (True, 16)])
def test_flash_native_masked_kernel_interpret(active, causal, window):
    """The Pallas kernel itself (not the XLA fallback) honors the lane
    mask: _fwd_masked_kernel gates the QK/PV dots on the SMEM predicate,
    so active lanes are bit-identical to the unmasked kernel and
    inactive lanes come out as exact zeros from the finalize step."""
    B, S, H, D = 4, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(37), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    dense = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                block_q=32, block_k=32, interpret=True)
    masked = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 block_q=32, block_k=32,
                                 active=jnp.asarray(active),
                                 interpret=True)
    for b, a in enumerate(active):
        if a:
            np.testing.assert_array_equal(np.asarray(masked[b]),
                                          np.asarray(dense[b]))
        else:
            np.testing.assert_array_equal(np.asarray(masked[b]),
                                          np.zeros((S, H, D), np.float32))


def test_flash_native_masked_kernel_grads_interpret():
    """ops.flash_attention's masked Pallas path (interpret mode) runs
    the in-kernel gate forward and the masked-sdpa recompute backward;
    grads match dense on active lanes and are exact zeros elsewhere."""
    B, S, H, D = 4, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(41), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    active = jnp.asarray([0, 1, 1, 0])

    g_masked = jax.grad(
        lambda q_: ops.flash_attention(q_, k, v, causal=True,
                                       impl="pallas_interpret",
                                       active=active).sum())(q)
    g_dense = jax.grad(
        lambda q_: ops.flash_attention(q_, k, v, causal=True,
                                       impl="pallas_interpret").sum())(q)
    for b in range(B):
        if int(active[b]):
            np.testing.assert_allclose(np.asarray(g_masked[b]),
                                       np.asarray(g_dense[b]),
                                       rtol=1e-6, atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(g_masked[b]),
                                          np.zeros((S, H, D), np.float32))


@pytest.mark.parametrize("active", [(1, 0, 1, 0), (0, 1, 0, 0)])
def test_ssd_masked_lanes_y_and_state(active):
    """ops.ssd masks BOTH outputs: y and the final state are zero on
    inactive lanes and bit-identical on active ones."""
    b, S, nh, hd, N = 4, 64, 2, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(31), 5)
    x = jax.random.normal(ks[0], (b, S, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    Bm = jax.random.normal(ks[3], (b, S, N))
    C = jax.random.normal(ks[4], (b, S, N))
    y_d, st_d = ops.ssd(x, dt, A, Bm, C, chunk=32)
    y_m, st_m = ops.ssd(x, dt, A, Bm, C, chunk=32,
                        active=jnp.asarray(active))
    for j, a in enumerate(active):
        if a:
            np.testing.assert_array_equal(np.asarray(y_m[j]),
                                          np.asarray(y_d[j]))
            np.testing.assert_array_equal(np.asarray(st_m[j]),
                                          np.asarray(st_d[j]))
        else:
            np.testing.assert_array_equal(np.asarray(y_m[j]),
                                          np.zeros_like(np.asarray(y_d[j])))
            np.testing.assert_array_equal(np.asarray(st_m[j]),
                                          np.zeros_like(np.asarray(st_d[j])))
