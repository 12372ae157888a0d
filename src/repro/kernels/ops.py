"""Jit'd dispatch wrappers around the Pallas kernels.

``impl`` is chosen in one place, ``resolve_impl``: an explicit
"pallas", "pallas_interpret" or "xla" is taken as given (an explicit
"pallas" off-TPU fails to lower instead of quietly running XLA), and
``None`` means the platform's own path — the Pallas kernels on a TPU,
XLA elsewhere. Tests pass "pallas_interpret". The flash-attention
wrapper carries a custom_vjp whose backward is recompute through the
memory-efficient jnp path, so the kernels are usable inside train_step.

Lane masking: every packed/lane-batched entrypoint here —
``packed_matmul``, ``packed_norm``, ``flash_attention``, ``ssd`` —
accepts a per-lane ``active`` predicate with an ``active=None``
zero-overhead fast path (the contract MASK201 in repro.analysis
enforces). For packed_matmul/packed_norm/flash_attention on the Pallas
path the mask is fused into the kernel (inactive grid tiles skip the
MXU/VPU work — the packed_gemm / packed_rmsnorm / flash masked
variants; PAL403 in repro.analysis enforces the in-kernel gating); for
``ssd`` (and every XLA fallback) it is a post-hoc where-zero,
semantically identical but not cheaper — the ssd in-kernel gate is the
remaining ROADMAP item 3(a) debt, tracked as the one LINT_BASELINE
entry. These are the building blocks of the pool's three
masked-execution modes — "where", "compact" and "kernel" — dispatched
by core.packing.masked_pool_step (see DESIGN.md §12 for when each
wins).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


IMPLS = ("pallas", "pallas_interpret", "xla")


def resolve_impl(impl: Optional[str] = None) -> str:
    """The kernel path for ``impl``: an explicit choice as given, and
    for ``None`` the Pallas kernels on a TPU backend, XLA elsewhere."""
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; expected one of "
                         f"{IMPLS} or None")
    return impl


# ---------------------------------------------------------------------------
# flash attention (fwd kernel + recompute bwd)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_core(q, k, v, causal: bool, window: int, impl: str,
                          scale: Optional[float]):
    if impl != "xla":
        from repro.kernels.flash_attention import flash_attention_fwd
        return flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   scale=scale,
                                   interpret=impl == "pallas_interpret")
    from repro.models.attention import sdpa_chunked
    return sdpa_chunked(q, k, v, causal=causal, window=window, scale=scale)


def _fa_fwd(q, k, v, causal, window, impl, scale):
    return (_flash_attention_core(q, k, v, causal, window, impl, scale),
            (q, k, v))


def _fa_bwd(causal, window, impl, scale, res, g):
    q, k, v = res
    from repro.models.attention import sdpa_chunked
    _, vjp = jax.vjp(
        lambda q, k, v: sdpa_chunked(q, k, v, causal=causal, window=window,
                                     scale=scale),
        q, k, v)
    return vjp(g)


_flash_attention_core.defvjp(_fa_fwd, _fa_bwd)


def _mask_lanes(active, *arrays):
    """where-zero an ``active`` (J,)-predicated lane axis onto every
    array's leading dim — inactive lanes become exact zeros, active
    lanes pass through bit-identically. The post-hoc mask is
    semantically identical to in-kernel gating, just not cheaper; it
    backs the XLA fallbacks and the ssd kernel (the remaining
    Pallas-native gate — ROADMAP item 3(a) follow-up)."""
    mask = jnp.asarray(active) != 0
    outs = tuple(
        jnp.where(mask.reshape((-1,) + (1,) * (a.ndim - 1)), a,
                  jnp.zeros((), a.dtype))
        for a in arrays)
    return outs[0] if len(outs) == 1 else outs


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_attention_masked_core(q, k, v, active, causal: bool,
                                 window: int, impl: str):
    if impl != "xla":
        from repro.kernels.flash_attention import flash_attention_fwd
        return flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   active=active,
                                   interpret=impl == "pallas_interpret")
    from repro.models.attention import sdpa_chunked
    return _mask_lanes(active,
                       sdpa_chunked(q, k, v, causal=causal, window=window))


def _fam_fwd(q, k, v, active, causal, window, impl):
    out = _flash_attention_masked_core(q, k, v, active, causal, window,
                                       impl)
    return out, (q, k, v, active)


def _fam_bwd(causal, window, impl, res, g):
    q, k, v, active = res
    from repro.models.attention import sdpa_chunked
    _, vjp = jax.vjp(
        lambda q, k, v: _mask_lanes(
            active, sdpa_chunked(q, k, v, causal=causal, window=window)),
        q, k, v)
    dq, dk, dv = vjp(g)
    # integer predicate: its cotangent space is float0, not zeros-like
    d_active = np.zeros(np.shape(active), dtype=jax.dtypes.float0)
    return dq, dk, dv, d_active


_flash_attention_masked_core.defvjp(_fam_fwd, _fam_bwd)


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    impl: Optional[str] = None, *, active=None,
                    scale: Optional[float] = None):
    """Flash attention with the lane-mask contract of DESIGN.md §12:
    ``active`` (bool/int (B,), optional) treats the batch dim as lane
    axis — inactive lanes' outputs are exact zeros, active lanes are
    bit-identical to the unmasked call; ``active=None`` is the
    zero-overhead fast path (the program is byte-unchanged). On the
    Pallas path the predicate rides in SMEM and gates the QK/PV dots
    in-kernel (flash_attention._fwd_masked_kernel); the XLA fallback
    where-zeroes outside the dots. Both run under a custom_vjp whose
    backward is recompute through sdpa_chunked. ``scale`` multiplies the
    scores (head_dim ** -0.5 when None; unmasked calls only)."""
    impl = resolve_impl(impl)
    if active is None:
        return _flash_attention_core(q, k, v, causal, window, impl, scale)
    if scale is not None:
        raise NotImplementedError("a lane-masked flash call takes no scale")
    act = jnp.asarray(active, jnp.int32)
    return _flash_attention_masked_core(q, k, v, act, causal, window, impl)


# ---------------------------------------------------------------------------
# decode attention (one token against a layer-stacked cache)
# ---------------------------------------------------------------------------

def decode_attention(q, k, v, valid, layer, impl: Optional[str] = None, *,
                     scale: Optional[float] = None):
    """One new token per sequence against layer ``layer`` of a
    layer-stacked KV cache: q (B,1,Hq,D); k/v (L,B,Smax,Hkv*D); valid
    (B,Smax) bool. Returns (B,1,Hq,D) in q's dtype. The kernel reads the
    layer straight out of the stack; the XLA path slices it out
    (``attention.sdpa_decode``). ``scale`` as ``flash_attention``'s."""
    impl = resolve_impl(impl)
    if impl != "xla":
        from repro.kernels.decode_attention import decode_attention_fwd
        out = decode_attention_fwd(q[:, 0], k, v, valid, layer, scale=scale,
                                   interpret=impl == "pallas_interpret")
        return out[:, None].astype(q.dtype)
    from repro.models.attention import sdpa_decode
    return sdpa_decode(q, k[layer], v[layer], valid, scale)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

def ssd(x, dt, A, B, C, *, chunk: int = 128, impl: Optional[str] = None,
        active=None):
    """Dispatch to the kernel or the chunked jnp path (``resolve_impl``).

    ``active`` (bool/int (b,), optional): per-lane predicate over the
    batch dim — inactive lanes' y AND final state are exact zeros
    (where-zero applied to both outputs), active lanes bit-identical;
    ``active=None`` leaves the program untouched."""
    impl = resolve_impl(impl)
    if impl != "xla":
        from repro.kernels.ssd_scan import ssd_scan
        y, state = ssd_scan(x, dt, A, B, C, chunk=chunk,
                            interpret=impl == "pallas_interpret")
    else:
        from repro.models.ssm import ssd_chunked
        y, state = ssd_chunked(x, dt, A, B, C, chunk=chunk)
    if active is None:
        return y, state
    return _mask_lanes(active, y, state)


# ---------------------------------------------------------------------------
# packed (multi-job) GEMM
# ---------------------------------------------------------------------------

def packed_matmul(x, w, *, active=None, impl: Optional[str] = None):
    """x (J,M,K) @ w (J,K,N) per job. ``active`` (bool/int (J,), optional)
    zeroes inactive lanes — fused into the kernel on the Pallas path,
    where-masked on the XLA fallback."""
    impl = resolve_impl(impl)
    if impl != "xla":
        from repro.kernels.packed_gemm import packed_gemm
        return packed_gemm(x, w, active=active,
                           interpret=impl == "pallas_interpret")
    from repro.kernels.ref import packed_gemm_ref
    out = packed_gemm_ref(x, w)
    if active is not None:
        mask = jnp.asarray(active).reshape(-1, 1, 1) != 0
        out = jnp.where(mask, out, jnp.zeros((), out.dtype))
    return out


def packed_norm(x, w, *, active=None, eps: float = 1e-5,
                impl: Optional[str] = None):
    """Lane-batched RMSNorm: x (J,rows,d), per-lane weights w (J,d).
    Same ``active`` contract as packed_matmul (inactive lanes -> zeros)."""
    impl = resolve_impl(impl)
    if impl != "xla":
        from repro.kernels.fused_rmsnorm import packed_rmsnorm
        return packed_rmsnorm(x, w, active=active, eps=eps,
                              interpret=impl == "pallas_interpret")
    from repro.models.layers import rms_norm
    out = jax.vmap(lambda xi, wi: rms_norm(xi, wi, eps))(x, w)
    if active is not None:
        mask = jnp.asarray(active).reshape(-1, 1, 1) != 0
        out = jnp.where(mask, out, jnp.zeros((), out.dtype))
    return out
