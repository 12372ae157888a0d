"""Manual compute/communication overlap: ppermute-pipelined collective
matmul (the classic "all-gather matmul" overlap pattern).

FSDP's per-layer weight all-gather is a bulk collective that XLA may or
may not overlap with compute. This shard_map primitive does it by
construction: the weight's sharded dim rotates around the ring via
collective-permute while each shard's partial matmul runs, so communication
of chunk i+1 hides behind compute of chunk i on TPU (on CPU this is a
semantics/equivalence vehicle — tested against the plain matmul).

    y = x @ W  with W sharded on its FIRST dim over ``axis``:
    each step computes x_chunk_i @ W_shard_i and rotates W.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def allgather_matmul(x: jax.Array, w_shard: jax.Array, axis: str) -> jax.Array:
    """Inside shard_map. x (T, K) replicated over ``axis``; w_shard
    (K/n, N) = this rank's shard of W's rows. Returns x @ W (T, N)."""
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    k_shard = w_shard.shape[0]

    def body(i, carry):
        acc, w_cur = carry
        # which shard of W do we hold at step i? (rotated up i times)
        src = (idx + i) % n
        x_chunk = jax.lax.dynamic_slice_in_dim(x, src * k_shard, k_shard, 1)
        acc = acc + x_chunk @ w_cur
        # rotate shards one step around the ring (overlaps with next matmul)
        w_nxt = jax.lax.ppermute(
            w_cur, axis, [(j, (j - 1) % n) for j in range(n)])
        return acc, w_nxt

    acc0 = jnp.zeros((x.shape[0], w_shard.shape[1]), x.dtype)
    acc, _ = jax.lax.fori_loop(0, n, body, (acc0, w_shard))
    return acc


def reducescatter_matmul(x: jax.Array, w_shard: jax.Array, axis: str
                         ) -> jax.Array:
    """Inside shard_map. x (T, K) replicated; w_shard (K, N/n) = this
    rank's column shard. Returns this rank's (T, N/n) — a TP matmul whose
    output stays sharded (no collective at all; for symmetry/benchmarks)."""
    return x @ w_shard
