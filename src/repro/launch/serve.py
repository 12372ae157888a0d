"""Serving driver: prefill + continuously-batched decode on a lane pool.

The serve_step builders are what the dry-run lowers for decode shapes; the
``BatchServer`` is a runnable mini-server for the examples. It is TRUE
continuous batching (the inference-side analogue of the paper's
concurrent-jobs-per-GPU packing, on the persistent-lane-pool model of
core/lanepool.py):

  * the decode state is a fixed-capacity pool: the model's own decode
    cache at batch = lanes, layer-major (``(L, lanes, Smax, H*D)`` for
    K/V), the layout the layer scan carries. Decode is the model's batched
    ``decode_step``, compiled ONCE per pool width; each step writes only
    the new token's K/V row per layer and lane into the donated pool, so
    no step copies, transposes or re-stacks it;
  * a request joins MID-DECODE the moment a lane frees: its prompt is
    prefilled at batch 1 and its cache written into the free lane by one
    jitted, donated ``attach_lane`` (no recompilation, other lanes
    undisturbed);
  * a finished lane stops burning decode budget — its request is retired
    immediately (``Request.done``) and the next queued request takes the
    lane, so total active lane-steps equal the sum of per-request
    ``max_new``, not ``capacity × max(max_new)`` (the wave-mode waste).

Lanes are independent: every op of a decode step is per sequence (a
routed MoE step gives every token room at every expert), so a request's
tokens are identical whatever co-residents it decodes next to (prompts are
left-padded to one fixed length per ``run``).

A pool may hold two kinds of state side by side (zamba2): each Mamba
layer's recurrent state (``conv`` and ``ssm`` leaves, rewritten whole by
every step) beside the attention layers' K/V (one row written a step).

``run`` is traced (core/monitor.span): ``serve.pool`` (the empty pool's
allocation, with its ``cache_bytes`` and ``state_bytes``, the recurrent
state's share of them), then per loop iteration ``serve.step`` holding
``serve.decode`` (the step's dispatch, with its live ``lanes``,
``kv_positions``: the sum over them of the cache positions each one's
attention reads, and ``kv_blocks`` and ``kv_blocks_read``: the
decode-attention kernel's position blocks over those lanes' caches, and
the live ones among them, which are all it reads; all 0 for a model with
no attention), ``serve.wait`` (the host waiting for the
next tokens), ``serve.readback`` (their copy to the host) and one
``serve.join`` per joining request (``serve.prefill``, ``serve.attach``,
then the read of its first token).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.monitor import span
from repro.kernels.decode_attention import position_block
from repro.models.model import Model


def make_prefill(model: Model, max_len: int) -> Callable:
    def prefill(params, batch):
        return model.prefill(params, batch, max_len=max_len)
    return prefill


def make_serve_step(model: Model) -> Callable:
    """(params, batch{tokens,pos[,mrope_pos]}, cache) -> (logits, cache)."""
    def serve_step(params, batch, cache):
        return model.decode_step(params, batch, cache)
    return serve_step


def lane_axes(model: Model, max_len: int) -> Any:
    """The batch axis of each leaf of ``model``'s decode cache (the axis
    whose size follows the batch), as a pytree of ints."""
    one, two = (jax.eval_shape(lambda b=b: model.make_cache(b, max_len))
                for b in (1, 2))
    return jax.tree_util.tree_map(
        lambda a, b: next(i for i, (m, n) in enumerate(zip(a.shape, b.shape))
                          if m != n), one, two)


#: the leaves of a decode cache that hold recurrent (SSM) state
STATE_LEAVES = ("conv", "ssm")


def cache_bytes(shapes: Any) -> Tuple[int, int]:
    """(all bytes, recurrent-state bytes) of a decode cache's shapes."""
    total = state = 0
    for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = x.size * x.dtype.itemsize
        total += n
        if getattr(path[-1], "key", None) in STATE_LEAVES:
            state += n
    return total, state


def make_attach_lane(axes: Any) -> Callable:
    """(pool, lane_cache, lane) -> pool with the batch-1 ``lane_cache``
    written into lane ``lane`` (traced) of every leaf, on its batch axis."""
    def attach_lane(pool, lane_cache, lane):
        return jax.tree_util.tree_map(
            lambda p, x, ax: jax.lax.dynamic_update_slice_in_dim(
                p, x.astype(p.dtype), lane, axis=ax), pool, lane_cache, axes)
    return attach_lane


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray            # (S,) int32
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class ServeStats:
    """Decode accounting for the last ``BatchServer.run``."""
    global_steps: int = 0         # vmapped decode invocations
    lane_steps: int = 0           # tokens produced (invariant: Σ max_new)
    lane_slots: int = 0           # lane-slots stepped (Σ pool width/step —
                                  # what adaptive resizing shrinks)
    prefills: int = 0
    n_requests: int = 0
    resizes: int = 0              # adaptive lane-pool rebuilds
    lane_trace: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list)     # (global_step, lane count) per resize

    @property
    def occupancy(self) -> float:
        if not self.global_steps:
            return 0.0
        return self.lane_steps / self.global_steps

    @property
    def step_efficiency(self) -> float:
        """Fraction of stepped lane-slots that produced a kept token."""
        if not self.lane_slots:
            return 0.0
        return self.lane_steps / self.lane_slots


class BatchServer:
    """Greedy-decode server over a persistent lane pool.

    With ``adaptive_lanes`` the pool RESIZES to queue depth between decode
    steps (the serving face of online elastic repacking, core/repack.py):
    as the request tail drains, live lanes are compacted into a smaller
    pool so the batched step stops paying for dead lanes. Lane counts are
    rounded to powers of two, so at most log2(batch_lanes) decode variants
    ever compile; per-request tokens are unchanged (lanes are
    independent).
    """

    def __init__(self, model: Model, params, batch_lanes: int, max_len: int,
                 adaptive_lanes: bool = False):
        self.model = model
        self.params = params
        self.lanes = batch_lanes
        self.max_len = max_len
        self.adaptive_lanes = adaptive_lanes
        self.stats = ServeStats()
        self._prefill = jax.jit(make_prefill(model, max_len))
        # the model's batched decode over the whole pool (one sequence per
        # lane), updating the donated pool in place
        self._step = jax.jit(make_serve_step(model), donate_argnums=(2,))
        self._axes = lane_axes(model, max_len)
        self._attach = jax.jit(make_attach_lane(self._axes),
                               donate_argnums=(0,))
        self._empty = jax.jit(model.make_cache, static_argnums=(0, 1))

    def run(self, requests: List[Request]) -> Dict[int, List[int]]:
        queue = [r for r in list(requests) if r.max_new > 0]
        for r in requests:
            if r.max_new <= 0:
                r.done = True
        results: Dict[int, List[int]] = {r.id: r.out for r in requests}
        self.stats = ServeStats(n_requests=len(queue))
        if not queue:
            return results
        S_pad = max(len(r.prompt) for r in queue)
        # enqueue-time KV guard: decode writes positions S_pad .. S_pad +
        # max_new - 2 (the first token comes from prefill), so the cache
        # must hold S_pad + max_new - 1 positions. Reject up front instead
        # of silently walking ``pos`` past the cache length.
        for r in queue:
            if S_pad + r.max_new - 1 > self.max_len:
                raise ValueError(
                    f"request {r.id}: padded prompt ({S_pad}) + max_new "
                    f"({r.max_new}) needs {S_pad + r.max_new - 1} KV "
                    f"positions > max_len ({self.max_len}); shorten the "
                    f"prompt or raise max_len")
        C = min(self.lanes, len(queue))

        def prefill_one(r: Request):
            toks = np.zeros((1, S_pad), np.int32)
            toks[0, S_pad - len(r.prompt):] = r.prompt   # left-pad
            logits, cache = self._prefill(self.params,
                                          {"tokens": jnp.asarray(toks)})
            self.stats.prefills += 1
            first = jnp.argmax(logits, -1).astype(jnp.int32)   # (1,)
            return first, cache

        # the pool's shapes are fixed until an adaptive resize
        shapes = jax.eval_shape(lambda: self.model.make_cache(C, self.max_len))
        nbytes, state_bytes = cache_bytes(shapes)
        with span("serve.pool", lanes=C, cache_bytes=nbytes,
                  state_bytes=state_bytes):
            pool_cache = self._empty(C, self.max_len)
        # positions one lane's attention reads at position p: p + 1, up
        # to the cache's (or the window's) length
        cfg, window = self.model.cfg, self.model.window
        attn_len = 0 if cfg.is_attention_free else (
            min(self.max_len, window) if window else self.max_len)
        # the decode kernel's position blocks over that length: a lane
        # reads those up to the one that holds its position
        bs = 1 if cfg.is_attention_free else position_block(
            attn_len, cfg.num_kv_heads, cfg.resolved_head_dim)
        cur = np.zeros((C, 1), np.int32)             # per-lane token (B, T=1)
        pos = np.full((C,), S_pad, np.int32)
        lane_req: List[Optional[Request]] = [None] * C

        def attach(lane: int, r: Request):
            nonlocal pool_cache
            with span("serve.join", request=r.id, lane=lane):
                with span("serve.prefill"):
                    first, cache = prefill_one(r)
                with span("serve.attach"):
                    pool_cache = self._attach(pool_cache, cache,
                                              np.int32(lane))
                # the first token is read after the attach is dispatched,
                # so that it queues behind the prefill on the device
                cur[lane, 0] = int(first[0])
                pos[lane] = S_pad
                lane_req[lane] = r

        def resize(new_c: int):
            """Compact live lanes into a pool of ``new_c`` lanes: a gather
            on each leaf's lane axis (per-lane state is untouched)."""
            nonlocal pool_cache, cur, pos, lane_req, C
            live = [l for l, r in enumerate(lane_req) if r is not None]
            # lanes past the live ones hold copies of another lane's cache
            # until a request attaches there; their tokens are never read
            take = np.array((live + (live[:1] or [0]) * new_c)[:new_c],
                            np.int32)
            pool_cache = jax.tree_util.tree_map(
                lambda p, ax: jnp.take(p, take, axis=ax), pool_cache,
                self._axes)
            new_cur = np.zeros((new_c, 1), np.int32)
            new_pos = np.full((new_c,), S_pad, np.int32)
            new_req: List[Optional[Request]] = [None] * new_c
            for i, l in enumerate(live):
                new_cur[i] = cur[l]
                new_pos[i] = pos[l]
                new_req[i] = lane_req[l]
            cur, pos, lane_req, C = new_cur, new_pos, new_req, new_c
            self.stats.resizes += 1
            self.stats.lane_trace.append((self.stats.global_steps, new_c))

        for lane in range(C):
            attach(lane, queue.pop(0))

        while True:
            with span("serve.step",
                      tokens=sum(r is not None for r in lane_req)):
                # emit + retire phase: the token each active lane carries
                # came from the PREVIOUS step (or its prefill). Record it,
                # and retire lanes whose budget is now exhausted BEFORE
                # stepping — stepping a finished lane would produce a
                # token nobody consumes (one wasted vmapped step per
                # request).
                for lane, r in enumerate(lane_req):
                    if r is None:
                        continue
                    r.out.append(int(cur[lane, 0]))
                    self.stats.lane_steps += 1
                    if len(r.out) >= r.max_new:
                        r.done = True    # lane frees NOW — no wave barrier
                        lane_req[lane] = None
                n_live = sum(1 for r in lane_req if r is not None)
                if n_live == 0 and not queue:
                    break
                if self.adaptive_lanes:
                    demand = n_live + len(queue)
                    desired = 1 << (max(1, demand) - 1).bit_length()
                    desired = min(self.lanes, max(desired, n_live, 1))
                    if desired < C:
                        resize(desired)
                if n_live:
                    active = np.array([r is not None for r in lane_req])
                    seen = np.minimum(pos[active] + 1, attn_len)
                    with span("serve.decode", lanes=n_live,
                              kv_positions=int(seen.sum()),
                              kv_blocks=n_live * (attn_len // bs),
                              kv_blocks_read=int((-(-seen // bs)).sum())):
                        logits, pool_cache = self._step(
                            self.params,
                            {"tokens": jnp.asarray(cur),
                             "pos": jnp.asarray(pos)},
                            pool_cache)
                        nxt = jnp.argmax(logits, -1)            # (C,)
                    with span("serve.wait"):
                        jax.block_until_ready(nxt)
                    with span("serve.readback"):
                        nxt = np.asarray(nxt, np.int32)
                    self.stats.global_steps += 1
                    self.stats.lane_slots += C
                    cur[active, 0] = nxt[active]
                    pos[active] += 1         # inactive lanes stay frozen
                # refill phase — strictly AFTER the step: a joiner's first
                # token (from its prefill) sits in ``cur`` and must be
                # emitted next iteration before the lane is ever stepped;
                # attaching pre-step would let the step consume and
                # overwrite it, shifting the request's whole output by one
                for lane, r in enumerate(lane_req):
                    if r is None and queue:  # joins mid-decode
                        attach(lane, queue.pop(0))
        return results
