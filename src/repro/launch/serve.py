"""Serving driver: prefill + continuously-batched decode on a lane pool.

The serve_step builders are what the dry-run lowers for decode shapes; the
``BatchServer`` is a runnable mini-server for the examples. It is TRUE
continuous batching (the inference-side analogue of the paper's
concurrent-jobs-per-GPU packing, on the persistent-lane-pool model of
core/lanepool.py):

  * the decode state is a fixed-capacity pool: the model's own decode
    cache at batch = lanes (``Model.make_cache``). Decode is the model's
    batched ``decode_step``, compiled ONCE per pool width; each step
    updates the donated pool in place, so no step copies it;
  * a request joins MID-DECODE the moment a lane frees: its prompt is
    prefilled at batch 1 and its cache written into the free lane by one
    jitted, donated ``Model.attach_lane`` (no recompilation, other lanes
    undisturbed);
  * a finished lane stops burning decode budget — its request is retired
    immediately (``Request.done``) and the next queued request takes the
    lane, so total active lane-steps equal the sum of per-request
    ``max_new``, not ``capacity × max(max_new)`` (the wave-mode waste).

Lanes are independent: every op of a decode step is per sequence (a
routed MoE step gives every token room at every expert), so a request's
tokens are identical whatever co-residents it decodes next to (prompts are
left-padded to one fixed length per ``run``). The pool's format (its
leaves, their lane axes, the attention's cache length and the decode
kernel's blocks) is the model's alone; this loop only moves requests
through lanes.

``run`` is traced (core/monitor.span): ``serve.pool`` (the empty pool's
allocation, with its ``cache_bytes`` and ``state_bytes``, the recurrent
state's share of them: ``Model.pool_bytes``), then per loop iteration
``serve.step`` holding ``serve.decode`` (the step's dispatch, with its
live ``lanes`` and what their attention reads, ``Model.decode_reads``:
``kv_positions``, ``kv_blocks`` and ``kv_blocks_read``), ``serve.wait``
(the host waiting for the next tokens), ``serve.readback`` (their copy to
the host) and one ``serve.join`` per joining request (``serve.prefill``,
``serve.attach``, then the read of its first token).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.monitor import span
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
    global_steps: int = 0         # batched decode invocations
    lane_steps: int = 0           # tokens produced (invariant: Σ max_new)
    prefills: int = 0
    n_requests: int = 0


class BatchServer:
    """Greedy-decode server over a persistent lane pool of
    ``batch_lanes`` lanes, each holding up to ``max_len`` positions."""

    def __init__(self, model: Model, params, batch_lanes: int, max_len: int):
        self.model = model
        self.params = params
        self.lanes = batch_lanes
        self.max_len = max_len
        self.stats = ServeStats()
        self._prefill = jax.jit(make_prefill(model, max_len))
        # the model's batched decode over the whole pool (one sequence per
        # lane), updating the donated pool in place
        self._step = jax.jit(make_serve_step(model), donate_argnums=(2,))
        self._attach = jax.jit(model.attach_lane, donate_argnums=(0,))
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

        nbytes, state_bytes = self.model.pool_bytes(C, self.max_len)
        with span("serve.pool", lanes=C, cache_bytes=nbytes,
                  state_bytes=state_bytes):
            pool_cache = self._empty(C, self.max_len)
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
                if n_live:
                    active = np.array([r is not None for r in lane_req])
                    reads = self.model.decode_reads(pos[active],
                                                    self.max_len)
                    with span("serve.decode", lanes=n_live, **reads):
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
