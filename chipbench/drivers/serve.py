"""Serve driver: a backlog of greedy requests through ``launch.serve.BatchServer``.

The weights are made on the device in one jitted call from the seed, by
the configuration's reference module (the program's layout and init
scheme, in the type they are served in). A warm-up ``run()`` of one
request per lane compiles the prefill at the mix's prompt length and the
decode at the full lane count; then one backlog ``run()`` is measured.

Each request's ``out`` is the harness's own list, which stamps the host
clock on every token the server appends: that is when a streaming client
would see it. The window opens as the backlog ``run()`` starts and closes
at the first token appended ``--seconds`` later, where the list raises
and so ends the run.

After the window the server's cache pool is freed and the plain reference
runs once over each request of a sample drawn from the seed among those
that finished (the longest one always in it): its prompt and served
tokens in one forward pass, float32. The compared number is the widest
gap by which a served token's logit lies below the reference's best at
its position. With ``--control 1`` the token that the reference's control
(its matmuls in float8) puts first takes the served token's place.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import bench
import generator
import xplane


#: tokens each warm-up request decodes (the decode compiles at once)
WARMUP_NEW = 2
#: the check compares finished requests until it holds this many served
#: tokens, or CHECK_REQUESTS requests
CHECK_TOKENS = 600
CHECK_REQUESTS = 8


class WindowClosed(Exception):
    pass


class StampedTokens(list):
    """A request's token list that stamps each append on the host clock
    and raises once the window's deadline has passed."""

    def __init__(self, window):
        super().__init__()
        self.window = window
        self.times = []

    def append(self, token):
        now = time.perf_counter()
        if now >= self.window.deadline:
            self.window.close(now)
            raise WindowClosed()
        super().append(token)
        self.times.append(now)


class Window:
    def __init__(self, clock, tracer):
        self.clock = clock
        self.tracer = tracer
        self.deadline = float("inf")
        self.t_open = self.t_close = None
        self.lap_open = self.lap_close = None

    def open(self, seconds: float):
        if self.tracer is not None:
            self.tracer.open()
        self.t_open = time.perf_counter()
        self.lap_open = self.clock.lap()
        self.deadline = self.t_open + seconds

    def close(self, now: float):
        self.t_close = now
        self.lap_close = self.clock.lap()
        if self.tracer is not None:
            self.tracer.close()


def run(run) -> bench.Obs:
    from repro.launch.serve import BatchServer, Request
    from repro.models import build_model

    cell, mix = run.cell, run.cell.traffic
    m, ref = cell.config["model"], cell.reference
    vocab = m["vocab_size"]
    params = jax.jit(lambda k: ref.init(k, m))(jax.random.PRNGKey(run.seed))
    model = build_model(cell.model_config())
    srv = BatchServer(model, params, batch_lanes=mix["lanes"],
                      max_len=mix["max_len"])
    warm = generator.serve_requests(mix, run.seed + 1, vocab, mix["lanes"])
    srv.run([Request(id=r["id"], prompt=r["prompt"],
                     max_new=WARMUP_NEW) for r in warm])
    jax.block_until_ready(params)

    win = Window(run.clock, xplane.Tracer() if run.trace else None)
    reqs = [Request(id=r["id"], prompt=r["prompt"], max_new=r["max_new"],
                    out=StampedTokens(win))
            for r in generator.serve_requests(mix, run.seed, vocab,
                                            mix["backlog"])]
    win.open(run.seconds)
    try:
        srv.run(reqs)
    except WindowClosed:
        pass
    if win.t_close is None:             # the backlog drained first
        win.close(time.perf_counter())

    obs = bench.Obs()
    window = win.t_close - win.t_open
    times = [r.out.times for r in reqs]
    n_tokens = sum(len(t) for t in times)
    gaps = np.concatenate([np.diff(t) for t in times if len(t) > 1] or
                          [np.zeros(0)])
    obs.window_s = window
    obs.end_to_end = {"setup_s": win.t_open - run.t_start,
                      "serve_tokens_per_s": n_tokens / window,
                      "token_gap_p95_ms": float(np.percentile(gaps, 95)) * 1e3}
    started = [r for r in reqs if r.out]
    obs.attempted = len(started)
    obs.programs_in_window = win.lap_close[1] - win.lap_open[1]
    prompt_len = len(reqs[0].prompt)
    decode_positions = [prompt_len + i for r in reqs
                        for i in range(1, len(r.out))]
    obs.counters = {
        "tokens": n_tokens, "requests_started": len(started),
        "gap_max_ms": float(gaps.max()) * 1e3 if gaps.size else None,
        "requests_finished": sum(r.done for r in reqs),
        "prompt_len": prompt_len,
        "served_flops": ref.prefill_flops(m, prompt_len) * len(started)
        + sum(ref.decode_flops(m, p) for p in decode_positions),
        **bench.setup_compiles(win.lap_open)}
    obs.read_memory(cell.chips)
    del srv, model
    gc.collect()
    if win.tracer is not None:
        obs.trace = win.tracer.finish(window)
    _check(run, reqs, params, obs)
    return obs


def _sample(reqs, seed: int, min_tokens: int, max_requests: int):
    """Finished requests to compare: the longest, then others in an order
    drawn from the seed, until ``min_tokens`` served tokens are in."""
    done = [r for r in reqs if r.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.out), -r.id))
    rest = [r for r in done if r is not longest]
    order = np.random.Generator(np.random.Philox(key=seed,
                                                 counter=[0, 0, 0, 9]))
    picked = [longest]
    for i in order.permutation(len(rest)):
        if (sum(len(r.out) for r in picked) >= min_tokens
                or len(picked) >= max_requests):
            break
        picked.append(rest[i])
    return picked


def _check(run, reqs, params, obs):
    cell, mix = run.cell, run.cell.traffic
    m, ref = cell.config["model"], cell.reference
    picked = _sample(reqs, run.seed, CHECK_TOKENS, CHECK_REQUESTS)
    length = mix["max_len"]
    variant = "control" if run.control else "reference"
    worst, compared = 0.0, 0
    for r in picked:
        p = len(r.prompt)
        n = len(r.out)
        toks = np.zeros((1, length), np.int32)
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        toks[0, :len(seq)] = seq
        served = np.zeros((length,), np.int32)
        served[p - 1:p - 1 + n] = r.out
        mask = np.zeros((length,), bool)
        mask[p - 1:p - 1 + n] = True
        gap = ref.served_gap(params, jnp.asarray(toks), jnp.asarray(served),
                             m, variant)
        worst = max(worst, float(np.max(np.asarray(gap)[mask])))
        compared += n
    obs.counters["compared_tokens"] = compared
    obs.counters["compared_requests"] = len(picked)
    if not picked:
        worst = float("inf")
    obs.checks = [bench.Check("logit_gap", worst, cell.limits["logit_gap"]),
                  bench.Check("programs_in_window",
                              float(obs.programs_in_window), 0.0)]
