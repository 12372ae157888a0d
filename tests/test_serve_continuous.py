"""Continuous-batching BatchServer: decode accounting and lane isolation."""
import jax
import numpy as np
import pytest

from repro import configs
from repro.launch.serve import BatchServer, Request
from repro.models import ParallelCtx, build_model


def _srv(lanes, max_len=32):
    cfg = configs.get("stablelm-1.6b").reduced()
    model = build_model(cfg, ParallelCtx(moe_oracle=True))
    params = model.init(jax.random.PRNGKey(0))
    return BatchServer(model, params, batch_lanes=lanes, max_len=max_len)


def test_decode_steps_equal_sum_max_new_not_batch_times_max():
    """Dead lanes stop burning decode budget: total active lane-steps are
    exactly Σ max_new, not lanes × max(max_new) (the wave-mode waste), and
    every request is marked done."""
    srv = _srv(lanes=2)
    max_news = [2, 8, 3, 5]
    reqs = [Request(id=i, prompt=np.arange(1, 5, dtype=np.int32),
                    max_new=m) for i, m in enumerate(max_news)]
    out = srv.run(reqs)
    assert srv.stats.lane_steps == sum(max_news)
    assert srv.stats.lane_steps < 2 * max(max_news) * 2  # << wave cost
    assert all(len(out[r.id]) == r.max_new for r in reqs)
    assert all(r.done for r in reqs)
    # requests joined mid-decode: fewer global steps than serial decode
    assert srv.stats.global_steps < sum(max_news)
    assert srv.stats.prefills == len(reqs)


def test_request_tokens_independent_of_coresidents():
    """A request decodes the same tokens whether it shares the pool with
    others (joining mid-flight) or runs alone — lanes are vmap-independent
    and prompts are padded to a fixed length."""
    prompt = np.arange(1, 5, dtype=np.int32)
    packed = _srv(lanes=2)
    out = packed.run([Request(id=0, prompt=prompt, max_new=2),
                      Request(id=1, prompt=prompt, max_new=6),
                      Request(id=2, prompt=np.arange(2, 6, dtype=np.int32),
                              max_new=4)])
    solo = _srv(lanes=1)
    ref = solo.run([Request(id=9, prompt=prompt, max_new=6)])
    assert out[1] == ref[9]
    # the MID-DECODE JOINER too: request 2 attached when request 0
    # retired; its first (prefill-derived) token must be emitted before
    # its lane is ever stepped (regression: attaching before the step
    # let the step consume and overwrite it, shifting the output by one)
    solo2 = _srv(lanes=1)
    ref2 = solo2.run([Request(id=8, prompt=np.arange(2, 6, dtype=np.int32),
                              max_new=4)])
    assert out[2] == ref2[8]


def test_final_decode_step_not_wasted():
    """Off-by-one regression: retirement happens BEFORE the step, so a
    request's last token (which came from the previous step or prefill)
    never triggers one more vmapped step whose output is discarded. A
    max_new=1 request needs ZERO decode steps (prefill supplies its only
    token); m tokens need exactly m-1 steps."""
    srv = _srv(lanes=1)
    out = srv.run([Request(id=0, prompt=np.arange(1, 5, dtype=np.int32),
                           max_new=1)])
    assert len(out[0]) == 1
    assert srv.stats.global_steps == 0          # no wasted step
    assert srv.stats.lane_steps == 1            # Σ max_new invariant
    srv2 = _srv(lanes=1)
    out2 = srv2.run([Request(id=0, prompt=np.arange(1, 5, dtype=np.int32),
                             max_new=5)])
    assert len(out2[0]) == 5
    assert srv2.stats.global_steps == 4         # m-1 steps for m tokens
    assert srv2.stats.lane_steps == 5


def test_enqueue_rejects_requests_past_kv_cache_length():
    """S_pad + max_new must fit max_len — a clear ValueError at enqueue
    instead of silently walking ``pos`` past the KV cache."""
    srv = _srv(lanes=2, max_len=8)
    good = Request(id=0, prompt=np.arange(1, 5, dtype=np.int32), max_new=4)
    bad = Request(id=1, prompt=np.arange(1, 5, dtype=np.int32), max_new=9)
    with pytest.raises(ValueError, match="max_len"):
        srv.run([good, bad])
    # padding counts: a long co-resident prompt pushes S_pad over for a
    # short request that would fit on its own (2 + 3 - 1 = 4 <= 8, but
    # padded to S_pad=7 it needs 9 KV positions)
    srv2 = _srv(lanes=2, max_len=8)
    long_prompt = Request(id=2, prompt=np.arange(1, 8, dtype=np.int32),
                          max_new=1)
    with pytest.raises(ValueError, match="max_len"):
        srv2.run([long_prompt,
                  Request(id=3, prompt=np.arange(1, 3, dtype=np.int32),
                          max_new=3)])
    # within budget runs fine, including the EXACT fit: S_pad=4,
    # max_new=5 writes KV positions 4..7 of an 8-slot cache
    assert len(_srv(lanes=2, max_len=8).run([good])[0]) == 4
    exact = Request(id=4, prompt=np.arange(1, 5, dtype=np.int32), max_new=5)
    assert len(_srv(lanes=1, max_len=8).run([exact])[4]) == 5


def test_zero_max_new_request_is_done_immediately():
    srv = _srv(lanes=1)
    reqs = [Request(id=0, prompt=np.arange(1, 4, dtype=np.int32), max_new=0),
            Request(id=1, prompt=np.arange(1, 4, dtype=np.int32), max_new=2)]
    out = srv.run(reqs)
    assert out[0] == [] and len(out[1]) == 2
    assert reqs[0].done and reqs[1].done
    # an all-empty run resets stats rather than keeping the previous run's
    srv.run([Request(id=2, prompt=np.arange(1, 4, dtype=np.int32),
                     max_new=0)])
    assert srv.stats.lane_steps == 0 and srv.stats.n_requests == 0
