"""The span recorder (core/monitor.span): the program's runtime tracing."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import monitor
from repro.core.monitor import span, span_log


def _new(before):
    """Spans logged since the log held ``before`` (the log is
    process-wide: earlier tests may have written to it)."""
    seen = {s.index for s in before}
    return [s for s in span_log() if s.index not in seen]


def test_nested_spans_record_their_parent():
    before = span_log()
    with span("t.outer"):
        with span("t.middle"):
            with span("t.inner"):
                pass
        with span("t.sibling"):
            pass
    with span("t.next"):
        pass
    got = {s.name: s for s in _new(before)}
    assert [s.name for s in _new(before)] == [
        "t.inner", "t.middle", "t.sibling", "t.outer", "t.next"]
    assert got["t.outer"].parent is None and got["t.next"].parent is None
    assert got["t.middle"].parent == got["t.outer"].index
    assert got["t.sibling"].parent == got["t.outer"].index
    assert got["t.inner"].parent == got["t.middle"].index
    outer, inner = got["t.outer"], got["t.inner"]
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_counts_are_kept_with_those_added_inside_the_body():
    before = span_log()
    with span("t.counted", task=3, lane=1) as counts:
        counts["lanes"] = 4
    (s,) = _new(before)
    assert s.counts == {"task": 3, "lane": 1, "lanes": 4}


def test_the_log_holds_at_most_its_capacity():
    n = monitor.SPAN_LOG_CAPACITY + 10
    for i in range(n):
        with span("t.flood", i=i):
            pass
    log = span_log()
    assert len(log) == monitor.SPAN_LOG_CAPACITY
    assert log[-1].counts == {"i": n - 1}
    assert log[0].counts == {"i": 10}


def test_a_span_whose_body_raises_is_logged_and_reraises():
    before = span_log()
    with pytest.raises(KeyError, match="boom"):
        with span("t.outer"):
            with span("t.raises", lane=2):
                raise KeyError("boom")
    got = {s.name: s for s in _new(before)}
    assert set(got) == {"t.outer", "t.raises"}
    assert got["t.raises"].counts == {"lane": 2}
    assert got["t.raises"].parent == got["t.outer"].index
    # the stack of open spans unwound: the next span is at the top again
    with span("t.after"):
        pass
    assert span_log()[-1].parent is None


def test_spans_lie_on_the_host_python_line_of_a_profiler_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("t.traced.outer", lanes=3):
            with span("t.traced.inner", task=7):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = {}
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if line.name.startswith("python"):
                    for e in line.events:
                        events[e.name] = e
    outer, inner = events["t.traced.outer"], events["t.traced.inner"]
    assert dict(outer.stats) == {"lanes": 3}
    assert dict(inner.stats) == {"task": 7}
    assert outer.start_ns <= inner.start_ns
    assert (inner.start_ns + inner.duration_ns
            <= outer.start_ns + outer.duration_ns)
