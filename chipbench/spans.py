"""The program's own spans, as the per-layer metrics read them.

The program logs a span at each layer boundary in memory
(``repro.core.monitor.span``, read through ``span_log()``): its name, its
start and end on ``time.perf_counter_ns()``, the index of the span open
around it and its counts. The log is the process's, so it holds set-up
and the window alike.

A span is in the window when it ended within the last ``obs.window_s``
seconds before the log's last entry. Both drivers close the window by
raising out of the program's last span (the sweep's ``early_stop`` from
inside ``lanepool.read``, the served token list from inside
``serve.step``) and run nothing of the program after it, so the last
entry closes the window.

Where the program keeps no span log, or the log holds no span of the
name asked for, these functions give None and the reader reports
nothing.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence


def log() -> list:
    """The program's span log, oldest first; [] where it keeps none."""
    try:
        from repro.core.monitor import span_log
    except ImportError:
        return []
    return span_log()


def in_window(obs, name: str, spans: Optional[list] = None) -> list:
    """The spans called ``name`` that ended in the window."""
    spans = log() if spans is None else spans
    if not spans:
        return []
    start = spans[-1].end_ns - obs.window_s * 1e9
    return [s for s in spans if s.name == name and s.end_ns >= start]


def _child_ns(spans: list, children: Sequence[str]) -> Dict[int, int]:
    """Parent index -> nanoseconds covered by its children of those
    names."""
    out: Dict[int, int] = {}
    for s in spans:
        if s.name in children and s.parent is not None:
            out[s.parent] = out.get(s.parent, 0) + s.end_ns - s.start_ns
    return out


def _median_ms(values_ns: List[float]) -> Optional[float]:
    return statistics.median(values_ns) * 1e-6 if values_ns else None


def median_ms(obs, name: str) -> Optional[float]:
    """Median duration of the window's ``name`` spans, in ms."""
    return _median_ms([s.end_ns - s.start_ns for s in in_window(obs, name)])


def self_ms(obs, name: str, less: Sequence[str]) -> Optional[float]:
    """Median, over the window's ``name`` spans, of each one's duration
    less what its children named in ``less`` cover, in ms."""
    spans = log()
    covered = _child_ns(spans, less)
    return _median_ms([s.end_ns - s.start_ns - covered.get(s.index, 0)
                       for s in in_window(obs, name, spans)])


def child_ms(obs, name: str, child: str) -> Optional[float]:
    """Median, over the window's ``name`` spans, of the time their
    ``child`` spans take, in ms."""
    spans = log()
    covered = _child_ns(spans, (child,))
    return _median_ms([covered.get(s.index, 0)
                       for s in in_window(obs, name, spans)])


def total_s(name: str) -> Optional[float]:
    """Total duration of every ``name`` span in the log, in s."""
    found = [s.end_ns - s.start_ns for s in log() if s.name == name]
    return sum(found) * 1e-9 if found else None
