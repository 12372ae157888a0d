"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, per-module and per-op device time, and
the longest idle gaps with what the host was doing in each.

Device planes are ``/device:TPU:<n>``; each has an ``XLA Modules`` line
(one event per executable run, named ``<jit name>(<fingerprint>)``) and an
``XLA Ops`` line (one event per HLO instruction, named by its HLO text).
Host planes (``/host:CPU``) hold one line per thread; their events share
the device events' clock.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Tuple

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_OP_NAME = re.compile(r"^%(\S+) = ")
#: host threads whose events say what the Python side was doing
_HOST_THREADS = ("python", "main")
#: the host span that marks the measured window inside a trace
WINDOW = "chipbench.window"


def module_base(name: str) -> str:
    """``jit_serve_step(123)`` -> ``jit_serve_step``."""
    return name.split("(", 1)[0]


@dataclasses.dataclass
class Op:
    name: str           # HLO instruction name (``fusion.12``)
    module: str         # jit name of the module it ran in
    start_ns: float
    dur_ns: float
    custom: bool        # a Pallas kernel (``tpu_custom_call``)


@dataclasses.dataclass
class Reduced:
    window_s: float                         # traced window, host clock
    chips: int
    busy_s: float                           # union of op intervals / chips
    modules: Dict[str, List[float]]         # jit name -> device seconds
    ops: List[Op]                           # leaf ops of chip 0
    gaps: List[Tuple[str, float]]           # (host activity, seconds)

    def module_mean_s(self, name: str):
        runs = self.modules.get(name)
        return sum(runs) / len(runs) if runs else None

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        tot: Dict[str, float] = collections.defaultdict(float)
        for op in self.ops:
            tot[f"{op.module}/{op.name}"] += op.dur_ns * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def _union(intervals: List[Tuple[float, float]]) -> Tuple[float, list]:
    """Total covered length and the merged intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _label(gap: Tuple[float, float], host: List[Tuple[float, float, str]]
           ) -> str:
    """The host event that best explains a device gap: the shortest one
    covering at least half of it, else the one overlapping it most."""
    g0, g1 = gap
    best, best_cover = None, 0.0
    covering = []
    for s, e, name in host:
        ov = min(e, g1) - max(s, g0)
        if ov <= 0:
            continue
        if ov >= 0.5 * (g1 - g0):
            covering.append((e - s, name))
        if ov > best_cover:
            best, best_cover = name, ov
    if covering:
        return min(covering)[1]
    return best or "no host span"


def reduce(path: str, window_s: float, n_gaps: int = 10):
    """Reduce the trace at ``path`` (a file, or a directory searched for
    ``*.xplane.pb``) to a ``Reduced``; None when it holds no TPU plane,
    as off the chip, where there is no device time to read."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f"expected one trace under {path}, "
                               f"found {len(found)}")
        path = found[0]
    data = ProfileData.from_file(path)
    devices = [pl for pl in data.planes if _DEVICE.match(pl.name)]
    if not devices:
        return None
    host: List[Tuple[float, float, str]] = []
    for pl in data.planes:
        if pl.name.startswith("/host:CPU"):
            for ln in pl.lines:
                if ln.name.startswith(_HOST_THREADS):
                    host.extend((e.start_ns, e.start_ns + e.duration_ns,
                                 e.name) for e in ln.events)
    marks = [(s, e) for s, e, name in host if name == WINDOW]
    w0, w1 = marks[0] if marks else (-float("inf"), float("inf"))
    if marks:
        window_s = (w1 - w0) * 1e-9
        host = [h for h in host if h[2] != WINDOW]

    busy = 0.0
    modules: Dict[str, List[float]] = collections.defaultdict(list)
    ops: List[Op] = []
    merged0: list = []
    for i, pl in enumerate(sorted(devices, key=lambda p: p.name)):
        lines = {ln.name: ln for ln in pl.lines}
        spans = []
        mods = []
        for e in lines["XLA Modules"].events if "XLA Modules" in lines else ():
            mods.append((e.start_ns, e.start_ns + e.duration_ns,
                         module_base(e.name)))
            if i == 0 and w0 <= e.start_ns < w1:
                modules[module_base(e.name)].append(e.duration_ns * 1e-9)
        mods.sort()
        k = 0
        evs = sorted(lines["XLA Ops"].events if "XLA Ops" in lines else (),
                     key=lambda e: e.start_ns)
        for j, e in enumerate(evs):
            s0, s1 = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
            if s1 <= s0:
                continue
            spans.append((s0, s1))
            # an op that holds the next one (a while loop around its body)
            # counts for busy time, but its body's ops name the time
            if i or (j + 1 < len(evs)
                     and evs[j + 1].start_ns < e.start_ns + e.duration_ns):
                continue
            while k + 1 < len(mods) and mods[k + 1][0] <= e.start_ns:
                k += 1
            mod = mods[k][2] if mods and mods[k][0] <= e.start_ns < mods[k][1] \
                else "?"
            m = _OP_NAME.match(e.name)
            ops.append(Op(m.group(1) if m else e.name, mod, e.start_ns,
                          e.duration_ns, "tpu_custom_call" in e.name))
        covered, merged = _union(spans)
        busy += covered
        if i == 0:
            merged0 = merged
    gaps = sorted(((a[1], b[0]) for a, b in zip(merged0, merged0[1:])),
                  key=lambda g: g[0] - g[1])[:n_gaps]
    return Reduced(window_s=window_s, chips=len(devices),
                   busy_s=busy * 1e-9 / len(devices), modules=dict(modules),
                   ops=ops,
                   gaps=[(_label(g, host), (g[1] - g[0]) * 1e-9)
                         for g in gaps])


class Tracer:
    """A profiler session around a window. It starts before the window
    opens, so that the profiler's own start-up falls outside it, and marks
    the window with a host span (``WINDOW``) that the reduction clips to.
    The trace goes to a temporary directory and is deleted once reduced.
    Python function tracing is off: it would slow the host path."""

    def __init__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = None

    def open(self):
        import jax
        self.span = jax.profiler.TraceAnnotation(WINDOW)
        self.span.__enter__()

    def close(self):
        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None

    def finish(self, window_s: float):
        import jax
        self.close()
        jax.profiler.stop_trace()
        try:
            return reduce(self.dir, window_s)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def idle_share(obs):
    """Percent of the traced window in which no op ran on the device."""
    if obs.trace is None or not obs.trace.window_s:
        return None
    return 100.0 * (1.0 - obs.trace.busy_s / obs.trace.window_s)
