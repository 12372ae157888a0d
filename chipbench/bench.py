"""The shared runner: finds a cell's parts by name and runs it once.

Every part of a cell is a file found by its name in ``BENCHMARK.json``:

* ``chipbench/configs/<config>.json``: the configuration as it is run,
  and ``chipbench/configs/<config>.py`` beside it: the plain reference and
  the work (FLOPs, bytes) of its programs;
* ``chipbench/traffic/<traffic>.json``: the mix, whose ``driver`` key
  names the entry point (``chipbench/drivers/<driver>.py``);
* ``chipbench/limits/<cell>.json``: the limit of each number the cell's
  correctness check compares;
* ``chipbench/metrics/<metric>.py``: one reader per per-layer metric.

A driver runs the cell and returns an ``Obs``: the end-to-end metrics it
took with the host clock, the counters it kept through its own callbacks,
the reduced trace of a traced run, and the numbers its check compared.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(path: str):
    """Import a file by its path (part names may hold ``-`` and ``.``)."""
    name = "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Obs:
    """What one run saw. Drivers fill what their entry point offers."""
    window_s: float = 0.0
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    checks: List[Check] = dataclasses.field(default_factory=list)
    control: Dict[str, float] = dataclasses.field(default_factory=dict)
    peak_bytes: Optional[int] = None
    bytes_limit: Optional[int] = None
    programs_in_window: int = 0
    trace: Any = None                  # xplane.Reduced of a traced run
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def read_memory(self, chips: int):
        """Peak bytes in use and the allocator's limit on the fullest of
        the first ``chips`` devices (None where the backend keeps no
        statistics, as the CPU)."""
        import jax
        stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
        stats = [s for s in stats if "peak_bytes_in_use" in s]
        if stats:
            top = max(stats, key=lambda s: s["peak_bytes_in_use"])
            self.peak_bytes = top["peak_bytes_in_use"]
            self.bytes_limit = top.get("bytes_limit")


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with every part it names, loaded."""
    root: str
    name: str
    chips: int
    config: dict                       # the configuration file's JSON
    reference: Any                     # the module beside it
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]             # the metric entries of this cell
    per_layer: List[dict]

    def model_config(self):
        """The program's ``ModelConfig`` built from the configuration
        file's ``model`` section."""
        from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
        m = dict(self.config["model"])
        if m.get("ssm") is not None:
            m["ssm"] = SSMConfig(**m["ssm"])
        if m.get("moe") is not None:
            m["moe"] = MoEConfig(**m["moe"])
        return ModelConfig(**m)

    def driver(self):
        return load_module(os.path.join(
            self.root, "chipbench", "drivers",
            self.traffic["driver"] + ".py"))

    def reader(self, metric: str):
        return load_module(os.path.join(self.root, "chipbench", "metrics",
                                        metric + ".py"))


def setup_compiles(lap_open: tuple) -> Dict[str, float]:
    """The compile clock's reading as the window opens: the seconds set-up
    spent building programs, how many it built and how many of those the
    persistent cache served."""
    seconds, programs, hits = lap_open
    return {"setup_compile_s": seconds, "setup_programs": programs,
            "setup_cache_hits": hits}


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _reads(metric: dict, cell: str, end_to_end: List[dict]) -> bool:
    """A per-layer metric with no ``workloads`` key is read in every cell
    that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in [m["name"] for m in end_to_end]


def load_cell(root: str, name: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    base = os.path.join(root, "chipbench")
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(base, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(base, "limits", name + ".json")) as f:
        limits = json.load(f)
    ref_path = os.path.splitext(os.path.join(root, cfg_entry["file"]))[0]
    end_to_end = [m for m in bench["end_to_end"] if _applies(m, name)]
    return Cell(root=root, name=name, chips=w["chips"], config=config,
                reference=load_module(ref_path + ".py"),
                traffic=traffic, limits=limits, end_to_end=end_to_end,
                per_layer=[m for m in bench["per_layer"]
                           if _reads(m, name, end_to_end)])


def result(cell: Cell, obs: Obs, device: dict, traced: bool) -> dict:
    """The result line's object. ``metrics`` holds the cell's end-to-end
    metrics, or with a traced run its per-layer metrics (each reader that
    finds nothing to read is left out)."""
    metrics: Dict[str, dict] = {}
    if traced:
        for m in cell.per_layer:
            value = cell.reader(m["name"]).read(obs, cell, device)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": obs.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": bool(obs.checks) and all(c.ok for c in obs.checks),
           "attempted": obs.attempted, "failed": obs.failed,
           "metrics": metrics, "device": device}
    if traced and obs.trace is not None:
        out["breakdown"] = {"device_ops": obs.trace.top_ops(10),
                            "idle_gaps": obs.trace.gaps[:10]}
    if obs.control:
        out["control"] = obs.control
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in obs.checks}
    return out
