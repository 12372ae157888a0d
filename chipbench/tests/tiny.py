"""A benchmark root at CPU size, built by adding files only.

``make_root(tmp)`` copies ``BENCHMARK.json`` and ``chipbench/`` into
``tmp`` and then *adds* what a later change would add for a new cell: a
configuration (its JSON and its reference module), a traffic mix, a
limits file and a per-layer metric, plus entries in ``BENCHMARK.json``
(and the new cells' names in the ``workloads`` of the end-to-end metrics
they report). No file that was copied is edited, so a run that finds the
new parts shows that they are found by name. The per-layer metrics that
carry no ``workloads`` key reach the new cells through the end-to-end
metric each moves.
"""
from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {
    "mamba2-tiny": ("mamba2-130m", {
        "num_layers": 2, "d_model": 64, "vocab_size": 256,
        "ssm": {"state_dim": 16, "head_dim": 16, "num_heads": 0, "expand": 2,
                "conv_width": 4, "chunk_size": 32},
        "compute_dtype": "float32", "remat": False}),
    "stablelm-tiny": ("stablelm-1.6b", {
        "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
        "d_ff": 128, "vocab_size": 256, "compute_dtype": "float32",
        "remat": False}),
}

MIXES = {
    "sweep-tiny": {"driver": "sweep", "tasks": 3,
                   "lr": {"kind": "geometric", "base": 1e-2, "ratio": 2.0,
                          "offset": -1},
                   "steps": {"kind": "fixed", "value": 500},
                   "batch": 2, "seq": 64, "max_pack": 2},
    "serve-tiny": {"driver": "serve", "lanes": 3, "max_len": 48,
                   "prompt_len": {"kind": "fixed", "value": 16},
                   "max_new": {"kind": "lognormal", "median": 8, "sigma": 0.8,
                               "low": 2, "high": 32, "integer": True},
                   "backlog": 400},
}

#: tiny cell -> (configuration, mix, the full-size cell whose limits it
#: keeps)
CELLS = {"mamba2-tiny.sweep-tiny": ("mamba2-tiny", "sweep-tiny",
                                    "mamba2-130m.sweep-long"),
         "stablelm-tiny.serve-tiny": ("stablelm-tiny", "serve-tiny",
                                      "stablelm-1.6b.serve-backlog")}

#: a per-layer metric added by file: window steps per cell
NEW_METRIC = '''"""Pool steps in the window (a metric added by file)."""


def read(obs, cell, device):
    return obs.counters.get("pool_steps")
'''


def make_root(tmp: str) -> str:
    root = os.path.join(tmp, "root")
    base = os.path.join(root, "chipbench")
    shutil.copytree(os.path.join(REPO, "chipbench"), base,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, (parent, change) in TINY.items():
        with open(os.path.join(base, "configs", parent + ".json")) as f:
            cfg = json.load(f)
        cfg["name"] = name
        cfg["model"].update(change, name=name)
        with open(os.path.join(base, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)
        shutil.copy(os.path.join(base, "configs", parent + ".py"),
                    os.path.join(base, "configs", name + ".py"))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"chipbench/configs/{name}.json",
                                 "reduced": sorted(change), "why": "test"})
    for name, mix in MIXES.items():
        with open(os.path.join(base, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    for cell, (config, mix, full) in CELLS.items():
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": mix, "chips": 1, "why": "test"})
        shutil.copy(os.path.join(base, "limits", full + ".json"),
                    os.path.join(base, "limits", cell + ".json"))
    with open(os.path.join(base, "metrics", "pool_steps.py"), "w") as f:
        f.write(NEW_METRIC)
    bench["per_layer"].append({"name": "pool_steps", "unit": "steps",
                               "better": "higher", "source": "program_counter",
                               "layer": "lane pool",
                               "moves": "train_tokens_per_s",
                               "workloads": ["mamba2-tiny.sweep-tiny"]})
    for m in bench["end_to_end"]:
        for cell, (_, _, full) in CELLS.items():
            if full in m.get("workloads", ()):
                m["workloads"].append(cell)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root
