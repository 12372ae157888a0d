"""Pruning-readiness report over every pallas_call site (DESIGN.md §14).

    PYTHONPATH=src python -m repro.analysis.kernel_report           # table
    PYTHONPATH=src python -m repro.analysis.kernel_report --json
    PYTHONPATH=src python -m repro.analysis.kernel_report --check   # CI gate

The JSON report is the machine-readable contract ROADMAP 3(b)'s
scalar-prefetch grid pruning consumes: per kernel, which index maps
are affine (rewritable to a prefetched index vector), which are
affine-with-div (prunable with a gather), which are pruned already
(clamped by prefetched bounds), whether the kernel already
carries a lane predicate, and the modeled HBM bytes per grid step. A
kernel is marked ``prunable`` when it is lane-gated AND every input
index map is statically rewritable — exactly the precondition for
skipping inactive tiles' HBM streams.

``--check`` is the CI gate: it re-runs the full lint (dep-free, AST
only) and fails on any PAL-family finding that is not tolerated by the
committed baseline, so the report and the gate can never disagree.
Exit status: 0 clean, 1 contract drift, 2 usage errors.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

from repro.analysis import pallas_model as pm
from repro.analysis.config import LintConfig, default_config
from repro.analysis.core import SourceModule, all_rule_ids
from repro.analysis.driver import collect_files, run_lint

REPORT_VERSION = 1


def _spec_entry(spec: pm.SpecModel) -> Dict:
    entry: Dict = {
        "role": spec.role,
        "position": spec.position,
        "block_shape": list(spec.block_shape) if spec.block_shape else None,
        "block_elems": spec.block_elems,
        "memory_space": spec.memory_space,
        "conditional": spec.conditional,
    }
    if spec.index_map is None:
        entry["index_map"] = None
    else:
        im = spec.index_map
        entry["index_map"] = {
            "params": list(im.params),
            "exprs": list(im.exprs),
            "classes": list(im.classes),
            "classification": im.classification,
        }
    return entry


def _kernel_entry(mod: SourceModule, m: pm.PallasCallModel,
                  config: LintConfig) -> Dict:
    bodies = [pm.analyze_kernel(mod, k, len(m.out_specs), m.n_scratch)
              for k in m.kernel_names]
    bodies = [b for b in bodies if b is not None]
    lane = any(pm.kernel_is_lane_gated(mod, b) for b in bodies)
    bytes_per_step, unresolved = m.bytes_per_step()
    in_maps = [s.index_map for s in m.in_specs if s.index_map is not None]
    rewritable = all(im.classification in (pm.AFFINE, pm.AFFINE_DIV,
                                           pm.PRUNED) for im in in_maps)
    return {
        "path": m.relpath,
        "entry": m.entry,
        "line": m.lineno,
        "grid": list(m.grid_exprs),
        "grid_rank": m.grid_rank,
        "dimension_semantics": (list(m.dimension_semantics)
                                if m.dimension_semantics else None),
        "kernels": list(m.kernel_names),
        "lane_predicate": lane,
        "scratch": list(m.scratch_exprs),
        "operands": [_spec_entry(s) for s in m.specs],
        "bytes_per_grid_step": bytes_per_step,
        "unresolved_dims": list(unresolved),
        "tile_budget": config.tile_budgets.get(m.key),
        "prunable": bool(lane and rewritable),
    }


def build_report(config: LintConfig) -> Dict:
    """The full pruning-readiness report as a JSON-serialisable dict.
    Deterministic: files come from the sorted walk, kernels are in
    source order within a file."""
    known = all_rule_ids()
    kernels: List[Dict] = []
    for path in collect_files(config):
        mod = SourceModule.load(path, config.root, known)
        nominal = config.tile_nominal_dims.get(mod.relpath, {})
        for m in pm.extract_pallas_calls(mod, nominal):
            kernels.append(_kernel_entry(mod, m, config))
    return {
        "version": REPORT_VERSION,
        "paths": list(config.paths),
        "kernels": kernels,
        "n_kernels": len(kernels),
        "n_prunable": sum(1 for k in kernels if k["prunable"]),
    }


def _format_table(rep: Dict) -> str:
    lines = []
    for k in rep["kernels"]:
        classes = sorted({s["index_map"]["classification"]
                          for s in k["operands"] if s["index_map"]})
        lines.append(
            f"{k['path']}:{k['line']}: {k['entry']} "
            f"grid={k['grid_rank']} lane_predicate={k['lane_predicate']} "
            f"maps={'/'.join(classes) or '-'} "
            f"bytes/step={k['bytes_per_grid_step'] or '?'} "
            f"prunable={k['prunable']}")
    lines.append(f"kernel_report: {rep['n_kernels']} pallas_call site(s), "
                 f"{rep['n_prunable']} prunable")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.kernel_report",
        description="static pruning-readiness report over every "
                    "pallas_call site")
    ap.add_argument("--root", default=None,
                    help="checkout root (default: derived from the "
                         "package location)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable report on stdout")
    ap.add_argument("--out", default=None,
                    help="also write the JSON report to this file")
    ap.add_argument("--check", action="store_true",
                    help="gate mode: exit 1 on any PAL finding not "
                         "tolerated by the committed baseline")
    args = ap.parse_args(argv)

    try:
        config = default_config(root=args.root)
        rep = build_report(config)
    except (OSError, SyntaxError, ValueError) as e:
        print(f"kernel_report: error: {e}", file=sys.stderr)
        return 2

    text = json.dumps(rep, indent=2, sort_keys=True)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(text + "\n")

    if args.check:
        result = run_lint(config)
        pal_new = [f for f in result.new if f.rule.startswith("PAL")]
        pal_stale = [fp for fp in result.stale if fp.startswith("PAL")]
        for f in pal_new:
            print(f.render())
        for fp in pal_stale:
            print(f"kernel_report: stale baseline entry (fixed but "
                  f"shrink not committed): {fp}")
        ok = not pal_new and not pal_stale
        print(f"kernel_report: {rep['n_kernels']} pallas_call site(s), "
              f"{rep['n_prunable']} prunable, "
              f"{len(pal_new)} new PAL finding(s)"
              + (" — clean" if ok else ""))
        return 0 if ok else 1

    if args.as_json:
        print(text)
    else:
        print(_format_table(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
