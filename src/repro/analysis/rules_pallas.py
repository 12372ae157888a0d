"""PAL rule family: pallas_call kernel-contract checks.

PR 8's rule families police the *Python entrypoint* layer (masking
dispatch, donation, determinism). These rules police the layer
underneath — the ``pallas_call`` itself — where the real hazards live:
an accumulator scratch without its init guard double-counts across
grid steps, an index map whose arity drifts from the grid silently
reads the wrong tiles, and masking applied after the kernel (where-
zero) burns MXU cycles the lane predicate was supposed to save.

Catalog (details in DESIGN.md §14):

  PAL401  index-map arity: lambda params == grid rank (+ scalar-
          prefetch operands), and the map's output tuple arity == the
          BlockSpec's block-shape rank.
  PAL402  index-map prunability: flag non-affine maps. Classification
          (affine / affine_div / pruned / non_affine) also feeds the
          pruning-readiness report (kernel_report.py) that ROADMAP
          3(b)'s scalar-prefetch grid pruning consumes; ``pruned`` is
          that pruning done (a grid index clamped by prefetched
          bounds).
  PAL403  lane masking must reach the kernel: every kernel registered
          in ``MASKED_KERNELS`` must gate its dot/einsum ops (or, for
          dot-free kernels, its ref writes) behind ``pl.when`` on an
          SMEM lane-predicate read. Post-hoc where-zero does not count.
  PAL404  accumulator discipline: scratch updated from itself needs a
          ``pl.when(k == 0)`` init guard, and a direct scratch emit
          into an output ref must sit under ``pl.when(k == nk - 1)``.
  PAL405  dimension_semantics arity == grid rank, and every grid axis
          appearing in an accumulator guard must be "arbitrary".
  PAL406  tile-traffic drift: per-grid-step HBM bytes computed from the
          block shapes (f32 model) must match the registered budget in
          ``roofline.hlo_costs.PALLAS_TILE_BUDGETS`` within tolerance.
"""
from __future__ import annotations

import ast
from typing import Dict, List

from repro.analysis import pallas_model as pm
from repro.analysis.core import SourceModule, register


def _models(mod: SourceModule, config) -> List[pm.PallasCallModel]:
    return pm.extract_pallas_calls(
        mod, config.tile_nominal_dims.get(mod.relpath, {}))


def _by_relpath(modules) -> Dict[str, SourceModule]:
    return {m.relpath: m for m in modules}


@register("PAL401", "pallas-index-map-arity",
          "index-map params must match grid rank; output arity must "
          "match block-shape rank")
def rule_pal401(modules, config):
    findings = []
    for mod in modules:
        for m in _models(mod, config):
            if m.grid_rank is None:
                findings.append(mod.finding(
                    "PAL401", "pallas-index-map-arity", m.lineno,
                    f"pallas_call in `{m.entry}` has no statically "
                    "resolvable grid — keep `grid=` a literal tuple (or "
                    "a local assigned one) so arity checks can run",
                    context=m.entry))
                continue
            for spec in m.specs:
                im = spec.index_map
                if im is None:
                    continue
                where = f"{spec.role}_specs[{spec.position}]"
                if len(im.params) != m.grid_rank + m.n_prefetch:
                    findings.append(mod.finding(
                        "PAL401", "pallas-index-map-arity", im.lineno,
                        f"`{m.entry}` {where}: index map takes "
                        f"{len(im.params)} arguments but the grid has "
                        f"rank {m.grid_rank} and {m.n_prefetch} "
                        f"scalar-prefetch operand(s)", context=m.entry))
                if (spec.block_shape is not None
                        and len(im.exprs) != len(spec.block_shape)):
                    findings.append(mod.finding(
                        "PAL401", "pallas-index-map-arity", im.lineno,
                        f"`{m.entry}` {where}: index map returns "
                        f"{len(im.exprs)} coordinates but the block shape "
                        f"has rank {len(spec.block_shape)}",
                        context=m.entry))
    return findings


@register("PAL402", "pallas-index-map-prunable",
          "index maps must stay affine (or affine-with-div) in the grid "
          "indices so scalar-prefetch pruning stays possible")
def rule_pal402(modules, config):
    findings = []
    for mod in modules:
        for m in _models(mod, config):
            for spec in m.specs:
                im = spec.index_map
                if im is None or im.classification != pm.NON_AFFINE:
                    continue
                bad = [e for e, c in zip(im.exprs, im.classes)
                       if c == pm.NON_AFFINE]
                findings.append(mod.finding(
                    "PAL402", "pallas-index-map-prunable", im.lineno,
                    f"`{m.entry}` {spec.role}_specs[{spec.position}]: "
                    f"index map element(s) {', '.join(bad)} are not "
                    "affine in the grid indices — this block cannot be "
                    "pruned by scalar-prefetch index rewriting "
                    "(ROADMAP 3b)", context=m.entry))
    return findings


@register("PAL403", "pallas-lane-mask-native",
          "MASKED_KERNELS pallas kernels must gate accumulate/dot work "
          "behind pl.when on an SMEM lane predicate")
def rule_pal403(modules, config):
    findings = []
    by_rel = _by_relpath(modules)
    for relpath in sorted(config.masked_kernels):
        mod = by_rel.get(relpath)
        if mod is None:
            continue
        models = _models(mod, config)
        for entry in config.masked_kernels[relpath]:
            entry_models = [m for m in models if m.entry == entry]
            if not entry_models:
                findings.append(mod.finding(
                    "PAL403", "pallas-lane-mask-native", 1,
                    f"MASKED_KERNELS registers `{entry}` but no "
                    "pallas_call site was found in that function — "
                    "update repro.analysis.config", context=entry))
                continue
            for m in entry_models:
                bodies = [pm.analyze_kernel(mod, k, len(m.out_specs),
                                            m.n_scratch)
                          for k in m.kernel_names]
                bodies = [b for b in bodies if b is not None]
                if any(pm.kernel_is_lane_gated(mod, b) for b in bodies):
                    continue
                findings.append(mod.finding(
                    "PAL403", "pallas-lane-mask-native", m.lineno,
                    f"`{entry}` has no kernel variant gating its "
                    "compute behind pl.when on an SMEM lane predicate — "
                    "inactive lanes still issue MXU work (post-hoc "
                    "where-zero does not count; see packed_gemm."
                    "_pg_masked_kernel for the pattern)",
                    context=entry))
    return findings


@register("PAL404", "pallas-accumulator-guards",
          "accumulator scratch needs pl.when(k==0) init; direct scratch "
          "emits into outputs need pl.when(k==nk-1)")
def rule_pal404(modules, config):
    findings = []
    for mod in modules:
        seen = set()
        for m in _models(mod, config):
            for kname in m.kernel_names:
                if kname in seen:
                    continue
                seen.add(kname)
                body = pm.analyze_kernel(mod, kname, len(m.out_specs),
                                         m.n_scratch)
                if body is None:
                    continue
                n_pos = len(body.params)
                n_out = len(m.out_specs)
                outs = set(body.params[n_pos - m.n_scratch - n_out:
                                       n_pos - m.n_scratch])

                for s in sorted(body.accumulated):
                    inited = any(
                        g.kind == "zero" and any(
                            isinstance(t, ast.Subscript)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == s
                            for n in ast.walk(g.node)
                            if isinstance(n, ast.Assign)
                            for t in n.targets)
                        for g in body.guards)
                    if not inited:
                        findings.append(mod.finding(
                            "PAL404", "pallas-accumulator-guards",
                            body.node.lineno,
                            f"kernel `{kname}`: accumulator scratch "
                            f"`{s}` is updated from itself but never "
                            "zero-initialised under pl.when(k == 0) — "
                            "it carries garbage across grid steps",
                            context=kname))

                # direct scratch emits into output refs must be guarded
                last_nodes = set()
                for g in body.guards:
                    if g.kind == "last":
                        for n in ast.walk(g.node):
                            last_nodes.add(id(n))
                for node in ast.walk(body.node):
                    if not (isinstance(node, ast.Assign)
                            and len(node.targets) == 1):
                        continue
                    t = node.targets[0]
                    if not (isinstance(t, ast.Subscript)
                            and isinstance(t.value, ast.Name)
                            and t.value.id in outs):
                        continue
                    reads = pm._subscript_reads(node.value,
                                                body.accumulated)
                    if reads and id(node) not in last_nodes:
                        findings.append(mod.finding(
                            "PAL404", "pallas-accumulator-guards",
                            node.lineno,
                            f"kernel `{kname}`: output ref "
                            f"`{t.value.id}` is written from accumulator "
                            f"scratch {sorted(reads)} outside a "
                            "pl.when(k == nk - 1) guard — partial sums "
                            "escape on every grid step",
                            context=kname))
    return findings


@register("PAL405", "pallas-dimension-semantics",
          "dimension_semantics arity must match grid rank; accumulation "
          "axes must be declared \"arbitrary\"")
def rule_pal405(modules, config):
    findings = []
    for mod in modules:
        for m in _models(mod, config):
            sem = m.dimension_semantics
            if sem is None or m.grid_rank is None:
                continue
            if len(sem) != m.grid_rank:
                findings.append(mod.finding(
                    "PAL405", "pallas-dimension-semantics", m.lineno,
                    f"`{m.entry}`: dimension_semantics has "
                    f"{len(sem)} entries but the grid has rank "
                    f"{m.grid_rank}", context=m.entry))
                continue
            axes = set()
            for kname in m.kernel_names:
                body = pm.analyze_kernel(mod, kname, len(m.out_specs),
                                         m.n_scratch)
                if body is None or not body.accumulated:
                    continue
                for g in body.guards:
                    if g.kind in ("zero", "last"):
                        axes.update(g.axes)
            for axis in sorted(axes):
                if axis < len(sem) and sem[axis] != "arbitrary":
                    findings.append(mod.finding(
                        "PAL405", "pallas-dimension-semantics", m.lineno,
                        f"`{m.entry}`: grid axis {axis} carries scratch "
                        f"accumulation but dimension_semantics declares "
                        f"it \"{sem[axis]}\" — a parallel axis may "
                        "execute out of order and corrupt the "
                        "accumulator", context=m.entry))
    return findings


@register("PAL406", "pallas-tile-traffic-budget",
          "per-grid-step HBM bytes from block shapes must match the "
          "registered roofline budget within tolerance")
def rule_pal406(modules, config):
    findings = []
    for mod in modules:
        for m in _models(mod, config):
            budget = config.tile_budgets.get(m.key)
            if budget is None:
                findings.append(mod.finding(
                    "PAL406", "pallas-tile-traffic-budget", m.lineno,
                    f"`{m.entry}`: no tile-traffic budget registered — "
                    f"add \"{m.key}\" to roofline.hlo_costs."
                    "PALLAS_TILE_BUDGETS (register before you build)",
                    context=m.entry))
                continue
            total, unresolved = m.bytes_per_step()
            if total is None:
                findings.append(mod.finding(
                    "PAL406", "pallas-tile-traffic-budget", m.lineno,
                    f"`{m.entry}`: block dims {list(unresolved)} are not "
                    "statically resolvable — add nominal sizes to "
                    "roofline.hlo_costs.PALLAS_NOMINAL_DIMS",
                    context=m.entry))
                continue
            tol = config.tile_tolerance
            if abs(total - budget) > tol * budget:
                findings.append(mod.finding(
                    "PAL406", "pallas-tile-traffic-budget", m.lineno,
                    f"`{m.entry}`: modeled tile traffic "
                    f"{total:.0f} B/step drifts from the registered "
                    f"budget {budget:.0f} B/step by more than "
                    f"{tol:.0%} — re-derive the BlockSpecs or update "
                    "PALLAS_TILE_BUDGETS alongside the kernel change",
                    context=m.entry))
    return findings
