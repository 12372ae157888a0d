"""Trip-count-aware cost analysis over compiled HLO text.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body ONCE, so any
scanned-layers model is undercounted by ~num_layers× (verified empirically
in tests). This module re-derives costs from the HLO text with loop
multipliers:

  * parse computations + instructions (symbol table per computation);
  * build the call graph (fusion ``calls=``, ``while`` body/cond,
    conditional branches, reduce ``to_apply`` ...);
  * trip counts from the while condition region (the loop-bound constant);
  * FLOPs: dot/convolution terms (2 × output elements × contraction size),
    multiplied by the product of enclosing trip counts — elementwise FLOPs
    are ignored (dots dominate at roofline relevance);
  * HBM bytes: per *top-level* instruction (entry / while / conditional
    regions — fusion internals excluded) operand+result bytes, the standard
    fusion-aware traffic model;
  * collective payload bytes with the same multipliers.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# --------------------------------------------------------------------------
# static pallas tile-traffic budgets (PAL406 / kernel_report; DESIGN.md §14)
# --------------------------------------------------------------------------

#: Nominal sizes for kernel block dims the AST traffic model cannot
#: resolve to a constant (runtime shape symbols with no declared
#: default), keyed per file so one kernel's symbols never leak into
#: another's. Values mirror the repo's benchmark shapes; the model is a
#: drift detector, so only the ratio to the budget matters.
PALLAS_NOMINAL_DIMS: Dict[str, Dict[str, int]] = {
    "src/repro/kernels/flash_attention.py": {"D": 128},   # head dim
    "src/repro/kernels/fused_rmsnorm.py": {"d": 1024},    # feature dim
    "src/repro/kernels/ssd_scan.py": {
        "nh": 8, "hd": 64, "N": 64},  # heads, head dim, state dim
    # heads per block, query group, head dim, cache length, positions
    # per grid step
    "src/repro/kernels/decode_attention.py": {
        "hb": 8, "G": 1, "D": 64, "S": 1024, "bs": 1024},
}

#: Expected HBM bytes streamed per grid step, keyed ``relpath::entry``,
#: priced at f32 per element (SMEM scalar operands are free). Derived
#: from the committed BlockSpecs; PAL406 fails the lint when an edit
#: drifts more than PALLAS_TILE_TOLERANCE from these numbers, so a
#: BlockSpec change must update its budget in the same review.
PALLAS_TILE_BUDGETS: Dict[str, float] = {
    "src/repro/kernels/packed_gemm.py::packed_gemm": 196608.0,
    "src/repro/kernels/flash_attention.py::flash_attention_fwd": 262144.0,
    "src/repro/kernels/fused_rmsnorm.py::fused_rmsnorm": 2101248.0,
    "src/repro/kernels/fused_rmsnorm.py::packed_rmsnorm": 2101248.0,
    "src/repro/kernels/ssd_scan.py::ssd_scan": 148992.0,
    "src/repro/kernels/decode_attention.py::_decode_attention": 4216832.0,
}

#: Allowed relative drift between the modeled bytes/step and the budget.
PALLAS_TILE_TOLERANCE = 0.25

_SHAPE_TOKEN = re.compile(r"(\w+)\[([0-9,]*)\]")
_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*((?:\([^)]*\))|(?:\w+\[[0-9,]*\](?:\{[^}]*\})?))\s+([\w\-]+)\((.*)$")
_CALLS = re.compile(r"calls=%?([\w\.\-]+)")
_WHILE = re.compile(r"condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)")
_TO_APPLY = re.compile(r"to_apply=%?([\w\.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CONSTANT = re.compile(r"=\s*s32\[\]\s+constant\((\d+)\)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERANDS = re.compile(r"%([\w\.\-]+)")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_FREE_OPS = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast",
             "after-all", "partition-id", "replica-id", "iota", "opt-barrier"}


def _type_elems_bytes(typespec: str) -> Tuple[int, int]:
    elems = b = 0
    for dtype, dims in _SHAPE_TOKEN.findall(typespec):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        elems += n
        b += n * _DTYPE_BYTES[dtype]
    return elems, b


@dataclasses.dataclass
class Instr:
    name: str
    typespec: str
    opcode: str
    rest: str


@dataclasses.dataclass
class Computation:
    name: str
    is_entry: bool
    instrs: List[Instr]
    shapes: Dict[str, str]             # symbol table: name -> typespec


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for line in text.splitlines():
        stripped = line.strip()
        if cur is None:
            m = _COMP_HEADER.match(stripped)
            if m:
                cur = Computation(name=m.group(2), is_entry=bool(m.group(1)),
                                  instrs=[], shapes={})
            continue
        if stripped == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _INSTR.match(line)
        if m:
            name, typespec, opcode, rest = m.groups()
            cur.instrs.append(Instr(name, typespec, opcode, rest))
            cur.shapes[name] = typespec
    return comps


def _dot_flops(instr: Instr, shapes: Dict[str, str]) -> float:
    out_elems, _ = _type_elems_bytes(instr.typespec)
    ops = _OPERANDS.findall(instr.rest)
    if not ops:
        return 0.0
    lhs_spec = shapes.get(ops[0], "")
    mtok = _SHAPE_TOKEN.search(lhs_spec)
    if not mtok:
        return 0.0
    dims = [int(d) for d in mtok.group(2).split(",") if d]
    mc = _CONTRACT.search(instr.rest)
    contract = 1
    if mc and mc.group(1):
        for idx in mc.group(1).split(","):
            i = int(idx)
            if i < len(dims):
                contract *= dims[i]
    return 2.0 * out_elems * contract


def _conv_flops(instr: Instr, shapes: Dict[str, str]) -> float:
    # output elems × 2 × (kernel spatial × in_channels): approximate via
    # rhs (kernel) total elems / out_channels
    out_elems, _ = _type_elems_bytes(instr.typespec)
    ops = _OPERANDS.findall(instr.rest)
    if len(ops) < 2:
        return 0.0
    k_spec = shapes.get(ops[1], "")
    k_elems, _ = _type_elems_bytes(k_spec)
    mtok = _SHAPE_TOKEN.search(instr.typespec)
    if not mtok:
        return 0.0
    return 2.0 * out_elems * max(k_elems, 1)  # loose upper bound; convs rare


def _instr_bytes(instr: Instr, shapes: Dict[str, str]) -> int:
    if instr.opcode in _FREE_OPS:
        return 0
    _, out_b = _type_elems_bytes(instr.typespec)
    if instr.opcode == "dynamic-update-slice":
        ops = _OPERANDS.findall(instr.rest)
        if len(ops) >= 2:
            _, upd = _type_elems_bytes(shapes.get(ops[1], ""))
            return 2 * upd
        return out_b
    total = out_b
    for op in _OPERANDS.findall(instr.rest.split(", calls=")[0]
                                .split(", condition=")[0]):
        spec = shapes.get(op)
        if spec is None:
            continue
        _, b = _type_elems_bytes(spec)
        total += b
    return total


def _trip_count(cond: Computation) -> int:
    consts = [int(x) for x in _CONSTANT.findall(
        "\n".join(f"%{i.name} = {i.typespec} {i.opcode}({i.rest}"
                  for i in cond.instrs))]
    # jax scan condition: induction < trip  (take the max plausible bound)
    return max(consts) if consts else 1


_SCOPE_TAGS = ("sdpa", "ssd")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def _scope_tag(rest: str) -> str:
    m = _OPNAME_RE.search(rest)
    if not m:
        return "other"
    name = m.group(1)
    for tag in _SCOPE_TAGS:
        if f"/{tag}/" in name or name.endswith(f"/{tag}"):
            return tag
    return "other"


@dataclasses.dataclass
class HloCosts:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    collective_operand_bytes: float = 0.0
    collective_traffic_bytes: float = 0.0
    coll_by_kind: Dict[str, float] = dataclasses.field(default_factory=dict)
    while_trips: List[int] = dataclasses.field(default_factory=list)
    # HBM bytes attributed to named scopes ("sdpa", "ssd", "other") — the
    # kernel-substitution accounting reads these (§Perf)
    bytes_by_tag: Dict[str, float] = dataclasses.field(default_factory=dict)


def analyze_hlo(text: str) -> HloCosts:
    from repro.roofline.analysis import CollectiveOp, _GROUPS_RE, _GROUPS_LEGACY_RE

    comps = parse_module(text)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None:
        return HloCosts()
    out = HloCosts()

    def visit(comp: Computation, mult: float, count_bytes: bool,
              depth: int = 0):
        if depth > 32:
            return
        for ins in comp.instrs:
            op = ins.opcode
            if op == "dot":
                out.flops += mult * _dot_flops(ins, comp.shapes)
            elif op == "convolution":
                out.flops += mult * _conv_flops(ins, comp.shapes)
            if count_bytes:
                b = mult * _instr_bytes(ins, comp.shapes)
                out.hbm_bytes += b
                if b:
                    tag = _scope_tag(ins.rest)
                    out.bytes_by_tag[tag] = out.bytes_by_tag.get(tag, 0.0) + b
            base = op[:-6] if op.endswith("-start") else op
            if base in COLLECTIVES and not op.endswith("-done"):
                _, rb = _type_elems_bytes(ins.typespec)
                gm = _GROUPS_RE.search(ins.rest)
                if gm:
                    gsize = int(gm.group(2))
                else:
                    gl = _GROUPS_LEGACY_RE.search(ins.rest)
                    gsize = len(gl.group(1).split(",")) if gl else 1
                cop = CollectiveOp(base, rb, gsize)
                out.collective_operand_bytes += mult * cop.operand_bytes
                out.collective_traffic_bytes += mult * cop.traffic_bytes
                out.coll_by_kind[base] = (out.coll_by_kind.get(base, 0.0)
                                          + mult * cop.operand_bytes)
            # ---- recurse into called computations ----
            wm = _WHILE.search(ins.rest)
            if op == "while" and wm:
                cond_name, body_name = wm.groups()
                cond = comps.get(cond_name)
                body = comps.get(body_name)
                trip = _trip_count(cond) if cond else 1
                out.while_trips.append(trip)
                if body:
                    visit(body, mult * trip, count_bytes, depth + 1)
                if cond:
                    visit(cond, mult * trip, False, depth + 1)
                continue
            bm = _BRANCHES.search(ins.rest)
            if op == "conditional" and bm:
                for br in _OPERANDS.findall(bm.group(1)):
                    c = comps.get(br)
                    if c:
                        visit(c, mult, count_bytes, depth + 1)
                continue
            cm = _CALLS.search(ins.rest)
            if cm and op == "fusion":
                c = comps.get(cm.group(1))
                if c:
                    visit(c, mult, False, depth + 1)  # flops only
                continue
            if op in ("call", "async-start"):
                tm = _TO_APPLY.search(ins.rest) or _CALLS.search(ins.rest)
                if tm:
                    c = comps.get(tm.group(1))
                    if c:
                        visit(c, mult, count_bytes, depth + 1)
            # reduce/scatter/sort to_apply bodies: scalar lambdas — ignore

    visit(entry, 1.0, True)
    return out
