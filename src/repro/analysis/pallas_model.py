"""Symbolic model of ``pallas_call`` sites, extracted from the AST.

The PAL rule family (rules_pallas.py) and the pruning-readiness report
(kernel_report.py) both need the same facts about every Pallas kernel:
the grid, the in/out BlockSpecs with their index-map lambdas, the
kernel function(s) a call site can dispatch to, scratch shapes and
``dimension_semantics``. This module extracts them statically — no jax
import, pure ``ast`` — so the checks run in the dep-free
``static-analysis`` CI job before any test matrix spins up.

Resolution model (deliberately simple, matched to the repo's kernel
idiom — see DESIGN.md §14):

  * a block dim that is a constant resolves to itself;
  * a Name resolves through the entry function's local assignments
    (tuple-unpacking included), then its parameter default, then the
    ``nominal`` table (``roofline.hlo_costs.PALLAS_NOMINAL_DIMS``) —
    ``bm = min(block_m, M)`` with unknown runtime ``M`` resolves to the
    declared default of ``block_m``, i.e. the per-step tile ceiling;
  * ``min``/``max`` over partially-resolvable args take the resolvable
    subset; arithmetic (`+ - * //`) folds when both sides resolve;
  * everything else stays symbolic (reported by name, priced as
    unresolved).

Index maps are classified per output element and the worst class wins:

  * ``affine``      — constants, grid indices, and +/-/× by
    grid-constant terms (prunable by scalar-prefetch index rewriting);
  * ``affine_div``  — a grid index under integer division by a
    grid-constant (the GQA ``h // G`` map; prunable with a gather);
  * ``pruned``      — an affine grid index clamped (``jnp.minimum`` /
    ``jnp.maximum`` / ``jnp.clip``) by scalar-prefetched bounds read at
    a grid index or a constant (``min(max(i, lo[b]), hi[b])``): the
    scalar-prefetch pruning itself, each step outside the bounds
    repeating a block index and so starting no DMA;
  * ``non_affine``  — anything else (data-dependent or multiplicative
    in two grid indices, or clamped by a value that is not prefetched;
    not statically prunable).
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Set, Tuple

from repro.analysis.core import SourceModule, resolve_call_name

AFFINE = "affine"
AFFINE_DIV = "affine_div"
PRUNED = "pruned"
NON_AFFINE = "non_affine"

_CLASS_RANK = {AFFINE: 0, AFFINE_DIV: 1, PRUNED: 2, NON_AFFINE: 3}

#: the calls that clamp an index (by their attribute name)
_CLAMPS = ("minimum", "maximum", "clip")

PALLAS_CALL = "jax.experimental.pallas.pallas_call"
BLOCK_SPEC = "jax.experimental.pallas.BlockSpec"
PARTIAL = "functools.partial"

#: Per-operand price of the traffic model: the model is *relative* (a
#: drift detector for BlockSpec edits), so every operand is priced at
#: f32 regardless of runtime dtype.
MODEL_DTYPE_BYTES = 4.0


@dataclasses.dataclass(frozen=True)
class IndexMapModel:
    """One BlockSpec index-map lambda."""
    params: Tuple[str, ...]
    exprs: Tuple[str, ...]        # unparsed output elements
    classes: Tuple[str, ...]      # per-element classification
    lineno: int

    @property
    def classification(self) -> str:
        worst = AFFINE
        for c in self.classes:
            if _CLASS_RANK[c] > _CLASS_RANK[worst]:
                worst = c
        return worst


@dataclasses.dataclass(frozen=True)
class SpecModel:
    """One BlockSpec operand of a pallas_call."""
    role: str                               # "in" | "out"
    position: int                           # index within the role
    block_shape: Optional[Tuple[str, ...]]  # unparsed dims (None: no shape)
    resolved: Optional[Tuple[Optional[int], ...]]
    index_map: Optional[IndexMapModel]
    memory_space: Optional[str]             # "SMEM" | "ANY" | ... | None
    conditional: bool                       # appended in a branch
    lineno: int

    @property
    def block_elems(self) -> Optional[int]:
        if self.resolved is None or any(d is None for d in self.resolved):
            return None
        n = 1
        for d in self.resolved:
            n *= d
        return n

    @property
    def unresolved_dims(self) -> Tuple[str, ...]:
        if self.block_shape is None or self.resolved is None:
            return ()
        return tuple(s for s, r in zip(self.block_shape, self.resolved)
                     if r is None)


@dataclasses.dataclass(frozen=True)
class PallasCallModel:
    """One pallas_call site inside a top-level entry function."""
    relpath: str
    entry: str                    # enclosing top-level function
    entry_lineno: int
    lineno: int                   # the call site
    grid_rank: Optional[int]      # None: not statically resolvable
    grid_exprs: Tuple[str, ...]
    kernel_names: Tuple[str, ...]   # candidate kernel functions
    in_specs: Tuple[SpecModel, ...]
    out_specs: Tuple[SpecModel, ...]
    n_scratch: int
    scratch_exprs: Tuple[str, ...]
    dimension_semantics: Optional[Tuple[str, ...]]
    n_prefetch: int = 0           # scalar-prefetch operands (index-map args)

    @property
    def key(self) -> str:
        """Budget-table key (roofline.hlo_costs.PALLAS_TILE_BUDGETS)."""
        return f"{self.relpath}::{self.entry}"

    @property
    def specs(self) -> Tuple[SpecModel, ...]:
        return self.in_specs + self.out_specs

    def bytes_per_step(self) -> Tuple[Optional[float], Tuple[str, ...]]:
        """(HBM bytes moved per grid step under the f32 model,
        unresolved dim names). SMEM/shapeless operands are free —
        scalar predicates and full-operand ANY specs are not part of
        the per-step streaming traffic."""
        total = 0.0
        unresolved: List[str] = []
        for spec in self.specs:
            if spec.block_shape is None or spec.memory_space == "SMEM":
                continue
            elems = spec.block_elems
            if elems is None:
                unresolved.extend(spec.unresolved_dims)
                continue
            total += elems * MODEL_DTYPE_BYTES
        if unresolved:
            return None, tuple(dict.fromkeys(unresolved))
        return total, ()


# --------------------------------------------------------------------------
# entry-function environment
# --------------------------------------------------------------------------

class _Env:
    """Local assignments, list-appends and parameter defaults of one
    entry function, for constant folding and name resolution."""

    def __init__(self, fn: ast.FunctionDef):
        self.assigns: Dict[str, List[ast.expr]] = {}
        self.appends: Dict[str, List[ast.expr]] = {}
        self.defaults: Dict[str, ast.expr] = {}
        args = fn.args
        pos = args.posonlyargs + args.args
        for a, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
            self.defaults[a.arg] = d
        for a, d in zip(args.kwonlyargs, args.kw_defaults):
            if d is not None:
                self.defaults[a.arg] = d
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    self._record(t, node.value)
            elif isinstance(node, ast.AugAssign) and isinstance(
                    node.target, ast.Name):
                # x = <unfoldable>: kill constant resolution for x
                self.assigns.setdefault(node.target.id, []).append(node)
            elif (isinstance(node, ast.Expr)
                  and isinstance(node.value, ast.Call)
                  and isinstance(node.value.func, ast.Attribute)
                  and node.value.func.attr == "append"
                  and isinstance(node.value.func.value, ast.Name)
                  and node.value.args):
                self.appends.setdefault(
                    node.value.func.value.id, []).append(node.value.args[0])

    def _record(self, target: ast.expr, value: ast.expr) -> None:
        if isinstance(target, ast.Name):
            self.assigns.setdefault(target.id, []).append(value)
        elif isinstance(target, (ast.Tuple, ast.List)):
            if (isinstance(value, (ast.Tuple, ast.List))
                    and len(value.elts) == len(target.elts)):
                for t, v in zip(target.elts, value.elts):
                    self._record(t, v)
            else:   # unpacking an opaque value: record as unresolvable
                for t in target.elts:
                    if isinstance(t, ast.Name):
                        self.assigns.setdefault(t.id, []).append(value)

    def lookup(self, name: str) -> List[ast.expr]:
        return self.assigns.get(name, [])


def _resolve_int(node: ast.AST, env: _Env, nominal: Mapping[str, int],
                 visiting: Optional[Set[str]] = None) -> Optional[int]:
    visiting = visiting if visiting is not None else set()
    if isinstance(node, ast.Constant):
        return node.value if isinstance(node.value, int) else None
    if isinstance(node, ast.Name):
        if node.id not in visiting:
            # only the LAST assignment counts: an earlier `rows = 1`
            # must not leak through a later unresolvable `rows *= s`
            values = env.lookup(node.id)
            if values:
                r = _resolve_int(values[-1], env, nominal,
                                 visiting | {node.id})
                if r is not None:
                    return r
        d = env.defaults.get(node.id)
        if d is not None:
            r = _resolve_int(d, env, nominal, visiting | {node.id})
            if r is not None:
                return r
        return nominal.get(node.id)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        r = _resolve_int(node.operand, env, nominal, visiting)
        return -r if r is not None else None
    if isinstance(node, ast.BinOp):
        lh = _resolve_int(node.left, env, nominal, visiting)
        rh = _resolve_int(node.right, env, nominal, visiting)
        if lh is None or rh is None:
            return None
        if isinstance(node.op, ast.Add):
            return lh + rh
        if isinstance(node.op, ast.Sub):
            return lh - rh
        if isinstance(node.op, ast.Mult):
            return lh * rh
        if isinstance(node.op, ast.FloorDiv) and rh != 0:
            return lh // rh
        if isinstance(node.op, ast.Mod) and rh != 0:
            return lh % rh
        return None
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
            and node.func.id in ("min", "max"):
        vals = [_resolve_int(a, env, nominal, visiting) for a in node.args]
        vals = [v for v in vals if v is not None]
        if not vals:
            return None
        return min(vals) if node.func.id == "min" else max(vals)
    return None


# --------------------------------------------------------------------------
# index-map classification
# --------------------------------------------------------------------------

def _contains_param(node: ast.AST, params: Set[str]) -> bool:
    return any(isinstance(n, ast.Name) and n.id in params
               for n in ast.walk(node))


def _is_prefetched_bound(node: ast.AST, params: Set[str],
                         prefetch: Set[str]) -> bool:
    """An element of a scalar-prefetch ref at a grid index or a
    constant (``lo[b]``)."""
    return (isinstance(node, ast.Subscript)
            and isinstance(node.value, ast.Name)
            and node.value.id in prefetch
            and (isinstance(node.slice, ast.Constant)
                 or (isinstance(node.slice, ast.Name)
                     and node.slice.id in params - prefetch)))


def _classify_clamp(node: ast.Call, params: Set[str],
                    prefetch: Set[str]) -> str:
    """``pruned`` for a clamp whose every argument is a prefetched bound
    or an index (an affine expression in the grid indices, or a nested
    clamp of this kind), with at least one of each; else
    ``non_affine``."""
    grid = params - prefetch
    bounds = indices = 0
    for arg in node.args:
        if _is_prefetched_bound(arg, params, prefetch):
            bounds += 1
        elif (isinstance(arg, ast.Call) and isinstance(arg.func,
                                                        ast.Attribute)
              and arg.func.attr in _CLAMPS):
            if _classify_clamp(arg, params, prefetch) != PRUNED:
                return NON_AFFINE
            indices += 1
        elif (_contains_param(arg, grid)
              and classify_index_expr(arg, params, prefetch)
              in (AFFINE, AFFINE_DIV)):
            indices += 1
        else:
            return NON_AFFINE
    return PRUNED if bounds and indices else NON_AFFINE


def classify_index_expr(node: ast.AST, params: Set[str],
                        prefetch: Set[str] = frozenset()) -> str:
    """Classify one index-map output element (see module docstring).
    ``prefetch`` names the scalar-prefetch refs: an element of one at a
    constant index is read once per call, a grid constant."""
    if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
            and node.func.attr in _CLAMPS and not node.keywords):
        return _classify_clamp(node, params, prefetch)
    if isinstance(node, ast.Constant):
        return AFFINE if isinstance(node.value, int) else NON_AFFINE
    if isinstance(node, ast.Name):
        return AFFINE     # grid index or closure constant, both affine
    if (isinstance(node, ast.Subscript) and isinstance(node.value, ast.Name)
            and node.value.id in prefetch
            and isinstance(node.slice, ast.Constant)):
        return AFFINE
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return classify_index_expr(node.operand, params, prefetch)
    if isinstance(node, ast.BinOp):
        lc = classify_index_expr(node.left, params, prefetch)
        rc = classify_index_expr(node.right, params, prefetch)
        worst = max(lc, rc, key=lambda c: _CLASS_RANK[c])
        if isinstance(node.op, (ast.Add, ast.Sub)):
            return worst
        if isinstance(node.op, ast.Mult):
            if (_contains_param(node.left, params)
                    and _contains_param(node.right, params)):
                return NON_AFFINE   # quadratic in grid indices
            return worst
        if isinstance(node.op, (ast.FloorDiv, ast.Mod)):
            if _contains_param(node.right, params):
                return NON_AFFINE   # grid index in the divisor
            if not _contains_param(node.left, params):
                return worst        # pure constant expression
            if lc == NON_AFFINE:
                return NON_AFFINE
            return AFFINE_DIV       # the h // G pattern
        return NON_AFFINE
    return NON_AFFINE


def _model_index_map(node: ast.AST, n_prefetch: int = 0
                     ) -> Optional[IndexMapModel]:
    if not isinstance(node, ast.Lambda):
        return None
    params = tuple(a.arg for a in node.args.posonlyargs + node.args.args)
    body = node.body
    elts = list(body.elts) if isinstance(body, (ast.Tuple, ast.List)) \
        else [body]
    pset = set(params)
    prefetch = set(params[len(params) - n_prefetch:]) if n_prefetch else set()
    return IndexMapModel(
        params=params,
        exprs=tuple(ast.unparse(e) for e in elts),
        classes=tuple(classify_index_expr(e, pset, prefetch) for e in elts),
        lineno=node.lineno)


# --------------------------------------------------------------------------
# BlockSpec / pallas_call extraction
# --------------------------------------------------------------------------

def _is_call_to(mod: SourceModule, node: ast.AST, canonical: str) -> bool:
    return (isinstance(node, ast.Call)
            and resolve_call_name(mod, node.func) == canonical)


def _model_spec(mod: SourceModule, call: ast.Call, role: str, position: int,
                env: _Env, nominal: Mapping[str, int],
                conditional: bool, n_prefetch: int = 0) -> SpecModel:
    block_shape = resolved = None
    index_map = None
    memory_space = None
    args = list(call.args)
    if args and isinstance(args[0], (ast.Tuple, ast.List)):
        dims = args[0].elts
        block_shape = tuple(ast.unparse(d) for d in dims)
        resolved = tuple(_resolve_int(d, env, nominal) for d in dims)
    if len(args) > 1:
        index_map = _model_index_map(args[1], n_prefetch)
    for kw in call.keywords:
        if kw.arg == "index_map":
            index_map = _model_index_map(kw.value, n_prefetch)
        elif kw.arg == "block_shape" and isinstance(
                kw.value, (ast.Tuple, ast.List)):
            dims = kw.value.elts
            block_shape = tuple(ast.unparse(d) for d in dims)
            resolved = tuple(_resolve_int(d, env, nominal) for d in dims)
        elif kw.arg == "memory_space":
            dotted = ast.unparse(kw.value)
            memory_space = dotted.rsplit(".", 1)[-1]
    return SpecModel(role=role, position=position, block_shape=block_shape,
                     resolved=resolved, index_map=index_map,
                     memory_space=memory_space, conditional=conditional,
                     lineno=call.lineno)


def _spec_nodes(mod: SourceModule, node: ast.AST, env: _Env
                ) -> List[Tuple[ast.Call, bool]]:
    """Resolve an in_specs/out_specs expression to BlockSpec call nodes,
    following one level of local-name indirection plus ``.append`` calls
    (the masked-operand idiom: build the base list, append the SMEM
    predicate spec under ``if active is not None``)."""
    out: List[Tuple[ast.Call, bool]] = []

    def collect(n: ast.AST, conditional: bool):
        if isinstance(n, (ast.List, ast.Tuple)):
            for el in n.elts:
                collect(el, conditional)
        elif _is_call_to(mod, n, BLOCK_SPEC):
            out.append((n, conditional))

    if isinstance(node, ast.Name):
        values = env.lookup(node.id)
        if values:
            collect(values[-1], False)
        for appended in env.appends.get(node.id, []):
            collect(appended, True)
    else:
        collect(node, False)
    return out


def _kernel_candidates(mod: SourceModule, node: ast.AST, env: _Env,
                       toplevel: Set[str],
                       visiting: Optional[Set[str]] = None) -> Set[str]:
    visiting = visiting or set()
    if isinstance(node, ast.Name):
        if node.id in toplevel:
            return {node.id}
        if node.id in visiting:
            return set()
        names: Set[str] = set()
        for value in env.lookup(node.id):
            names |= _kernel_candidates(mod, value, env, toplevel,
                                        visiting | {node.id})
        return names
    if isinstance(node, ast.Call) and resolve_call_name(
            mod, node.func) == PARTIAL and node.args:
        return _kernel_candidates(mod, node.args[0], env, toplevel, visiting)
    return set()


def _dimension_semantics(node: ast.AST) -> Optional[Tuple[str, ...]]:
    for n in ast.walk(node):
        if isinstance(n, ast.keyword) and n.arg == "dimension_semantics":
            if isinstance(n.value, (ast.Tuple, ast.List)):
                vals = []
                for el in n.value.elts:
                    if (isinstance(el, ast.Constant)
                            and isinstance(el.value, str)):
                        vals.append(el.value)
                    else:
                        return None
                return tuple(vals)
    return None


def _model_call(mod: SourceModule, fn: ast.FunctionDef, call: ast.Call,
                env: _Env, nominal: Mapping[str, int],
                toplevel: Set[str]) -> PallasCallModel:
    grid_rank = None
    grid_exprs: Tuple[str, ...] = ()
    in_specs: List[SpecModel] = []
    out_specs: List[SpecModel] = []
    n_scratch = 0
    scratch_exprs: Tuple[str, ...] = ()
    dim_sem = None

    kernel_names = tuple(sorted(_kernel_candidates(
        mod, call.args[0], env, toplevel))) if call.args else ()

    # a grid spec object (``pltpu.PrefetchScalarGridSpec``) carries the
    # grid, the specs and the scalar-prefetch count as its own keywords
    keywords = list(call.keywords)
    n_prefetch = 0
    for kw in call.keywords:
        if kw.arg == "grid_spec" and isinstance(kw.value, ast.Call):
            keywords += kw.value.keywords
    for kw in keywords:
        if kw.arg == "num_scalar_prefetch":
            n_prefetch = _resolve_int(kw.value, env, nominal) or 0

    for kw in keywords:
        if kw.arg == "grid":
            gnode = kw.value
            if isinstance(gnode, ast.Name):
                values = [v for v in env.lookup(gnode.id)
                          if isinstance(v, (ast.Tuple, ast.List))]
                gnode = values[-1] if values else gnode
            if isinstance(gnode, (ast.Tuple, ast.List)):
                grid_rank = len(gnode.elts)
                grid_exprs = tuple(ast.unparse(e) for e in gnode.elts)
            elif isinstance(gnode, ast.Constant) and isinstance(
                    gnode.value, int):
                grid_rank = 1
                grid_exprs = (repr(gnode.value),)
        elif kw.arg == "in_specs":
            for i, (spec, cond) in enumerate(
                    _spec_nodes(mod, kw.value, env)):
                in_specs.append(_model_spec(mod, spec, "in", i, env,
                                            nominal, cond, n_prefetch))
        elif kw.arg == "out_specs":
            for i, (spec, cond) in enumerate(
                    _spec_nodes(mod, kw.value, env)):
                out_specs.append(_model_spec(mod, spec, "out", i, env,
                                             nominal, cond, n_prefetch))
        elif kw.arg == "scratch_shapes":
            snode = kw.value
            if isinstance(snode, ast.Name):
                values = [v for v in env.lookup(snode.id)
                          if isinstance(v, (ast.Tuple, ast.List))]
                snode = values[-1] if values else snode
            if isinstance(snode, (ast.Tuple, ast.List)):
                n_scratch = len(snode.elts)
                scratch_exprs = tuple(ast.unparse(e) for e in snode.elts)
        elif kw.arg == "compiler_params":
            dim_sem = _dimension_semantics(kw.value)

    return PallasCallModel(
        relpath=mod.relpath, entry=fn.name, entry_lineno=fn.lineno,
        lineno=call.lineno, grid_rank=grid_rank, grid_exprs=grid_exprs,
        kernel_names=kernel_names, in_specs=tuple(in_specs),
        out_specs=tuple(out_specs), n_scratch=n_scratch,
        scratch_exprs=scratch_exprs, dimension_semantics=dim_sem,
        n_prefetch=n_prefetch)


def extract_pallas_calls(mod: SourceModule, nominal: Mapping[str, int]
                         ) -> List[PallasCallModel]:
    """All pallas_call sites in a module, one model per site, in source
    order. Only call sites inside top-level functions are modeled (the
    repo idiom: one entry function per kernel)."""
    cached = getattr(mod, "_pallas_models", None)
    if cached is not None:
        return cached
    toplevel = {n.name for n in mod.tree.body
                if isinstance(n, ast.FunctionDef)}
    models: List[PallasCallModel] = []
    for fn in mod.tree.body:
        if not isinstance(fn, ast.FunctionDef):
            continue
        env = _Env(fn)
        for node in ast.walk(fn):
            if _is_call_to(mod, node, PALLAS_CALL):
                models.append(_model_call(mod, fn, node, env, nominal,
                                          toplevel))
    models.sort(key=lambda m: m.lineno)
    mod._pallas_models = models
    return models


def find_kernel_def(mod: SourceModule, name: str
                    ) -> Optional[ast.FunctionDef]:
    for node in mod.tree.body:
        if isinstance(node, ast.FunctionDef) and node.name == name:
            return node
    return None


# --------------------------------------------------------------------------
# kernel-body analysis (guards, accumulation, lane gating)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class GuardModel:
    """One ``@pl.when(cond)``-decorated inner def of a kernel."""
    node: ast.FunctionDef
    kind: str                     # "zero" | "last" | "other"
    axes: Tuple[int, ...]         # program_id axes named in the condition
    lane_gated: bool              # condition derives from a lane predicate


@dataclasses.dataclass
class KernelBodyModel:
    """Static facts about one kernel function's body (PAL403-405)."""
    name: str
    node: ast.FunctionDef
    params: Tuple[str, ...]       # positional parameter names
    program_axes: Dict[str, int]  # local name -> pl.program_id axis
    guards: List[GuardModel]
    accumulated: Set[str]         # scratch params updated from themselves
    dots: List[ast.Call]          # dot_general / einsum / dot call sites
    lane_gated: bool              # some guard gates on a lane predicate

    def gated_nodes(self) -> Set[int]:
        ids: Set[int] = set()
        for g in self.guards:
            if g.lane_gated:
                for n in ast.walk(g.node):
                    ids.add(id(n))
        return ids


_DOT_TAILS = ("dot_general", "einsum", "dot")


def _stmt_iter(fn: ast.FunctionDef):
    """Statements of a function in source order, descending into
    compound statements but not nested defs."""
    def walk(stmts):
        for s in stmts:
            yield s
            if isinstance(s, (ast.If, ast.For, ast.While, ast.With)):
                for attr in ("body", "orelse", "finalbody"):
                    yield from walk(getattr(s, attr, []) or [])
    yield from walk(fn.body)


def _subscript_reads(node: ast.AST, names: Set[str]) -> Set[str]:
    """Names from ``names`` read via subscript anywhere under node."""
    out: Set[str] = set()
    for n in ast.walk(node):
        if (isinstance(n, ast.Subscript) and isinstance(n.value, ast.Name)
                and n.value.id in names):
            out.add(n.value.id)
    return out


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _is_lane_pred(node: ast.AST, params: Set[str],
                  program_axes: Mapping[str, int]) -> bool:
    """``param_ref[program_id_local] ==/!= const`` — the SMEM lane
    predicate read that PAL403 requires the compute to be gated on."""
    if not (isinstance(node, ast.Compare) and len(node.ops) == 1
            and isinstance(node.ops[0], (ast.Eq, ast.NotEq))):
        return False
    for side in (node.left, node.comparators[0]):
        if (isinstance(side, ast.Subscript)
                and isinstance(side.value, ast.Name)
                and side.value.id in params
                and isinstance(side.slice, ast.Name)
                and side.slice.id in program_axes):
            return True
    return False


def _guard_kind(cond: ast.AST, program_axes: Mapping[str, int]
                ) -> Tuple[str, Tuple[int, ...]]:
    """Classify a pl.when condition: the ``k == 0`` init form, the
    ``k == nk - 1`` final-write form, or other. Axes are the
    program_id axes of any locals named in the condition."""
    axes = tuple(sorted({program_axes[n] for n in _names_in(cond)
                         if n in program_axes}))
    if isinstance(cond, ast.Compare) and len(cond.ops) == 1 \
            and isinstance(cond.ops[0], ast.Eq):
        sides = (cond.left, cond.comparators[0])
        for a, b in (sides, sides[::-1]):
            if not (isinstance(a, ast.Name) and a.id in program_axes):
                continue
            if isinstance(b, ast.Constant) and b.value == 0:
                return "zero", axes
            if (isinstance(b, ast.BinOp) and isinstance(b.op, ast.Sub)
                    and isinstance(b.right, ast.Constant)
                    and b.right.value == 1):
                return "last", axes
    return "other", axes


def analyze_kernel(mod: SourceModule, name: str,
                   n_out: int, n_scratch: int
                   ) -> Optional[KernelBodyModel]:
    """Static facts about a kernel function (cached per module+name).

    Parameter roles follow the pallas calling convention — positional
    params are ``(*inputs, *outputs, *scratch)`` — so the LAST
    ``n_scratch`` params are scratch refs and the ``n_out`` before them
    are output refs, independent of how many masked operands a call
    site conditionally appends."""
    cache = getattr(mod, "_kernel_bodies", None)
    if cache is None:
        cache = mod._kernel_bodies = {}
    ck = (name, n_out, n_scratch)
    if ck in cache:
        return cache[ck]

    fn = find_kernel_def(mod, name)
    if fn is None:
        cache[ck] = None
        return None
    params = tuple(a.arg for a in fn.args.posonlyargs + fn.args.args)
    pset = set(params)
    scratch = set(params[len(params) - n_scratch:]) if n_scratch else set()

    # pl.program_id / pl.num_programs locals
    program_axes: Dict[str, int] = {}
    for stmt in _stmt_iter(fn):
        if isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Call):
            callee = resolve_call_name(mod, stmt.value.func) or ""
            if callee.endswith((".program_id", ".num_programs")) \
                    and stmt.value.args \
                    and isinstance(stmt.value.args[0], ast.Constant):
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        program_axes[t.id] = stmt.value.args[0].value

    # lane-predicate taint: locals derived from a predicate read
    tainted: Set[str] = set()
    # scratch-read taint: locals derived from a scratch read
    scratch_taint: Dict[str, Set[str]] = {}
    for stmt in _stmt_iter(fn):
        if not isinstance(stmt, ast.Assign):
            continue
        value = stmt.value
        is_pred = _is_lane_pred(value, pset, program_axes) or bool(
            _names_in(value) & tainted)
        reads = _subscript_reads(value, scratch)
        for n in _names_in(value):
            reads |= scratch_taint.get(n, set())
        for t in stmt.targets:
            if isinstance(t, ast.Name):
                if is_pred:
                    tainted.add(t.id)
                if reads:
                    scratch_taint[t.id] = (
                        scratch_taint.get(t.id, set()) | reads)

    # accumulated scratch: written from its own value (directly or via a
    # tainted local), or augmented-assigned
    accumulated: Set[str] = set()
    for node in ast.walk(fn):
        target = value = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target, value = node.targets[0], node.value
        elif isinstance(node, ast.AugAssign):
            target, value = node.target, node.value
        if not (isinstance(target, ast.Subscript)
                and isinstance(target.value, ast.Name)
                and target.value.id in scratch):
            continue
        s = target.value.id
        if isinstance(node, ast.AugAssign):
            accumulated.add(s)
            continue
        reads = _subscript_reads(value, scratch)
        for n in _names_in(value):
            reads |= scratch_taint.get(n, set())
        if s in reads:
            accumulated.add(s)

    # pl.when guards (decorator form)
    guards: List[GuardModel] = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.FunctionDef) or node is fn:
            continue
        for deco in node.decorator_list:
            if not (isinstance(deco, ast.Call)
                    and (resolve_call_name(mod, deco.func) or ""
                         ).endswith(".when")
                    and deco.args):
                continue
            cond = deco.args[0]
            kind, axes = _guard_kind(cond, program_axes)
            lane = _is_lane_pred(cond, pset, program_axes) or bool(
                _names_in(cond) & tainted)
            guards.append(GuardModel(node=node, kind=kind, axes=axes,
                                     lane_gated=lane))

    dots = [n for n in ast.walk(fn)
            if isinstance(n, ast.Call)
            and (resolve_call_name(mod, n.func) or "").rsplit(".", 1)[-1]
            in _DOT_TAILS]

    body = KernelBodyModel(
        name=name, node=fn, params=params, program_axes=program_axes,
        guards=guards, accumulated=accumulated, dots=dots,
        lane_gated=any(g.lane_gated for g in guards))
    cache[ck] = body
    return body


def kernel_is_lane_gated(mod: SourceModule, body: KernelBodyModel) -> bool:
    """PAL403 pass criterion for one kernel function: a lane-predicate
    ``pl.when`` exists, every dot/einsum issues inside one, and for
    dot-free (VPU) kernels the gated region does the ref writes."""
    if not body.lane_gated:
        return False
    gated = body.gated_nodes()
    if body.dots:
        return all(id(d) in gated for d in body.dots)
    for g in body.guards:
        if not g.lane_gated:
            continue
        for n in ast.walk(g.node):
            if isinstance(n, (ast.Assign, ast.AugAssign)):
                targets = n.targets if isinstance(n, ast.Assign) \
                    else [n.target]
                if any(isinstance(t, ast.Subscript) for t in targets):
                    return True
    return False
