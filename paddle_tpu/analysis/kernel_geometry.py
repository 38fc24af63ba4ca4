"""Abstract evaluation of Pallas kernel geometry.

Every ``pl.pallas_call`` site in a module is reduced to a static
:class:`SiteEval`: the grid, every BlockSpec's block shape and index-map
return arity, ``out_shape``/scratch shapes and the scalar-prefetch arity —
with block sizes, grid extents and operand dims resolved to *sets of
concrete ints* where the code pins them statically:

- literals, module-level constants (own module or imported), local
  assignments and ``functools.partial`` bindings;
- enclosing-function parameters traced to their intra-module call sites,
  each call site expanded into one *configuration* (so correlated values —
  a grid computed from the same block size the BlockSpec uses — stay
  correlated instead of mixing across candidates);
- the autotune protocol: a parameter of a builder passed to
  ``autotune(name, key, candidates, build, ...)`` takes each entry of the
  candidates tuple as its own configuration, which is how autotune
  candidate block sizes become concrete without running anything.

The evaluator is deliberately three-valued: a window is *proven* in
bounds, *refuted* (a concrete overrun — a PG902 finding), or *unproven* —
symbolic residue is reported as such, never silently passed (the same
honesty rule as the CLI's never-vacuous exits).  The PG checker family
(:mod:`paddle_tpu.analysis.checkers.pallas_geometry`) consumes these
reports; module reports are memoized in the run's
:class:`~paddle_tpu.analysis.dataflow.PackageIndex` so the tier-1
single-dataflow-pass and wall-time gates hold.
"""

from __future__ import annotations

import ast
import itertools
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

__all__ = [
    "ValueSet",
    "UNPROVEN",
    "SpecEval",
    "AxisProof",
    "VmemConfig",
    "SiteEval",
    "ModuleGeometry",
    "evaluate_module",
    "DTYPE_BYTES",
]

# jnp dtype name -> element width in bytes (geometry's only dtype fact)
DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1, "bool_": 1,
    "float8_e4m3fn": 1, "float8_e5m2": 1, "float8_e4m3": 1, "float8_e5m2fnuz": 1,
}

_FOLD_CAP = 64          # max values an abstract int may hold before widening
_CONFIG_CAP = 32        # max expanded per-site configurations
_CALLSITE_CAP = 16      # max call sites consulted when resolving a parameter
_DEPTH_CAP = 12


@dataclass(frozen=True)
class ValueSet:
    """Abstract integer: the set of values an expression may take.

    ``complete=True`` means the set is exhaustive, so a *proof* may rely on
    it; an incomplete set still witnesses violations ("some call site
    passes 96") but can never prove anything.  The empty incomplete set is
    the honest bottom, :data:`UNPROVEN`."""

    values: FrozenSet[int]
    complete: bool

    @staticmethod
    def of(*vals: int) -> "ValueSet":
        return ValueSet(frozenset(vals), True)

    @property
    def known(self) -> bool:
        return bool(self.values)

    def concrete(self) -> Optional[int]:
        """The single exact value, when there is one."""
        if self.complete and len(self.values) == 1:
            return next(iter(self.values))
        return None

    def __repr__(self) -> str:  # compact in messages
        if not self.values:
            return "unproven"
        body = ",".join(str(v) for v in sorted(self.values))
        return ("{%s}" % body) + ("" if self.complete else "+?")


UNPROVEN = ValueSet(frozenset(), False)


def _fold2(f, a, b) -> ValueSet:
    if not isinstance(a, ValueSet) or not isinstance(b, ValueSet):
        return UNPROVEN
    vals: Set[int] = set()
    for x in a.values:
        for y in b.values:
            try:
                v = f(x, y)
            except (ZeroDivisionError, ValueError, OverflowError):
                return UNPROVEN
            if isinstance(v, bool) or not isinstance(v, int):
                return UNPROVEN
            vals.add(v)
            if len(vals) > _FOLD_CAP:
                return UNPROVEN
    return ValueSet(frozenset(vals), a.complete and b.complete)


def _attr_chain(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _last(chain: Optional[str]) -> str:
    return chain.split(".")[-1] if chain else ""


def _dtype_name(node: Optional[ast.AST]) -> Optional[str]:
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value if node.value in DTYPE_BYTES else None
    name = _last(_attr_chain(node))
    return name if name in DTYPE_BYTES else None


# -- report dataclasses -------------------------------------------------------

@dataclass
class SpecEval:
    """One BlockSpec (or ``out_shape``-only output) at a pallas_call site."""

    kind: str                       # "in" | "out"
    index: int                      # position within its spec list
    lineno: int
    block_shape: Optional[Tuple]    # tuple of ValueSet, or None (whole-array)
    index_map: Optional[ast.AST]    # Lambda / FunctionDef, if any
    map_params: List[str] = field(default_factory=list)
    ret_arity: Optional[int] = None  # index-map return tuple length
    operand_rank: Optional[int] = None
    operand_dims: Optional[Tuple] = None   # tuple of ValueSet
    operand_dtype: Optional[str] = None
    # AST residue for per-configuration re-resolution (correlated values)
    shape_node: Optional[ast.AST] = None   # BlockSpec block_shape expr
    dims_node: Optional[ast.AST] = None    # operand expr or out-shape tuple expr
    dims_is_operand: bool = False          # dims_node needs operand inference


@dataclass
class AxisProof:
    """In-bounds status of one (spec, dim) window across all configurations."""

    kind: str
    spec_index: int
    dim: int
    status: str                     # "proven" | "unproven" | "overrun"
    detail: str = ""
    lineno: int = 0


@dataclass
class VmemConfig:
    """Per-grid-step VMEM window footprint under one configuration."""

    binding: Dict[str, int]         # concrete params this config pinned
    bytes_per_step: ValueSet        # window bytes (no double-buffer factor)
    assumed_width: bool = False     # some element width defaulted to 1 byte


@dataclass
class SiteEval:
    path: str
    lineno: int
    kernel_name: str
    kernel_node: Optional[ast.AST]
    kernel_params: Optional[List[str]]   # after functools.partial bindings
    has_vararg: bool
    grid_len: Optional[int]              # statically-known grid rank
    grid: Optional[Tuple]                # tuple of ValueSet (merged configs)
    num_scalar_prefetch: int
    prefetch_grid_spec: bool             # came from PrefetchScalarGridSpec
    grid_node: Optional[ast.AST] = None  # grid expr, for per-config re-resolution
    in_specs: List[SpecEval] = field(default_factory=list)
    out_specs: List[SpecEval] = field(default_factory=list)
    out_specs_declared: bool = False
    n_out_shapes: Optional[int] = None
    n_scratch: int = 0
    scratch: List[Tuple[str, Tuple, Optional[str]]] = field(default_factory=list)
    scratch_nodes: List[Optional[ast.AST]] = field(default_factory=list)
    axis_proofs: List[AxisProof] = field(default_factory=list)
    vmem_configs: List[VmemConfig] = field(default_factory=list)
    # the site's own ``CompilerParams(vmem_limit_bytes=...)``: None when it
    # states none (Mosaic's default applies), else the resolved ValueSet
    # (UNPROVEN when the site computes its limit from runtime shapes)
    vmem_limit: Optional[ValueSet] = None
    # (lineno, detail) — prefetch refs indexed by non-grid values (PG904)
    prefetch_indexing: List[Tuple[int, str]] = field(default_factory=list)
    notes: List[str] = field(default_factory=list)

    def proof(self, kind: str, spec_index: int, dim: int) -> Optional[AxisProof]:
        for p in self.axis_proofs:
            if (p.kind, p.spec_index, p.dim) == (kind, spec_index, dim):
                return p
        return None


@dataclass
class ModuleGeometry:
    path: str
    sites: List[SiteEval] = field(default_factory=list)


# -- the evaluator ------------------------------------------------------------

class _ModuleEval:
    def __init__(self, path: str, tree: ast.Module, index=None) -> None:
        self.path = path
        self.tree = tree
        self.index = index  # PackageIndex (optional, for imported constants)
        self.parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        self.module_consts: Dict[str, ast.expr] = {}
        self.defs: Dict[str, ast.FunctionDef] = {}
        self.from_imports: Dict[str, Tuple[str, str]] = {}
        self.import_aliases: Set[str] = set()
        self.calls_by_name: Dict[str, List[ast.Call]] = {}
        self._foreign_consts: Dict[str, Dict[str, ast.expr]] = {}
        self._name_stack: Set[Tuple[int, str]] = set()
        self._param_stack: Set[Tuple[str, str]] = set()
        self._collect()

    # -- module facts ---------------------------------------------------------
    def _collect(self) -> None:
        for stmt in self.tree.body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and isinstance(
                stmt.targets[0], ast.Name
            ):
                self.module_consts[stmt.targets[0].id] = stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None and isinstance(
                stmt.target, ast.Name
            ):
                self.module_consts[stmt.target.id] = stmt.value
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs.setdefault(node.name, node)  # type: ignore[arg-type]
            elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
                for alias in node.names:
                    self.from_imports[alias.asname or alias.name] = (
                        node.module, alias.name,
                    )
                    self.import_aliases.add(alias.asname or alias.name)
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    self.import_aliases.add(
                        alias.asname or alias.name.split(".", 1)[0]
                    )
            elif isinstance(node, ast.Call):
                name = _last(_attr_chain(node.func))
                if name:
                    self.calls_by_name.setdefault(name, []).append(node)

    def scope_of(self, node: ast.AST) -> Tuple[ast.AST, ...]:
        """Enclosing function chain, innermost first."""
        out: List[ast.AST] = []
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                out.append(cur)
            cur = self.parents.get(cur)
        return tuple(out)

    # -- scoped binding lookup ------------------------------------------------
    def _scoped_stmts(self, fn: ast.AST):
        """Statements of ``fn``'s body, not descending into nested defs."""
        body = getattr(fn, "body", [])
        if not isinstance(body, list):  # Lambda: body is an expression
            return
        stack = list(body)
        while stack:
            stmt = stack.pop()
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                continue
            yield stmt
            for f in ("body", "orelse", "finalbody"):
                stack.extend(getattr(stmt, f, []))
            for h in getattr(stmt, "handlers", []):
                stack.extend(h.body)

    def _binding_in(self, fn: ast.AST, name: str):
        """How ``name`` is bound inside ``fn``: ("assign", expr) |
        ("tupelem", expr, i) | ("loopvar", iter_expr) | ("dimof", base, i, n)
        | ("param", fn) | ("multi",) | None."""
        found = None
        count = 0
        for stmt in self._scoped_stmts(fn):
            targets: List[ast.expr] = []
            value: Optional[ast.expr] = None
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                targets, value = [stmt.target], stmt.value
            elif isinstance(stmt, ast.AugAssign):
                if isinstance(stmt.target, ast.Name) and stmt.target.id == name:
                    count += 2  # re-binding: give up
                continue
            elif isinstance(stmt, ast.For):
                if isinstance(stmt.target, ast.Name) and stmt.target.id == name:
                    found, count = ("loopvar", stmt.iter), count + 1
                continue
            else:
                continue
            for t in targets:
                if isinstance(t, ast.Name) and t.id == name:
                    found, count = ("assign", value), count + 1
                elif isinstance(t, (ast.Tuple, ast.List)):
                    for i, elt in enumerate(t.elts):
                        if isinstance(elt, ast.Name) and elt.id == name:
                            count += 1
                            if (
                                isinstance(value, ast.Attribute)
                                and value.attr == "shape"
                            ):
                                found = ("dimof", value.value, i, len(t.elts))
                            else:
                                found = ("tupelem", value, i)
        # comprehension targets bind like loop vars
        for node in ast.walk(fn) if not isinstance(fn, ast.Lambda) else ():
            if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
                for gen in node.generators:
                    if isinstance(gen.target, ast.Name) and gen.target.id == name:
                        found, count = ("loopvar", gen.iter), count + 1
        if count > 1:
            return ("multi",)
        if found is not None:
            return found
        params = self._positional_params(fn) + self._kwonly_params(fn)
        if name in params:
            return ("param", fn)
        return None

    @staticmethod
    def _positional_params(fn: ast.AST) -> List[str]:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return []
        a = fn.args
        return [p.arg for p in (*a.posonlyargs, *a.args)]

    @staticmethod
    def _kwonly_params(fn: ast.AST) -> List[str]:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return []
        return [p.arg for p in fn.args.kwonlyargs]

    # -- abstract resolution --------------------------------------------------
    def resolve(self, node, scopes=(), overrides=None, depth=0):
        """Resolve an expression to a ValueSet, a tuple of resolved values,
        or :data:`UNPROVEN`."""
        if node is None or depth > _DEPTH_CAP:
            return UNPROVEN
        ov = overrides or {}
        if isinstance(node, ast.Constant):
            v = node.value
            if isinstance(v, bool) or not isinstance(v, int):
                return UNPROVEN
            return ValueSet.of(v)
        if isinstance(node, (ast.Tuple, ast.List)):
            return tuple(
                self.resolve(e, scopes, ov, depth + 1) for e in node.elts
            )
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return _fold2(lambda a, b: a - b, ValueSet.of(0),
                          self.resolve(node.operand, scopes, ov, depth + 1))
        if isinstance(node, ast.BinOp):
            a = self.resolve(node.left, scopes, ov, depth + 1)
            b = self.resolve(node.right, scopes, ov, depth + 1)
            if isinstance(node.op, ast.Add) and isinstance(a, tuple) and isinstance(b, tuple):
                return a + b
            ops = {
                ast.Add: lambda x, y: x + y,
                ast.Sub: lambda x, y: x - y,
                ast.Mult: lambda x, y: x * y,
                ast.FloorDiv: lambda x, y: x // y,
                ast.Mod: lambda x, y: x % y,
                ast.Pow: lambda x, y: x ** y if y >= 0 and y < 64 else 1 // 0,
                ast.LShift: lambda x, y: x << y if 0 <= y < 64 else 1 // 0,  # 64 << 20
            }
            f = ops.get(type(node.op))
            return _fold2(f, a, b) if f else UNPROVEN
        if isinstance(node, ast.Call):
            return self._resolve_call(node, scopes, ov, depth)
        if isinstance(node, ast.Name):
            return self._resolve_name(node.id, scopes, ov, depth)
        if isinstance(node, ast.Subscript):
            base = self.resolve(node.value, scopes, ov, depth + 1)
            if isinstance(base, tuple):
                idx = node.slice
                if isinstance(idx, ast.Constant) and isinstance(idx.value, int):
                    i = idx.value
                    if -len(base) <= i < len(base):
                        return base[i]
            return UNPROVEN
        if isinstance(node, ast.Attribute):
            # mod.CONST through a from-import of the module
            chain = _attr_chain(node)
            if chain and "." in chain:
                head, attr = chain.split(".", 1)
                if "." not in attr and head in self.from_imports:
                    mod, orig = self.from_imports[head]
                    return self._imported_const(f"{mod}.{orig}", attr)
            return UNPROVEN
        if isinstance(node, ast.IfExp):
            a = self.resolve(node.body, scopes, ov, depth + 1)
            b = self.resolve(node.orelse, scopes, ov, depth + 1)
            if isinstance(a, ValueSet) and isinstance(b, ValueSet):
                return ValueSet(a.values | b.values, a.complete and b.complete)
            return UNPROVEN
        return UNPROVEN

    def _resolve_call(self, node: ast.Call, scopes, ov, depth):
        name = _last(_attr_chain(node.func))
        args = [self.resolve(a, scopes, ov, depth + 1) for a in node.args]
        if name == "cdiv" and len(args) == 2:
            return _fold2(lambda a, b: -(-a // b), args[0], args[1])
        if name in ("min", "minimum") and len(args) == 2:
            return _fold2(min, args[0], args[1])
        if name in ("max", "maximum") and len(args) == 2:
            return _fold2(max, args[0], args[1])
        if name == "len" and len(args) == 1 and isinstance(args[0], tuple):
            return ValueSet.of(len(args[0]))
        if name == "int" and len(args) == 1:
            return args[0]
        if name == "tuple" and len(args) == 1 and isinstance(args[0], tuple):
            return args[0]
        return UNPROVEN

    def _resolve_name(self, name: str, scopes, ov, depth):
        if name in ov:
            return ov[name]
        key = (id(scopes[0]) if scopes else 0, name)
        if key in self._name_stack:
            return UNPROVEN
        self._name_stack.add(key)
        try:
            for i, fn in enumerate(scopes):
                b = self._binding_in(fn, name)
                if b is None:
                    continue
                outer = scopes[i:]
                if b[0] == "assign":
                    return self.resolve(b[1], outer, ov, depth + 1)
                if b[0] == "tupelem":
                    val = self.resolve(b[1], outer, ov, depth + 1)
                    if isinstance(val, tuple) and b[2] < len(val):
                        return val[b[2]]
                    return UNPROVEN
                if b[0] == "loopvar":
                    val = self.resolve(b[1], outer, ov, depth + 1)
                    if isinstance(val, tuple):
                        vals: Set[int] = set()
                        complete = True
                        for v in val:
                            if isinstance(v, ValueSet) and v.known:
                                vals |= v.values
                                complete = complete and v.complete
                            else:
                                complete = False
                        return ValueSet(frozenset(vals), complete)
                    return UNPROVEN
                if b[0] == "param":
                    return self._resolve_param(fn, name, scopes[i + 1:], ov, depth)
                return UNPROVEN  # "multi" / "dimof": not a static int
            if name in self.module_consts:
                return self.resolve(self.module_consts[name], (), ov, depth + 1)
            if name in self.from_imports:
                mod, orig = self.from_imports[name]
                return self._imported_const(mod, orig)
            return UNPROVEN
        finally:
            self._name_stack.discard(key)

    # -- parameters via intra-module call sites (incl. the autotune protocol) -
    def _param_bindings(self, fn: ast.AST, outer_scopes, ov, depth):
        """(arg_expr | ValueSet, call_node) pairs for each intra-module call
        of ``fn``, one entry per parameter, as raw material for configs."""
        fname = getattr(fn, "name", None)
        if not fname:
            return None
        sites: List[Tuple[Dict[str, ast.expr], ast.Call]] = []
        pos = self._positional_params(fn)
        for call in self.calls_by_name.get(fname, ())[:_CALLSITE_CAP]:
            if call in getattr(self, "_seen_calls", ()):
                continue
            bind: Dict[str, ast.expr] = {}
            ok = True
            if any(isinstance(a, ast.Starred) for a in call.args):
                ok = False
            else:
                for i, a in enumerate(call.args):
                    if _last(_attr_chain(call.func)) != fname:
                        ok = False
                        break
                    if i < len(pos):
                        bind[pos[i]] = a
                for kw in call.keywords:
                    if kw.arg:
                        bind[kw.arg] = kw.value
            if ok:
                sites.append((bind, call))
        # autotune protocol: fn passed as the builder to
        # autotune(name, key, candidates, build, default=...) — each candidate
        # becomes a synthetic one-param call site
        if len(pos) == 1:
            for call in self.calls_by_name.get("autotune", ()):
                if (
                    len(call.args) >= 4
                    and isinstance(call.args[3], ast.Name)
                    and call.args[3].id == fname
                ):
                    cands = self.resolve(
                        call.args[2], self.scope_of(call), ov, depth + 1
                    )
                    if isinstance(cands, tuple):
                        for c in cands:
                            sites.append(({pos[0]: c}, call))  # type: ignore[dict-item]
        return sites or None

    def _resolve_param(self, fn: ast.AST, name: str, outer_scopes, ov, depth):
        fname = getattr(fn, "name", None) or "<lambda>"
        key = (fname, name)
        if key in self._param_stack or depth > _DEPTH_CAP:
            return UNPROVEN
        self._param_stack.add(key)
        try:
            sites = self._param_bindings(fn, outer_scopes, ov, depth)
            default = self._param_default(fn, name)
            if sites is None:
                return UNPROVEN
            vals: Set[int] = set()
            complete = True
            for bind, call in sites:
                expr = bind.get(name, default)
                if expr is None:
                    complete = False
                    continue
                v = (
                    expr
                    if isinstance(expr, (ValueSet, tuple))
                    else self.resolve(expr, self.scope_of(call), {}, depth + 1)
                )
                if isinstance(v, ValueSet) and v.known:
                    vals |= v.values
                    complete = complete and v.complete
                else:
                    complete = False
            return ValueSet(frozenset(vals), complete)
        finally:
            self._param_stack.discard(key)

    def _param_default(self, fn: ast.AST, name: str) -> Optional[ast.expr]:
        a = fn.args
        pos = [p.arg for p in (*a.posonlyargs, *a.args)]
        if name in pos:
            i = pos.index(name) - (len(pos) - len(a.defaults))
            if 0 <= i < len(a.defaults):
                return a.defaults[i]
        for p, d in zip(a.kwonlyargs, a.kw_defaults):
            if p.arg == name and d is not None:
                return d
        return None

    def _imported_const(self, mod: str, orig: str):
        """A constant imported from another indexed module — literal values
        only (the cross-module leg of the resolution chain)."""
        if self.index is None:
            return UNPROVEN
        if mod not in self._foreign_consts:
            consts: Dict[str, ast.expr] = {}
            for g in self.index.modules():
                if g.dotted_name == mod:
                    for stmt in g.tree.body:
                        if (
                            isinstance(stmt, ast.Assign)
                            and len(stmt.targets) == 1
                            and isinstance(stmt.targets[0], ast.Name)
                        ):
                            consts[stmt.targets[0].id] = stmt.value
            self._foreign_consts[mod] = consts
        expr = self._foreign_consts[mod].get(orig)
        if expr is None:
            return UNPROVEN
        return self._literal_only(expr)

    def _literal_only(self, node: ast.AST):
        if isinstance(node, ast.Constant):
            v = node.value
            if isinstance(v, bool) or not isinstance(v, int):
                return UNPROVEN
            return ValueSet.of(v)
        if isinstance(node, (ast.Tuple, ast.List)):
            return tuple(self._literal_only(e) for e in node.elts)
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            inner = self._literal_only(node.operand)
            return _fold2(lambda a, b: a - b, ValueSet.of(0), inner)
        return UNPROVEN

    # -- operand rank / dims / dtype ------------------------------------------
    def operand_info(self, expr, scopes, ov, depth=0):
        """(rank, dims tuple | None, dtype name | None) for a pallas_call
        operand expression, resolved opportunistically."""
        if expr is None or depth > 6:
            return (None, None, None)
        if isinstance(expr, ast.Call):
            name = _last(_attr_chain(expr.func))
            if name in ("zeros", "ones", "empty"):
                dims = self.resolve(expr.args[0], scopes, ov) if expr.args else UNPROVEN
                dt = _dtype_name(
                    expr.args[1] if len(expr.args) > 1 else self._kw(expr, "dtype")
                )
                if isinstance(dims, tuple):
                    return (len(dims), dims, dt)
                return (None, None, dt)
            if name == "full":
                dims = self.resolve(expr.args[0], scopes, ov) if expr.args else UNPROVEN
                dt = _dtype_name(
                    expr.args[2] if len(expr.args) > 2 else self._kw(expr, "dtype")
                )
                if isinstance(dims, tuple):
                    return (len(dims), dims, dt)
                return (None, None, dt)
            if name == "astype" and isinstance(expr.func, ast.Attribute):
                rank, dims, _ = self.operand_info(expr.func.value, scopes, ov, depth + 1)
                dt = _dtype_name(expr.args[0] if expr.args else None)
                return (rank, dims, dt)
            if name == "reshape" and isinstance(expr.func, ast.Attribute):
                _, _, dt = self.operand_info(expr.func.value, scopes, ov, depth + 1)
                shape_args = expr.args
                if len(shape_args) == 1 and isinstance(shape_args[0], (ast.Tuple, ast.List)):
                    shape_args = list(shape_args[0].elts)
                dims = tuple(self.resolve(a, scopes, ov) for a in shape_args)
                return (len(dims), dims, dt)
            if name == "asarray" and expr.args:
                rank, dims, _ = self.operand_info(expr.args[0], scopes, ov, depth + 1)
                dt = _dtype_name(
                    expr.args[1] if len(expr.args) > 1 else self._kw(expr, "dtype")
                )
                return (rank, dims, dt)
            if name == "broadcast_to" and len(expr.args) >= 2:
                dims = self.resolve(expr.args[1], scopes, ov)
                if isinstance(dims, tuple):
                    return (len(dims), dims, None)
            return (None, None, None)
        if isinstance(expr, ast.Name):
            for i, fn in enumerate(scopes):
                b = self._binding_in(fn, expr.id)
                if b is not None and b[0] == "assign":
                    return self.operand_info(b[1], scopes[i:], ov, depth + 1)
                if b is not None:
                    break
            # `b, s, h, d = x.shape` anywhere in scope fixes x's rank
            for fn in scopes:
                for stmt in self._scoped_stmts(fn):
                    if (
                        isinstance(stmt, ast.Assign)
                        and len(stmt.targets) == 1
                        and isinstance(stmt.targets[0], (ast.Tuple, ast.List))
                        and isinstance(stmt.value, ast.Attribute)
                        and stmt.value.attr == "shape"
                        and isinstance(stmt.value.value, ast.Name)
                        and stmt.value.value.id == expr.id
                    ):
                        elts = stmt.targets[0].elts
                        dims = tuple(
                            self.resolve(e, scopes, ov)
                            if isinstance(e, ast.Name)
                            else UNPROVEN
                            for e in elts
                        )
                        return (len(elts), dims, None)
            return (None, None, None)
        if isinstance(expr, ast.Attribute) or isinstance(expr, ast.Subscript):
            return (None, None, None)
        return (None, None, None)

    @staticmethod
    def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
        for kw in call.keywords:
            if kw.arg == name:
                return kw.value
        return None

    # -- site extraction ------------------------------------------------------
    def evaluate(self) -> ModuleGeometry:
        geom = ModuleGeometry(self.path)
        for node in ast.walk(self.tree):
            if (
                isinstance(node, ast.Call)
                and _last(_attr_chain(node.func)) == "pallas_call"
                and (node.args or node.keywords)
            ):
                try:
                    geom.sites.append(self._eval_site(node))
                except RecursionError:  # pragma: no cover - defensive
                    continue
        return geom

    def _deref(self, expr, scopes):
        """Follow Name -> single local/module assignment hops."""
        hops = 0
        while isinstance(expr, ast.Name) and hops < 3:
            hops += 1
            nxt = None
            for i, fn in enumerate(scopes):
                b = self._binding_in(fn, expr.id)
                if b is not None:
                    if b[0] == "assign":
                        nxt = b[1]
                    break
            if nxt is None and expr.id in self.module_consts:
                nxt = self.module_consts[expr.id]
            if nxt is None:
                return expr
            expr = nxt
        return expr

    def _parse_blockspec(self, expr, scopes, kind, idx) -> SpecEval:
        expr = self._deref(expr, scopes)
        spec = SpecEval(kind=kind, index=idx, lineno=getattr(expr, "lineno", 0),
                        block_shape=None, index_map=None)
        if not (isinstance(expr, ast.Call) and _last(_attr_chain(expr.func)) == "BlockSpec"):
            return spec
        shape_expr = expr.args[0] if expr.args else self._kw(expr, "block_shape")
        map_expr = expr.args[1] if len(expr.args) > 1 else self._kw(expr, "index_map")
        # legacy argument order: BlockSpec(index_map, block_shape)
        if isinstance(shape_expr, ast.Lambda):
            shape_expr, map_expr = map_expr, shape_expr
        if shape_expr is not None:
            shape = self.resolve(shape_expr, scopes)
            if isinstance(shape, tuple):
                spec.block_shape = shape
                spec.shape_node = shape_expr
        if map_expr is not None:
            map_node = map_expr
            if isinstance(map_node, ast.Name):
                target = None
                for fn in scopes:
                    for sub in ast.walk(fn):
                        if (
                            isinstance(sub, ast.FunctionDef)
                            and sub.name == map_node.id
                        ):
                            target = sub
                            break
                    if target:
                        break
                target = target or self.defs.get(map_node.id)
                map_node = target
            if isinstance(map_node, (ast.Lambda, ast.FunctionDef)):
                spec.index_map = map_node
                spec.map_params = self._positional_params(map_node)
                spec.ret_arity = self._ret_arity(map_node)
        return spec

    @staticmethod
    def _ret_arity(fn: ast.AST) -> Optional[int]:
        if isinstance(fn, ast.Lambda):
            body = fn.body
            return len(body.elts) if isinstance(body, ast.Tuple) else 1
        arities: Set[int] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Return) and node.value is not None:
                v = node.value
                arities.add(len(v.elts) if isinstance(v, ast.Tuple) else 1)
        return arities.pop() if len(arities) == 1 else None

    def _ret_exprs(self, fn: ast.AST) -> Optional[List[ast.expr]]:
        if isinstance(fn, ast.Lambda):
            body = fn.body
            return list(body.elts) if isinstance(body, ast.Tuple) else [body]
        rets = [n for n in ast.walk(fn) if isinstance(n, ast.Return) and n.value]
        if len(rets) != 1:
            return None
        v = rets[0].value
        return list(v.elts) if isinstance(v, ast.Tuple) else [v]

    def _spec_list(self, expr, scopes, kind) -> List[SpecEval]:
        expr = self._deref(expr, scopes)
        if isinstance(expr, (ast.Tuple, ast.List)):
            return [
                self._parse_blockspec(e, scopes, kind, i)
                for i, e in enumerate(expr.elts)
            ]
        return [self._parse_blockspec(expr, scopes, kind, 0)]

    def _out_shapes(self, expr, scopes):
        """[(dims tuple | None, dtype name | None, shape expr node | None)]"""
        expr = self._deref(expr, scopes)
        # [ShapeDtypeStruct(...)] * 3 replication idiom
        if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Mult):
            seq, n = expr.left, expr.right
            if isinstance(n, (ast.Tuple, ast.List)):
                seq, n = n, seq
            reps = self.resolve(n, scopes)
            if (
                isinstance(seq, (ast.Tuple, ast.List))
                and isinstance(reps, ValueSet)
                and reps.concrete() is not None
            ):
                out: List[Tuple[Optional[Tuple], Optional[str], Optional[ast.AST]]] = []
                for _ in range(min(32, reps.concrete() or 0)):
                    for item in seq.elts:
                        out.extend(self._out_shapes(item, scopes))
                return out
        items = expr.elts if isinstance(expr, (ast.Tuple, ast.List)) else [expr]
        out: List[Tuple[Optional[Tuple], Optional[str], Optional[ast.AST]]] = []
        for item in items:
            item = self._deref(item, scopes)
            if isinstance(item, ast.Call) and _last(_attr_chain(item.func)) == "ShapeDtypeStruct":
                shape_e = item.args[0] if item.args else self._kw(item, "shape")
                dtype_e = item.args[1] if len(item.args) > 1 else self._kw(item, "dtype")
                dims = self.resolve(shape_e, scopes) if shape_e is not None else UNPROVEN
                out.append(
                    (
                        dims if isinstance(dims, tuple) else None,
                        _dtype_name(dtype_e),
                        shape_e,
                    )
                )
            else:
                out.append((None, None, None))
        return out

    def _scratch_list(self, expr, scopes):
        """([(space, shape tuple, dtype)], [shape expr node]) pairs."""
        expr = self._deref(expr, scopes)
        items = expr.elts if isinstance(expr, (ast.Tuple, ast.List)) else [expr]
        out: List[Tuple[str, Tuple, Optional[str]]] = []
        nodes: List[Optional[ast.AST]] = []
        for item in items:
            if isinstance(item, ast.Call):
                space = _last(_attr_chain(item.func))
                shape = self.resolve(item.args[0], scopes) if item.args else UNPROVEN
                dt = _dtype_name(item.args[1] if len(item.args) > 1 else None)
                out.append(
                    (space, shape if isinstance(shape, tuple) else (), dt)
                )
                nodes.append(item.args[0] if item.args else None)
            else:
                out.append(("?", (), None))
                nodes.append(None)
        return out, nodes

    def _resolve_kernel(self, expr, scopes):
        """(kernel def node | None, name, bound kwarg names, bound leading
        positional count) through partial/local-assign hops."""
        expr = self._deref(expr, scopes)
        bound_kw: Set[str] = set()
        bound_pos = 0
        if isinstance(expr, ast.Call) and _last(_attr_chain(expr.func)) in (
            "partial",
        ):
            bound_kw = {kw.arg for kw in expr.keywords if kw.arg}
            bound_pos = max(0, len(expr.args) - 1)
            expr = self._deref(expr.args[0], scopes) if expr.args else expr
        if isinstance(expr, ast.Lambda):
            return expr, "<lambda>", bound_kw, bound_pos
        if isinstance(expr, ast.Name):
            target = None
            for fn in scopes:
                for sub in ast.walk(fn):
                    if isinstance(sub, ast.FunctionDef) and sub.name == expr.id:
                        target = sub
                        break
                if target:
                    break
            target = target or self.defs.get(expr.id)
            if target is not None:
                return target, expr.id, bound_kw, bound_pos
            return None, expr.id, bound_kw, bound_pos
        if isinstance(expr, ast.FunctionDef):
            return expr, expr.name, bound_kw, bound_pos
        return None, "<unresolved>", bound_kw, bound_pos

    # -- configurations -------------------------------------------------------
    def _site_configs(self, scopes) -> List[Dict[str, object]]:
        """Expand the innermost *named* enclosing function's parameters into
        per-call-site configurations, splitting small complete value sets so
        correlated quantities (grid derived from a block-size param) stay
        consistent within each configuration."""
        fn = next(
            (s for s in scopes if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))),
            None,
        )
        chain_fns = [
            s for s in scopes if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        configs: List[Dict[str, object]] = [{}]
        for fn in chain_fns[:2]:  # innermost def and its enclosing def
            sites = self._param_bindings(fn, (), {}, 0)
            if not sites:
                continue
            expanded: List[Dict[str, object]] = []
            for bind, call in sites:
                env: Dict[str, object] = {}
                for pname in self._positional_params(fn) + self._kwonly_params(fn):
                    expr = bind.get(pname, self._param_default(fn, pname))
                    if expr is None:
                        continue
                    v = (
                        expr
                        if isinstance(expr, (ValueSet, tuple))
                        else self.resolve(expr, self.scope_of(call), {}, 1)
                    )
                    if isinstance(v, ValueSet) and not v.known:
                        continue
                    env[pname] = v
                expanded.append(env)
            # split multi-valued complete params into singleton configs
            split: List[Dict[str, object]] = []
            for env in expanded:
                axes = [
                    (k, sorted(v.values))
                    for k, v in env.items()
                    if isinstance(v, ValueSet) and v.complete and 1 < len(v.values) <= 8
                ]
                if not axes or len(split) > _CONFIG_CAP:
                    split.append(env)
                    continue
                keys = [k for k, _ in axes]
                for combo in itertools.product(*(vs for _, vs in axes)):
                    if len(split) > _CONFIG_CAP:
                        break
                    e = dict(env)
                    for k, val in zip(keys, combo):
                        e[k] = ValueSet.of(val)
                    split.append(e)
            merged: List[Dict[str, object]] = []
            for base in configs:
                for env in split[:_CONFIG_CAP]:
                    if len(merged) > _CONFIG_CAP:
                        break
                    m = dict(env)
                    m.update(base)  # inner binding wins
                    merged.append(m)
            configs = merged or configs
        # dedupe identical configs
        uniq: List[Dict[str, object]] = []
        seen: Set[str] = set()
        for c in configs:
            key = repr(sorted((k, repr(v)) for k, v in c.items()))
            if key not in seen:
                seen.add(key)
                uniq.append(c)
        return uniq[:_CONFIG_CAP]

    # -- full site evaluation -------------------------------------------------
    def _eval_site(self, call: ast.Call) -> SiteEval:
        scopes = self.scope_of(call)
        kw = {k.arg: k.value for k in call.keywords if k.arg}
        kernel_node, kernel_name, bound_kw, bound_pos = (
            self._resolve_kernel(call.args[0], scopes)
            if call.args
            else (None, "<none>", set(), 0)
        )

        grid_expr = kw.get("grid")
        in_specs_expr = kw.get("in_specs")
        out_specs_expr = kw.get("out_specs")
        scratch_expr = kw.get("scratch_shapes")
        out_shape_expr = kw.get("out_shape")
        nsp = 0
        prefetch = False
        gs = kw.get("grid_spec")
        if gs is not None:
            gs = self._deref(gs, scopes)
            if isinstance(gs, ast.Call):
                gname = _last(_attr_chain(gs.func))
                prefetch = gname == "PrefetchScalarGridSpec"
                gkw = {k.arg: k.value for k in gs.keywords if k.arg}
                grid_expr = gkw.get("grid", grid_expr)
                in_specs_expr = gkw.get("in_specs", in_specs_expr)
                out_specs_expr = gkw.get("out_specs", out_specs_expr)
                scratch_expr = gkw.get("scratch_shapes", scratch_expr)
                if prefetch:
                    nexpr = gkw.get("num_scalar_prefetch") or (
                        gs.args[0] if gs.args else None
                    )
                    nval = self.resolve(nexpr, scopes) if nexpr is not None else UNPROVEN
                    if isinstance(nval, ValueSet) and nval.concrete() is not None:
                        nsp = nval.concrete() or 0

        site = SiteEval(
            path=self.path,
            lineno=call.lineno,
            kernel_name=kernel_name,
            kernel_node=kernel_node,
            kernel_params=None,
            has_vararg=False,
            grid_len=None,
            grid=None,
            num_scalar_prefetch=nsp,
            prefetch_grid_spec=prefetch,
        )
        if kernel_node is not None:
            params = self._positional_params(kernel_node)
            params = params[bound_pos:]
            params = [p for p in params if p not in bound_kw]
            site.kernel_params = params
            site.has_vararg = bool(
                getattr(kernel_node, "args", None)
                and (kernel_node.args.vararg or kernel_node.args.kwarg)
            )

        configs = self._site_configs(scopes)

        # grid: resolve under the first config for structure, merge extents
        grid_vals: List[Tuple] = []
        for cfg in configs:
            g = self.resolve(grid_expr, scopes, cfg) if grid_expr is not None else None
            if isinstance(g, ValueSet):
                g = (g,)
            if isinstance(g, tuple):
                grid_vals.append(g)
        if grid_expr is not None:
            lens = {len(g) for g in grid_vals}
            if len(lens) == 1:
                site.grid_len = lens.pop()
                merged = []
                for d in range(site.grid_len):
                    vals: Set[int] = set()
                    complete = True
                    for g in grid_vals:
                        v = g[d]
                        if isinstance(v, ValueSet) and v.known:
                            vals |= v.values
                            complete = complete and v.complete
                        else:
                            complete = False
                    merged.append(ValueSet(frozenset(vals), complete))
                site.grid = tuple(merged)
            else:
                # structurally unresolvable grid (e.g. computed tuple)
                g = self.resolve(grid_expr, scopes) if grid_expr is not None else None
                if isinstance(g, tuple):
                    site.grid_len = len(g)
                    site.grid = g

        if in_specs_expr is not None:
            site.in_specs = self._spec_list(in_specs_expr, scopes, "in")
        if out_specs_expr is not None:
            site.out_specs = self._spec_list(out_specs_expr, scopes, "out")
            site.out_specs_declared = True
        if scratch_expr is not None:
            site.scratch, site.scratch_nodes = self._scratch_list(scratch_expr, scopes)
            site.n_scratch = len(site.scratch)
        out_shapes = (
            self._out_shapes(out_shape_expr, scopes)
            if out_shape_expr is not None
            else []
        )
        site.n_out_shapes = len(out_shapes) if out_shape_expr is not None else None

        # operands: pallas_call(...)(op0, op1, ...)
        outer = self.parents.get(call)
        operands: List[ast.expr] = []
        if isinstance(outer, ast.Call) and outer.func is call:
            operands = list(outer.args)
        for i, spec in enumerate(site.in_specs):
            oi = nsp + i
            if oi < len(operands):
                rank, dims, dt = self.operand_info(operands[oi], scopes, {})
                spec.operand_rank, spec.operand_dims, spec.operand_dtype = rank, dims, dt
                spec.dims_node, spec.dims_is_operand = operands[oi], True
        for i, spec in enumerate(site.out_specs):
            if i < len(out_shapes):
                dims, dt, shape_e = out_shapes[i]
                if dims is not None:
                    spec.operand_rank = len(dims)
                    spec.operand_dims = dims
                    spec.dims_node, spec.dims_is_operand = shape_e, False
                spec.operand_dtype = dt
        if not site.out_specs and out_shapes:
            # out_shape without out_specs: whole-array outputs, no window math
            for i, (dims, dt, shape_e) in enumerate(out_shapes):
                site.out_specs.append(
                    SpecEval(
                        kind="out", index=i, lineno=call.lineno,
                        block_shape=None, index_map=None,
                        operand_rank=len(dims) if dims is not None else None,
                        operand_dims=dims, operand_dtype=dt,
                        dims_node=shape_e, dims_is_operand=False,
                    )
                )
        site.grid_node = grid_expr

        site.vmem_limit = self._stated_vmem_limit(kw.get("compiler_params"), scopes)
        self._prove_axes(site, scopes, configs)
        self._eval_vmem(site, scopes, configs)
        if site.prefetch_grid_spec and site.num_scalar_prefetch > 0:
            self._check_prefetch_indexing(site, scopes)
        return site

    # -- prefetch-ref indexing discipline (PG904) ------------------------------
    def _is_immutable_literal(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Tuple):
            return all(self._is_immutable_literal(e) for e in node.elts)
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return self._is_immutable_literal(node.operand)
        return False

    _BUILTIN_NAMES = {"len", "min", "max", "int", "abs", "range", "sum", "divmod"}

    def _check_prefetch_indexing(self, site: SiteEval, scopes) -> None:
        """Inside a PrefetchScalarGridSpec index map, a prefetch ref may only
        be subscripted by grid/prefetch-derived values, map locals, and
        immutable constants — never by unbound names or mutable module
        state."""
        for spec in site.in_specs + site.out_specs:
            if spec.index_map is None or not spec.map_params:
                continue
            n_grid = site.grid_len if site.grid_len is not None else max(
                0, len(spec.map_params) - site.num_scalar_prefetch
            )
            prefetch_params = set(spec.map_params[n_grid:])
            if not prefetch_params:
                continue
            fn = spec.index_map
            local_names: Set[str] = set(spec.map_params)
            if isinstance(fn, ast.FunctionDef):
                for sub in ast.walk(fn):
                    if isinstance(sub, (ast.Assign, ast.AnnAssign)):
                        targets = (
                            sub.targets
                            if isinstance(sub, ast.Assign)
                            else [sub.target]
                        )
                        for t in targets:
                            for n in ast.walk(t):
                                if isinstance(n, ast.Name):
                                    local_names.add(n.id)
            for sub in ast.walk(fn):
                if not (
                    isinstance(sub, ast.Subscript)
                    and isinstance(sub.value, ast.Name)
                    and sub.value.id in prefetch_params
                ):
                    continue
                bad: List[str] = []
                for n in ast.walk(sub.slice):
                    if not (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)):
                        continue
                    name = n.id
                    if (
                        name in local_names
                        or name in self.import_aliases
                        or name in self._BUILTIN_NAMES
                        or name in self.from_imports
                    ):
                        continue
                    if any(
                        self._binding_in(f, name) is not None for f in scopes
                    ):
                        continue  # closure-derived: grid/param lineage
                    const = self.module_consts.get(name)
                    if const is not None and self._is_immutable_literal(const):
                        continue
                    bad.append(name)
                if bad:
                    site.prefetch_indexing.append(
                        (
                            getattr(sub, "lineno", spec.lineno),
                            f"prefetch ref '{sub.value.id}' indexed by non-grid "
                            f"value(s): {', '.join(sorted(set(bad)))}",
                        )
                    )

    # -- in-bounds proofs ------------------------------------------------------
    def _prove_axes(self, site: SiteEval, scopes, configs) -> None:
        n_grid = site.grid_len
        for spec in site.in_specs + site.out_specs:
            if spec.block_shape is None or spec.index_map is None:
                continue
            rets = self._ret_exprs(spec.index_map)
            if rets is None or len(rets) != len(spec.block_shape):
                continue  # rank mismatch — PG901 territory, not window math
            map_scopes = (spec.index_map,) + tuple(scopes)
            grid_params = (
                spec.map_params[: n_grid]
                if n_grid is not None
                else spec.map_params[: max(0, len(spec.map_params) - site.num_scalar_prefetch)]
            )
            prefetch_params = spec.map_params[len(grid_params):]
            for d in range(len(spec.block_shape)):
                status, detail = self._prove_dim(
                    site, spec, d, rets[d], grid_params, prefetch_params,
                    map_scopes, configs,
                )
                site.axis_proofs.append(
                    AxisProof(
                        kind=spec.kind, spec_index=spec.index, dim=d,
                        status=status, detail=detail, lineno=spec.lineno,
                    )
                )

    def _cfg_tuple(self, node, scopes, cfg, fallback=None):
        """Re-resolve a stored shape/grid expr under one configuration, so
        correlated quantities (a grid computed from the block-size param a
        BlockSpec also uses) stay consistent per config."""
        if node is not None:
            v = self.resolve(node, scopes, cfg)
            if isinstance(v, ValueSet):
                v = (v,)
            if isinstance(v, tuple):
                return v
        return fallback

    def _cfg_dims(self, spec, scopes, cfg):
        if spec.dims_node is not None:
            if spec.dims_is_operand:
                _, dims, _ = self.operand_info(spec.dims_node, scopes, cfg)
                if dims is not None:
                    return dims
            else:
                v = self.resolve(spec.dims_node, scopes, cfg)
                if isinstance(v, tuple):
                    return v
        return spec.operand_dims

    def _prove_dim(
        self, site, spec, d, comp, grid_params, prefetch_params, map_scopes, configs,
    ) -> Tuple[str, str]:
        scopes = tuple(map_scopes[1:])
        any_unproven = False
        for cfg in configs:
            ov: Dict[str, object] = dict(cfg)
            for p in prefetch_params:
                ov[p] = UNPROVEN
            blk_t = self._cfg_tuple(spec.shape_node, scopes, cfg, spec.block_shape)
            blk_v = (
                blk_t[d]
                if blk_t is not None and d < len(blk_t) and isinstance(blk_t[d], ValueSet)
                else UNPROVEN
            )
            if not blk_v.known:
                any_unproven = True
                continue
            dims_cfg = self._cfg_dims(spec, scopes, cfg)
            dim_v = (
                dims_cfg[d]
                if dims_cfg is not None
                and d < len(dims_cfg)
                and isinstance(dims_cfg[d], ValueSet)
                else UNPROVEN
            )
            grid_t = self._cfg_tuple(site.grid_node, scopes, cfg, site.grid)
            # corner assignments over the grid params this component reads
            free = {
                n.id
                for n in ast.walk(comp)
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
            }
            deps = [p for p in grid_params if p in free]
            corner_sets: List[List[int]] = []
            complete_corners = True
            for p in deps:
                gi = grid_params.index(p)
                ext = (
                    grid_t[gi]
                    if grid_t is not None
                    and gi < len(grid_t)
                    and isinstance(grid_t[gi], ValueSet)
                    else UNPROVEN
                )
                if ext.known:
                    corners = sorted({0} | {e - 1 for e in ext.values if e > 0})
                    complete_corners = complete_corners and ext.complete
                    corner_sets.append(corners)
                else:
                    corner_sets.append([0])
                    complete_corners = False
            proven_here = True
            for combo in itertools.product(*corner_sets) if corner_sets else [()]:
                ov_c = dict(ov)
                for p in grid_params:
                    ov_c.setdefault(p, ValueSet.of(0))
                for p, v in zip(deps, combo):
                    ov_c[p] = ValueSet.of(v)
                idx = self.resolve(comp, map_scopes, ov_c)
                if not isinstance(idx, ValueSet) or not idx.known:
                    proven_here = False
                    continue
                win_end = _fold2(
                    lambda i, b: i * b + b, idx, blk_v
                )
                if not win_end.known:
                    proven_here = False
                    continue
                if dim_v.known:
                    # a concrete overrun witness refutes the window
                    if (
                        idx.concrete() is not None
                        and blk_v.concrete() is not None
                        and dim_v.concrete() is not None
                        and win_end.concrete() is not None
                        and win_end.concrete() > dim_v.concrete()
                    ):
                        return (
                            "overrun",
                            f"{spec.kind}_spec[{spec.index}] dim {d}: window end "
                            f"{win_end.concrete()} > dim {dim_v.concrete()} "
                            f"(block {blk_v.concrete()}, block index {idx.concrete()}"
                            + (
                                ", config "
                                + ",".join(
                                    f"{k}={v.concrete()}"
                                    for k, v in cfg.items()
                                    if isinstance(v, ValueSet) and v.concrete() is not None
                                )
                                if cfg
                                else ""
                            )
                            + ")",
                        )
                    if not (
                        win_end.complete
                        and dim_v.complete
                        and max(win_end.values) <= min(dim_v.values)
                    ):
                        proven_here = False
                else:
                    proven_here = False
            if not (proven_here and complete_corners and blk_v.complete):
                any_unproven = True
        if any_unproven or not configs:
            return ("unproven", f"{spec.kind}_spec[{spec.index}] dim {d}: symbolic residue")
        return ("proven", "")

    # -- VMEM footprint --------------------------------------------------------
    def _stated_vmem_limit(self, expr, scopes) -> Optional[ValueSet]:
        """``vmem_limit_bytes`` of the site's ``compiler_params``, written at
        the site or in the local helper that builds them (whose limit then
        comes from its arguments: stated, not resolvable)."""
        expr = self._deref(expr, scopes) if expr is not None else None
        if not isinstance(expr, ast.Call):
            return None
        limit = self._kw(expr, "vmem_limit_bytes")
        if limit is not None:
            if isinstance(limit, ast.Constant) and limit.value is None:
                return None
            value = self.resolve(limit, scopes)
            return value if isinstance(value, ValueSet) else UNPROVEN
        helper = self.defs.get(_last(_attr_chain(expr.func)))
        if helper is not None and any(
            isinstance(r, ast.Return)
            and isinstance(r.value, ast.Call)
            and self._kw(r.value, "vmem_limit_bytes") is not None
            for r in ast.walk(helper)
        ):
            return UNPROVEN
        return None

    def _eval_vmem(self, site: SiteEval, scopes, configs) -> None:
        for cfg in configs:
            total = ValueSet.of(0)
            assumed = False
            for spec in site.in_specs + site.out_specs:
                shape = self._cfg_tuple(spec.shape_node, scopes, cfg, spec.block_shape)
                if shape is None:
                    shape = self._cfg_dims(spec, scopes, cfg)  # whole-array window
                if shape is None:
                    total = UNPROVEN
                    break
                width = DTYPE_BYTES.get(spec.operand_dtype or "", 0)
                if width == 0:
                    width = 1  # sound lower bound when the dtype is unknown
                    assumed = True
                bytes_v = ValueSet.of(width)
                for dv in shape:
                    dv_c = dv if isinstance(dv, ValueSet) else UNPROVEN
                    bytes_v = _fold2(lambda a, b: a * b, bytes_v, dv_c)
                total = _fold2(lambda a, b: a + b, total, bytes_v)
            if isinstance(total, ValueSet) and total.known:
                for i, (space, shape, dt) in enumerate(site.scratch):
                    if space not in ("VMEM", "SMEM"):
                        continue
                    node = (
                        site.scratch_nodes[i]
                        if i < len(site.scratch_nodes)
                        else None
                    )
                    shape_t = self._cfg_tuple(node, scopes, cfg, shape)
                    width = DTYPE_BYTES.get(dt or "", 0)
                    if width == 0:
                        width = 1
                        assumed = True
                    bytes_v = ValueSet.of(width)
                    for dv in shape_t or ():
                        bytes_v = _fold2(
                            lambda a, b: a * b, bytes_v,
                            dv if isinstance(dv, ValueSet) else UNPROVEN,
                        )
                    total = _fold2(lambda a, b: a + b, total, bytes_v)
            binding = {
                k: v.concrete()
                for k, v in cfg.items()
                if isinstance(v, ValueSet) and v.concrete() is not None
            }
            site.vmem_configs.append(
                VmemConfig(
                    binding=binding,
                    bytes_per_step=total if isinstance(total, ValueSet) else UNPROVEN,
                    assumed_width=assumed,
                )
            )

def evaluate_module(path: str, tree: ast.Module, index=None) -> ModuleGeometry:
    """Evaluate every ``pl.pallas_call`` site in ``tree``.  ``index`` is the
    run's :class:`~paddle_tpu.analysis.dataflow.PackageIndex`, used for
    imported-constant resolution; pass None for single-file runs."""
    return _ModuleEval(path, tree, index).evaluate()
