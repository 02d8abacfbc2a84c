"""Trace-reachability analysis over the linted source tree.

The core question every graftlint rule asks is *"does this code run during
a jax trace?"* — the QUIVER_COUNTS bug (PR 3) was exactly an ``os.environ``
read that LOOKED live but executed once at first trace. Answering it
statically needs a conservative call-graph walk:

1. **Entry points**: functions decorated with ``jit``/``pmap`` (directly or
   via ``partial``), functions/lambdas passed into trace wrappers
   (``jit``, ``shard_map``, ``vmap``, ``grad``, ``lax.scan``/``cond``/
   ``while_loop``/``fori_loop``/``switch``/``associative_scan``, ...), and
   every method of a ``flax`` ``nn.Module`` subclass (flax traces them by
   construction).
2. **Propagation**: from a traced function, a call by name marks the callee
   traced. Name calls resolve lexically (params/locals shadow globals);
   attribute calls (``self.routed_gather(...)``) resolve by terminal name
   against every named function in the analyzed file set — conservative:
   homonyms all get marked. Class instantiation marks ``__init__``;
   property *access* from traced code marks the property body (a
   property read inside a jitted body runs at trace time); local functions/lambdas
   passed as arguments or returned from traced code are marked (closure
   callbacks like ``BucketRoute.exchange``'s ``serve``).
3. **Barriers**: a *resolve-once* function — ``global X`` + an
   ``if X is [not] None`` guard + an assignment to ``X`` — runs its slow
   path once per process, not once per trace. The walk neither flags nor
   descends into it: this is the sanctioned pattern
   (``models/layers.resolve_counts_strategy``) the env-at-trace rule points
   users at.

Everything here is stdlib ``ast``; the analyzed code is never imported.
"""

from __future__ import annotations

import ast
import dataclasses
import re

__all__ = [
    "FuncInfo",
    "SourceFile",
    "Project",
    "analyze",
    "terminal_name",
    "iter_owned",
    "is_env_read",
]

# terminal callable name -> positional indices holding traced functions
TRACE_WRAPPERS: dict[str, tuple[int, ...]] = {
    "jit": (0,),
    "pjit": (0,),
    "pmap": (0,),
    "vmap": (0,),
    "grad": (0,),
    "value_and_grad": (0,),
    "jacfwd": (0,),
    "jacrev": (0,),
    "hessian": (0,),
    "linearize": (0,),
    "checkpoint": (0,),
    "remat": (0,),
    "named_call": (0,),
    "shard_map": (0,),
    "scan": (0,),
    "associative_scan": (0,),
    "while_loop": (0, 1),
    "fori_loop": (2,),
    "cond": (1, 2),
    "custom_jvp": (0,),
    "custom_vjp": (0,),
}

# decorator names that make the decorated function a trace entry
_JIT_DECORATORS = {"jit", "pjit", "pmap"}

# attribute accesses that read STATIC array metadata, not traced values
STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "aval", "sharding",
                "weak_type", "itemsize"}

# attribute-call names that are overwhelmingly builtin container/array
# methods: linking them by terminal name to same-named project functions
# produces wrong call-graph edges (e.g. ``tiers.append(...)`` marking a
# project-level ``def append`` traced)
_BUILTIN_METHOD_NAMES = frozenset(
    n for t in (list, dict, str, set, tuple, bytes, frozenset)
    for n in dir(t) if not n.startswith("_")
) | {"astype", "reshape", "item", "view", "tolist", "block_until_ready",
     "at", "set", "add", "max", "min", "sum", "mean", "all", "any"}

# callables whose function-valued arguments run on the HOST (outside the
# trace): passing a function here must not mark it traced
_HOST_CALLBACK_WRAPPERS = {"callback", "io_callback", "pure_callback",
                           "debug_callback"}

# a registry metric name: dotted lowercase segments, each starting with a
# letter ("feature.routed_overflow") — version strings like "1.0" do not
# match
_METRIC_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")


def terminal_name(expr: ast.AST) -> str | None:
    """The rightmost name of a call target: ``jax.lax.psum`` -> ``psum``."""
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    return None


def iter_owned(func_node: ast.AST):
    """Yield the AST nodes lexically owned by one function — its body minus
    the bodies of nested function/class definitions (those have their own
    FuncInfo / are analyzed separately). Nested defs themselves are
    yielded (the ``def`` executes in this scope) but never descended
    into — including when they sit directly in the body (a module's
    top-level functions must not leak their statements into the module
    pseudo-function)."""
    defs = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef)
    if isinstance(func_node, ast.Lambda):
        roots = [func_node.body]
    else:
        roots = list(func_node.body)
    stack = list(roots)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, defs):
            continue
        for child in ast.iter_child_nodes(node):
            if isinstance(child, defs):
                stack.append(child)  # yield the def, not its body
                continue
            stack.append(child)


def is_env_read(node: ast.AST) -> str | None:
    """Return a short description when ``node`` reads the environment:
    ``os.environ.get(...)``, ``os.environ[...]``, ``os.getenv(...)`` (plus
    the bare-``environ`` spellings a ``from os import environ`` leaves)."""
    if isinstance(node, ast.Call):
        t = terminal_name(node.func)
        if t == "getenv":
            return "os.getenv(...)"
        if t == "get" and isinstance(node.func, ast.Attribute):
            if terminal_name(node.func.value) == "environ":
                return "os.environ.get(...)"
    elif isinstance(node, ast.Subscript):
        if terminal_name(node.value) == "environ":
            return "os.environ[...]"
    return None


@dataclasses.dataclass
class FuncInfo:
    """Per-function facts collected in one parse pass."""

    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda | Module
    path: str
    name: str | None  # None for lambdas and the module pseudo-function
    qualname: str
    parent: "FuncInfo | None"
    class_name: str | None = None
    params: list[str] = dataclasses.field(default_factory=list)
    # positional parameters WITHOUT defaults, minus self/cls: the arguments
    # that plausibly carry tracers (keyword-only / defaulted args are
    # config by convention in this codebase)
    taint_params: list[str] = dataclasses.field(default_factory=list)
    local_names: set[str] = dataclasses.field(default_factory=set)
    imported_names: set[str] = dataclasses.field(default_factory=set)
    local_funcs: dict[str, list["FuncInfo"]] = dataclasses.field(
        default_factory=dict)
    # (kind, name, node): kind is "name" | "attr" | "class"
    calls: list[tuple[str, str, ast.AST]] = dataclasses.field(
        default_factory=list)
    # local functions/lambdas referenced as call arguments or returned
    passed_local_funcs: list["FuncInfo"] = dataclasses.field(
        default_factory=list)
    attr_loads: set[str] = dataclasses.field(default_factory=set)
    is_property: bool = False
    is_resolve_once: bool = False
    # pinned eager by annotation: ``# graftlint: eager -- <reason>`` on (or
    # directly above) the def line — for functions that are lexically
    # reachable from traced code but eager-only by contract (e.g. the
    # between-batches auto-tuners, which no-op under trace)
    is_eager_pinned: bool = False
    is_module: bool = False
    traced: bool = False
    trace_reason: str | None = None
    trace_chain: tuple[str, ...] = ()

    @property
    def line(self) -> int:
        return getattr(self.node, "lineno", 1)


@dataclasses.dataclass
class SourceFile:
    path: str  # display path (relative where possible)
    text: str
    tree: ast.Module
    module_info: FuncInfo = None  # set by analyze()
    funcs: list[FuncInfo] = dataclasses.field(default_factory=list)
    # def-line -> reason, from ``# graftlint: eager -- <reason>`` comments
    eager_lines: dict[int, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Project:
    files: list[SourceFile]
    funcs: list[FuncInfo] = dataclasses.field(default_factory=list)
    # simple name -> named functions/methods anywhere in the file set
    index: dict[str, list[FuncInfo]] = dataclasses.field(default_factory=dict)
    class_index: dict[str, list[FuncInfo]] = dataclasses.field(
        default_factory=dict)  # class name -> [__init__ FuncInfo]
    property_index: dict[str, list[FuncInfo]] = dataclasses.field(
        default_factory=dict)
    declared_axes: dict[str, str] = dataclasses.field(
        default_factory=dict)  # constant name -> axis string
    # metric-name constants (obs/registry.py discipline): ALL_CAPS module
    # constants whose value is a dotted lowercase metric name
    declared_metrics: dict[str, str] = dataclasses.field(
        default_factory=dict)
    node_func: dict[int, FuncInfo] = dataclasses.field(default_factory=dict)
    # id(func node) -> CFG, filled lazily by tools.lint.cfg.cfg_of
    cfg_cache: dict = dataclasses.field(default_factory=dict)

    def owner_of(self, node: ast.AST) -> FuncInfo | None:
        return self.node_func.get(id(node))


# -- per-file collection ------------------------------------------------------


def _decorator_names(dec: ast.AST) -> set[str]:
    """Terminal names reachable in a decorator expression, unwrapping
    ``partial(jax.jit, ...)``."""
    names = set()
    t = terminal_name(dec)
    if t:
        names.add(t)
    if isinstance(dec, ast.Call):
        ft = terminal_name(dec.func)
        if ft:
            names.add(ft)
        if ft == "partial" and dec.args:
            inner = terminal_name(dec.args[0])
            if inner:
                names.add(inner)
    return names


def _collect_params(node: ast.AST) -> tuple[list[str], list[str]]:
    """(all param names, taint params: positional-without-default minus
    self/cls)."""
    if isinstance(node, ast.Module):
        return [], []
    a = node.args
    allp = [p.arg for p in
            list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)]
    if a.vararg:
        allp.append(a.vararg.arg)
    if a.kwarg:
        allp.append(a.kwarg.arg)
    pos = list(a.posonlyargs) + list(a.args)
    n_default = len(a.defaults)
    no_default = pos[: len(pos) - n_default] if n_default else pos
    taint = [p.arg for p in no_default if p.arg not in ("self", "cls")]
    return allp, taint


def _detect_resolve_once(info: FuncInfo) -> bool:
    """The sanctioned memoization idiom: ``global X`` + ``if X is [not]
    None`` + an assignment to X. Such a function's slow path runs once per
    process — a barrier for the traced-reachability walk."""
    if isinstance(info.node, (ast.Lambda, ast.Module)):
        return False
    globals_declared: set[str] = set()
    guarded: set[str] = set()
    assigned: set[str] = set()
    for node in iter_owned(info.node):
        if isinstance(node, ast.Global):
            globals_declared.update(node.names)
        elif isinstance(node, ast.If) and isinstance(node.test, ast.Compare):
            cmp = node.test
            if (isinstance(cmp.left, ast.Name)
                    and len(cmp.ops) == 1
                    and isinstance(cmp.ops[0], (ast.Is, ast.IsNot))
                    and len(cmp.comparators) == 1
                    and isinstance(cmp.comparators[0], ast.Constant)
                    and cmp.comparators[0].value is None):
                guarded.add(cmp.left.id)
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    assigned.add(t.id)
    return bool(globals_declared & guarded & assigned)


class _Collector(ast.NodeVisitor):
    """One pass over a file: build FuncInfos, local scopes, call edges."""

    def __init__(self, src: SourceFile, project: Project):
        self.src = src
        self.project = project
        module = FuncInfo(node=src.tree, path=src.path, name=None,
                          qualname="<module>", parent=None, is_module=True)
        src.module_info = module
        self.stack: list[FuncInfo] = [module]
        self.class_stack: list[str] = []
        self._register(module)

    # -- helpers --

    def _register(self, info: FuncInfo):
        self.src.funcs.append(info)
        self.project.funcs.append(info)

    def _own(self, node: ast.AST):
        self.project.node_func[id(node)] = self.stack[-1]

    def _bind_local(self, name: str):
        self.stack[-1].local_names.add(name)

    def _bind_func(self, name: str, info: FuncInfo):
        self.stack[-1].local_funcs.setdefault(name, []).append(info)

    # -- defs --

    def _enter_func(self, node, name: str | None):
        parent = self.stack[-1]
        qual = (parent.qualname + "." if not parent.is_module else "") + (
            name or "<lambda>")
        cls = self.class_stack[-1] if self.class_stack else None
        allp, taint = _collect_params(node)
        info = FuncInfo(node=node, path=self.src.path, name=name,
                        qualname=qual, parent=parent, class_name=cls,
                        params=allp, taint_params=taint)
        info.local_names.update(allp)
        self._register(info)
        return info

    def visit_FunctionDef(self, node):
        self._visit_funcdef(node)

    def visit_AsyncFunctionDef(self, node):
        self._visit_funcdef(node)

    def _visit_funcdef(self, node):
        info = self._enter_func(node, node.name)
        info.is_eager_pinned = node.lineno in self.src.eager_lines
        dec_names = set()
        for dec in node.decorator_list:
            dec_names |= _decorator_names(dec)
            # decorator expressions evaluate in the ENCLOSING scope
            self.visit(dec)
        info.is_property = "property" in dec_names or "cached_property" in dec_names
        if dec_names & _JIT_DECORATORS and not info.is_eager_pinned:
            info.traced = True
            info.trace_reason = (
                f"decorated with {sorted(dec_names & _JIT_DECORATORS)[0]}")
        # the def binds its name in the enclosing scope; methods bind in
        # the class namespace, which plain calls cannot see lexically
        directly_in_class = bool(self.class_stack) and self.stack[-1].is_module
        if not directly_in_class:
            self._bind_func(node.name, info)
            self._bind_local(node.name)
        # index every named function by simple name (conservative linking)
        self.project.index.setdefault(node.name, []).append(info)
        if info.is_property:
            self.project.property_index.setdefault(
                node.name, []).append(info)
        if directly_in_class and node.name == "__init__":
            self.project.class_index.setdefault(
                self.class_stack[-1], []).append(info)
        self.stack.append(info)
        for stmt in node.body:
            self.visit(stmt)
        self.stack.pop()
        info.is_resolve_once = _detect_resolve_once(info)

    def visit_Lambda(self, node):
        info = self._enter_func(node, None)
        self.stack.append(info)
        self.visit(node.body)
        self.stack.pop()

    def visit_ClassDef(self, node):
        self._own(node)
        self._bind_local(node.name)
        base_names = {terminal_name(b) for b in node.bases}
        is_flax = "Module" in base_names
        self.class_stack.append(node.name)
        # remember which FuncInfos the class body defines so flax methods
        # can be marked as entries after visiting
        before = len(self.src.funcs)
        for stmt in node.body:
            self.visit(stmt)
        new_funcs = self.src.funcs[before:]
        self.class_stack.pop()
        if is_flax:
            for f in new_funcs:
                if (f.class_name == node.name and f.name
                        and not f.traced and not f.is_eager_pinned):
                    f.traced = True
                    f.trace_reason = (
                        f"method of flax Module '{node.name}' "
                        "(flax traces module methods)")

    # -- scope bindings --

    def visit_Global(self, node):
        self._own(node)

    def visit_Import(self, node):
        self._own(node)
        for alias in node.names:
            name = alias.asname or alias.name.split(".")[0]
            self.stack[-1].imported_names.add(name)

    def visit_ImportFrom(self, node):
        self._own(node)
        for alias in node.names:
            self.stack[-1].imported_names.add(alias.asname or alias.name)

    def _bind_target(self, target):
        if isinstance(target, ast.Name):
            self._bind_local(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._bind_target(elt)
        elif isinstance(target, ast.Starred):
            self._bind_target(target.value)

    def visit_Assign(self, node):
        self._own(node)
        # a name bound to a lambda behaves like a local def
        if isinstance(node.value, ast.Lambda):
            before = len(self.src.funcs)
            self.visit(node.value)
            lam = self.src.funcs[before]  # the outermost lambda just visited
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self._bind_func(t.id, lam)
                    lam.qualname = (self.stack[-1].qualname + "." + t.id
                                    + ".<lambda>")
        else:
            self.visit(node.value)
        for t in node.targets:
            self._bind_target(t)
            self.visit(t)
        # module-level axis-name constants: NAME_AXIS = "literal"; and
        # metric-name constants: ALL_CAPS = "dotted.lowercase"
        if (self.stack[-1].is_module
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            for t in node.targets:
                if not isinstance(t, ast.Name):
                    continue
                if t.id.endswith("_AXIS"):
                    self.project.declared_axes[t.id] = node.value.value
                elif (t.id.isupper()
                      and _METRIC_NAME_RE.match(node.value.value)):
                    self.project.declared_metrics[t.id] = node.value.value

    def visit_AnnAssign(self, node):
        self._own(node)
        if node.value is not None:
            self.visit(node.value)
        self._bind_target(node.target)

    def visit_AugAssign(self, node):
        self._own(node)
        self.visit(node.value)
        self._bind_target(node.target)

    def visit_For(self, node):
        self._own(node)
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_AsyncFor(self, node):
        self.visit_For(node)

    def visit_With(self, node):
        self._own(node)
        for item in node.items:
            if item.optional_vars is not None:
                self._bind_target(item.optional_vars)
        self.generic_visit(node)

    def visit_AsyncWith(self, node):
        self.visit_With(node)

    def visit_ExceptHandler(self, node):
        self._own(node)
        if node.name:
            self._bind_local(node.name)
        self.generic_visit(node)

    def visit_NamedExpr(self, node):
        self._own(node)
        self._bind_target(node.target)
        self.visit(node.value)

    def visit_comprehension(self, node):
        self._bind_target(node.target)
        self.generic_visit(node)

    # -- uses --

    def visit_Attribute(self, node):
        self._own(node)
        if isinstance(node.ctx, ast.Load):
            self.stack[-1].attr_loads.add(node.attr)
        self.generic_visit(node)

    def visit_Return(self, node):
        self._own(node)
        if node.value is not None:
            self._note_passed(node.value)
        self.generic_visit(node)

    def _note_passed(self, expr):
        """A local function referenced as a value (argument / return) from
        traced code will almost certainly be invoked during the trace.
        Module-level functions passed by name are excluded: those are
        usually host callbacks (``jax.debug.callback`` targets)."""
        names = []
        if isinstance(expr, ast.Name):
            names = [expr.id]
        elif isinstance(expr, (ast.Tuple, ast.List)):
            names = [e.id for e in expr.elts if isinstance(e, ast.Name)]
        here = self.stack[-1]
        for n in names:
            scope = here
            while scope is not None and not scope.is_module:
                if n in scope.local_funcs:
                    here.passed_local_funcs.extend(scope.local_funcs[n])
                    break
                if n in scope.local_names or n in scope.imported_names:
                    break
                scope = scope.parent

    def visit_Call(self, node):
        self._own(node)
        here = self.stack[-1]
        t = terminal_name(node.func)
        if t is not None:
            kind = "name" if isinstance(node.func, ast.Name) else "attr"
            here.calls.append((kind, t, node))
        if t not in _HOST_CALLBACK_WRAPPERS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                self._note_passed(arg)
        self.generic_visit(node)

    def generic_visit(self, node):
        self._own(node)
        super().generic_visit(node)


# -- entry marking + propagation ---------------------------------------------


def _func_candidates(expr: ast.AST, scope: FuncInfo,
                     project: Project) -> list[FuncInfo]:
    """Resolve an expression in trace-wrapper argument position to the
    functions it may denote."""
    if isinstance(expr, ast.Lambda):
        owner = project.owner_of(expr.body)
        return [owner] if owner is not None else []
    if isinstance(expr, ast.Call):  # partial(f, ...) and friends
        if terminal_name(expr.func) == "partial" and expr.args:
            return _func_candidates(expr.args[0], scope, project)
        return []
    if isinstance(expr, ast.Name):
        s = scope
        while s is not None:
            if expr.id in s.local_funcs:
                return list(s.local_funcs[expr.id])
            if expr.id in s.local_names:
                return []  # shadowed by a plain local — unresolvable
            if expr.id in s.imported_names:
                return list(project.index.get(expr.id, []))
            s = s.parent
        return list(project.index.get(expr.id, []))
    if isinstance(expr, ast.Attribute):
        return list(project.index.get(expr.attr, []))
    if isinstance(expr, (ast.Tuple, ast.List)):  # lax.switch branch lists
        out = []
        for e in expr.elts:
            out.extend(_func_candidates(e, scope, project))
        return out
    return []


def _mark(info: FuncInfo, reason: str, chain: tuple[str, ...],
          work: list[FuncInfo]):
    if (info.traced or info.is_resolve_once or info.is_eager_pinned
            or info.is_module):
        return
    info.traced = True
    info.trace_reason = reason
    info.trace_chain = chain
    work.append(info)


def analyze(files: list[SourceFile]) -> Project:
    project = Project(files=files)
    for src in files:
        _Collector(src, project).visit(src.tree)

    # pass 2: trace-wrapper call sites anywhere in any file
    work: list[FuncInfo] = []
    for src in files:
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            t = terminal_name(node.func)
            positions = TRACE_WRAPPERS.get(t)
            scope = project.owner_of(node) or src.module_info
            cands: list[tuple[FuncInfo, str]] = []
            if positions is not None:
                for pos in positions:
                    if pos < len(node.args):
                        for c in _func_candidates(node.args[pos], scope,
                                                  project):
                            cands.append(
                                (c, f"passed to {t} at "
                                    f"{src.path}:{node.lineno}"))
            elif t == "switch" and len(node.args) >= 2:
                for c in _func_candidates(node.args[1], scope, project):
                    cands.append((c, f"passed to switch at "
                                     f"{src.path}:{node.lineno}"))
            for info, reason in cands:
                _mark(info, reason, (), work)

    # decorator / flax entries found during collection seed the worklist too
    for f in project.funcs:
        if f.traced:
            work.append(f)

    # pass 3: propagate over the call graph
    seen_edges: set[tuple[int, int]] = set()
    while work:
        f = work.pop()
        chain = f.trace_chain + (f.qualname,)
        short_chain = chain[-4:]
        via = f"called from {f.qualname} ({f.path}:{f.line})"
        for kind, name, node in f.calls:
            targets: list[FuncInfo] = []
            if kind == "name":
                s = f
                resolved = None
                while s is not None:
                    if name in s.local_funcs:
                        resolved = list(s.local_funcs[name])
                        break
                    if name in s.local_names and not s.is_module:
                        resolved = []  # a plain local variable — opaque
                        break
                    if name in s.imported_names:
                        resolved = list(project.index.get(name, []))
                        break
                    s = s.parent
                targets = (resolved if resolved is not None
                           else list(project.index.get(name, [])))
                # instantiation of a known class runs its __init__ at trace
                targets += project.class_index.get(name, [])
            else:  # attribute call: conservative terminal-name linking,
                # except names that are overwhelmingly builtin methods
                if name in _BUILTIN_METHOD_NAMES:
                    targets = []
                else:
                    targets = list(project.index.get(name, []))
            for g in targets:
                edge = (id(f), id(g))
                if edge in seen_edges:
                    continue
                seen_edges.add(edge)
                _mark(g, via, short_chain, work)
        for g in f.passed_local_funcs:
            edge = (id(f), id(g))
            if edge not in seen_edges:
                seen_edges.add(edge)
                _mark(g, f"closure passed from {f.qualname}", short_chain,
                      work)
        for attr in f.attr_loads:
            for g in project.property_index.get(attr, []):
                edge = (id(f), id(g))
                if edge not in seen_edges:
                    seen_edges.add(edge)
                    _mark(g, f"property read from {f.qualname}", short_chain,
                          work)
    return project
