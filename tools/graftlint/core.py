"""graftlint core — the shared AST project model every checker runs on.

The value proposition of sptag_tpu is that the search/build hot paths stay
on-device as a small number of compiled XLA programs (PAPER.md; TPU-KNN
arXiv:2206.14286 holds peak FLOP/s only while host<->device syncs and
recompilations stay out of the query loop).  Nothing in Python enforces
that — a stray `.item()`, a retrace on a Python-int shape, or an unlocked
cross-thread mutation lands silently and shows up rounds later as a bench
regression.  graftlint is the static backstop: an AST pass with
codebase-specific knowledge (which functions are jitted, which attributes
are lock-protected, which modules are error-code boundaries).

This module provides:

* `Project` — parse a file tree (or in-memory sources) into `ModuleInfo` /
  `FunctionInfo` records with import-alias tables and a call graph;
* jit-root detection (`@jax.jit`, `@functools.partial(jax.jit, ...)`,
  `jax.jit(f, ...)` call sites, `shard_map(f, ...)`) including
  `static_argnames` extraction, and transitive jit-REACHABILITY over the
  call graph (nested defs inside a jitted body are traced too);
* a single-pass local taint analysis marking names that hold traced jax
  values (`tracer_taint`), used by the host-sync checker;
* the `Finding` record and rule registry every checker reports through.

Checkers live in sibling modules (hostsync, retrace, concurrency,
errorpath, dtype_parity); `runner.py` wires them to the baseline and CLI.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

#: modules whose attributes produce traced values inside a jit region
JAX_VALUE_MODULES = {"jax.numpy", "jax.lax", "jax"}

#: alias heads treated as numpy (host) for the host-sync checker
NUMPY_MODULES = {"numpy"}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint hit.  `symbol` is the enclosing function qualname (or ""
    at module level) — baseline entries match on (rule, path, symbol) so
    unrelated line drift does not invalidate a suppression."""

    rule: str          # e.g. "GL101"
    path: str          # repo-relative posix path
    line: int          # 1-based
    message: str
    symbol: str = ""

    def format(self) -> str:
        where = f" [in {self.symbol}]" if self.symbol else ""
        return f"{self.path}:{self.line}: {self.rule} {self.message}{where}"


@dataclasses.dataclass
class FunctionInfo:
    node: ast.AST                     # FunctionDef / AsyncFunctionDef / Lambda
    qualname: str                     # module-relative, dotted
    module: "ModuleInfo"
    parent: Optional["FunctionInfo"]
    is_jit_root: bool = False
    is_shard_root: bool = False
    static_args: Set[str] = dataclasses.field(default_factory=set)
    jit_reachable: bool = False

    @property
    def name(self) -> str:
        return getattr(self.node, "name", "<lambda>")

    @property
    def line(self) -> int:
        return self.node.lineno

    def param_names(self) -> List[str]:
        a = self.node.args
        params = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
        if a.vararg:
            params.append(a.vararg.arg)
        if a.kwarg:
            params.append(a.kwarg.arg)
        return params


class ModuleInfo:
    """One parsed source file: AST, import aliases, functions, classes."""

    def __init__(self, relpath: str, source: str):
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.tree = ast.parse(source, filename=self.relpath)
        # alias -> full module path, e.g. {"np": "numpy",
        # "jnp": "jax.numpy", "dist_ops": "sptag_tpu.ops.distance"}
        self.import_aliases: Dict[str, str] = {}
        # name -> "module.symbol" for from-imports of functions, e.g.
        # {"query_bucket": "sptag_tpu.utils.query_bucket"}
        self.from_imports: Dict[str, str] = {}
        self.functions: List[FunctionInfo] = []
        self._by_qualname: Dict[str, FunctionInfo] = {}
        self._collect_imports()
        self._collect_functions()

    # -------------------------------------------------------------- imports

    def _collect_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.asname:
                        self.import_aliases[alias.asname] = alias.name
                    else:
                        # `import a.b` binds the name `a` (to package a),
                        # NOT a.b — mapping 'a' -> 'a.b' would misresolve
                        # every other a.* reference in the module (a lazy
                        # `import jax.profiler` must not hijack `jax.jit`)
                        head = alias.name.split(".")[0]
                        self.import_aliases[head] = head
            elif isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    self.from_imports[alias.asname or alias.name] = \
                        f"{node.module}.{alias.name}"
                    # `from sptag_tpu.ops import distance as dist_ops`
                    # also registers a module alias
                    self.import_aliases.setdefault(
                        alias.asname or alias.name,
                        f"{node.module}.{alias.name}")

    def resolve_head(self, name: str) -> Optional[str]:
        """Map the head of a dotted reference to a full module path."""
        return self.import_aliases.get(name)

    # ------------------------------------------------------------ functions

    def _collect_functions(self) -> None:
        def visit(node: ast.AST, prefix: str, parent: Optional[FunctionInfo]):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}" if prefix else child.name
                    info = FunctionInfo(child, qual, self, parent)
                    self.functions.append(info)
                    self._by_qualname[qual] = info
                    visit(child, qual + ".", info)
                elif isinstance(child, ast.ClassDef):
                    visit(child, (prefix or "") + child.name + ".", parent)
                else:
                    visit(child, prefix, parent)

        visit(self.tree, "", None)

    def function(self, qualname: str) -> Optional[FunctionInfo]:
        return self._by_qualname.get(qualname)

    def functions_named(self, name: str) -> List[FunctionInfo]:
        return [f for f in self.functions if f.name == name]

    def classes(self) -> List[ast.ClassDef]:
        return [n for n in ast.walk(self.tree)
                if isinstance(n, ast.ClassDef)]


# ---------------------------------------------------------------------------
# jit-root detection
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    """`jax.numpy.sum` -> "jax.numpy.sum"; None for non-name chains."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jax_jit(node: ast.AST, mod: ModuleInfo) -> bool:
    d = _dotted(node)
    if d is None:
        return False
    head, _, rest = d.partition(".")
    full = mod.resolve_head(head)
    if full is not None:
        d = full + ("." + rest if rest else "")
    return d in ("jax.jit", "jax.jit.jit") or d.endswith("jax.jit") or \
        d == "jit" and mod.from_imports.get("jit", "").endswith("jax.jit")


def _is_shard_map(node: ast.AST, mod: ModuleInfo) -> bool:
    d = _dotted(node)
    if d is None:
        return False
    if d.split(".")[-1] != "shard_map":
        return False
    head = d.split(".")[0]
    full = mod.resolve_head(head) or head
    return full.startswith("jax") or d == "shard_map"


def _static_args_from_call(call: ast.Call) -> Set[object]:
    """Constants named in static_argnames (str) / static_argnums (int).
    Ints are positional indices — `_resolve_static` maps them to the
    owning function's parameter names."""
    out: Set[object] = set()
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            v = kw.value
            if isinstance(v, ast.Constant) and \
                    isinstance(v.value, (str, int)):
                out.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List, ast.Set)):
                out |= {e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, (str, int))}
    return out


def _resolve_static(fn: FunctionInfo, items: Set[object]) -> Set[str]:
    params = fn.param_names()
    names: Set[str] = set()
    for item in items:
        if isinstance(item, str):
            names.add(item)
        elif isinstance(item, int) and 0 <= item < len(params):
            names.add(params[item])
    return names


def _mark_jit_roots(mod: ModuleInfo) -> None:
    # decorator forms
    for fn in mod.functions:
        for dec in getattr(fn.node, "decorator_list", []):
            if _is_jax_jit(dec, mod):
                fn.is_jit_root = True
            elif isinstance(dec, ast.Call):
                if _is_jax_jit(dec.func, mod):
                    fn.is_jit_root = True
                    fn.static_args |= _resolve_static(
                        fn, _static_args_from_call(dec))
                elif _dotted(dec.func) in ("functools.partial", "partial") \
                        and dec.args and _is_jax_jit(dec.args[0], mod):
                    fn.is_jit_root = True
                    fn.static_args |= _resolve_static(
                        fn, _static_args_from_call(dec))
    # call forms: jax.jit(f, ...) / shard_map(f, ...) / pallas_call(f, ...)
    # anywhere in the module
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        target = node.args[0]
        if not isinstance(target, ast.Name):
            continue
        for fn in mod.functions_named(target.id):
            if _is_jax_jit(node.func, mod):
                fn.is_jit_root = True
                fn.static_args |= _resolve_static(
                    fn, _static_args_from_call(node))
            elif _is_shard_map(node.func, mod):
                fn.is_shard_root = True
            elif (_dotted(node.func) or "").split(".")[-1] == \
                    "pallas_call":
                # a Pallas kernel body is traced/compiled like a jit
                # root (device program; host syncs inside are fatal)
                fn.is_jit_root = True


# ---------------------------------------------------------------------------
# call graph + jit reachability
# ---------------------------------------------------------------------------

def _called_names(fn: FunctionInfo) -> List[Tuple[str, Optional[str]]]:
    """(simple_name, module_alias_or_None) for every call inside `fn`,
    excluding calls that belong to nested function bodies (those get their
    own FunctionInfo)."""
    out: List[Tuple[str, Optional[str]]] = []
    nested = {f.node for f in fn.module.functions
              if f.parent is fn}

    def visit(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if child in nested:
                continue
            if isinstance(child, ast.Call):
                f = child.func
                if isinstance(f, ast.Name):
                    out.append((f.id, None))
                elif isinstance(f, ast.Attribute) and \
                        isinstance(f.value, ast.Name):
                    out.append((f.attr, f.value.id))
                # also treat bare function references passed as args as
                # potential calls (lax.while_loop(cond, body, ...),
                # lax.map(body, xs), vmap(f)(..))
                for arg in child.args:
                    if isinstance(arg, ast.Name):
                        out.append((arg.id, None))
            visit(child)

    visit(fn.node)
    return out


class Project:
    """All parsed modules plus the cross-module function index."""

    def __init__(self, sources: Dict[str, str],
                 package_root: str = "sptag_tpu"):
        self.package_root = package_root
        self.modules: Dict[str, ModuleInfo] = {}
        self.errors: List[Finding] = []
        #: shared per-pass memo store: checkers that build expensive
        #: derived models (lock topology, guarded-by pass, the class
        #: attribute registry, the trace-contract call model) key them
        #: here so every registered pass shares ONE parse + call graph
        #: per lint invocation instead of rebuilding its own
        self.cache: Dict[str, object] = {}
        #: non-Python sources handed to the project (e.g. a planted
        #: "docs/PARAMETERS.md" in an obsgraph fixture) — checkers that
        #: cross-reference doc surfaces read them from here first, then
        #: fall back to `source_root` on disk
        self.extra_sources: Dict[str, str] = {}
        #: repo root when this project was parsed from a real tree
        #: (from_tree sets it); None for in-memory fixture projects —
        #: cross-tree surfaces (docs/, tests/, tools/) are only
        #: consulted when this is set
        self.source_root: Optional[str] = None
        for relpath, src in sorted(sources.items()):
            if not relpath.endswith(".py"):
                self.extra_sources[relpath.replace(os.sep, "/")] = src
                continue
            try:
                self.modules[relpath] = ModuleInfo(relpath, src)
            except SyntaxError as e:
                self.errors.append(Finding(
                    "GL000", relpath, e.lineno or 1,
                    f"syntax error: {e.msg}"))
        # module path ("sptag_tpu.ops.distance") -> ModuleInfo
        self.by_modpath: Dict[str, ModuleInfo] = {}
        for relpath, mod in self.modules.items():
            modpath = relpath[:-3].replace("/", ".")
            if modpath.endswith(".__init__"):
                modpath = modpath[: -len(".__init__")]
            self.by_modpath[modpath] = mod
        for mod in self.modules.values():
            _mark_jit_roots(mod)
        self._propagate_reachability()

    @classmethod
    def from_tree(cls, root: str,
                  package_root: str = "sptag_tpu") -> "Project":
        """Parse every .py file under `root`.  Paths in findings are
        CWD-relative when `root` sits under the current directory (so
        `graftlint sptag_tpu/core` from the repo root still reports
        `sptag_tpu/core/index.py`, matching baseline entries and the
        path-scoped checkers); otherwise they fall back to relative to
        the parent of `root`."""
        root = os.path.abspath(root.rstrip("/"))
        base = os.path.dirname(root)
        cwd_rel = os.path.relpath(root, os.getcwd())
        if not cwd_rel.startswith(os.pardir) and not os.path.isabs(cwd_rel):
            base = os.getcwd()
        sources: Dict[str, str] = {}
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            for fname in sorted(filenames):
                if not fname.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fname)
                rel = os.path.relpath(full, base)
                with open(full, encoding="utf-8") as f:
                    sources[rel] = f.read()
        project = cls(sources, package_root=package_root)
        project.source_root = base
        return project

    # -------------------------------------------------------- reachability

    def _resolve_call(self, mod: ModuleInfo, name: str,
                      alias: Optional[str]) -> List[FunctionInfo]:
        if alias is None:
            # same module (any nesting level — simple-name resolution)
            local = mod.functions_named(name)
            if local:
                return local
            # from-import of a project function
            target = mod.from_imports.get(name)
            if target and target.startswith(self.package_root):
                modpath, _, sym = target.rpartition(".")
                tmod = self.by_modpath.get(modpath)
                if tmod:
                    return tmod.functions_named(sym)
            return []
        if alias == "self":
            # method call on the same class — approximate by name within
            # the module (method names are unique enough in practice)
            return mod.functions_named(name)
        full = mod.resolve_head(alias)
        if full and full.startswith(self.package_root):
            tmod = self.by_modpath.get(full)
            if tmod:
                return tmod.functions_named(name)
        return []

    def _propagate_reachability(self) -> None:
        queue: List[FunctionInfo] = []
        for mod in self.modules.values():
            for fn in mod.functions:
                if fn.is_jit_root or fn.is_shard_root:
                    fn.jit_reachable = True
                    queue.append(fn)
        seen: Set[int] = {id(f) for f in queue}
        while queue:
            fn = queue.pop()
            # nested defs inside a jitted body are traced with it
            for child in fn.module.functions:
                if child.parent is fn and id(child) not in seen:
                    child.jit_reachable = True
                    seen.add(id(child))
                    queue.append(child)
            for name, alias in _called_names(fn):
                for callee in self._resolve_call(fn.module, name, alias):
                    if id(callee) not in seen:
                        callee.jit_reachable = True
                        seen.add(id(callee))
                        queue.append(callee)

    def jit_reachable_functions(self) -> List[FunctionInfo]:
        return [fn for mod in self.modules.values()
                for fn in mod.functions if fn.jit_reachable]


# ---------------------------------------------------------------------------
# local taint analysis (traced-value tracking)
# ---------------------------------------------------------------------------

#: attribute accesses that yield STATIC (host) values even on a tracer
STATIC_ATTRS = {"shape", "dtype", "ndim", "size", "itemsize"}


#: jax/jnp functions that return HOST values even under trace — metadata
#: queries, not array computations
_JAX_STATIC_FNS = {"issubdtype", "dtype", "result_type", "shape", "ndim",
                   "iinfo", "finfo", "can_cast", "promote_types", "size",
                   # what the process runs on: Device objects, no arrays
                   "devices", "local_devices", "default_backend"}


def _is_jax_producing_call(call: ast.Call, mod: ModuleInfo) -> bool:
    """Does this call produce a traced jax value?  True for jnp.* / lax.* /
    jax.* attribute calls (resolved through the module's import aliases),
    excluding dtype/shape metadata queries which are trace-time static."""
    d = _dotted(call.func)
    if d is None:
        return False
    head, _, rest = d.partition(".")
    full = mod.resolve_head(head)
    if full is None:
        return False
    if d.split(".")[-1] in _JAX_STATIC_FNS:
        return False
    base = full.split(".")[0]
    return base == "jax"


def tracer_taint(fn: FunctionInfo,
                 inherited: Optional[Set[str]] = None) -> Set[str]:
    """Names in `fn` that (statically) hold traced jax values.

    Seeds: non-static parameters of a jit/shard ROOT (those are tracers by
    construction) and any name assigned from a jnp./lax./jax. call.  Taint
    propagates through arithmetic, comparisons, subscripts and calls that
    take a tainted argument; it is KILLED by `.shape` / `.dtype` / `.ndim`
    access and by `len()` / `np.*` (host) calls — shape-derived Python ints
    are static, not traced.  One forward pass, no fixpoint: good enough for
    straight-line kernel code, and a missed loop-carried taint only costs
    a false negative, never a false positive.
    """
    mod = fn.module
    tainted: Set[str] = set(inherited or ())
    if fn.is_jit_root or fn.is_shard_root:
        for p in fn.param_names():
            if p not in fn.static_args and p != "self":
                tainted.add(p)

    def expr_tainted(node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in tainted
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return False
            return expr_tainted(node.value)
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            if d is not None:
                head = d.split(".")[0]
                full = mod.resolve_head(head)
                if full and full.split(".")[0] in NUMPY_MODULES:
                    return False          # host value (its own lint)
                if d.split(".")[-1] == "len" or head == "len":
                    return False
            if _is_jax_producing_call(node, mod):
                return True
            return any(expr_tainted(a) for a in node.args) or \
                any(expr_tainted(k.value) for k in node.keywords)
        if isinstance(node, ast.BinOp):
            return expr_tainted(node.left) or expr_tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return expr_tainted(node.operand)
        if isinstance(node, ast.Compare):
            # `x is None` / `x is not None` is a structural host check,
            # decidable on a tracer without materializing it
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            return expr_tainted(node.left) or \
                any(expr_tainted(c) for c in node.comparators)
        if isinstance(node, ast.Subscript):
            return expr_tainted(node.value)
        if isinstance(node, ast.IfExp):
            return expr_tainted(node.body) or expr_tainted(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(expr_tainted(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return expr_tainted(node.value)
        return False

    nested = {f.node for f in mod.functions if f.parent is fn}

    def visit(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if child in nested:
                continue
            if isinstance(child, ast.Assign) and \
                    expr_tainted(child.value):
                for tgt in child.targets:
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name):
                            tainted.add(n.id)
            elif isinstance(child, (ast.AugAssign, ast.AnnAssign)) and \
                    child.value is not None and expr_tainted(child.value):
                if isinstance(child.target, ast.Name):
                    tainted.add(child.target.id)
            visit(child)

    visit(fn.node)
    fn._taint_expr = expr_tainted          # checkers reuse the evaluator
    return tainted


def body_nodes(fn: FunctionInfo) -> Iterable[ast.AST]:
    """Walk `fn`'s body EXCLUDING nested function bodies (those are
    analyzed as their own FunctionInfo)."""
    nested = {f.node for f in fn.module.functions if f.parent is fn}

    def visit(node: ast.AST):
        for child in ast.iter_child_nodes(node):
            if child in nested:
                continue
            yield child
            yield from visit(child)

    yield from visit(fn.node)


def statements_under_with(fn: FunctionInfo,
                          ctx_names: Sequence[str]) -> Set[int]:
    """Line numbers of statements inside a `with <self.X>:` block where X
    is one of `ctx_names` — the concurrency checker's "lock held" set."""
    held: Set[int] = set()

    def visit(node: ast.AST, under: bool) -> None:
        for child in ast.iter_child_nodes(node):
            now = under
            if isinstance(child, (ast.With, ast.AsyncWith)):
                for item in child.items:
                    d = _dotted(item.context_expr)
                    if d is None and isinstance(item.context_expr, ast.Call):
                        d = _dotted(item.context_expr.func)
                    if d and d.split(".")[-1] in ctx_names:
                        now = True
            if now and hasattr(child, "lineno"):
                held.add(child.lineno)
            visit(child, now)

    visit(fn.node, False)
    return held
