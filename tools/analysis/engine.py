"""The analysis engine: repo walker, AST index, call graph, baseline.

Pure stdlib (``ast``/``json``/``os``) by contract — importing jax here
would cost seconds per tier-1 run and drag backend state into a tool
whose whole point is to run before any backend exists.  The test suite
pins the no-third-party-import contract by linting this package's own
import list.

Resolution model (deliberately modest, deliberately explicit):

- ``self.m(...)`` resolves to method ``m`` of the enclosing class;
- ``self.X(...)`` / ``self.X.m(...)`` resolve through ``self.X =
  ClassName(...)`` assignments (constructor type inference), with a
  callable-object convention mapping ``K(...)`` instances called
  directly onto ``K.forward`` / ``K.__call__``;
- ``self._foo_jit(...)`` resolves through ``self._foo_jit =
  jax.jit(self._target, ...)`` bindings (the decode engine's idiom) —
  and the binding records ``donate_argnums`` for the donation rule;
- ``name(...)`` resolves to same-module functions, then module-level
  functions anywhere by bare name;
- local ``x = ClassName(...)`` infers ``x.m(...)`` inside one function;
- everything else is unresolved unless :data:`config.EXTRA_EDGES`
  names the dynamic seam.

Unresolved calls are NOT treated as reaching everything: the hot-path
rules prefer a small, reviewable reachable set plus explicit edges over
a name-match explosion that would bury real findings in noise.
"""
from __future__ import annotations

import ast
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from . import config

__all__ = ["Finding", "FuncInfo", "ClassInfo", "FileInfo", "RepoIndex",
           "Baseline", "load_baseline", "run_analysis"]

import builtins as _builtins

_BUILTIN_NAMES = set(dir(_builtins))



class Finding:
    """One rule hit: where, what, and the stable key the baseline uses.

    ``detail`` is the normalized source of the offending node (not the
    line number) so baseline entries survive unrelated edits above the
    finding; ``count``-aware matching disambiguates repeats of the same
    snippet inside one scope."""

    __slots__ = ("rule", "severity", "file", "line", "scope", "message",
                 "detail")

    def __init__(self, rule: str, severity: str, file: str, line: int,
                 scope: str, message: str, detail: str):
        self.rule = rule
        self.severity = severity
        self.file = file
        self.line = int(line)
        self.scope = scope
        self.message = message
        self.detail = detail

    def key(self) -> str:
        return "%s|%s|%s|%s" % (self.rule, self.file, self.scope,
                                self.detail)

    def location(self) -> str:
        return "%s:%d" % (self.file, self.line)

    def to_dict(self) -> dict:
        return {"rule": self.rule, "severity": self.severity,
                "file": self.file, "line": self.line, "scope": self.scope,
                "message": self.message, "detail": self.detail}

    def __repr__(self) -> str:  # diagnostics in test failures
        return "Finding(%s %s %s)" % (self.rule, self.location(),
                                      self.detail)


def _detail_of(node: ast.AST, limit: int = 88) -> str:
    try:
        text = ast.unparse(node)
    except Exception:  # pragma: no cover - unparse is total on 3.9+
        text = type(node).__name__
    text = " ".join(text.split())
    return text[:limit]


class FuncInfo:
    """One function/method: AST node + resolution context.

    ``calls``/``names``/``nested``/``local_types`` are precomputed in
    ONE walk per function at index build — every later rule reads the
    cache instead of re-walking the tree (255 files stay ~1s total)."""

    __slots__ = ("qualname", "name", "class_name", "file", "node",
                 "lineno", "params", "parent_class", "decorators",
                 "calls", "names", "nested", "local_types")

    def __init__(self, qualname, name, class_name, file, node,
                 parent_class, decorators):
        self.qualname = qualname
        self.name = name
        self.class_name = class_name
        self.file = file
        self.node = node
        self.lineno = node.lineno
        args = node.args
        self.params = [a.arg for a in (
            list(args.posonlyargs) + list(args.args)
            + list(args.kwonlyargs))]
        self.parent_class = parent_class  # ClassInfo or None
        self.decorators = decorators      # list of source strings
        self.calls: List[ast.Call] = []
        self.names: Set[str] = set()
        self.nested: Dict[str, "FuncInfo"] = {}
        self.local_types: Dict[str, str] = {}


class ClassInfo:
    __slots__ = ("name", "file", "methods", "lock_attrs", "thread_attrs",
                 "attr_classes", "jit_bindings", "node", "decorators")

    def __init__(self, name, file, node, decorators):
        self.name = name
        self.file = file
        self.node = node
        self.methods: Dict[str, FuncInfo] = {}
        self.lock_attrs: Set[str] = set()     # self.X = threading.Lock()
        self.thread_attrs: Set[str] = set()   # self.X = threading.Thread()
        self.attr_classes: Dict[str, str] = {}  # self.X = ClassName(...)
        # self.X = jax.jit(self._m, donate_argnums=...) ->
        #   {attr: (method_name, donated_positions)}
        self.jit_bindings: Dict[str, Tuple[str, Tuple[int, ...]]] = {}
        self.decorators = decorators


class FileInfo:
    __slots__ = ("relpath", "tree", "functions", "classes", "np_aliases",
                 "jnp_aliases", "jax_aliases", "module_funcs",
                 "pytest_aliases")

    def __init__(self, relpath, tree):
        self.relpath = relpath
        self.tree = tree
        self.functions: List[FuncInfo] = []
        self.classes: Dict[str, ClassInfo] = {}
        # pre-seeded with the conventional aliases so classification is
        # independent of import-vs-use visit order (lazy in-function
        # imports are pervasive in this codebase); real aliases are
        # added as the indexing pass sees the import statements
        self.np_aliases: Set[str] = {"np", "numpy"}
        self.jnp_aliases: Set[str] = {"jnp"}
        self.jax_aliases: Set[str] = {"jax"}
        self.pytest_aliases: Set[str] = {"pytest"}
        self.module_funcs: Dict[str, FuncInfo] = {}


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _donated_positions(call: ast.Call,
                       scope: Optional[ast.AST] = None
                       ) -> Tuple[int, ...]:
    """donate_argnums of a ``jax.jit(...)`` call; conditional
    expressions like ``(2,) if donate else ()`` take the donating arm
    (the lint assumes donation CAN be on), and a plain-name argument is
    chased through one local assignment in ``scope``."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        return _positions_of(kw.value, scope)
    return ()


def _positions_of(node: ast.AST,
                  scope: Optional[ast.AST]) -> Tuple[int, ...]:
    if isinstance(node, ast.IfExp):
        for arm in (node.body, node.orelse):
            got = _positions_of(arm, scope)
            if got:
                return got
        return ()
    got = _tuple_ints(node)
    if got is not None:
        return got
    if isinstance(node, ast.Name) and scope is not None:
        for sub in ast.walk(scope):
            if isinstance(sub, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == node.id
                    for t in sub.targets):
                return _positions_of(sub.value, None)
    return ()


def _tuple_ints(node: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Tuple):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                out.append(elt.value)
            else:
                return None
        return tuple(out)
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    return None


def _contains_jax_math(node: ast.AST, info: "FileInfo") -> bool:
    """Does the expression contain a call into jnp/jax (a traced
    computation, as opposed to a python scalar or a static shape)?"""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            dotted = _dotted(sub.func) or ""
            head = dotted.split(".")[0]
            if head in info.jnp_aliases or head in info.jax_aliases:
                return True
    return False


def _is_jit_call(call: ast.Call, info: FileInfo) -> bool:
    dotted = _dotted(call.func)
    if dotted is None:
        return False
    parts = dotted.split(".")
    return (len(parts) >= 2 and parts[-1] == "jit"
            and parts[0] in info.jax_aliases)


class _FileIndexer(ast.NodeVisitor):
    """Populate a FileInfo in ONE pass: functions, classes, per-class
    attribute facts (locks, threads, constructor types, jit bindings),
    imports, and the per-function call/name caches (a call inside a
    nested function is attributed to every enclosing function — the
    same containment semantics as walking each function's subtree)."""

    def __init__(self, info: FileInfo, known_classes: Set[str]):
        self.info = info
        self.known_classes = known_classes
        self._class_stack: List[ClassInfo] = []
        self._func_stack: List[FuncInfo] = []
        # interleaved class/function scopes: a def's OWNER is the
        # innermost scope — `_class_stack[-1]` alone would claim
        # functions nested inside methods as methods, and `not
        # in_func` would orphan methods of function-nested classes
        # (serving/http.py's handler factory shape)
        self._scopes: List[Tuple[str, object]] = []

    def visit_Call(self, node: ast.Call) -> None:
        for fi in self._func_stack:
            fi.calls.append(node)
        self.generic_visit(node)

    def visit_Name(self, node: ast.Name) -> None:
        for fi in self._func_stack:
            fi.names.add(node.id)

    def visit_Import(self, node: ast.Import) -> None:
        info = self.info
        for alias in node.names:
            name = alias.asname or alias.name.split(".")[0]
            if alias.name == "numpy":
                info.np_aliases.add(name)
            elif alias.name == "jax.numpy":
                info.jnp_aliases.add(alias.asname or "jax")
            elif alias.name == "jax" or alias.name.startswith("jax."):
                info.jax_aliases.add(name)
            elif alias.name == "pytest":
                info.pytest_aliases.add(name)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if (node.module or "") == "jax":
            for alias in node.names:
                if alias.name == "numpy":
                    self.info.jnp_aliases.add(alias.asname or "numpy")

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        decos = [_detail_of(d) for d in node.decorator_list]
        ci = ClassInfo(node.name, self.info.relpath, node, decos)
        self.info.classes[node.name] = ci
        self._class_stack.append(ci)
        self._scopes.append(("class", ci))
        self.generic_visit(node)
        self._scopes.pop()
        self._class_stack.pop()

    def _visit_func(self, node) -> None:
        in_func = bool(self._func_stack)
        cls = self._scopes[-1][1] if self._scopes \
            and self._scopes[-1][0] == "class" else None
        if cls is not None:
            qual = "%s.%s" % (cls.name, node.name)
            class_name = cls.name
        else:
            qual = node.name
            class_name = None
        decos = [_detail_of(d) for d in node.decorator_list]
        fi = FuncInfo(qual, node.name, class_name, self.info.relpath,
                      node, cls, decos)
        self.info.functions.append(fi)
        if cls is not None:
            cls.methods[node.name] = fi
        elif not in_func:
            self.info.module_funcs[node.name] = fi
        if in_func:
            self._func_stack[-1].nested[node.name] = fi
        self._func_stack.append(fi)
        self._scopes.append(("func", fi))
        self.generic_visit(node)
        self._scopes.pop()
        self._func_stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    def visit_Assign(self, node: ast.Assign) -> None:
        self._record_self_assign(node)
        if isinstance(node.value, ast.Call):
            tail = (_dotted(node.value.func) or "").split(".")[-1]
            if tail in self.known_classes:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        for fi in self._func_stack:
                            fi.local_types[tgt.id] = tail
        self.generic_visit(node)

    def _record_self_assign(self, node: ast.Assign) -> None:
        if not self._class_stack or not isinstance(node.value, ast.Call):
            return
        cls = self._class_stack[-1]
        call = node.value
        for tgt in node.targets:
            if not (isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"):
                continue
            attr = tgt.attr
            dotted = _dotted(call.func) or ""
            tail = dotted.split(".")[-1]
            if dotted in ("threading.Lock", "threading.RLock"):
                cls.lock_attrs.add(attr)
            elif dotted == "threading.Thread":
                cls.thread_attrs.add(attr)
            elif _is_jit_call(call, self.info) and call.args:
                # target may be self._method (resolvable) or a local
                # function (positions still matter for donation-reuse)
                target = _dotted(call.args[0]) or ""
                scope = self._func_stack[-1].node \
                    if self._func_stack else None
                cls.jit_bindings[attr] = (
                    target.split(".")[-1],
                    _donated_positions(call, scope))
            elif tail in self.known_classes:
                cls.attr_classes[attr] = tail


class RepoIndex:
    """Parsed repo + cross-file resolution + reachability."""

    def __init__(self, root: str,
                 walk_roots: Sequence[str] = config.WALK_ROOTS):
        self.root = os.path.abspath(root)
        self.files: Dict[str, FileInfo] = {}
        self.errors: List[str] = []
        paths = self._walk(walk_roots)
        trees = {}
        for rel in paths:
            try:
                with open(os.path.join(self.root, rel), "r",
                          encoding="utf-8") as f:
                    trees[rel] = ast.parse(f.read())
            except (SyntaxError, UnicodeDecodeError, OSError) as e:
                self.errors.append("%s: %s" % (rel, e))
        known_classes: Set[str] = set()
        for tree in trees.values():
            for node in ast.walk(tree):
                if isinstance(node, ast.ClassDef):
                    known_classes.add(node.name)
        self.known_classes = known_classes
        for rel, tree in trees.items():
            info = FileInfo(rel, tree)
            _FileIndexer(info, known_classes).visit(tree)
            self.files[rel] = info
        # cross-file indexes
        self.functions: Dict[str, FuncInfo] = {}      # qualname -> first
        self.by_name: Dict[str, List[FuncInfo]] = {}  # bare name -> all
        self.classes: Dict[str, ClassInfo] = {}
        for info in self.files.values():
            for fi in info.functions:
                self.functions.setdefault(fi.qualname, fi)
                self.by_name.setdefault(fi.name, []).append(fi)
            for name, ci in info.classes.items():
                self.classes.setdefault(name, ci)
        self._edges: Optional[Dict[str, Set[str]]] = None
        self._jit_traced: Optional[Set[str]] = None
        self._jit_static: Dict[str, Set[str]] = {}
        self._may_sync: Optional[Set[str]] = None
        self._may_jax: Optional[Set[str]] = None
        self._reachable: Dict[Tuple[str, ...], Set[str]] = {}

    # -- walking ---------------------------------------------------------
    def _walk(self, walk_roots: Sequence[str]) -> List[str]:
        out: List[str] = []
        roots = [r for r in walk_roots
                 if os.path.exists(os.path.join(self.root, r))]
        if not roots:
            roots = ["."]  # fixture tree: walk everything under root
        for r in roots:
            full = os.path.join(self.root, r)
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [d for d in dirnames
                               if d not in config.SKIP_DIRS]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        rel = os.path.relpath(os.path.join(dirpath, fn),
                                              self.root)
                        out.append(rel)
        return sorted(set(out))

    # -- resolution ------------------------------------------------------
    def resolve_call(self, caller: FuncInfo, call: ast.Call,
                     local_types: Optional[Dict[str, str]] = None,
                     loose: bool = False) -> List[FuncInfo]:
        """Callee candidates for one Call node (see module docstring).
        ``loose=True`` adds a bare-name fallback for unresolved
        attribute calls — used only for may-sync classification, never
        for hot-path reachability."""
        info = self.files[caller.file]
        func = call.func
        out: List[FuncInfo] = []
        if isinstance(func, ast.Name):
            name = func.id
            if name in _BUILTIN_NAMES:
                # `list(...)`/`help(...)` mean the builtin even when an
                # API module shadows the name (paddle.hub.list)
                return out
            if name in info.module_funcs:
                out.append(info.module_funcs[name])
            elif name in self.known_classes:
                pass  # constructor: type, not code we analyze here
            else:
                fi = self.functions.get(name)
                if fi is not None:
                    out.append(fi)
            # nested function defined in the caller's body
            got = caller.nested.get(name)
            if got is not None and got not in out:
                out.append(got)
            return out
        if not isinstance(func, ast.Attribute):
            return out
        attr = func.attr
        base = func.value
        cls = caller.parent_class
        # self.m(...) / self.X(...) / self.X.m(...)
        if isinstance(base, ast.Name) and base.id == "self" \
                and cls is not None:
            if attr in cls.methods:
                return [cls.methods[attr]]
            if attr in cls.jit_bindings:
                target = cls.jit_bindings[attr][0]
                if target in cls.methods:
                    return [cls.methods[target]]
            if attr in cls.attr_classes:
                return self._callable_object(cls.attr_classes[attr])
        if isinstance(base, ast.Attribute) \
                and isinstance(base.value, ast.Name) \
                and base.value.id == "self" and cls is not None:
            owner = cls.attr_classes.get(base.attr)
            if owner is not None:
                oc = self.classes.get(owner)
                if oc is not None and attr in oc.methods:
                    return [oc.methods[attr]]
        # local-var constructor inference: x = ClassName(...); x.m(...)
        if isinstance(base, ast.Name) and local_types \
                and base.id in local_types:
            oc = self.classes.get(local_types[base.id])
            if oc is not None:
                if attr in oc.methods:
                    return [oc.methods[attr]]
                if attr in oc.jit_bindings:
                    target = oc.jit_bindings[attr][0]
                    if target in oc.methods:
                        return [oc.methods[target]]
        # superclass resolution: GenerationPool method called on
        # SpeculativePool etc. — single-level base-class name match
        if cls is not None:
            for b in getattr(cls.node, "bases", []):
                bname = _dotted(b)
                if bname is None:
                    continue
                bc = self.classes.get(bname.split(".")[-1])
                if bc is not None and isinstance(base, ast.Name) \
                        and base.id == "self" and attr in bc.methods:
                    return [bc.methods[attr]]
        if loose:
            return list(self.by_name.get(attr, []))
        return out

    def _callable_object(self, class_name: str) -> List[FuncInfo]:
        """K(...) instance called directly -> K.forward / K.__call__."""
        oc = self.classes.get(class_name)
        if oc is None:
            return []
        out = []
        for m in ("__call__", "forward"):
            if m in oc.methods:
                out.append(oc.methods[m])
        return out

    # -- reachability ----------------------------------------------------
    def edges(self) -> Dict[str, Set[str]]:
        if self._edges is not None:
            return self._edges
        edges: Dict[str, Set[str]] = {}
        for fi in self.functions.values():
            outs: Set[str] = set()
            for node in fi.calls:
                for callee in self.resolve_call(fi, node,
                                                fi.local_types):
                    outs.add(callee.qualname)
            for root_suffix, callees in config.EXTRA_EDGES.items():
                if fi.qualname == root_suffix \
                        or fi.qualname.endswith("." + root_suffix):
                    for c in callees:
                        if c in self.functions:
                            outs.add(c)
            edges[fi.qualname] = outs
        self._edges = edges
        return edges

    def _closure(self, seeds: Set[str]) -> Set[str]:
        """Transitive closure of ``seeds`` over :meth:`edges`."""
        edges = self.edges()
        seen = set(seeds)
        frontier = list(seeds)
        while frontier:
            cur = frontier.pop()
            for nxt in edges.get(cur, ()):
                if nxt not in seen:
                    seen.add(nxt)
                    frontier.append(nxt)
        return seen

    def reachable(self, root_suffixes: Iterable[str]) -> Set[str]:
        """Qualnames reachable from any function matching a suffix."""
        cache_key = tuple(sorted(root_suffixes))
        if cache_key in self._reachable:
            return self._reachable[cache_key]
        seeds = set()
        for fi in self.functions.values():
            for suf in root_suffixes:
                if fi.qualname == suf or fi.qualname.endswith("." + suf):
                    seeds.add(fi.qualname)
        self._reachable[cache_key] = self._closure(seeds)
        return self._reachable[cache_key]

    def jit_traced(self) -> Set[str]:
        """Functions handed to jax.jit anywhere — as a call argument
        (``jax.jit(f)``) or by decorator (``@jax.jit`` /
        ``@partial(jax.jit, ...)``) — plus their callees."""
        if self._jit_traced is not None:
            return self._jit_traced
        self._jit_static = {}
        seeds: Set[str] = set()
        for info in self.files.values():
            for fi in info.functions:
                deco = self._jit_decorator(fi)
                if deco is not None:
                    seeds.add(fi.qualname)
                    self._record_static_params(fi, deco)
                for node in fi.calls:
                    if _is_jit_call(node, info) and node.args:
                        target = _dotted(node.args[0])
                        if target is None:
                            continue
                        got = None
                        if target.startswith("self.") \
                                and fi.parent_class is not None:
                            m = target.split(".", 1)[1]
                            got = fi.parent_class.methods.get(m)
                        elif target in self.functions:
                            got = self.functions[target]
                        else:
                            tail = target.split(".")[-1]
                            got = self.files[fi.file].module_funcs.get(
                                tail)
                        if got is not None:
                            seeds.add(got.qualname)
                            self._record_static_params(got, node)
        self._jit_traced = self._closure(seeds)
        return self._jit_traced

    @staticmethod
    def _jit_decorator(fi: FuncInfo) -> Optional[ast.AST]:
        """The jit-ish decorator node of ``fi``, if any: ``@jax.jit``,
        ``@jax.jit(...)``, ``@partial(jax.jit, ...)``."""
        for deco in fi.node.decorator_list:
            if isinstance(deco, ast.Call):
                dotted = _dotted(deco.func) or ""
                args_jit = any((_dotted(a) or "").endswith("jit")
                               for a in deco.args)
                if (dotted.endswith("partial") and args_jit) \
                        or dotted.endswith(".jit") or dotted == "jit":
                    return deco
            else:
                dotted = _dotted(deco) or ""
                if dotted.endswith(".jit") or dotted == "jit":
                    return deco
        return None

    def _record_static_params(self, fi: FuncInfo,
                              jit_expr: ast.AST) -> None:
        """Param names of ``fi`` declared static at the jit site
        (``static_argnums``/``static_argnames``) — python control flow
        on THOSE is the documented contract, not a traced-branch."""
        static: Set[str] = set()
        if isinstance(jit_expr, ast.Call):
            for kw in jit_expr.keywords:
                if kw.arg == "static_argnums":
                    for pos in _positions_of(kw.value, None):
                        if 0 <= pos < len(fi.params):
                            static.add(fi.params[pos])
                elif kw.arg == "static_argnames" \
                        and isinstance(kw.value, (ast.Tuple, ast.List)):
                    for elt in kw.value.elts:
                        if isinstance(elt, ast.Constant) \
                                and isinstance(elt.value, str):
                            static.add(elt.value)
        if static:
            self._jit_static.setdefault(fi.qualname, set()).update(
                static)

    def jit_static_params(self, qualname: str) -> Set[str]:
        """Statically-declared param names of a direct jit target."""
        self.jit_traced()  # populates the map
        return self._jit_static.get(qualname, set())

    def may_touch_jax(self) -> Set[str]:
        """Functions that (transitively) reference jax/jnp — the
        dispatch-candidates a timing span cares about, as opposed to
        pure host helpers."""
        if self._may_jax is not None:
            return self._may_jax
        direct: Set[str] = set()
        for info in self.files.values():
            aliases = info.jax_aliases | info.jnp_aliases
            if not aliases:
                continue
            for fi in info.functions:
                if fi.names & aliases:
                    direct.add(fi.qualname)
                    continue
                cls = fi.parent_class
                if cls is not None and cls.jit_bindings:
                    for node in fi.calls:
                        dotted = _dotted(node.func) or ""
                        if dotted.startswith("self.") and \
                                dotted.split(".")[1] in cls.jit_bindings:
                            direct.add(fi.qualname)
                            break
        self._may_jax = self._propagate_up(direct)
        return self._may_jax

    def _propagate_up(self, direct: Set[str]) -> Set[str]:
        edges = self.edges()
        out = set(direct)
        changed = True
        while changed:
            changed = False
            for src, dsts in edges.items():
                if src not in out and dsts & out:
                    out.add(src)
                    changed = True
        return out

    def may_sync(self) -> Set[str]:
        """Functions that (transitively) contain an explicit host sync
        — the set the unblocked-timing rule consults before flagging a
        span whose sync is buried inside a callee.  Builtin casts
        (``int``/``float``/``bool``) count only when forcing jax math
        to host — a callee's config-scalar cast must not launder a
        caller's timed span transitively any more than it does
        in-span."""
        if self._may_sync is not None:
            return self._may_sync
        direct: Set[str] = set()
        for info in self.files.values():
            for fi in info.functions:
                for node in fi.calls:
                    dotted = _dotted(node.func) or ""
                    tail = dotted.split(".")[-1]
                    if tail not in config.SPAN_SYNC_CALLS:
                        continue
                    if tail in config.BUILTIN_SYNC_FUNCS and not any(
                            _contains_jax_math(a, info)
                            for a in node.args):
                        continue
                    direct.add(fi.qualname)
                    break
        self._may_sync = self._propagate_up(direct)
        return self._may_sync


# -- baseline ------------------------------------------------------------
class Baseline:
    """Grandfathered findings: key -> (count, justification)."""

    def __init__(self, entries: Optional[List[dict]] = None):
        self.entries: List[dict] = entries or []

    @staticmethod
    def entry_key(e: dict) -> str:
        return "%s|%s|%s|%s" % (e["rule"], e["file"], e.get("scope", ""),
                                e["detail"])

    def apply(self, findings: List[Finding]
              ) -> Tuple[List[Finding], int, List[dict]]:
        """(surviving findings, suppressed count, stale entries).

        An entry is stale when ANY of its count goes unused — a
        partially-fixed multi-count entry would otherwise keep surplus
        suppression budget that silently swallows the next regression
        of the same key, defeating the any-new-finding-fails
        contract."""
        budget: Dict[str, int] = {}
        for e in self.entries:
            budget[self.entry_key(e)] = budget.get(
                self.entry_key(e), 0) + int(e.get("count", 1))
        used: Dict[str, int] = {}
        out: List[Finding] = []
        suppressed = 0
        for f in findings:
            k = f.key()
            if used.get(k, 0) < budget.get(k, 0):
                used[k] = used.get(k, 0) + 1
                suppressed += 1
            else:
                out.append(f)
        stale = [e for e in self.entries
                 if used.get(self.entry_key(e), 0)
                 < budget[self.entry_key(e)]]
        return out, suppressed, stale

    @staticmethod
    def from_findings(findings: List[Finding],
                      old: Optional["Baseline"] = None) -> "Baseline":
        """Regenerate entries from current findings, keeping any
        existing justification whose key still matches."""
        just: Dict[str, str] = {}
        if old is not None:
            for e in old.entries:
                just[Baseline.entry_key(e)] = e.get("justification", "")
        grouped: Dict[str, dict] = {}
        for f in findings:
            k = f.key()
            if k in grouped:
                grouped[k]["count"] += 1
            else:
                grouped[k] = {
                    "rule": f.rule, "file": f.file, "scope": f.scope,
                    "detail": f.detail, "count": 1,
                    "justification": just.get(
                        k, "TODO: justify this finding or fix it"),
                }
        entries = sorted(grouped.values(),
                         key=lambda e: (e["rule"], e["file"], e["detail"]))
        return Baseline(entries)

    def dump(self, path: str) -> None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"version": 1, "entries": self.entries}, f,
                      indent=1, sort_keys=False)
            f.write("\n")


def load_baseline(path: str) -> Baseline:
    if not os.path.exists(path):
        return Baseline([])
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    return Baseline(list(data.get("entries", [])))


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def run_analysis(root: Optional[str] = None,
                 rules: Optional[Sequence] = None,
                 baseline: Optional[Baseline] = None,
                 baseline_path: Optional[str] = None) -> dict:
    """Walk ``root``, run every rule, apply the baseline.

    Returns a report dict: findings (non-baselined), suppressed count,
    stale baseline entries, per-rule counts, files scanned.  The CLI
    and the tier-1 test both consume this structure; ``--json`` prints
    it verbatim."""
    from .rules import ALL_RULES

    root = repo_root() if root is None else root
    index = RepoIndex(root)
    rules = ALL_RULES if rules is None else rules
    findings: List[Finding] = []
    for rule in rules:
        findings.extend(rule.run(index))
    findings.sort(key=lambda f: (f.file, f.line, f.rule))
    # one report per defect SITE across scopes: the per-function call
    # caches attribute a nested function's calls to every enclosing
    # scope, which would otherwise report (and count) the same node
    # once per scope.  Same-scope repeats survive — they are distinct
    # findings on one node (donation-reuse emits one per donated
    # position).  Stable sort keeps the outermost scope's finding —
    # the qualname a hot-path reader recognizes.
    site_scope: Dict[tuple, str] = {}
    deduped: List[Finding] = []
    for f in findings:
        site = (f.rule, f.file, f.line, f.detail)
        owner = site_scope.setdefault(site, f.scope)
        if owner != f.scope:
            continue
        deduped.append(f)
    findings = deduped
    if baseline is None:
        path = baseline_path if baseline_path is not None \
            else default_baseline_path()
        baseline = load_baseline(path)
    surviving, suppressed, stale = baseline.apply(findings)
    counts: Dict[str, int] = {}
    for f in findings:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    return {
        "root": root,
        "files_scanned": len(index.files),
        "parse_errors": index.errors,
        "total_findings": len(findings),
        "suppressed_by_baseline": suppressed,
        "stale_baseline_entries": stale,
        "counts_by_rule": counts,
        "findings": surviving,
        "all_findings": findings,
    }
