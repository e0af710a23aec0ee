"""Project-wide symbol index + traced-code call graph.

Built once per :class:`~paddle_tpu.analysis.engine.Project` and shared by
every rule that needs more than single-file pattern matching. Three
layers:

* **imports** — per module: alias -> absolute module name (``import x.y
  as z``) and name -> (module, original) for ``from x import y``;
  relative imports are resolved against the importing module's package.
* **definitions** — every function/method with its scope-qualified name
  and owning class; every class with its method table.
* **traced reachability** — the call graph walked from *jit roots*:
  functions handed to ``jax.jit`` / ``pl.pallas_call`` (positionally or
  via ``functools.partial(jax.jit, ...)`` decorators), ``@jit``-style
  decorated functions, lambdas jitted inline, and each model module's
  serving step (``_MODEL_STEP``). Resolution is
  deliberately conservative (same-scope names, same-class ``self.``
  methods, explicitly imported module attributes) so the purity rules
  over-approximate reachable code only through edges that are certainly
  real — a missing edge costs recall, never a false positive.

This is the substrate ROADMAP item 2's telemetry-guided fusion pass
needs: a static view of which Python code runs under trace.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .engine import Project, SourceModule

#: call targets that mark their function argument as traced
_JIT_NAMES = {"jit", "pallas_call"}

#: traced roots no call edge reaches: the serving engine jits the step of
#: whichever module the model's config names
#: (``inference/decoding._serving_module``), a lookup by string the
#: conservative resolution cannot follow
_MODEL_STEP = ("paddle_tpu/models/", "ragged_step")


def dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def is_jit_expr(node: ast.AST) -> bool:
    """True when ``node`` names a tracing entry point (``jax.jit``,
    ``jit``, ``pl.pallas_call``, ``pallas_call``)."""
    d = dotted(node)
    return d is not None and d.split(".")[-1] in _JIT_NAMES


class FunctionInfo:
    """One def/lambda with enough context to resolve its calls."""

    __slots__ = ("module", "node", "qualname", "class_name", "scope")

    def __init__(self, module: SourceModule, node: ast.AST, qualname: str,
                 class_name: Optional[str],
                 scope: Dict[str, "FunctionInfo"]):
        self.module = module
        self.node = node
        self.qualname = qualname        # e.g. "Engine._build.<locals>.run"
        self.class_name = class_name
        #: names visible where this function is DEFINED (enclosing defs
        #: + module top level) — used to resolve bare-name calls
        self.scope = scope

    @property
    def name(self) -> str:
        return getattr(self.node, "name", "<lambda>")

    def own_nodes(self) -> Iterable[ast.AST]:
        """Walk this function's body WITHOUT descending into nested
        function/class definitions (those are separate graph nodes)."""
        body = (self.node.body if isinstance(self.node.body, list)
                else [self.node.body])
        stack: List[ast.AST] = list(body)
        while stack:
            n = stack.pop()
            yield n
            for child in ast.iter_child_nodes(n):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef,
                                      ast.Lambda, ast.ClassDef)):
                    continue
                stack.append(child)

    def param_names(self) -> Set[str]:
        a = self.node.args
        names = [p.arg for p in getattr(a, "posonlyargs", []) + a.args
                 + a.kwonlyargs]
        if a.vararg:
            names.append(a.vararg.arg)
        if a.kwarg:
            names.append(a.kwarg.arg)
        return set(names)


class ModuleInfo:
    """Per-module symbol tables."""

    def __init__(self, module: SourceModule, modname: str):
        self.module = module
        self.modname = modname              # "paddle_tpu.serving.scheduler"
        self.import_aliases: Dict[str, str] = {}     # alias -> module
        self.from_imports: Dict[str, Tuple[str, str]] = {}
        self.functions: List[FunctionInfo] = []      # every def, any depth
        self.top_level: Dict[str, FunctionInfo] = {}
        self.classes: Dict[str, Dict[str, FunctionInfo]] = {}
        self.lambdas: Dict[int, FunctionInfo] = {}   # id(node) -> info


def _modname(rel: str) -> str:
    return rel[:-3].replace("/", ".") if rel.endswith(".py") else \
        rel.replace("/", ".")


class ProjectIndex:
    def __init__(self, project: Project):
        self.project = project
        self.mods: Dict[str, ModuleInfo] = {}        # modname -> info
        self.by_rel: Dict[str, ModuleInfo] = {}
        for m in project.modules:
            mi = ModuleInfo(m, _modname(m.rel))
            self._index_imports(mi)
            self._index_defs(mi)
            self.mods[mi.modname] = mi
            self.by_rel[m.rel] = mi
        self._traced: Optional[Set[int]] = None      # id(FunctionInfo.node)
        self._traced_fns: List[FunctionInfo] = []
        self._roots: List[FunctionInfo] = []

    # -- construction -------------------------------------------------------

    def _index_imports(self, mi: ModuleInfo) -> None:
        pkg_parts = mi.modname.split(".")[:-1]
        for node in mi.module.nodes_of(ast.Import, ast.ImportFrom):
            if isinstance(node, ast.Import):
                for a in node.names:
                    mi.import_aliases[a.asname or a.name.split(".")[0]] = \
                        a.name if a.asname else a.name.split(".")[0]
                    if a.asname:
                        mi.import_aliases[a.asname] = a.name
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    base = pkg_parts[:len(pkg_parts) - (node.level - 1)]
                    src = ".".join(base + ([node.module]
                                           if node.module else []))
                else:
                    src = node.module or ""
                for a in node.names:
                    mi.from_imports[a.asname or a.name] = (src, a.name)

    @staticmethod
    def _level_stmts(body) -> List[ast.stmt]:
        """Statements at one scope level, descending through compound
        statements (if/try/with/for/while) but not into defs/classes —
        a def inside an ``if`` still binds in the enclosing scope."""
        out: List[ast.stmt] = []
        stack = list(body)
        while stack:
            node = stack.pop(0)
            out.append(node)
            if isinstance(node, (ast.If, ast.For, ast.While, ast.With,
                                 ast.Try)):
                for field in ("body", "orelse", "finalbody"):
                    stack.extend(getattr(node, field, []))
                for h in getattr(node, "handlers", []):
                    stack.extend(h.body)
        return out

    def _index_defs(self, mi: ModuleInfo) -> None:
        def visit(node, qual: List[str], class_name: Optional[str],
                  scope: Dict[str, FunctionInfo]):
            # two passes per level so sibling defs see each other
            local: Dict[str, FunctionInfo] = {}
            body = self._level_stmts(node.body
                                     if hasattr(node, "body") else [])
            infos = []
            for child in body:
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    q = ".".join(qual + [child.name]) if qual else child.name
                    fi = FunctionInfo(mi.module, child, q, class_name,
                                      scope)  # placeholder; fixed below
                    local[child.name] = fi
                    infos.append((child, fi))
            merged = {**scope, **local}
            for child, fi in infos:
                fi.scope = merged
                mi.functions.append(fi)
                if not qual:
                    mi.top_level[child.name] = fi
                if class_name is not None and len(qual) == 1:
                    mi.classes.setdefault(class_name, {})[child.name] = fi
                visit(child, qual + [child.name, "<locals>"], None, merged)
            for child in body:
                if isinstance(child, ast.ClassDef):
                    visit(child, qual + [child.name], child.name, merged)

        visit(mi.module.tree, [], None, {})
        # lambdas are indexed LAZILY (see _lambda_info): walking every
        # function subtree up front for them blew the tier-1 speed
        # budget, and only jitted lambdas are ever looked up

    def _lambda_info(self, mi: ModuleInfo, node: ast.Lambda
                     ) -> FunctionInfo:
        li = mi.lambdas.get(id(node))
        if li is None:
            owner = self._enclosing(mi, node)
            li = FunctionInfo(
                mi.module, node,
                (owner.qualname + ".<lambda>") if owner else "<lambda>",
                owner.class_name if owner else None,
                owner.scope if owner else mi.top_level)
            mi.functions.append(li)
            mi.lambdas[id(node)] = li
        return li

    # -- traced reachability ------------------------------------------------

    def traced_functions(self) -> List[FunctionInfo]:
        """Every function reachable from a jit/pallas root."""
        if self._traced is None:
            self._compute_traced()
        return self._traced_fns

    def traced_roots(self) -> List[FunctionInfo]:
        if self._traced is None:
            self._compute_traced()
        return self._roots

    def _compute_traced(self) -> None:
        roots: List[FunctionInfo] = []
        for mi in self.mods.values():
            if not mi.module.rel.startswith("paddle_tpu/"):
                continue
            for node in mi.module.nodes_of(ast.Call, ast.FunctionDef,
                                            ast.AsyncFunctionDef):
                # jax.jit(fn, ...) / pl.pallas_call(kernel, ...)
                if isinstance(node, ast.Call) and is_jit_expr(node.func):
                    for arg in node.args[:1]:
                        fi = self._fn_for_arg(mi, arg, node)
                        if fi is not None:
                            roots.append(fi)
                # decorators: @jax.jit / @jit / @partial(jax.jit, ...)
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    for dec in node.decorator_list:
                        if is_jit_expr(dec) or (
                                isinstance(dec, ast.Call)
                                and (is_jit_expr(dec.func)
                                     or any(is_jit_expr(a)
                                            for a in dec.args))):
                            fi = self._info_for_def(mi, node)
                            if fi is not None:
                                roots.append(fi)
            if mi.module.rel.startswith(_MODEL_STEP[0]):
                step = mi.top_level.get(_MODEL_STEP[1])
                if step is not None:
                    roots.append(step)
        self._roots = roots
        seen: Set[int] = set()
        queue = list(roots)
        ordered: List[FunctionInfo] = []
        while queue:
            fi = queue.pop()
            if id(fi.node) in seen:
                continue
            seen.add(id(fi.node))
            ordered.append(fi)
            queue.extend(self._callees(fi))
        self._traced = seen
        self._traced_fns = ordered

    def _fn_for_arg(self, mi: ModuleInfo, arg: ast.AST,
                    call: ast.Call) -> Optional[FunctionInfo]:
        if isinstance(arg, ast.Lambda):
            return self._lambda_info(mi, arg)
        if isinstance(arg, ast.Call):
            # transparent wrappers: the wrapped function still traces
            # (partial statics, shard_map bodies, vmap/grad/remat, the
            # compat shim's resolved shard_map)
            d = dotted(arg.func)
            wrappers = {"partial", "shard_map", "vmap", "grad",
                        "value_and_grad", "remat", "checkpoint"}
            if d is not None and d.split(".")[-1] in wrappers and arg.args:
                return self._fn_for_arg(mi, arg.args[0], call)
            return None
        if isinstance(arg, ast.Name):
            # resolve in the scope of the function containing the call:
            # its OWN local defs first (jax.jit(run, ...) at the end of a
            # builder method), then enclosing scopes, then module level
            owner = self._enclosing(mi, call)
            if owner is not None:
                child_qual = f"{owner.qualname}.<locals>.{arg.id}"
                for fi in mi.functions:
                    if fi.qualname == child_qual:
                        return fi
            scope = owner.scope if owner is not None else mi.top_level
            target = scope.get(arg.id) or mi.top_level.get(arg.id)
            if target is not None:
                return target
            imp = mi.from_imports.get(arg.id)
            if imp is not None:
                other = self.mods.get(imp[0])
                if other is not None:
                    return other.top_level.get(imp[1])
            # local rebinding: kernel = functools.partial(_kernel, ...)
            if owner is not None:
                for node in ast.walk(owner.node):
                    if (isinstance(node, ast.Assign)
                            and isinstance(node.value, ast.Call)
                            and any(isinstance(t, ast.Name)
                                    and t.id == arg.id
                                    for t in node.targets)):
                        return self._fn_for_arg(mi, node.value, call)
        return None

    def _info_for_def(self, mi: ModuleInfo, node) -> Optional[FunctionInfo]:
        for fi in mi.functions:
            if fi.node is node:
                return fi
        return None

    def _enclosing(self, mi: ModuleInfo, node: ast.AST
                   ) -> Optional[FunctionInfo]:
        """The innermost FunctionInfo whose body contains ``node`` (by
        line containment — cheap and adequate for call-site scoping)."""
        best: Optional[FunctionInfo] = None
        ln = getattr(node, "lineno", None)
        if ln is None:
            return None
        for fi in mi.functions:
            n = fi.node
            end = getattr(n, "end_lineno", None)
            if n.lineno <= ln and end is not None and ln <= end:
                if best is None or n.lineno >= best.node.lineno:
                    best = fi
        return best

    def resolve_call(self, fi: FunctionInfo,
                     node: ast.Call) -> Optional[FunctionInfo]:
        """Conservative single-call resolution — THE per-node convention
        shared by the call-graph edges and the dataflow rules (same-scope
        locals, self methods, from-imports, module-attribute calls
        through import aliases); ``None`` at resolution gaps."""
        mi = self.by_rel.get(fi.module.rel)
        if mi is None:
            return None
        f = node.func
        if isinstance(f, ast.Name):
            child_qual = f"{fi.qualname}.<locals>.{f.id}"
            child = next((c for c in mi.functions
                          if c.qualname == child_qual), None)
            if child is not None:
                return child
            target = fi.scope.get(f.id) or mi.top_level.get(f.id)
            if target is not None:
                return target
            imp = mi.from_imports.get(f.id)
            if imp is not None:
                other = self.mods.get(imp[0])
                if other is not None:
                    return other.top_level.get(imp[1])
            return None
        if isinstance(f, ast.Attribute):
            d = dotted(f)
            if d is None:
                return None
            parts = d.split(".")
            if parts[0] == "self" and len(parts) == 2 \
                    and fi.class_name is not None:
                return mi.classes.get(fi.class_name, {}).get(parts[1])
            # module-attribute call through an import alias
            if len(parts) == 2:
                target_mod = None
                if parts[0] in mi.import_aliases:
                    target_mod = self.mods.get(mi.import_aliases[parts[0]])
                elif parts[0] in mi.from_imports:
                    src, orig = mi.from_imports[parts[0]]
                    target_mod = self.mods.get(f"{src}.{orig}")
                if target_mod is not None:
                    return target_mod.top_level.get(parts[-1])
        return None

    def _callees(self, fi: FunctionInfo) -> List[FunctionInfo]:
        out: List[FunctionInfo] = []
        for node in fi.own_nodes():
            if isinstance(node, ast.Call):
                t = self.resolve_call(fi, node)
                if t is not None:
                    out.append(t)
        return out
