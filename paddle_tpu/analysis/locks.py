"""Rule family 2: lock discipline in ``serving/`` and ``observability/``.

These are the only packages where scheduler watchdog threads, consumer
threads and the DiagServer scrape thread genuinely run concurrently.
Discipline is inferred per class, not configured:

* a class that assigns ``self.<x> = threading.Lock()/RLock()/Condition()``
  in ``__init__`` is *lock-owning*;
* an attribute is *lock-guarded* when any method touches it inside a
  ``with self.<lock>:`` block;
* ``lock-unguarded-write`` flags mutations of guarded attributes outside
  the lock (``__init__`` excluded — the object is not shared yet; methods
  whose name ends in ``_locked`` excluded — the repo-wide convention for
  "caller holds the lock", see ``TokenStream._close_locked``);
* ``lock-blocking-call`` flags blocking operations (sleep, thread joins,
  future ``.result()``, queue ``.get()``) while the lock is held —
  including inside ``*_locked`` helpers.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .callgraph import dotted
from .engine import Finding, Project, flat_walk

SCOPE_PREFIXES = ("paddle_tpu/serving/", "paddle_tpu/observability/")

_LOCK_FACTORIES = {"Lock", "RLock", "Condition"}

#: method calls that mutate their receiver in place
_MUTATORS = {"append", "appendleft", "extend", "insert", "pop", "popleft",
             "remove", "clear", "update", "add", "discard", "setdefault",
             "rotate", "sort", "reverse"}

_BLOCKING_SLEEP = {"time.sleep", "sleep", "self._sleep"}
_BLOCKING_ATTRS = {"join", "result", "acquire"}


def _lock_attrs(cls: ast.ClassDef) -> Set[str]:
    """Names of threading.Lock/RLock/Condition attributes assigned in
    ``__init__``."""
    out: Set[str] = set()
    for item in cls.body:
        if isinstance(item, ast.FunctionDef) and item.name == "__init__":
            for node in flat_walk(item):
                if not isinstance(node, ast.Assign):
                    continue
                if not (isinstance(node.value, ast.Call)):
                    continue
                d = dotted(node.value.func)
                if d is None or d.split(".")[-1] not in _LOCK_FACTORIES:
                    continue
                for t in node.targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        out.add(t.attr)
    return out


def _with_lock_blocks(fn: ast.FunctionDef, locks: Set[str]
                      ) -> List[Tuple[ast.With, str]]:
    out = []
    for node in flat_walk(fn):
        if isinstance(node, ast.With):
            for item in node.items:
                ce = item.context_expr
                if (isinstance(ce, ast.Attribute)
                        and isinstance(ce.value, ast.Name)
                        and ce.value.id == "self" and ce.attr in locks):
                    out.append((node, ce.attr))
    return out


def _self_attr(node: ast.AST) -> Optional[str]:
    if (isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _span(node: ast.AST) -> Tuple[int, int]:
    return (node.lineno, getattr(node, "end_lineno", node.lineno))


def _inside_any(node: ast.AST, blocks: List[Tuple[ast.With, str]]) -> bool:
    ln = getattr(node, "lineno", None)
    if ln is None:
        return False
    for blk, _ in blocks:
        lo, hi = _span(blk)
        if lo <= ln <= hi:
            return True
    return False


class _ClassScan:
    """Shared per-class facts for both lock rules."""

    def __init__(self, mod_rel: str, cls: ast.ClassDef):
        self.rel = mod_rel
        self.cls = cls
        self.locks = _lock_attrs(cls)
        self.methods = [n for n in cls.body
                        if isinstance(n, ast.FunctionDef)]
        # attribute names touched (read OR written) under any lock block
        self.guarded: Set[str] = set()
        # keyed by node identity, NOT name: property getter/setter pairs
        # and if/else redefinitions share a name but not lock regions
        self._blocks: Dict[int, List[Tuple[ast.With, str]]] = {}
        for m in self.methods:
            blocks = _with_lock_blocks(m, self.locks)
            self._blocks[id(m)] = blocks
            for blk, _ in blocks:
                for sub in flat_walk(blk):
                    attr = _self_attr(sub)
                    if attr is not None and attr not in self.locks:
                        self.guarded.add(attr)

    def blocks(self, m: ast.FunctionDef) -> List[Tuple[ast.With, str]]:
        return self._blocks.get(id(m), [])


def scan_lock_classes(project: Project) -> List[Tuple[str, _ClassScan]]:
    """The lock-owning classes in scope, each scanned once
    (``Project.lock_classes`` keeps the list for both lock rules)."""
    scans = []
    for mod in project.iter_modules(SCOPE_PREFIXES):
        for node in mod.tree.body:
            if isinstance(node, ast.ClassDef):
                scan = _ClassScan(mod.rel, node)
                if scan.locks:
                    scans.append((mod.rel, scan))
    return scans


class LockUnguardedWriteRule:
    id = "lock-unguarded-write"
    protects = ("every mutation of a lock-guarded attribute of a "
                "lock-owning class in serving//observability/ happens "
                "under 'with self._lock' (or in a *_locked helper)")
    example = ("class C:  # has self._lock and reads self._buf under it\n"
               "    def add(self, x): self._buf.append(x)  # no lock")

    def run(self, project: Project) -> Iterable[Finding]:
        out: List[Finding] = []
        for rel, scan in project.lock_classes:
            for m in scan.methods:
                if m.name in ("__init__", "__new__", "__del__") \
                        or m.name.endswith("_locked"):
                    continue
                blocks = scan.blocks(m)
                for node in flat_walk(m):
                    attr = self._mutated_attr(node)
                    if attr is None or attr not in scan.guarded:
                        continue
                    if _inside_any(node, blocks):
                        continue
                    out.append(Finding(
                        rel, node.lineno, self.id,
                        f"{scan.cls.name}.{m.name} mutates lock-guarded "
                        f"'self.{attr}' outside 'with self."
                        f"{sorted(scan.locks)[0]}' — races every reader "
                        "that takes the lock",
                        symbol=f"{scan.cls.name}.{m.name}:{attr}"))
        return out

    @staticmethod
    def _mutated_attr(node: ast.AST) -> Optional[str]:
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                attr = _self_attr(t)
                if attr is not None:
                    return attr
                if isinstance(t, ast.Subscript):
                    attr = _self_attr(t.value)
                    if attr is not None:
                        return attr
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                attr = _self_attr(t)
                if attr is not None:
                    return attr
                if isinstance(t, ast.Subscript):
                    attr = _self_attr(t.value)
                    if attr is not None:
                        return attr
        elif (isinstance(node, ast.Call)
              and isinstance(node.func, ast.Attribute)
              and node.func.attr in _MUTATORS):
            return _self_attr(node.func.value)
        return None


class LockBlockingCallRule:
    id = "lock-blocking-call"
    protects = ("no blocking call (sleep, Thread.join, Future.result, "
                "queue get, second acquire) while holding a serving/"
                "observability lock — stalls every thread contending it")
    example = ("with self._lock:\n"
               "    time.sleep(backoff)  # scrape thread now stalls too")

    def run(self, project: Project) -> Iterable[Finding]:
        out: List[Finding] = []
        for rel, scan in project.lock_classes:
            for m in scan.methods:
                if m.name.endswith("_locked"):
                    # caller holds the lock: the whole body is a region
                    # (which covers any with-lock blocks inside it)
                    regions = [m]
                else:
                    regions = [blk for blk, _ in scan.blocks(m)]
                seen: Set[int] = set()      # nested with-lock blocks
                for region in regions:      # must not double-report
                    for node in flat_walk(region):
                        if id(node) in seen:
                            continue
                        seen.add(id(node))
                        tok = self._blocking_token(node, scan.locks)
                        if tok is None:
                            continue
                        out.append(Finding(
                            rel, node.lineno, self.id,
                            f"blocking call {tok} while "
                            f"{scan.cls.name}.{m.name} holds the lock "
                            "— every contending thread stalls behind it",
                            symbol=f"{scan.cls.name}.{m.name}:{tok}"))
        return out

    @staticmethod
    def _blocking_token(node: ast.AST, locks: Set[str]) -> Optional[str]:
        if not isinstance(node, ast.Call):
            return None
        d = dotted(node.func)
        if d in _BLOCKING_SLEEP:
            return f"{d}()"
        if isinstance(node.func, ast.Attribute):
            recv = node.func.value
            # Condition.wait on the lock itself is the sanctioned way to
            # block; a *second* acquire of a self-lock is a deadlock
            if node.func.attr == "acquire":
                attr = _self_attr(recv)
                return (f"self.{attr}.acquire()"
                        if attr in locks else None)
            if node.func.attr == "join":
                # str.join is everywhere (",".join, os.path.join) — only
                # receivers that look like threads/workers block
                rname = (dotted(recv) or "").lower()
                if any(t in rname for t in ("thread", "worker", "proc")):
                    return f"{d}()"
                return None
            if node.func.attr == "result":
                return f"{d or node.func.attr}()"
            if node.func.attr == "get":
                rname = (dotted(recv) or "").lower()
                if "queue" in rname:
                    return f"{d}()"
        return None


LOCK_RULES = (LockUnguardedWriteRule(), LockBlockingCallRule())
