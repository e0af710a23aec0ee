"""Rule family 4: layering / encapsulation contracts.

Declarative replacements for the five regex lints that used to be
scattered across ``tests/`` (each with its own ``_offenders()`` copy),
plus a module-dependency contract the regexes never could express. Every
contract is data at the top of this file — adding one is adding a row.

Ported contracts (rule id — what it subsumes):

* ``layer-http``        — tests/test_observability_lint.py http.server
* ``layer-socket``      — tests/test_observability_lint.py raw sockets
* ``layer-wall-clock``  — tests/test_observability_lint.py slo/goodput
* ``private-replica``   — tests/test_observability_lint.py ReplicaHandle
* ``private-kvcache``   — tests/test_kvcache.py ``._free``/``._pages_for``
* ``layer-shard-map``   — tests/test_serving.py direct jax shard_map
* ``layer-atomic-write``— tests/test_resilience.py unstaged checkpoint IO
* ``layer-prom-format`` — tests/test_observability.py ad-hoc formatters

New:

* ``layer-deps`` — module-level import direction between subsystems
  (e.g. resilience must never import the serving stack — PR 2 moved
  ``Histogram`` into core precisely to keep that edge out).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .callgraph import dotted
from .engine import Finding, Project

PKG = "paddle_tpu/"
ALL_ROOTS = ("paddle_tpu/", "tests/", "benchmarks/")


def _module_level_stmts(tree: ast.Module) -> Iterable[ast.stmt]:
    """Top-level statements, descending through top-level try/if bodies
    (conditional imports) but never into defs/classes."""
    stack: List[ast.stmt] = list(tree.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.Try, ast.If)):
            for part in ("body", "orelse", "finalbody", "handlers"):
                for sub in getattr(node, part, []):
                    if isinstance(sub, ast.ExceptHandler):
                        stack.extend(sub.body)
                    elif isinstance(sub, ast.stmt):
                        stack.append(sub)


def _abs_import_targets(mod_rel: str, node: ast.stmt) -> List[str]:
    """Absolute module names a module-level import statement binds."""
    out: List[str] = []
    if isinstance(node, ast.Import):
        out.extend(a.name for a in node.names)
    elif isinstance(node, ast.ImportFrom):
        if node.level:
            pkg_parts = mod_rel[:-3].split("/")[:-1]
            base = pkg_parts[:len(pkg_parts) - (node.level - 1)]
            src = ".".join(base + ([node.module] if node.module else []))
        else:
            src = node.module or ""
        out.append(src)
    return out


class ImportConfinementRule:
    """Generic "module X may only be imported inside these files"."""

    def __init__(self, rule_id: str, modules: Sequence[str],
                 allowed: Sequence[str], protects: str, example: str,
                 hint: str):
        self.id = rule_id
        self.modules = tuple(modules)       # top-level module names
        self.allowed = set(allowed)         # repo-relative files
        self.protects = protects
        self.example = example
        self.hint = hint

    def run(self, project: Project) -> Iterable[Finding]:
        out: List[Finding] = []
        for mod in project.iter_modules((PKG,)):
            if mod.rel in self.allowed:
                continue
            for node in mod.nodes_of(ast.Import, ast.ImportFrom):
                targets = _abs_import_targets(mod.rel, node)
                for t in targets:
                    top = t.split(".")[0]
                    if top in self.modules:
                        out.append(Finding(
                            mod.rel, node.lineno, self.id,
                            f"import of {t!r} outside "
                            f"{sorted(self.allowed)}; {self.hint}",
                            symbol=f"import:{top}"))
        return out


class WallClockFreeRule:
    """``time.time`` never referenced in the deterministic SLO/goodput/
    sensor-plane math (injected step-driven clocks only)."""

    id = "layer-wall-clock"
    protects = ("observability/slo.py + goodput.py + the sensor plane "
                "(timeseries.py, anomaly.py, signals.py) never read the "
                "wall clock — breach/recover transitions, goodput "
                "splits and anomaly events stay byte-reproducible in "
                "chaos replays")
    example = "self._clock = time.time  # in slo.py"
    FILES = ("paddle_tpu/observability/slo.py",
             "paddle_tpu/observability/goodput.py",
             "paddle_tpu/observability/timeseries.py",
             "paddle_tpu/observability/anomaly.py",
             "paddle_tpu/observability/signals.py")

    def run(self, project: Project) -> Iterable[Finding]:
        out: List[Finding] = []
        for rel in self.FILES:
            mod = project.module(rel)
            if mod is None:
                out.append(Finding(rel, 1, self.id,
                                   "expected module missing",
                                   symbol="missing"))
                continue
            for node in mod.nodes_of(ast.Attribute):
                if dotted(node) == "time.time":
                    out.append(Finding(
                        rel, node.lineno, self.id,
                        "wall-clock reference time.time in "
                        "deterministic SLO/goodput math — use the "
                        "injected step-driven clock",
                        symbol="time.time"))
        return out


class PrivateAccessRule:
    """Attribute access to named privates confined to owner packages."""

    def __init__(self, rule_id: str, attrs: Sequence[str],
                 allowed_prefixes: Sequence[str], protects: str,
                 example: str, hint: str,
                 roots: Sequence[str] = ALL_ROOTS):
        self.id = rule_id
        self.attrs = set(attrs)
        self.allowed_prefixes = tuple(allowed_prefixes)
        self.protects = protects
        self.example = example
        self.hint = hint
        self.roots = tuple(roots)

    def run(self, project: Project) -> Iterable[Finding]:
        out: List[Finding] = []
        for mod in project.iter_modules(self.roots):
            if mod.rel.startswith(self.allowed_prefixes):
                continue
            for node in mod.nodes_of(ast.Attribute):
                if node.attr in self.attrs \
                        and not (isinstance(node.value, ast.Name)
                                 and node.value.id in ("self", "cls")):
                    # a class touching its OWN private of the same name
                    # is not an encapsulation breach
                    out.append(Finding(
                        mod.rel, node.lineno, self.id,
                        f"access to private '.{node.attr}' outside "
                        f"{list(self.allowed_prefixes)}; {self.hint}",
                        symbol=f"attr:{node.attr}"))
        return out


class ShardMapRule:
    id = "layer-shard-map"
    protects = ("core/compat.py stays the single version-tolerant "
                "shard_map source (the seed broke on a bare jax import "
                "path; the resolver is the fix)")
    example = "from jax.experimental.shard_map import shard_map"
    ALLOWED = "paddle_tpu/core/compat.py"

    def run(self, project: Project) -> Iterable[Finding]:
        out: List[Finding] = []
        for mod in project.iter_modules(ALL_ROOTS):
            if mod.rel == self.ALLOWED:
                continue
            for node in mod.nodes_of(ast.ImportFrom, ast.Attribute):
                bad: Optional[str] = None
                if isinstance(node, ast.ImportFrom) and not node.level \
                        and (node.module or "").startswith("jax") \
                        and any(a.name == "shard_map"
                                for a in node.names):
                    bad = f"from {node.module} import shard_map"
                elif isinstance(node, ast.Attribute):
                    d = dotted(node)
                    if d in ("jax.shard_map",
                             "jax.experimental.shard_map.shard_map"):
                        bad = d
                if bad is not None:
                    out.append(Finding(
                        mod.rel, node.lineno, self.id,
                        f"direct jax shard_map use ({bad}); import it "
                        "from paddle_tpu.core.compat instead",
                        symbol="shard_map"))
        return out


class AtomicWriteRule:
    id = "layer-atomic-write"
    protects = ("every write under distributed/checkpoint/ goes through "
                "utils.atomic_write (stage + fsync + CRC32 + rename) — "
                "a crash can never leave a torn checkpoint file")
    example = 'open(path, "wb")  # in distributed/checkpoint/metadata.py'
    SCOPE = "paddle_tpu/distributed/checkpoint/"
    ALLOWED = "paddle_tpu/distributed/checkpoint/utils.py"
    _WRITE_MODE = re.compile(r"^(?:[wax]b?\+?|r\+b?)$")

    def run(self, project: Project) -> Iterable[Finding]:
        out: List[Finding] = []
        for mod in project.iter_modules((self.SCOPE,)):
            if mod.rel == self.ALLOWED:
                continue
            for node in mod.nodes_of(ast.Call):
                # bare open() AND attribute writers (gzip.open, io.open,
                # os.fdopen) — the regex this rule replaced caught all of
                # them, and a torn gzip'd checkpoint is just as torn
                is_open = (isinstance(node.func, ast.Name)
                           and node.func.id == "open") or \
                          (isinstance(node.func, ast.Attribute)
                           and node.func.attr in ("open", "fdopen"))
                if not is_open:
                    continue
                mode = None
                if len(node.args) >= 2:
                    mode = node.args[1]
                for kw in node.keywords:
                    if kw.arg == "mode":
                        mode = kw.value
                if (isinstance(mode, ast.Constant)
                        and isinstance(mode.value, str)
                        and self._WRITE_MODE.match(mode.value)):
                    out.append(Finding(
                        mod.rel, node.lineno, self.id,
                        f"unstaged write-mode open(..., "
                        f"{mode.value!r}) in the checkpoint package; "
                        "use utils.atomic_write",
                        symbol=f"open:{mode.value}"))
        return out


class PromFormatRule:
    id = "layer-prom-format"
    protects = ("Prometheus exposition syntax is assembled ONLY in "
                "observability/format.py — one formatter means one "
                "valid /metrics document")
    example = "lines.append(f'{name}_bucket{{le=\"{b}\"}} {n}')"
    ALLOWED = ("paddle_tpu/observability/format.py",
               "paddle_tpu/analysis/")       # the contract's own table
    _TOKENS = ('_bucket{', '{le="', "# TYPE ", 'quantile="')

    def run(self, project: Project) -> Iterable[Finding]:
        out: List[Finding] = []
        for mod in project.iter_modules((PKG,)):
            if mod.rel.startswith(self.ALLOWED) \
                    or mod.rel in self.ALLOWED:
                continue
            for node in mod.nodes_of(ast.Constant):
                if not isinstance(node.value, str):
                    continue
                hit = next((t for t in self._TOKENS
                            if t in node.value), None)
                if hit is not None:
                    out.append(Finding(
                        mod.rel, node.lineno, self.id,
                        f"ad-hoc Prometheus formatting ({hit!r} in a "
                        "string literal); assemble exposition lines via "
                        "paddle_tpu.observability.format",
                        symbol=f"token:{hit}"))
        return out


class LayerDepsRule:
    """Module-level import direction between subsystems. Lazy (function
    -scope) imports are allowed — they are the sanctioned way to break
    cycles — so only top-level statements are checked."""

    id = "layer-deps"
    protects = ("subsystem import edges point downward only: core/"
                "observability are base layers; kvcache sits under the "
                "engine; resilience never pulls in the serving stack")
    example = "from ..serving.metrics import ServingMetrics  # in resilience/"

    #: package prefix -> forbidden paddle_tpu sub-packages
    CONTRACTS: Dict[str, Tuple[str, ...]] = {
        "paddle_tpu/core/": ("serving", "resilience", "inference",
                             "kvcache", "models"),
        "paddle_tpu/observability/": ("serving", "resilience",
                                      "inference", "kvcache", "models",
                                      "distributed"),
        "paddle_tpu/kvcache/": ("serving", "resilience", "inference",
                                "models", "distributed"),
        "paddle_tpu/resilience/": ("serving",),
        "paddle_tpu/analysis/": ("serving", "resilience", "inference",
                                 "kvcache", "models", "distributed",
                                 "observability", "core", "ops"),
    }

    #: file -> sub-packages it may not import AT ANY SCOPE (lazy
    #: function-scope imports included). The memory ledger is FED by the
    #: serving stack and never pulls from it — even a lazy import would
    #: let accounting reach back into the layers it measures.
    STRICT_CONTRACTS: Dict[str, Tuple[str, ...]] = {
        "paddle_tpu/observability/memory.py": (
            "serving", "inference", "kvcache", "models", "resilience",
            "distributed"),
        # the fusion pass consumes SYMBOLS (the hot-chain artifact +
        # ProjectIndex), never the serving stack — its one region
        # installs on a duck-typed optimizer
        "paddle_tpu/jit/fusion.py": (
            "serving", "inference", "kvcache", "models"),
    }

    def run(self, project: Project) -> Iterable[Finding]:
        out: List[Finding] = []
        for mod in project.iter_modules((PKG,)):
            strict = self.STRICT_CONTRACTS.get(mod.rel)
            if strict is not None:
                for node in mod.nodes_of(ast.Import, ast.ImportFrom):
                    for t in _abs_import_targets(mod.rel, node):
                        parts = t.split(".")
                        if parts[0] != "paddle_tpu" or len(parts) < 2:
                            continue
                        if parts[1] in strict:
                            out.append(Finding(
                                mod.rel, node.lineno, self.id,
                                f"import of paddle_tpu.{parts[1]} from "
                                f"{mod.rel} violates its STRICT layering "
                                "contract (the ledger is fed, never "
                                "pulls — lazy imports are banned here "
                                "too)", symbol=f"strict:{parts[1]}"))
            forbidden: Optional[Tuple[str, ...]] = None
            for prefix, banned in self.CONTRACTS.items():
                if mod.rel.startswith(prefix):
                    forbidden = banned
                    break
            if forbidden is None:
                continue
            for node in _module_level_stmts(mod.tree):
                if not isinstance(node, (ast.Import, ast.ImportFrom)):
                    continue
                for t in _abs_import_targets(mod.rel, node):
                    parts = t.split(".")
                    if parts[0] != "paddle_tpu" or len(parts) < 2:
                        continue
                    if parts[1] in forbidden:
                        out.append(Finding(
                            mod.rel, node.lineno, self.id,
                            f"module-level import of paddle_tpu."
                            f"{parts[1]} from {mod.rel} violates the "
                            "layering contract (lazy function-scope "
                            "imports are the sanctioned escape hatch)",
                            symbol=f"dep:{parts[1]}"))
        return out


LAYERING_RULES = (
    ImportConfinementRule(
        "layer-http", ("http",),
        ("paddle_tpu/observability/server.py",),
        protects=("http.server lives ONLY in observability/server.py — "
                  "the DiagServer is the ONE debug endpoint"),
        example="import http.server  # in serving/router.py",
        hint=("register a /statusz provider on the DiagServer instead "
              "of opening another listener")),
    ImportConfinementRule(
        "layer-socket", ("socket",),
        ("paddle_tpu/observability/server.py",
         "paddle_tpu/distributed/launch/context.py",
         "paddle_tpu/distributed/launch/master.py",
         "paddle_tpu/distributed/store.py"),
        protects=("raw sockets only in the DiagServer and the "
                  "grandfathered distributed rendezvous modules"),
        example="import socket  # in observability/flight.py",
        hint=("new listeners belong in observability/server.py or the "
              "sanctioned rendezvous modules")),
    WallClockFreeRule(),
    PrivateAccessRule(
        "private-replica", ("_scheduler", "_fault"),
        ("paddle_tpu/serving/",),
        protects=("nothing outside serving/ reaches into ReplicaHandle "
                  "privates — the breaker/drain state machine owns them"),
        example="router.replicas[0]._scheduler.step(params)  # in a bench",
        hint=("route through the public replica surface (submit/cancel/"
              "step/statusz/health) or the FleetRouter")),
    PrivateAccessRule(
        "private-kvcache", ("_free", "_pages_for"),
        ("paddle_tpu/ops/", "paddle_tpu/kvcache/"),
        protects=("pool internals stay behind the ops/kvcache boundary "
                  "— refcount/cached states make direct free-list "
                  "surgery unsound"),
        example="mgr._free.append(page)  # in serving/scheduler.py",
        hint="use pages_for()/usable_pages or paddle_tpu.kvcache"),
    PrivateAccessRule(
        "private-engine", ("_queue", "_slot_rid", "_pend", "_live"),
        ("paddle_tpu/inference/",),
        protects=("runtime code never reaches into the decoding "
                  "engine's slot/FIFO internals — admission math goes "
                  "through num_queued/num_free_slots (the engine's own "
                  "white-box tests are exempt)"),
        example="self.engine._queue  # in serving/scheduler.py",
        hint="use engine.num_queued / num_free_slots / submit()",
        roots=("paddle_tpu/", "benchmarks/")),
    ShardMapRule(),
    AtomicWriteRule(),
    PromFormatRule(),
    LayerDepsRule(),
)
