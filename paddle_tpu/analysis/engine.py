"""tpu-lint core: parse-once AST engine, findings, suppressions, baseline.

The engine is deliberately tiny:

* every file under the configured roots is read and ``ast.parse``-d
  exactly ONCE (:class:`SourceModule`); rules share the trees (and the
  lazily built project-wide index, see :mod:`.callgraph`) instead of
  re-walking the filesystem per rule the way the five retired regex
  lints did;
* a rule is any object with an ``id``, a one-line ``protects`` string, an
  ``example`` violation (both feed the README catalog and the CLI) and a
  ``run(project) -> Iterable[Finding]``;
* ``# tpu-lint: disable=<rule>[,<rule>...]`` on the finding's line (or on
  a comment-only line directly above it) silences exactly those rules on
  exactly that line;
* a checked-in baseline grandfathers known findings by *fingerprint*
  (line-number-free, so unrelated edits don't invalidate it); a baseline
  entry whose finding disappeared is STALE and fails the run, keeping the
  file honest.

Nothing here imports jax/numpy — the analyzer stays importable and fast
in any environment that can parse the source.
"""

from __future__ import annotations

import ast
import json
import re
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

_SUPPRESS_RE = re.compile(r"#\s*tpu-lint:\s*disable=([A-Za-z0-9_\-, ]+)")


def flat_walk(tree: ast.AST) -> List[ast.AST]:
    """Every node under ``tree`` in ``ast.walk``'s order (breadth first,
    children in field order), reading ``_fields`` directly: ``ast.walk``
    spends most of its time in the generators of ``iter_child_nodes`` and
    ``iter_fields``, and this walk of the whole tree is the largest cost
    after the parse."""
    out = [tree]
    append = out.append
    i = 0
    while i < len(out):
        node = out[i]
        i += 1
        for name in node._fields:
            value = getattr(node, name, None)
            if isinstance(value, list):
                for item in value:
                    if isinstance(item, ast.AST):
                        append(item)
            elif isinstance(value, ast.AST):
                append(value)
    return out

#: sub-directories of the repo root the analyzer looks at by default
DEFAULT_ROOTS: Tuple[str, ...] = ("paddle_tpu", "tests", "benchmarks")


@dataclass(frozen=True)
class Finding:
    """One rule violation.

    ``symbol`` is the rule-chosen stable token (function qualname,
    attribute, metric name, ...) that makes the fingerprint survive line
    drift; it must not contain line numbers."""

    file: str           # repo-relative posix path
    line: int
    rule: str
    message: str
    symbol: str = ""

    @property
    def fingerprint(self) -> str:
        return f"{self.file}:{self.rule}:{self.symbol or self.message}"

    def text(self) -> str:
        return f"{self.file}:{self.line}: [{self.rule}] {self.message}"

    def sort_key(self):
        return (self.file, self.line, self.rule, self.message)


class SourceModule:
    """One parsed file: path, source, AST, per-line suppressions."""

    def __init__(self, path: Path, rel: str, text: str):
        self.path = path
        self.rel = rel                      # posix, relative to repo root
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=rel)
        self._nodes = None
        self._by_type = None
        # line -> set of rule ids disabled on that line
        self.suppressions: Dict[int, set] = {}
        for i, line in enumerate(self.lines, 1):
            m = _SUPPRESS_RE.search(line)
            if m:
                rules = {r.strip() for r in m.group(1).split(",")
                         if r.strip()}
                self.suppressions[i] = rules

    @property
    def nodes(self):
        """Flat list of every AST node, computed once — rules iterate
        this instead of re-walking the tree (the walk, not the parse,
        dominated rule time)."""
        if self._nodes is None:
            self._nodes = flat_walk(self.tree)
        return self._nodes

    def nodes_of(self, *types):
        """Nodes of the given AST types, from a per-type index built on
        first use — most rules only care about one node kind, and nine
        full-tree iterations per module blew the 5 s tier-1 budget."""
        if self._by_type is None:
            by_type: Dict[type, list] = {}
            for n in self.nodes:
                by_type.setdefault(type(n), []).append(n)
            self._by_type = by_type
        if len(types) == 1:
            return self._by_type.get(types[0], ())
        out = []
        for t in types:
            out.extend(self._by_type.get(t, ()))
        return out

    def suppressed(self, line: int, rule: str) -> bool:
        """True when ``rule`` is disabled on ``line`` — by a trailing
        comment on the line itself, or by a comment-only line directly
        above it (for statements whose line is already full)."""
        rules = self.suppressions.get(line)
        if rules and (rule in rules or "all" in rules):
            return True
        prev = self.suppressions.get(line - 1)
        if prev and (rule in prev or "all" in prev):
            text = self.lines[line - 2].strip() if line >= 2 else ""
            if text.startswith("#"):
                return True
        return False


class Project:
    """All modules under ``root``'s configured sub-roots, parsed once.

    ``only`` (an optional set of repo-relative posix paths) restricts
    parsing to those files — the ``--changed-only`` pre-commit mode,
    where the caller has already computed the reverse-dependency
    closure of a git diff."""

    def __init__(self, root: Path, roots: Sequence[str] = DEFAULT_ROOTS,
                 only: Optional[set] = None):
        self.root = Path(root)
        self.roots = tuple(roots)
        self.only = only
        self.modules: List[SourceModule] = []
        self.parse_errors: List[Finding] = []
        self.parse_count = 0
        for sub in self.roots:
            base = self.root / sub
            if not base.exists():
                continue
            for p in sorted(base.rglob("*.py")):
                rel = p.relative_to(self.root).as_posix()
                if only is not None and rel not in only:
                    continue
                try:
                    mod = SourceModule(p, rel, p.read_text())
                except SyntaxError as e:
                    self.parse_errors.append(Finding(
                        rel, e.lineno or 1, "parse-error",
                        f"syntax error: {e.msg}", symbol="syntax"))
                    continue
                except (UnicodeDecodeError, OSError, ValueError) as e:
                    # one undecodable/unreadable file must not kill the
                    # whole run — surface it as a finding like a syntax
                    # error
                    self.parse_errors.append(Finding(
                        rel, 1, "parse-error",
                        f"unreadable file: {e}", symbol="unreadable"))
                    continue
                self.parse_count += 1
                self.modules.append(mod)
        self._by_rel = {m.rel: m for m in self.modules}
        self._index = None
        self._lock_classes = None

    def module(self, rel: str) -> Optional[SourceModule]:
        return self._by_rel.get(rel)

    def iter_modules(self, prefixes: Sequence[str] = ("",)
                     ) -> Iterable[SourceModule]:
        for m in self.modules:
            if any(m.rel.startswith(p) for p in prefixes):
                yield m

    @property
    def index(self):
        """Lazily built :class:`~paddle_tpu.analysis.callgraph.
        ProjectIndex` (imports, defs, traced reachability)."""
        if self._index is None:
            from .callgraph import ProjectIndex
            self._index = ProjectIndex(self)
        return self._index

    @property
    def lock_classes(self):
        """Lazily built ``(rel, locks._ClassScan)`` list of the
        lock-owning classes in the lock rules' scope: both lock rules read
        it, and each class is scanned once."""
        if self._lock_classes is None:
            from .locks import scan_lock_classes
            self._lock_classes = scan_lock_classes(self)
        return self._lock_classes


class Baseline:
    """Grandfathered findings: ``fingerprint | justification`` lines."""

    def __init__(self, entries: Optional[Dict[str, str]] = None):
        self.entries: Dict[str, str] = dict(entries or {})

    @classmethod
    def load(cls, path: Path) -> "Baseline":
        entries: Dict[str, str] = {}
        if Path(path).exists():
            for raw in Path(path).read_text().splitlines():
                line = raw.strip()
                if not line or line.startswith("#"):
                    continue
                fp, _, why = line.partition(" | ")
                entries[fp.strip()] = why.strip()
        return cls(entries)

    def dumps(self) -> str:
        """Deterministic serialisation: sorted by fingerprint, one entry
        per line — re-writing an unchanged baseline is byte-identical."""
        lines = ["# tpu-lint baseline: grandfathered findings.",
                 "# format: <fingerprint> | <one-line justification>",
                 "# regenerate with: python -m paddle_tpu.analysis"
                 " --write-baseline", ""]
        for fp in sorted(self.entries):
            why = self.entries[fp] or "grandfathered"
            lines.append(f"{fp} | {why}")
        return "\n".join(lines) + "\n"

    def write(self, path: Path) -> None:
        Path(path).write_text(self.dumps())


class Report:
    """Outcome of one engine run: every finding, the unbaselined subset,
    and stale baseline entries.

    Staleness is judged only for baseline entries whose rule actually
    RAN (fingerprints are ``file:rule:symbol``; paths/rule ids contain
    no colons): a ``--rules`` subset run must not condemn every other
    rule's grandfathered findings as stale."""

    def __init__(self, findings: List[Finding], baseline: Baseline,
                 elapsed_s: float, files: int,
                 ran_rules: Optional[set] = None):
        self.findings = sorted(findings, key=Finding.sort_key)
        self.baseline = baseline
        self.elapsed_s = elapsed_s
        self.files = files
        found = {f.fingerprint for f in self.findings}
        self.new = [f for f in self.findings
                    if f.fingerprint not in baseline.entries]

        def _rule_of(fp: str) -> str:
            parts = fp.split(":")
            return parts[1] if len(parts) > 1 else ""

        self.stale = sorted(
            fp for fp in baseline.entries
            if fp not in found
            and (ran_rules is None or _rule_of(fp) in ran_rules))

    def new_for_rule(self, rule: str) -> List[Finding]:
        return [f for f in self.new if f.rule == rule]

    def for_rule(self, rule: str) -> List[Finding]:
        return [f for f in self.findings if f.rule == rule]

    @property
    def exit_code(self) -> int:
        return 1 if (self.new or self.stale) else 0

    def to_json(self) -> str:
        return json.dumps({
            "files": self.files,
            "elapsed_s": round(self.elapsed_s, 3),
            "findings": [{"file": f.file, "line": f.line, "rule": f.rule,
                          "message": f.message,
                          "fingerprint": f.fingerprint,
                          "baselined": f.fingerprint
                          in self.baseline.entries}
                         for f in self.findings],
            "stale_baseline": self.stale,
            "exit_code": self.exit_code,
        }, indent=1, sort_keys=True)

    def to_sarif(self, rules: Sequence = ()) -> str:
        """SARIF 2.1.0 document for CI annotation (GitHub code
        scanning et al.). New findings are ``error``, baselined ones
        ``note``; the line-number-free fingerprint rides along as a
        partial fingerprint so annotation dedup survives line drift."""
        rule_meta = [{"id": r.id,
                      "shortDescription": {"text": r.protects}}
                     for r in rules]
        results = []
        for f in self.findings:
            baselined = f.fingerprint in self.baseline.entries
            results.append({
                "ruleId": f.rule,
                "level": "note" if baselined else "error",
                "message": {"text": f.message},
                "locations": [{"physicalLocation": {
                    "artifactLocation": {"uri": f.file},
                    "region": {"startLine": f.line}}}],
                "partialFingerprints": {"tpuLint/v1": f.fingerprint},
            })
        return json.dumps({
            "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                        "sarif-spec/master/Schemata/sarif-schema-2.1.0"
                        ".json"),
            "version": "2.1.0",
            "runs": [{
                "tool": {"driver": {"name": "tpu-lint",
                                    "rules": rule_meta}},
                "results": results,
            }],
        }, indent=1, sort_keys=True)

    def to_text(self) -> str:
        out: List[str] = []
        for f in self.new:
            out.append(f.text())
        for fp in self.stale:
            out.append(f"stale baseline entry (finding no longer "
                       f"present): {fp}")
        n_base = len(self.findings) - len(self.new)
        out.append(f"tpu-lint: {self.files} files, "
                   f"{len(self.new)} finding(s), {n_base} baselined, "
                   f"{len(self.stale)} stale baseline entr(y/ies) "
                   f"[{self.elapsed_s:.2f}s]")
        return "\n".join(out)


class AnalysisEngine:
    """Run a rule list over a project; apply suppressions + baseline."""

    def __init__(self, rules: Sequence, baseline: Optional[Baseline] = None):
        self.rules = list(rules)
        ids = [r.id for r in self.rules]
        dupes = {i for i in ids if ids.count(i) > 1}
        if dupes:
            raise ValueError(f"duplicate rule ids: {sorted(dupes)}")
        self.baseline = baseline or Baseline()

    def run(self, project: Project) -> Report:
        t0 = time.perf_counter()
        findings: List[Finding] = list(project.parse_errors)
        for rule in self.rules:
            for f in rule.run(project):
                mod = project.module(f.file)
                if mod is not None and mod.suppressed(f.line, f.rule):
                    continue
                findings.append(f)
        return Report(findings, self.baseline,
                      time.perf_counter() - t0, project.parse_count,
                      ran_rules={r.id for r in self.rules})
