"""tpu-lint (paddle_tpu.analysis) — ISSUE 8 tier-1 suite.

Three layers:

* **whole-package acceptance** — the analyzer runs over the real tree
  and must be clean against the checked-in baseline (zero unbaselined
  findings, zero stale entries), inside the 5 s speed budget, parsing
  every file exactly once;
* **per-rule meta-tests** — every rule catches a synthetic violation
  planted in a throwaway tree (this is what keeps a rule from silently
  rotting into a no-op);
* **mechanism tests** — ``# tpu-lint: disable=`` silences exactly the
  named rule on exactly that line, stale baseline entries fail the run,
  and baseline serialisation is deterministic/sorted.
"""

import ast
import os
import textwrap
import time

import pytest

from paddle_tpu import analysis
from paddle_tpu.analysis import (AnalysisEngine, Baseline, Project,
                                 default_rules)
from paddle_tpu.analysis.contracts import CONTRACT_RULES
from paddle_tpu.analysis.layering import LAYERING_RULES
from paddle_tpu.analysis.locks import LOCK_RULES
from paddle_tpu.analysis.purity import PURITY_RULES

RULES_BY_ID = {r.id: r for r in default_rules()}


def _run(tmp_path, files, rule_ids):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    proj = Project(tmp_path)
    rules = [RULES_BY_ID[r] for r in rule_ids]
    return AnalysisEngine(rules, Baseline()).run(proj)


# ---------------------------------------------------------------------------
# whole-package acceptance
# ---------------------------------------------------------------------------

def test_whole_package_clean_against_baseline():
    rep = analysis.cached_report()
    assert not rep.new, "unbaselined findings:\n" + "\n".join(
        f.text() for f in rep.new)
    assert not rep.stale, f"stale baseline entries: {rep.stale}"
    assert rep.exit_code == 0


def test_every_rule_has_id_protects_example():
    seen = set()
    for r in default_rules():
        assert r.id and r.protects and r.example, r
        assert r.id not in seen
        seen.add(r.id)


def test_speed_budget_and_single_parse(monkeypatch):
    """Full-package analysis stays under 5 s on the CPU smoke and parses
    each file exactly ONCE (the whole point of the shared engine).

    GC is paused around the measured run: late in the tier-1 suite the
    process heap holds millions of live jax objects, and the ~1M AST
    nodes a full parse allocates trigger repeated gen-2 collections
    whose cost scales with the SUITE's heap, not the analyzer's — the
    budget asserts the analyzer's own algorithmic cost (standalone wall
    time is ~2 s; a regression past 5 s here is a real blowup)."""
    import gc
    calls = {"n": 0}
    real_parse = ast.parse

    def counting_parse(*a, **kw):
        calls["n"] += 1
        return real_parse(*a, **kw)

    monkeypatch.setattr(ast, "parse", counting_parse)
    gc.collect()
    gc.disable()
    try:
        t0 = time.perf_counter()
        rep = analysis.run_repo()
        elapsed = time.perf_counter() - t0
    finally:
        gc.enable()
    # 1-core CI containers run the hot-heap suite context ~2x slower
    # per core than a dev box (cold CLI wall is ~1.7s on both); keep
    # the tight budget where the extra headroom exists
    budget = 5.0 if (os.cpu_count() or 1) > 1 else 10.0
    assert elapsed < budget, f"analysis took {elapsed:.2f}s (budget {budget}s)"
    assert rep.files > 200          # the real tree, not a stub
    assert calls["n"] == rep.files, (
        f"{calls['n']} ast.parse calls for {rep.files} files — "
        "a rule is re-parsing instead of sharing the engine's trees")


_WALK_SAMPLE = '''
import os
@dec(x=lambda y: y + 1)
class C(Base, metaclass=M):
    k: int = 3
    def f(self, a, *b, c=[1, 2], **d) -> None:
        with self._lock, open(a) as fh:
            g = [i for i in b if i]
            def inner(z=lambda: 0):
                return z
        try:
            yield {k: v for k, v in d.items()}
        except (ValueError, KeyError) as e:
            raise RuntimeError(f"{e!r}") from e
        match a:
            case [1, *rest] if rest:
                pass
f = lambda q, *, r=2: (q, r)
'''


@pytest.mark.parametrize("source", ["sample", "decoding", "analysis"])
def test_flat_walk_and_own_nodes_keep_the_ast_walks_order(source):
    """``flat_walk`` lists ``ast.walk``'s nodes in its order, and a
    function's kept own nodes are the depth-first walk the rules were
    written against: nested defs, lambdas and classes left out."""
    from paddle_tpu.analysis.engine import flat_walk
    root = analysis.REPO_ROOT
    text = {"sample": _WALK_SAMPLE,
            "decoding": (root / "paddle_tpu/inference/decoding.py"
                         ).read_text(),
            "analysis": (root / "paddle_tpu/analysis/dataflow.py"
                         ).read_text()}[source]
    tree = ast.parse(text)
    assert [id(n) for n in flat_walk(tree)] == \
        [id(n) for n in ast.walk(tree)]

    def reference(node):
        out, stack = [], list(node.body if isinstance(node.body, list)
                              else [node.body])
        while stack:
            n = stack.pop()
            out.append(n)
            stack.extend(c for c in ast.iter_child_nodes(n)
                         if not isinstance(c, (ast.FunctionDef,
                                               ast.AsyncFunctionDef,
                                               ast.Lambda, ast.ClassDef)))
        return out

    from paddle_tpu.analysis.callgraph import FunctionInfo
    fns = [n for n in ast.walk(tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda))]
    assert len(fns) >= 4
    for fn in fns:
        fi = FunctionInfo(None, fn, "q", None, {})
        first = fi.own_nodes()
        assert [id(n) for n in first] == [id(n) for n in reference(fn)]
        assert fi.own_nodes() is first          # walked once, then kept


@pytest.mark.parametrize("enabled", [True, False])
def test_run_repo_pauses_the_collector_and_restores_it(enabled,
                                                       monkeypatch):
    import gc
    seen = []
    real_run = AnalysisEngine.run

    def run(self, project):
        seen.append(gc.isenabled())
        return real_run(self, project)

    monkeypatch.setattr(AnalysisEngine, "run", run)
    was = gc.isenabled()
    (gc.enable if enabled else gc.disable)()
    try:
        analysis.run_repo(rules=[RULES_BY_ID["layer-http"]])
        assert gc.isenabled() is enabled
    finally:
        (gc.enable if was else gc.disable)()
    assert seen == [False]


def test_lock_rules_scan_each_class_once(monkeypatch):
    from paddle_tpu.analysis import locks
    scans = []
    real_init = locks._ClassScan.__init__

    def counting_init(self, rel, cls):
        scans.append((rel, cls.name))
        real_init(self, rel, cls)

    monkeypatch.setattr(locks._ClassScan, "__init__", counting_init)
    rep = analysis.run_repo(rules=list(LOCK_RULES))
    assert scans and len(scans) == len(set(scans))
    assert not rep.new and not rep.stale


def test_cli_json_and_text(capsys, tmp_path):
    from paddle_tpu.analysis.__main__ import main
    # acceptance: the CLI exits 0 on the real tree against the baseline
    assert main(["--format", "json"]) == 0
    out = capsys.readouterr().out
    import json
    doc = json.loads(out)
    assert doc["exit_code"] == 0 and doc["files"] > 200
    # text mode + exit 1 on a dirty tree (tiny synthetic root)
    bad = tmp_path / "paddle_tpu" / "x.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import http.server\n")
    rc = main(["--root", str(tmp_path), "--no-baseline",
               "--rules", "layer-http", "--format", "text"])
    assert rc == 1
    assert "[layer-http]" in capsys.readouterr().out
    assert main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    for rid in RULES_BY_ID:
        assert rid in listed
    assert main(["--rules", "no-such-rule"]) == 2


# ---------------------------------------------------------------------------
# rule meta-tests: one planted violation each
# ---------------------------------------------------------------------------

_JIT_PREAMBLE = """
    import time, random, jax
    import numpy as np
"""


@pytest.mark.parametrize("rule_id,src,token", [
    ("trace-wall-clock", _JIT_PREAMBLE + """
    def helper(x):
        return x + time.time()
    def build():
        def run(x):
            return helper(x)
        return jax.jit(run)
    """, "time.time"),
    ("trace-random", _JIT_PREAMBLE + """
    def build():
        def run(x):
            return x * np.random.uniform()
        return jax.jit(run)
    """, "np.random.uniform"),
    ("trace-random", _JIT_PREAMBLE + """
    def build():
        def run(x):
            return x * jax.random.uniform(jax.random.PRNGKey(0), x.shape)
        return jax.jit(run)
    """, "jax.random.uniform"),
    ("trace-host-sync", _JIT_PREAMBLE + """
    def build():
        def run(x):
            return float(x) + x[0].item()
        return jax.jit(run)
    """, "item"),
    ("trace-shape-branch", _JIT_PREAMBLE + """
    def build():
        def run(x):
            if x.shape[0] > 8:
                return x * 2
            return x
        return jax.jit(run)
    """, "x.shape"),
    ("trace-host-state", _JIT_PREAMBLE + """
    from paddle_tpu.flags import flag_value
    def build():
        def run(x):
            if flag_value("some_flag"):
                return x * 2
            return x
        return jax.jit(run)
    """, "flag_value"),
])
def test_purity_rule_catches_synthetic_violation(tmp_path, rule_id, src,
                                                 token):
    rep = _run(tmp_path, {"paddle_tpu/mod.py": src}, [rule_id])
    hits = rep.for_rule(rule_id)
    assert hits, f"{rule_id} missed the planted violation"
    assert any(token in f.message for f in hits)


def test_trace_random_sanctions_threaded_keys(tmp_path):
    """The sampling epilogue's idiom — keys built from a traced seed
    array and threaded into the draw — is the SANCTIONED pattern: only
    an inline literal-seeded PRNGKey (a constant masquerading as a
    draw) trips the refined trace-random rule."""
    rep = _run(tmp_path, {"paddle_tpu/mod.py": _JIT_PREAMBLE + """
    def build():
        def run(seeds, pos, logits):
            keys = jax.vmap(jax.random.PRNGKey)(seeds)
            keys = jax.vmap(jax.random.fold_in)(keys, pos)
            tok = jax.vmap(jax.random.categorical)(keys, logits)
            u = jax.random.uniform(keys[0], logits.shape[1:])
            v = jax.random.uniform(key=keys[0])
            return tok, u, v
        return jax.jit(run)
    """}, ["trace-random"])
    assert not rep.findings, [f.text() for f in rep.findings]


def test_trace_random_constant_key_via_keyword(tmp_path):
    rep = _run(tmp_path, {"paddle_tpu/mod.py": _JIT_PREAMBLE + """
    def build():
        def run(x):
            return jax.random.normal(key=jax.random.key(42), shape=x.shape)
        return jax.jit(run)
    """}, ["trace-random"])
    hits = rep.for_rule("trace-random")
    assert len(hits) == 1 and "constant-keyed" in hits[0].message


_LOCKY = """
    import threading, time

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []

        def read(self):
            with self._lock:
                return list(self._items)

        def bad_write(self, x):
            self._items.append(x)            # no lock: should flag

        def bad_block(self):
            with self._lock:
                time.sleep(1)                # blocking under the lock
"""


def test_lock_unguarded_write_meta(tmp_path):
    rep = _run(tmp_path, {"paddle_tpu/serving/box.py": _LOCKY},
               ["lock-unguarded-write"])
    hits = rep.for_rule("lock-unguarded-write")
    assert len(hits) == 1 and "_items" in hits[0].message
    assert "bad_write" in hits[0].message


def test_lock_blocking_call_meta(tmp_path):
    rep = _run(tmp_path, {"paddle_tpu/observability/box.py": _LOCKY},
               ["lock-blocking-call"])
    hits = rep.for_rule("lock-blocking-call")
    assert len(hits) == 1 and "time.sleep" in hits[0].message


def test_lock_blocking_call_not_duplicated_in_locked_helper(tmp_path):
    """A blocking call inside a with-lock block of a ``*_locked`` method
    sits in two overlapping regions (the method and the block) — it must
    still be reported exactly once."""
    rep = _run(tmp_path, {"paddle_tpu/serving/box2.py": """
        import threading, time

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self._x = []

            def read(self):
                with self._lock:
                    return list(self._x)

            def _flush_locked(self):
                with self._lock:
                    time.sleep(0.1)
    """}, ["lock-blocking-call"])
    assert len(rep.for_rule("lock-blocking-call")) == 1


def test_unreadable_file_is_a_finding_not_a_crash(tmp_path):
    p = tmp_path / "paddle_tpu" / "bad.py"
    p.parent.mkdir(parents=True)
    p.write_bytes(b"# caf\xe9\n")          # latin-1 bytes: invalid utf-8
    rep = AnalysisEngine([RULES_BY_ID["layer-http"]],
                         Baseline()).run(Project(tmp_path))
    assert any(f.rule == "parse-error" and f.symbol == "unreadable"
               for f in rep.findings)


def test_lock_rules_scope_excludes_other_packages(tmp_path):
    rep = _run(tmp_path, {"paddle_tpu/vision/box.py": _LOCKY},
               ["lock-unguarded-write", "lock-blocking-call"])
    assert not rep.findings      # discipline applies to serving/obs only


_CATALOG = """
    METRICS = {
        "paddle_demo_total": ("counter", ("op",)),
        "paddle_unused_total": ("counter", ()),
    }
    EVENT_KINDS = {"good_event", "never_emitted"}
    SPANS = {
        "queue_wait": ("request_id",),
        "engine.prefill": ("request_id", "slot"),
        "never_spanned": (),
    }
"""

_SINK = """
    class ServingMetrics:
        def __init__(self):
            self.histograms = {"ttft_ms": None}
            self.counters = {"requests_total": 0}
            self.gauges = {"queue_depth": 0.0}
"""


def test_metric_contract_meta(tmp_path):
    rep = _run(tmp_path, {
        "paddle_tpu/observability/catalog.py": _CATALOG,
        "paddle_tpu/serving/metrics.py": _SINK,
        "paddle_tpu/demo.py": """
            from .observability.registry import get_registry
            reg = get_registry()
            c = reg.counter("paddle_demo_total", "d", labels=("typo",))
            c2 = reg.gauge("paddle_undeclared_thing", "d")
            c.inc(wrong_label=1)
        """,
        "paddle_tpu/serving/sched.py": """
            def tick(m):
                m.set_gauge("not_a_declared_gauge", 1.0)
                m.inc("requests_total")
        """,
    }, ["metric-contract"])
    syms = {f.symbol for f in rep.for_rule("metric-contract")}
    assert "labels:paddle_demo_total" in syms           # wrong label tuple
    assert "undeclared:paddle_undeclared_thing" in syms
    assert "unused:paddle_unused_total" in syms         # dead catalog row
    assert "use:paddle_demo_total:inc" in syms          # wrong use labels
    assert "sink:set_gauge:not_a_declared_gauge" in syms
    assert not any("requests_total" in s for s in syms)


def test_event_contract_meta(tmp_path):
    rep = _run(tmp_path, {
        "paddle_tpu/observability/catalog.py": _CATALOG,
        "paddle_tpu/demo.py": """
            from .observability.events import emit_event
            def f():
                emit_event("good_event", a=1)
                emit_event("typo_evnt", a=1)
        """,
    }, ["event-contract"])
    syms = {f.symbol for f in rep.for_rule("event-contract")}
    assert "undeclared:typo_evnt" in syms
    assert "unused:never_emitted" in syms
    assert not any("good_event" in s for s in syms)


def test_span_contract_meta(tmp_path):
    rep = _run(tmp_path, {
        "paddle_tpu/observability/catalog.py": _CATALOG,
        "paddle_tpu/demo.py": """
            from .profiler.record import emit_span, make_span
            def f(ns, t0, t1, rid):
                emit_span("engine.prefill", t0, t1,
                          args={"request_id": rid, "slot": 0})
                emit_span(f"{ns}.queue_wait", t0, t1,
                          args={"request_id": rid})
                emit_span("engine.prefil", t0, t1)          # typo'd name
                make_span("engine.prefill", t0, t1,
                          args={"request_id": rid, "bogus_field": 1})
        """,
    }, ["span-contract"])
    syms = {f.symbol for f in rep.for_rule("span-contract")}
    assert "undeclared:engine.prefil" in syms
    assert "fields:engine.prefill" in syms      # undeclared args field
    assert "unused:never_spanned" in syms       # dead catalog row
    # good literal + f-string-suffix emissions produce no findings
    assert not any("queue_wait" in s for s in syms)
    assert len([s for s in syms if s.startswith("fields:")]) == 1


@pytest.mark.parametrize("rule_id,rel,src,needle", [
    ("layer-http", "paddle_tpu/serving/dbg.py",
     "import http.server\n", "http"),
    ("layer-socket", "paddle_tpu/observability/flight2.py",
     "import socket\n", "socket"),
    ("private-replica", "tests/test_x.py",
     "def f(r):\n    return r._scheduler\n", "_scheduler"),
    ("private-kvcache", "benchmarks/bench_x.py",
     "def f(mgr):\n    mgr._free.append(1)\n", "_free"),
    ("private-engine", "benchmarks/bench_y.py",
     "def f(eng):\n    return len(eng._queue)\n", "_queue"),
    ("layer-shard-map", "paddle_tpu/parallel/x.py",
     "from jax.experimental.shard_map import shard_map\n", "shard_map"),
    ("layer-atomic-write", "paddle_tpu/distributed/checkpoint/x.py",
     "def f(p):\n    open(p, 'wb')\n", "wb"),
    ("layer-atomic-write", "paddle_tpu/distributed/checkpoint/y.py",
     "import gzip\ndef f(p):\n    gzip.open(p, 'wb')\n", "wb"),
    ("layer-prom-format", "paddle_tpu/serving/fmt.py",
     "def f(n, le, v):\n    return f'{n}_bucket{{le=\"{le}\"}} {v}'\n",
     "Prometheus"),
    ("layer-deps", "paddle_tpu/resilience/bad.py",
     "from paddle_tpu.serving.scheduler import ServingScheduler\n",
     "serving"),
    # the memory ledger's STRICT contract: even a LAZY function-scope
    # import of the layers that feed it is a violation (fed, never pulls)
    ("layer-deps", "paddle_tpu/observability/memory.py",
     "def f():\n"
     "    from paddle_tpu.inference.decoding import "
     "ContinuousBatchingEngine\n"
     "    return ContinuousBatchingEngine\n",
     "STRICT"),
    # the fusion pass consumes symbols + injected callables, never the
    # serving stack it optimizes — lazy imports banned too (ISSUE 13)
    ("layer-deps", "paddle_tpu/jit/fusion.py",
     "def install(target):\n"
     "    from paddle_tpu.inference.decoding import "
     "ContinuousBatchingEngine\n"
     "    return ContinuousBatchingEngine\n",
     "STRICT"),
    ("layer-deps", "paddle_tpu/jit/fusion.py",
     "from paddle_tpu.serving.scheduler import ServingScheduler\n",
     "STRICT"),
])
def test_layering_rule_catches_synthetic_violation(tmp_path, rule_id, rel,
                                                   src, needle):
    rep = _run(tmp_path, {rel: src}, [rule_id])
    hits = rep.for_rule(rule_id)
    # drop "expected module missing" self-checks from rules that pin
    # real files (wall-clock rule); every entry left must be the plant
    hits = [f for f in hits if f.file == rel]
    assert hits, f"{rule_id} missed the planted violation in {rel}"
    assert any(needle in f.message for f in hits)


def test_private_access_own_self_attribute_not_flagged(tmp_path):
    rep = _run(tmp_path, {"paddle_tpu/demo.py": """
        class Q:
            def __init__(self):
                self._queue = []
            def depth(self):
                return len(self._queue)     # own private: fine
    """}, ["private-engine"])
    assert not rep.for_rule("private-engine")


def test_layer_deps_allows_lazy_function_scope_import(tmp_path):
    rep = _run(tmp_path, {"paddle_tpu/resilience/ok.py": """
        def f():
            from paddle_tpu.serving.scheduler import ServingScheduler
            return ServingScheduler
    """}, ["layer-deps"])
    assert not rep.for_rule("layer-deps")


def test_wall_clock_free_meta(tmp_path):
    rep = _run(tmp_path, {
        "paddle_tpu/observability/slo.py":
            "import time\ndef f():\n    return time.time()\n",
        "paddle_tpu/observability/goodput.py": "x = 1\n",
    }, ["layer-wall-clock"])
    hits = [f for f in rep.for_rule("layer-wall-clock")
            if f.symbol == "time.time"]
    assert len(hits) == 1
    assert hits[0].file.endswith("slo.py")


# ---------------------------------------------------------------------------
# suppression mechanism
# ---------------------------------------------------------------------------

_SUPPRESSIBLE = """
    import http.server  {comment}
"""


def test_suppression_silences_exactly_that_rule(tmp_path):
    src = "import http.server  # tpu-lint: disable=layer-http\n"
    rep = _run(tmp_path, {"paddle_tpu/x.py": src}, ["layer-http"])
    assert not rep.for_rule("layer-http")


def test_suppression_of_other_rule_does_not_silence(tmp_path):
    src = "import http.server  # tpu-lint: disable=layer-socket\n"
    rep = _run(tmp_path, {"paddle_tpu/x.py": src}, ["layer-http"])
    assert rep.for_rule("layer-http")


def test_suppression_is_line_scoped(tmp_path):
    src = ("import json  # tpu-lint: disable=layer-http\n"
           "import http.server\n")
    rep = _run(tmp_path, {"paddle_tpu/x.py": src}, ["layer-http"])
    assert rep.for_rule("layer-http")       # wrong line: still flagged


def test_suppression_comment_line_above(tmp_path):
    src = ("# tpu-lint: disable=layer-http\n"
           "import http.server\n")
    rep = _run(tmp_path, {"paddle_tpu/x.py": src}, ["layer-http"])
    assert not rep.for_rule("layer-http")


# ---------------------------------------------------------------------------
# baseline mechanism
# ---------------------------------------------------------------------------

def _one_finding_project(tmp_path):
    files = {"paddle_tpu/x.py": "import http.server\n"}
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    return Project(tmp_path)


def test_baselined_finding_not_new_and_exit_zero(tmp_path):
    proj = _one_finding_project(tmp_path)
    rule = RULES_BY_ID["layer-http"]
    rep = AnalysisEngine([rule], Baseline()).run(proj)
    (fp,) = {f.fingerprint for f in rep.findings}
    rep2 = AnalysisEngine([rule], Baseline({fp: "known"})).run(proj)
    assert rep2.findings and not rep2.new and not rep2.stale
    assert rep2.exit_code == 0


def test_stale_baseline_entry_fails_run(tmp_path):
    proj = _one_finding_project(tmp_path)
    rule = RULES_BY_ID["layer-http"]
    base = Baseline({"paddle_tpu/gone.py:layer-http:import:http": "old"})
    rep = AnalysisEngine([rule], base).run(proj)
    assert rep.stale == ["paddle_tpu/gone.py:layer-http:import:http"]
    assert rep.exit_code == 1


def test_baseline_serialisation_deterministic_and_sorted(tmp_path):
    a = Baseline({"z:rule:1": "why z", "a:rule:2": "why a",
                  "m:rule:3": ""})
    b = Baseline(dict(reversed(list(a.entries.items()))))
    assert a.dumps() == b.dumps()
    lines = [l for l in a.dumps().splitlines()
             if l and not l.startswith("#")]
    assert lines == sorted(lines)
    p1, p2 = tmp_path / "b1.txt", tmp_path / "b2.txt"
    a.write(p1)
    b.write(p2)
    assert p1.read_bytes() == p2.read_bytes()
    assert Baseline.load(p1).entries == {"z:rule:1": "why z",
                                         "a:rule:2": "why a",
                                         "m:rule:3": "grandfathered"}


def test_stale_check_scoped_to_rules_that_ran(tmp_path):
    """A ``--rules`` subset run must NOT condemn other rules' baseline
    entries as stale (their rules never looked, so absence proves
    nothing) — but entries for a rule that DID run still fail."""
    proj = _one_finding_project(tmp_path)
    base = Baseline({
        "paddle_tpu/x.py:trace-wall-clock:f:time.time": "other rule",
    })
    rep = AnalysisEngine([RULES_BY_ID["layer-http"]], base).run(proj)
    assert rep.stale == []                  # trace-wall-clock didn't run
    rep2 = AnalysisEngine([RULES_BY_ID["layer-http"],
                           RULES_BY_ID["trace-wall-clock"]],
                          base).run(proj)
    assert rep2.stale == [
        "paddle_tpu/x.py:trace-wall-clock:f:time.time"]


def test_write_baseline_with_rules_subset_preserves_other_entries(
        tmp_path, capsys):
    """``--write-baseline --rules <subset>`` refreshes only the subset's
    entries; other rules' grandfathered findings (and justifications)
    survive."""
    from paddle_tpu.analysis.__main__ import main
    bad = tmp_path / "paddle_tpu" / "x.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import http.server\nimport socket\n")
    bpath = tmp_path / "baseline.txt"
    keep = "paddle_tpu/x.py:layer-socket:import:socket"
    Baseline({keep: "socket is grandfathered here"}).write(bpath)
    assert main(["--root", str(tmp_path), "--baseline", str(bpath),
                 "--rules", "layer-http", "--write-baseline"]) == 0
    reloaded = Baseline.load(bpath)
    assert reloaded.entries[keep] == "socket is grandfathered here"
    assert any(fp.startswith("paddle_tpu/x.py:layer-http:")
               for fp in reloaded.entries)
    # and the refreshed baseline makes a full run over both rules clean
    rep = AnalysisEngine([RULES_BY_ID["layer-http"],
                          RULES_BY_ID["layer-socket"]],
                         reloaded).run(Project(tmp_path))
    assert not rep.new and not rep.stale


def test_fingerprints_survive_line_drift(tmp_path):
    """The baseline keys on (file, rule, symbol) — inserting lines above
    a finding must not invalidate its entry."""
    rule = RULES_BY_ID["layer-http"]
    proj1 = _one_finding_project(tmp_path / "v1")
    rep1 = AnalysisEngine([rule], Baseline()).run(proj1)
    files = {"paddle_tpu/x.py": "import json\nimport os\n\n"
                                "import http.server\n"}
    for rel, src in files.items():
        p = tmp_path / "v2" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    rep2 = AnalysisEngine([rule], Baseline()).run(
        Project(tmp_path / "v2"))
    assert [f.fingerprint for f in rep1.findings] == \
        [f.fingerprint for f in rep2.findings]
    assert rep1.findings[0].line != rep2.findings[0].line


def test_fusion_builders_are_traced_roots_for_purity_rules():
    """ISSUE 13 satellite: jit/fusion.py hands the optimizer megaregion's
    staged program to jax.jit, so the ProjectIndex call graph must see it
    as a traced root — the purity/recompile-hazard rules then cover the
    megaregion's body (the whole-package acceptance test above proves it
    comes back clean)."""
    from paddle_tpu.analysis import REPO_ROOT
    proj = Project(REPO_ROOT, roots=("paddle_tpu",))
    root_files = {fi.module.rel for fi in proj.index.traced_roots()}
    assert "paddle_tpu/jit/fusion.py" in root_files
    fusion_roots = {fi.qualname for fi in proj.index.traced_roots()
                    if fi.module.rel == "paddle_tpu/jit/fusion.py"}
    # the program FusedOptimizerStep dispatches: stage_eager's evaluator
    assert any(q.startswith("stage_eager") for q in fusion_roots), \
        fusion_roots


def test_fusion_purity_violation_in_builder_is_caught(tmp_path):
    """A wall-clock read planted inside a fusion-style region builder is
    reachable from its jax.jit root and flagged — proof the coverage is
    real, not vacuous."""
    rep = _run(tmp_path, {"paddle_tpu/jit/fusion2.py": """
        import time
        import jax

        def build_region(model_step):
            def run(params, x):
                t = time.time()
                return model_step(params, x) * t
            return jax.jit(run)
    """}, ["trace-wall-clock"])
    hits = rep.for_rule("trace-wall-clock")
    assert hits and any("time.time" in f.message for f in hits)
