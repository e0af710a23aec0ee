"""tpu-lint v2 (analysis/dataflow.py) — ISSUE 15 tier-1 suite.

Four layers:

* **CFG meta-tests** — loops carry back edges, try/finally duplicates
  the finally body onto the exception path, early returns reach the
  exit, handler edges exist and a non-catch-all handler still lets the
  exception continue out;
* **fixpoint/termination** — the worklist solver converges on loops and
  the interprocedural summaries terminate on cyclic call graphs;
* **per-rule synthetic violations + suppression/baseline semantics** —
  page-leak / dtype-flow / cache-key each catch a planted bug, stay
  quiet on the sanctioned shapes, and honor ``# tpu-lint: disable=`` +
  baseline fingerprints like every other family;
* **triage regressions** — the three genuine defects the first run of
  the new families surfaced stay fixed: the admission window leaking
  pages on exception, the kernel-backend flags missing from the
  compile-cache keys, and the quantized training layer widening the
  residual carry to f32.
"""

import ast
import json
import os
import subprocess
import textwrap
import time
from contextlib import contextmanager

import numpy as np
import pytest

from paddle_tpu import analysis
from paddle_tpu.analysis import (AnalysisEngine, Baseline, Project,
                                 default_rules)
from paddle_tpu.analysis.dataflow import (DATAFLOW_RULES, Summaries,
                                          build_cfg, solve_forward)

RULES_BY_ID = {r.id: r for r in default_rules()}
NEW_FAMILIES = ("page-leak", "dtype-flow", "cache-key")


def _run(tmp_path, files, rule_ids):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    proj = Project(tmp_path)
    rules = [RULES_BY_ID[r] for r in rule_ids]
    return AnalysisEngine(rules, Baseline()).run(proj)


def _cfg_of(src, name="f"):
    tree = ast.parse(textwrap.dedent(src))
    fn = next(n for n in ast.walk(tree)
              if isinstance(n, ast.FunctionDef) and n.name == name)
    return build_cfg(fn)


def _reachable(block):
    seen, queue = set(), [block]
    while queue:
        b = queue.pop()
        if b.bid in seen:
            continue
        seen.add(b.bid)
        queue.extend(b.succ)
        queue.extend(b.esucc)
    return seen


# ---------------------------------------------------------------------------
# CFG construction meta-tests
# ---------------------------------------------------------------------------

def test_cfg_loop_has_back_edge_and_exit():
    cfg = _cfg_of("""
        def f(xs):
            total = 0
            for x in xs:
                total += x
            return total
    """)
    back = [(b, s) for b in cfg.blocks for s in b.succ if s.bid < b.bid]
    assert back, "loop produced no back edge"
    assert cfg.exit.bid in _reachable(cfg.entry)


def test_cfg_early_return_reaches_exit_and_kills_fallthrough():
    cfg = _cfg_of("""
        def f(x):
            if x:
                return 1
            return 2
    """)
    returns = [b for b in cfg.blocks
               if isinstance(b.stmt, ast.Return)]
    assert len(returns) == 2
    for r in returns:
        assert cfg.exit in r.succ
        assert not any(s.kind == "stmt" for s in r.succ)


def test_cfg_try_finally_duplicates_finally_on_exception_path():
    cfg = _cfg_of("""
        def f(mgr):
            mgr.acquire()
            try:
                risky()
            finally:
                mgr.release()
    """)
    release_blocks = [
        b for b in cfg.blocks
        if b.stmt is not None and "release" in ast.dump(b.stmt)]
    # at least two instances: the normal continuation and the
    # exception-path copy (whose tail re-raises into exc_exit)
    assert len(release_blocks) >= 2
    risky = next(b for b in cfg.blocks
                 if b.stmt is not None and "risky" in ast.dump(b.stmt))
    assert risky.esucc, "call in try body has no exception edge"
    exc_reach = _reachable(risky.esucc[0])
    assert cfg.exc_exit.bid in exc_reach
    assert any(b.bid in exc_reach for b in release_blocks), \
        "exception path bypasses the finally body"


def test_cfg_except_handler_edge_and_propagation():
    cfg = _cfg_of("""
        def f(mgr):
            try:
                risky()
            except MemoryError:
                fallback()
    """)
    risky = next(b for b in cfg.blocks
                 if b.stmt is not None and "risky" in ast.dump(b.stmt))
    # the handler is reachable along the exception edge...
    assert any("fallback" in ast.dump(s.stmt)
               for t in risky.esucc for s in _iter_blocks(cfg, t)
               if s.stmt is not None)
    # ...and a non-MemoryError exception still propagates out
    assert cfg.exc_exit.bid in _reachable(risky)


def _iter_blocks(cfg, start):
    return [b for b in cfg.blocks if b.bid in _reachable(start)]


def test_cfg_with_block_and_while():
    cfg = _cfg_of("""
        def f(lock, xs):
            with lock:
                while xs:
                    xs.pop()
            return xs
    """)
    assert cfg.exit.bid in _reachable(cfg.entry)
    back = [(b, s) for b in cfg.blocks for s in b.succ if s.bid < b.bid]
    assert back


def test_solver_converges_on_loops():
    cfg = _cfg_of("""
        def f(mgr, rid, xs):
            for x in xs:
                pages = mgr.allocate(rid, x)
                mgr.free(rid)
            return None
    """)

    class Count:
        def initial(self):
            return frozenset()

        def join(self, a, b):
            if a is None:
                return b
            if b is None:
                return a
            return a | b

        def transfer(self, state, block):
            if block.stmt is not None:
                state = state | {type(block.stmt).__name__}
            return state, state

    t0 = time.perf_counter()
    states = solve_forward(cfg, Count())
    assert time.perf_counter() - t0 < 1.0
    assert cfg.exit.bid in states


def test_summaries_terminate_on_cyclic_call_graph(tmp_path):
    (tmp_path / "paddle_tpu").mkdir(parents=True)
    (tmp_path / "paddle_tpu" / "cyc.py").write_text(textwrap.dedent("""
        from paddle_tpu.flags import flag_value

        def a(mgr, rid):
            return b(mgr, rid)

        def b(mgr, rid):
            if rid:
                return a(mgr, rid - 1)
            mgr.free(rid)
            return flag_value("cyc_flag")
    """))
    proj = Project(tmp_path)
    summaries = Summaries(proj.index)
    mi = proj.index.by_rel["paddle_tpu/cyc.py"]
    fa = mi.top_level["a"]
    t0 = time.perf_counter()
    assert summaries.releases(fa) is True       # through the cycle
    assert "cyc_flag" in summaries.flags_read(fa)
    assert time.perf_counter() - t0 < 1.0


def test_summaries_cycle_cut_results_are_not_poisoned(tmp_path):
    """Review fix (PR 15): a walk that hits the cycle cut computes a
    PROVISIONAL under-approximation — memoizing it poisoned every later
    query (the mutually-recursive helper that does release stayed
    "no-release" forever, minting page-leak false positives). The query
    ORDER matters: ``a`` first, so ``b`` is evaluated under the cut."""
    (tmp_path / "paddle_tpu").mkdir(parents=True)
    (tmp_path / "paddle_tpu" / "cyc2.py").write_text(textwrap.dedent("""
        from paddle_tpu.flags import flag_value

        def a(mgr, rid, n):
            if n:
                return b(mgr, rid, n - 1)
            return helper(mgr, rid)

        def b(mgr, rid, n):
            if n:
                return a(mgr, rid, n - 1)

        def helper(mgr, rid):
            mgr.free(rid)
            return flag_value("cyc2_flag")
    """))
    proj = Project(tmp_path)
    summaries = Summaries(proj.index)
    mi = proj.index.by_rel["paddle_tpu/cyc2.py"]
    fa, fb = mi.top_level["a"], mi.top_level["b"]
    assert summaries.releases(fa) is True
    # b releases through a -> helper; before the fix the a-walk memoized
    # b as False at the cut and this query returned the poisoned value
    assert summaries.releases(fb) is True
    assert "cyc2_flag" in summaries.flags_read(fa)
    assert "cyc2_flag" in summaries.flags_read(fb)


# ---------------------------------------------------------------------------
# page-leak synthetics
# ---------------------------------------------------------------------------

_LEAK_HEADER = "import jax\n"


@pytest.mark.parametrize("src,expect", [
    # plain leak: acquired, never released, never escapes
    ("""
     def f(mgr, rid):
         mgr.allocate(rid, 64)
         return None
     """, True),
    # exception-edge-only leak: the call between acquire and the
    # ownership transfer can raise with the pages still held
    ("""
     def f(mgr, rid, sink):
         pages = mgr.allocate(rid, 64)
         risky()
         sink.append(pages)
     """, True),
    # clean: try/finally releases on every path
    ("""
     def f(mgr, rid):
         mgr.allocate(rid, 64)
         try:
             risky()
         finally:
             mgr.free(rid)
     """, False),
    # clean: exception handler releases and re-raises
    ("""
     def f(mgr, rid, sink):
         pages = mgr.allocate(rid, 64)
         try:
             risky()
         except BaseException:
             mgr.free(rid)
             raise
         sink.append(pages)
     """, False),
    # clean: the result escapes immediately (ownership transfer)
    ("""
     def f(mgr, rid):
         return mgr.allocate(rid, 64)
     """, False),
    # clean: interprocedural release through a helper's summary
    ("""
     def cleanup(mgr, rid):
         mgr.free(rid)

     def f(mgr, rid):
         mgr.allocate(rid, 64)
         cleanup(mgr, rid)
     """, False),
    # clean: pool constructed in this frame dies with the frame
    ("""
     def f(rid):
         mgr = PagedKVCacheManager(1, 8, 4, 1, 8)
         mgr.allocate(rid, 64)
         risky()
     """, False),
    # clean: rollback via truncate_pages counts as a release
    ("""
     def f(mgr, rid):
         mgr.grow_to(rid, 128)
         try:
             risky()
         finally:
             mgr.truncate_pages(rid, 2)
     """, False),
    # clean: finally nested inside try/except — the exception continues
    # past the finally INTO the enclosing handler, which releases (CFG
    # _exc_targets regression: routing propagation only through outer
    # finallys skipped enclosing handlers and minted a false positive)
    ("""
     def f(mgr, rid, sink):
         try:
             pages = mgr.allocate(rid, 64)
             try:
                 risky()
             finally:
                 tick()
             sink.append(pages)
         except Exception:
             mgr.free(rid)
             raise
     """, False),
    # leak: same nesting but the enclosing handler never releases
    ("""
     def f(mgr, rid, sink):
         try:
             pages = mgr.allocate(rid, 64)
             try:
                 risky()
             finally:
                 tick()
             sink.append(pages)
         except ValueError:
             log()
             raise
     """, True),
    # clean: break leaves the loop THROUGH the enclosing finally, which
    # releases (CFG regression: break/continue jumped straight to the
    # loop exit, skipping finally bodies, and minted a false positive
    # on code that frees on every real path)
    ("""
     def f(mgr, reqs, sink):
         for r in reqs:
             try:
                 pages = mgr.allocate(r, 4)
                 if r > 3:
                     break
                 sink.append(pages)
             finally:
                 mgr.free(r)
     """, False),
    # clean: continue routes through the finally the same way
    ("""
     def f(mgr, reqs, sink):
         for r in reqs:
             try:
                 pages = mgr.allocate(r, 4)
                 if r > 3:
                     continue
                 sink.append(pages)
             finally:
                 mgr.free(r)
     """, False),
    # leak: without a finally, the break path really does bypass the
    # release (the jump edge itself must survive the finally routing)
    ("""
     def f(mgr, reqs):
         for r in reqs:
             mgr.allocate(r, 4)
             if r > 3:
                 break
             mgr.free(r)
     """, True),
])
def test_page_leak_synthetics(tmp_path, src, expect):
    rep = _run(tmp_path,
               {"paddle_tpu/inference/mod.py":
                _LEAK_HEADER + textwrap.dedent(src)},
               ["page-leak"])
    hits = rep.for_rule("page-leak")
    assert bool(hits) == expect, "\n".join(f.text() for f in hits)


def test_page_leak_scope_is_kvcache_and_inference_only(tmp_path):
    src = _LEAK_HEADER + textwrap.dedent("""
    def f(mgr, rid):
        mgr.allocate(rid, 64)
        return None
    """)
    rep = _run(tmp_path, {"paddle_tpu/serving/mod.py": src},
               ["page-leak"])
    assert not rep.for_rule("page-leak")


def test_page_leak_suppression_and_baseline(tmp_path):
    src = _LEAK_HEADER + textwrap.dedent("""
    def f(mgr, rid):
        mgr.allocate(rid, 64)  # tpu-lint: disable=page-leak
        return None

    def g(mgr, rid):
        mgr.allocate(rid, 64)
        return None
    """)
    rep = _run(tmp_path, {"paddle_tpu/kvcache/mod.py": src},
               ["page-leak"])
    hits = rep.for_rule("page-leak")
    assert len(hits) == 1 and "g" in hits[0].message
    fp = hits[0].fingerprint
    proj = Project(tmp_path)
    rep2 = AnalysisEngine([RULES_BY_ID["page-leak"]],
                          Baseline({fp: "known"})).run(proj)
    assert rep2.findings and not rep2.new and rep2.exit_code == 0


# ---------------------------------------------------------------------------
# dtype-flow synthetics
# ---------------------------------------------------------------------------

_DT_HEADER = """
    import jax
    import jax.numpy as jnp
"""


@pytest.mark.parametrize("body,kind,expect", [
    # mixed-dtype contraction: bf16 x f32 einsum, no explicit cast
    ("""
     a = x.astype(jnp.bfloat16)
     w = jnp.zeros((4, 4), jnp.float32)
     return jnp.einsum("ij,jk->ik", a, w)
     """, "mixed", True),
    # same contraction with the cast made explicit at the site: clean
    ("""
     a = x.astype(jnp.bfloat16)
     w = jnp.zeros((4, 4), jnp.float32)
     return jnp.einsum("ij,jk->ik", a.astype(jnp.float32), w)
     """, "mixed", False),
    # preferred_element_type chooses the accumulator: clean
    ("""
     a = x.astype(jnp.bfloat16)
     w = jnp.zeros((4, 4), jnp.float32)
     return jnp.dot(a, w, preferred_element_type=jnp.float32)
     """, "mixed", False),
    # silent arithmetic promotion bf16 + f32
    ("""
     a = x.astype(jnp.bfloat16)
     b = jnp.zeros((4, 4), jnp.float32)
     return a + b
     """, "promote", True),
    # dequant without scale reaching a contraction
    ("""
     q = jnp.zeros((4, 4), jnp.int8)
     deq = q.astype(jnp.float32)
     return jnp.einsum("ij,jk->ik", deq, deq)
     """, "dequant", True),
    # dequant WITH its scale multiply: clean
    ("""
     q = jnp.zeros((4, 4), jnp.int8)
     deq = q.astype(jnp.float32) * scale
     return jnp.einsum("ij,jk->ik", deq, deq)
     """, "dequant", False),
])
def test_dtype_flow_synthetics(tmp_path, body, kind, expect):
    indented = textwrap.indent(textwrap.dedent(body), "        ")
    src = _DT_HEADER + f"""
    def build():
        def run(x, scale):
{textwrap.indent(indented, "    ")}
        return jax.jit(run)
    """
    rep = _run(tmp_path, {"paddle_tpu/ops/mod.py": src}, ["dtype-flow"])
    hits = [f for f in rep.for_rule("dtype-flow")
            if f.symbol.endswith(f":{kind}")]
    assert bool(hits) == expect, "\n".join(
        f.text() for f in rep.for_rule("dtype-flow"))


def test_dtype_flow_scope_is_traced_ops_models_only(tmp_path):
    src = _DT_HEADER + """
    def run(x):
        a = x.astype(jnp.bfloat16)
        w = jnp.zeros((4, 4), jnp.float32)
        return jnp.einsum("ij,jk->ik", a, w)
    """
    # not reachable from any jit/pallas root -> out of scope
    rep = _run(tmp_path, {"paddle_tpu/ops/mod.py": src}, ["dtype-flow"])
    assert not rep.for_rule("dtype-flow")
    # traced but outside ops//models/ -> out of scope
    src2 = _DT_HEADER + """
    def build():
        def run(x):
            a = x.astype(jnp.bfloat16)
            w = jnp.zeros((4, 4), jnp.float32)
            return jnp.einsum("ij,jk->ik", a, w)
        return jax.jit(run)
    """
    rep2 = _run(tmp_path, {"paddle_tpu/serving/mod.py": src2},
                ["dtype-flow"])
    assert not rep2.for_rule("dtype-flow")


# ---------------------------------------------------------------------------
# cache-key synthetics
# ---------------------------------------------------------------------------

_CK_ENGINE = """
    import jax
    from paddle_tpu.flags import flag_value

    def _flags():
        return (bool(flag_value("mode_flag")),)

    class Eng:
        def __init__(self):
            self._compiled = {}
            self._one_shot = None

        def _build(self):
            def run(x):
                if flag_value("mode_flag"):
                    return x * 2
                return x
            return jax.jit(run)

        def step(self, bucket, x):
            key = %s
            if key not in self._compiled:
                self._compiled[key] = self._build()
            return self._compiled[key](x)
"""


def test_cache_key_missing_flag_is_flagged(tmp_path):
    rep = _run(tmp_path, {
        "paddle_tpu/inference/eng.py": _CK_ENGINE % "(bucket,)",
    }, ["cache-key"])
    hits = rep.for_rule("cache-key")
    assert len(hits) == 1
    assert "mode_flag" in hits[0].message
    assert hits[0].symbol.endswith(":self._compiled:mode_flag")


def test_cache_key_flag_derived_via_helper_is_clean(tmp_path):
    rep = _run(tmp_path, {
        "paddle_tpu/inference/eng.py": _CK_ENGINE % "(bucket,) + _flags()",
    }, ["cache-key"])
    assert not rep.for_rule("cache-key")


def test_cache_key_unguarded_one_time_build_is_not_a_cache(tmp_path):
    rep = _run(tmp_path, {"paddle_tpu/inference/eng2.py": """
        import jax
        from paddle_tpu.flags import flag_value

        class Eng:
            def _build(self):
                def run(x):
                    if flag_value("mode_flag"):
                        return x * 2
                    return x
                return jax.jit(run)

            def prime(self):
                # one-time unguarded build: trace-host-state's problem
                # (the read is still flagged there), not a cache-key one
                self._step = self._build()
    """}, ["cache-key"])
    assert not rep.for_rule("cache-key")


def test_cache_key_attribute_cache_with_freshness_guard(tmp_path):
    src = """
        import jax
        from paddle_tpu.flags import flag_value

        class Eng:
            def __init__(self):
                self._step = None

            def _build(self):
                def run(x):
                    if flag_value("mode_flag"):
                        return x * 2
                    return x
                return jax.jit(run)

            def step(self, x):
                if self._step is None:
                    self._step = self._build()
                return self._step(x)
    """
    rep = _run(tmp_path, {"paddle_tpu/inference/eng3.py": src},
               ["cache-key"])
    hits = rep.for_rule("cache-key")
    assert len(hits) == 1 and "mode_flag" in hits[0].message


def test_dtype_and_cache_key_suppression(tmp_path):
    """The shared disable=/baseline machinery covers the new families
    exactly like the PR 8 ones — same line scoping, same rule-id
    matching."""
    src = _DT_HEADER + """
    def build():
        def run(x):
            a = x.astype(jnp.bfloat16)
            w = jnp.zeros((4, 4), jnp.float32)
            # tpu-lint: disable=dtype-flow
            return jnp.einsum("ij,jk->ik", a, w)
        return jax.jit(run)
    """
    rep = _run(tmp_path, {"paddle_tpu/ops/mod.py": src}, ["dtype-flow"])
    assert not rep.for_rule("dtype-flow")

    eng = (_CK_ENGINE % "(bucket,)").replace(
        "self._compiled[key] = self._build()",
        "self._compiled[key] = self._build()"
        "  # tpu-lint: disable=cache-key")
    rep2 = _run(tmp_path, {"paddle_tpu/inference/eng.py": eng},
                ["cache-key"])
    assert not rep2.for_rule("cache-key")


# ---------------------------------------------------------------------------
# whole-package: new families clean + budget
# ---------------------------------------------------------------------------

def test_new_families_clean_on_tree_and_inside_budget():
    """The three dataflow families alone run the real tree inside the
    whole-package budget and come back clean against the baseline (the
    all-rules <5 s assertion lives in test_static_analysis)."""
    t0 = time.perf_counter()
    rep = analysis.run_repo(rules=list(DATAFLOW_RULES))
    elapsed = time.perf_counter() - t0
    # same 1-core-container allowance as test_static_analysis's budget
    budget = 5.0 if (os.cpu_count() or 1) > 1 else 10.0
    assert elapsed < budget, f"dataflow rules took {elapsed:.2f}s"
    assert not rep.new, "\n".join(f.text() for f in rep.new)
    assert not rep.stale
    # the deliberate speculative grow_to is baselined WITH a reason
    base = analysis.Baseline.load(analysis.BASELINE_PATH)
    leak_entries = {fp: why for fp, why in base.entries.items()
                    if ":page-leak:" in fp}
    assert leak_entries and all(why for why in leak_entries.values())


# ---------------------------------------------------------------------------
# CLI: SARIF + --changed-only
# ---------------------------------------------------------------------------

def test_sarif_output(tmp_path, capsys):
    from paddle_tpu.analysis.__main__ import main
    bad = tmp_path / "paddle_tpu" / "x.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import http.server\n")
    rc = main(["--root", str(tmp_path), "--no-baseline",
               "--rules", "layer-http", "--format", "sarif"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert doc["version"] == "2.1.0"
    (run,) = doc["runs"]
    assert run["tool"]["driver"]["name"] == "tpu-lint"
    (result,) = run["results"]
    assert result["ruleId"] == "layer-http"
    assert result["level"] == "error"
    assert result["locations"][0]["physicalLocation"][
        "artifactLocation"]["uri"] == "paddle_tpu/x.py"
    assert result["partialFingerprints"]["tpuLint/v1"].startswith(
        "paddle_tpu/x.py:layer-http:")


def _git(root, *args):
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    *args], cwd=root, check=True, capture_output=True)


def test_changed_only_scopes_to_diff_plus_reverse_deps(tmp_path, capsys):
    from paddle_tpu.analysis.__main__ import main
    pkg = tmp_path / "paddle_tpu"
    pkg.mkdir(parents=True)
    (pkg / "a.py").write_text("X = 1\n")
    (pkg / "b.py").write_text("from paddle_tpu.a import X\n"
                              "import http.server\n")
    (pkg / "c.py").write_text("import socket\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (pkg / "a.py").write_text("X = 2\n")    # only a.py changes
    rc = main(["--root", str(tmp_path), "--no-baseline",
               "--changed-only", "HEAD", "--format", "json"])
    out = capsys.readouterr()
    doc = json.loads(out.out)
    files = {f["file"] for f in doc["findings"]}
    # b.py rides along (reverse dependency of the changed a.py); c.py's
    # socket violation is out of scope for this run
    assert "paddle_tpu/b.py" in files
    assert "paddle_tpu/c.py" not in files
    assert rc == 1
    assert "2 file(s)" in out.err


def test_changed_only_clean_diff_is_fast_and_green(tmp_path, capsys):
    from paddle_tpu.analysis.__main__ import main
    pkg = tmp_path / "paddle_tpu"
    pkg.mkdir(parents=True)
    (pkg / "a.py").write_text("X = 1\n")
    (pkg / "c.py").write_text("import socket\n")   # pre-existing, untouched
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    t0 = time.perf_counter()
    rc = main(["--root", str(tmp_path), "--no-baseline",
               "--changed-only", "HEAD"])
    assert time.perf_counter() - t0 < 1.0   # the pre-commit contract
    capsys.readouterr()
    assert rc == 0


def test_changed_only_closure_includes_package_inits(tmp_path):
    """A package ``__init__.py``'s one-dot relative import refers to the
    package ITSELF (its modname already is the package), so re-exporting
    __init__ files must land in the reverse-dependency closure —
    before the fix the base resolved one level too high and they were
    silently skipped by pre-commit runs."""
    from paddle_tpu.analysis.__main__ import changed_closure
    pkg = tmp_path / "paddle_tpu" / "sub"
    pkg.mkdir(parents=True)
    (tmp_path / "paddle_tpu" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("from .engine import X\n")
    (pkg / "engine.py").write_text("X = 1\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (pkg / "engine.py").write_text("X = 2\n")
    closure = changed_closure(tmp_path, ("paddle_tpu",), "HEAD")
    assert "paddle_tpu/sub/engine.py" in closure
    assert "paddle_tpu/sub/__init__.py" in closure


def test_changed_only_closure_includes_bare_relative_imports(tmp_path):
    """``from . import format as fmt`` depends on the SUBMODULE, not
    just the package — before the fix only the bare package name was
    recorded, so a change to ``format.py`` left this dependent out of
    the closure and a pre-commit run could report clean with a new
    finding in it."""
    from paddle_tpu.analysis.__main__ import changed_closure
    pkg = tmp_path / "paddle_tpu" / "obs"
    pkg.mkdir(parents=True)
    (tmp_path / "paddle_tpu" / "__init__.py").write_text("")
    (pkg / "__init__.py").write_text("")
    (pkg / "format.py").write_text("X = 1\n")
    (pkg / "registry.py").write_text("from . import format as fmt\n")
    # absolute form of the same gap: the submodule, not the package,
    # is the dependency
    (pkg / "server.py").write_text("from paddle_tpu.obs import format\n")
    (pkg / "other.py").write_text("Y = 1\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (pkg / "format.py").write_text("X = 2\n")
    closure = changed_closure(tmp_path, ("paddle_tpu",), "HEAD")
    assert "paddle_tpu/obs/registry.py" in closure
    assert "paddle_tpu/obs/server.py" in closure
    assert "paddle_tpu/obs/other.py" not in closure


def test_changed_only_root_below_git_toplevel(tmp_path):
    """Review fix (PR 15): ``git diff --name-only`` emits toplevel-
    relative paths; without ``--relative`` a --root below the toplevel
    matched nothing and the scoped run silently analyzed (almost)
    nothing with exit 0."""
    from paddle_tpu.analysis.__main__ import changed_closure
    root = tmp_path / "checkout"
    pkg = root / "paddle_tpu"
    pkg.mkdir(parents=True)
    (pkg / "a.py").write_text("X = 1\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (pkg / "a.py").write_text("X = 2\n")
    closure = changed_closure(root, ("paddle_tpu",), "HEAD")
    assert closure == {"paddle_tpu/a.py"}


def test_changed_only_includes_untracked_files(tmp_path):
    """Brand-new files never show in ``git diff --name-only REF`` until
    staged — the pre-commit mode must still analyze them (before the
    fix a leak in a new file reported a clean 0-finding run)."""
    from paddle_tpu.analysis.__main__ import changed_closure
    pkg = tmp_path / "paddle_tpu"
    pkg.mkdir(parents=True)
    (pkg / "a.py").write_text("X = 1\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (pkg / "fresh.py").write_text("import socket\n")   # untracked
    closure = changed_closure(tmp_path, ("paddle_tpu",), "HEAD")
    assert "paddle_tpu/fresh.py" in closure
    assert "paddle_tpu/a.py" not in closure


def test_int_promotion_uses_widths_not_lexicographic():
    """int8 x int16 promotes to int16 (lexicographic comparison said
    int8); equal-width signed/unsigned mixes (numpy: int16) and unknown
    tokens fall to TOP — an unknown dtype only loses recall, a wrong
    one mints false mixed-dtype findings downstream."""
    from paddle_tpu.analysis.dataflow import TOP, _promote
    assert _promote("int8", "int16") == "int16"
    assert _promote("int16", "int8") == "int16"
    assert _promote("uint8", "int64") == "int64"
    assert _promote("int8", "uint8") is TOP
    assert _promote("int8", "bool") is TOP
    assert _promote("bfloat16", "int8") == "bfloat16"


def test_changed_only_rejects_write_baseline(tmp_path, capsys):
    from paddle_tpu.analysis.__main__ import main
    pkg = tmp_path / "paddle_tpu"
    pkg.mkdir(parents=True)
    (pkg / "a.py").write_text("X = 1\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    rc = main(["--root", str(tmp_path), "--changed-only", "HEAD",
               "--write-baseline"])
    capsys.readouterr()
    assert rc == 2


# ---------------------------------------------------------------------------
# triage regressions: one genuine defect per family stays fixed
# ---------------------------------------------------------------------------

def _tiny_engine(**over):
    from paddle_tpu.inference.decoding import (ContinuousBatchingEngine,
                                               GenerationConfig)
    from paddle_tpu.models import llama as L
    cfg = L.llama_tiny(num_hidden_layers=1)
    kw = dict(num_slots=2, page_size=4, max_seq_len=32, chunk=4)
    kw.update(over)
    eng = ContinuousBatchingEngine(
        cfg, GenerationConfig(max_new_tokens=4, seed=0), **kw)
    params = L.init_stacked_params(cfg, seed=0)
    return eng, params


@contextmanager
def _ledger_boom(fail_at=1):
    """Arm the memory ledger with a note_request that raises on the
    ``fail_at``-th call — a REAL in-window raise site of _admit_window
    (between allocate and the slot hand-off)."""
    from paddle_tpu.observability.memory import memory_ledger
    calls = {"n": 0}
    orig = memory_ledger.note_request

    def boom(*a, **k):
        calls["n"] += 1
        if calls["n"] >= fail_at:
            raise RuntimeError("injected admission fault")
        return orig(*a, **k)

    memory_ledger.reset()
    memory_ledger.arm()
    memory_ledger.note_request = boom
    try:
        yield
    finally:
        memory_ledger.note_request = orig
        memory_ledger.disarm()
        memory_ledger.reset()


def test_admission_failure_frees_pages_and_requeues():
    """page-leak triage (PR 15): anything raising between allocate and
    the slot hand-off in _admit_pick must return the pages and requeue
    the request — before the fix the pages leaked and the request was
    silently dropped."""
    eng, params = _tiny_engine(prefix_cache=True)
    prompt = np.arange(1, 9, dtype=np.int32)
    rid = eng.submit(prompt)

    with _ledger_boom():
        with pytest.raises(RuntimeError, match="injected"):
            eng.step(params)
        eng.mgr.check_conservation()        # no page left behind
        assert eng.num_queued == 1          # the request survived
    for _ in range(64):
        eng.step(params)
        if rid in eng._finished:
            break
    assert rid in eng._finished and len(eng._finished[rid]) == 4
    eng.mgr.check_conservation()


def test_admission_failure_rolls_back_every_picked_request():
    """Review fix (PR 15): the admission rollback covers the WHOLE
    window, not just the current iteration — with two requests picked
    into two slots in one step, a raise during the window frees BOTH
    allocations and requeues both; before the fix only the in-flight
    request was rolled back while the earlier pick's pages leaked
    (never reaching _slot_rid, invisible to cancel/retire) and its
    request silently vanished."""
    eng, params = _tiny_engine(prefix_cache=True)
    p1 = np.arange(1, 9, dtype=np.int32)
    p2 = np.arange(3, 11, dtype=np.int32)
    r1 = eng.submit(p1)
    r2 = eng.submit(p2)

    with _ledger_boom(fail_at=2):           # both picked, then raise
        with pytest.raises(RuntimeError, match="injected"):
            eng.step(params)
        eng.mgr.check_conservation()        # no page left behind
        assert eng.num_queued == 2          # BOTH requests survived
    for _ in range(64):
        eng.step(params)
        if r1 in eng._finished and r2 in eng._finished:
            break
    assert len(eng._finished[r1]) == 4
    assert len(eng._finished[r2]) == 4
    eng.mgr.check_conservation()


def test_stats_sink_failure_does_not_abort_admission():
    """Review fix (PR 15): cache.record is stats-only and runs AFTER
    the admission window commits — a broken sink must neither tear the
    window down (rolling back would re-admit and double-count the hits
    already recorded) nor leak pages; the serve completes normally."""
    eng, params = _tiny_engine(prefix_cache=True)
    prompt = np.arange(1, 9, dtype=np.int32)
    rid = eng.submit(prompt)

    orig = eng.cache.record
    eng.cache.record = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("broken stats sink"))
    try:
        eng.step(params)                    # must NOT raise
    finally:
        eng.cache.record = orig
    assert eng.num_queued == 0              # admission stuck
    for _ in range(64):
        eng.step(params)
        if rid in eng._finished:
            break
    assert rid in eng._finished and len(eng._finished[rid]) == 4
    eng.mgr.check_conservation()


def test_backend_flag_flip_retraces_unified_step():
    """cache-key triage (PR 15): the kernel-backend selectors
    (use_pallas_kernels / use_pallas_rms_norm) are read at trace time,
    so every guarding compile-cache key must derive from them — a
    set_flags flip now rebuilds the unified program as a counted
    recompile instead of silently serving the old backend."""
    from paddle_tpu.flags import get_flags, set_flags
    eng, params = _tiny_engine()
    # a budget that outlasts the two dispatches the first step enqueues:
    # the flip reaches the next dispatch the engine enqueues
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=12)
    eng.step(params)
    first = eng._unified_step
    flags0 = eng._unified_flags
    assert first is not None and len(flags0) == 3
    saved = get_flags("use_pallas_rms_norm")
    try:
        set_flags({"use_pallas_rms_norm":
                   not saved["use_pallas_rms_norm"]})
        eng.step(params)
        assert eng._unified_flags != flags0
        assert eng._unified_step is not first   # retraced, not stale
    finally:
        set_flags(saved)


def test_quantized_training_layer_keeps_residual_carry_dtype():
    """dtype-flow triage (PR 15): _decoder_layer_manual (the shard_map
    training layer) silently widened the residual stream to f32 when
    weights are int8-quantized dicts (weight_dequantize returns f32) —
    the serving scan paths pin the carry dtype and now the training
    layer does too. Dense weights are untouched (the cast is a no-op)."""
    import jax.numpy as jnp
    from paddle_tpu.models import llama as L
    from paddle_tpu.ops import rope as rope_ops

    cfg = L.llama_tiny(num_hidden_layers=1)
    params = L.init_stacked_params(cfg, seed=0)
    p = {k: v[0] for k, v in params["layers"].items()} \
        if "layers" in params else None
    if p is None:
        # stacked layout keys live at the top level with a leading L axis
        names = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up",
                 "w_down")
        p = {k: params[k][0] for k in names}

    def quantize(w):
        w = np.asarray(w, np.float32)
        scale = np.abs(w).max(axis=0) / 127.0 + 1e-8
        q = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
        return {"q": jnp.asarray(q), "scale": jnp.asarray(scale)}

    pq = dict(p)
    for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        pq[k] = quantize(p[k])
    x = jnp.ones((1, 4, cfg.hidden_size), jnp.bfloat16)
    cos, sin = rope_ops.build_rope_cache(4, cfg.head_dim, cfg.rope_theta)
    out_q = L._decoder_layer_manual(pq, x, cos, sin, cfg, None, None)
    assert out_q.dtype == jnp.bfloat16, (
        "quantized weights widened the residual carry to "
        f"{out_q.dtype}")
    out_d = L._decoder_layer_manual(
        {k: jnp.asarray(v, jnp.bfloat16) if k.startswith(("w", "ln"))
         else v for k, v in p.items()}, x, cos, sin, cfg, None, None)
    assert out_d.dtype == jnp.bfloat16
