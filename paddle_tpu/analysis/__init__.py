"""tpu-lint: AST-based invariant analyzer for the paddle_tpu tree.

One parse per file, pluggable visitor rules, line suppressions and a
checked-in baseline (see :mod:`.engine`). Seven rule families protect
the stack's hard-won guarantees:

* **trace purity / recompile hazards** (:mod:`.purity`) — a call graph
  from every ``jax.jit``/``pallas_call`` root; wall-clock reads, host
  RNG, host syncs and shape-branching flagged inside traced code;
* **lock discipline** (:mod:`.locks`) — unguarded mutation of lock-
  guarded state and blocking calls under a held lock in ``serving/`` and
  ``observability/``;
* **metrics/events contracts** (:mod:`.contracts`) — every metric name,
  label tuple and event kind checked against
  ``observability/catalog.py``, both directions;
* **layering/encapsulation** (:mod:`.layering`) — declarative import and
  private-access contracts (subsuming the five retired regex lints) plus
  subsystem dependency direction;
* **resource flow / dtype flow / cache-key completeness**
  (:mod:`.dataflow`, tpu-lint v2) — interprocedural dataflow over a
  per-function CFG (exception edges included): paged acquisitions must
  release on every path, traced bf16/int8 chains must not silently
  promote, and every trace-time flag read must be derivable from the
  guarding compile-cache key.

CLI::

    python -m paddle_tpu.analysis [--format text|json|sarif]
                                  [--rules a,b] [--changed-only [REF]]
                                  [--write-baseline]

exits 1 on any unbaselined finding or stale baseline entry. Tests use
:func:`cached_report` so the whole suite pays for ONE analysis run.
"""

from __future__ import annotations

import functools
import gc
from pathlib import Path
from typing import Optional, Sequence

from .contracts import CONTRACT_RULES
from .dataflow import DATAFLOW_RULES
from .engine import (AnalysisEngine, Baseline, Finding, Project,  # noqa: F401
                     Report, SourceModule)
from .layering import LAYERING_RULES
from .locks import LOCK_RULES
from .purity import PURITY_RULES

#: repo root (…/paddle_tpu/analysis/__init__.py -> two levels up)
REPO_ROOT = Path(__file__).resolve().parents[2]
BASELINE_PATH = Path(__file__).resolve().parent / "baseline.txt"


def default_rules():
    return (*PURITY_RULES, *LOCK_RULES, *CONTRACT_RULES, *LAYERING_RULES,
            *DATAFLOW_RULES)


def run_repo(root: Optional[Path] = None,
             rules: Optional[Sequence] = None,
             baseline_path: Optional[Path] = BASELINE_PATH,
             roots: Optional[Sequence[str]] = None) -> Report:
    """One full analysis run over the repo (or any compatible tree).

    The cycle collector is paused for the run: it allocates about a
    million AST nodes that stay live until it ends, and each collection
    they trigger scans the whole process heap to free nothing (inside the
    test suite, that heap holds millions of jax objects)."""
    enabled = gc.isenabled()
    gc.disable()
    try:
        project = Project(root or REPO_ROOT,
                          roots=roots or ("paddle_tpu", "tests",
                                          "benchmarks"))
        baseline = (Baseline.load(baseline_path)
                    if baseline_path is not None else Baseline())
        engine = AnalysisEngine(
            rules if rules is not None else default_rules(), baseline)
        return engine.run(project)
    finally:
        if enabled:
            gc.enable()


@functools.lru_cache(maxsize=1)
def cached_report() -> Report:
    """The shared analysis run for the test suite: every ported lint
    test asserts over this ONE report instead of re-walking the tree."""
    return run_repo()
