"""The routing record of an expert layer whose router has ZERO-COMPUTE
experts (outputs that name no weights: the token itself, weighted), in the
profiler trace.

Beside the four stats of ``perfbench/expert_work.py`` (the experts HELD
only), the engine writes two more on the ``cbe.unpack`` span of such a
model (``ContinuousBatchingEngine._expert_stats``):
``zero_expert_assignments`` (token-expert pairs that chose an identity) and
``router_assignments`` (valid tokens x k: every pair the router made,
whoever computes it). A model without zero-compute experts, or an older
commit, writes neither: ``for_obs`` returns None and every reader built on
it leaves its metric out. Nothing here imports the program.
"""

from __future__ import annotations

from typing import Dict, Optional

from . import expert_work, program_trace

STATS = ("expert_assignments", "zero_expert_assignments",
         "router_assignments")

_CACHE: Dict[str, Optional[Dict]] = {}


def reduce(trace: Dict) -> Optional[Dict]:
    """Sums of ``STATS`` over the trace's ``cbe.unpack`` spans that carry
    the router's count, and how many did; None where none did."""
    spans = [s[3] for s in trace.get("spans", [])
             if s[0] == expert_work.UNPACK and "router_assignments" in s[3]]
    if not spans:
        return None
    out = {key: sum(int(s.get(key, 0)) for s in spans) for key in STATS}
    out["dispatches"] = len(spans)
    return out


def for_obs(obs) -> Optional[Dict]:
    if obs.trace is None:
        return None
    path = program_trace.find_xplane(obs.cell.name)
    if path is None:
        return None
    if path not in _CACHE:
        _CACHE[path] = reduce(program_trace.load(path))
    return _CACHE[path]
