"""The expert layer's routing record in the profiler trace, and the work a
grouped expert product HAS to do for it.

A model with experts returns, with each dispatch's tokens, how its router
spread them; the engine writes the sums as four integer stats on its
``cbe.unpack`` span (``ContinuousBatchingEngine._expert_stats``):
``expert_calls`` (micro-rounds x expert layers: calls of the expert layer),
``experts_hit`` (over those calls, the experts that received a token),
``expert_assignments`` (token-expert pairs computed) and ``max_expert_load``
(over those calls, the largest number of pairs one expert received). A
program without experts, or an older commit, writes none: ``for_obs``
returns None and every reader built on it leaves its metric out.

``required_work`` counts what the three grouped products of a SwiGLU expert
layer (gate, up, down; the Pallas kernel ``moe_grouped_matmul`` on the chip)
must move and compute whatever implements them: the weights of the experts
HIT, once; each assignment's row in and out of each product; 2 FLOPs a
multiply-add. Nothing here imports the program.
"""

from __future__ import annotations

from typing import Dict, Optional

from . import program_trace

UNPACK = "cbe.unpack"
STATS = ("expert_calls", "experts_hit", "expert_assignments",
         "max_expert_load")

_CACHE: Dict[str, Optional[Dict]] = {}


def reduce(trace: Dict) -> Optional[Dict]:
    """Sums of the routing stats over the trace's ``cbe.unpack`` spans, and
    how many spans carried them; None where none did."""
    spans = [s[3] for s in trace.get("spans", [])
             if s[0] == UNPACK and "expert_calls" in s[3]]
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


def required_work(stats: Dict, config: Dict, itemsize: int = 2) -> Dict:
    """Bytes and FLOPs of the grouped products behind ``stats`` (sums over
    any number of calls; everything is linear)."""
    h, m = config["hidden_size"], config["moe_intermediate_size"]
    weights = stats["experts_hit"] * 3 * h * m * itemsize
    # gate and up read a row of h and write one of m each; down the reverse
    rows = stats["expert_assignments"] * 3 * (h + m) * itemsize
    return {"bytes": weights + rows,
            "flops": 2.0 * stats["expert_assignments"] * 3 * h * m}
