"""Expert layer, ReGLU experts: of the intermediate units the grouped
products computed for the assignments made (assignments x the expert
width), the share that ReLU left non-zero: ``100 x sum expert_active_units
/ sum expert_units``, two routing stats on the program's ``cbe.unpack``
spans, over the traced window's complete dispatches (an unpack inside a
``cbe.step`` that lies inside the window). What an expert product that
skipped the inactive units could leave out. A program that writes neither
stat (a SwiGLU family, an older commit) gives None."""

from typing import Dict, Optional

from perfbench import expert_work, program_trace

_CACHE: Dict[str, Optional[float]] = {}


def share(trace: Dict) -> Optional[float]:
    """The reading from a loaded trace (``program_trace.load``'s form)."""
    spans = trace.get("spans", [])
    window = trace.get("window") or (float("-inf"), float("inf"))
    steps = [(s[1], s[1] + s[2]) for s in spans
             if s[0] == program_trace.STEP
             and window[0] <= s[1] and s[1] + s[2] <= window[1]]
    active = units = 0
    for name, start, _, stats in spans:
        if name == expert_work.UNPACK and "expert_units" in stats \
                and any(a <= start < b for a, b in steps):
            active += int(stats["expert_active_units"])
            units += int(stats["expert_units"])
    return 100.0 * active / units if units else None


def read(obs):
    if obs.trace is None:
        return None
    path = program_trace.find_xplane(obs.cell.name)
    if path is None:
        return None
    if path not in _CACHE:
        _CACHE[path] = share(program_trace.load(path))
    return _CACHE[path]
