"""The host work of a serving round that is no engine phase of its own, by
cause: the scheduler's admission, the prefix index's walks and the garbage
collector's pauses, as the program's spans in the profiler trace name them.

Since PR 35 the program writes, beside its seven ``cbe.*`` phases, six spans
named ``paddle_serving.<word>`` with integer stats:

  ``admit``          ``ServingScheduler._admit`` over a queue that holds
                     something (``queued``, ``handed``, ``deferred``); the
                     ``prefix_peek`` and any ``prefix_evict`` of its loop
                     are INSIDE it and counted in its time
  ``prefix_peek``    the scheduler's sizing walk (``tokens``, ``blocks``)
  ``prefix_lookup``  the engine's admission walk, in ``cbe.admit`` (same)
  ``prefix_insert``  a retired sequence's conversion to Python ints and its
                     insertion, in ``cbe.unpack`` (``tokens``, ``pages``)
  ``prefix_evict``   an eviction, from either admission (``asked``,
                     ``pages``)
  ``gc``             one garbage collection (``generation``), on whichever
                     thread set it off; it holds every thread

``program_trace.load`` already returns them (it keeps every host event named
``cbe.*`` or ``paddle_serving.*``, of every thread) and
``program_trace.reduce`` leaves them out of its own arithmetic. ``reduce``
here gives, per name: how many spans STARTED inside the window, their summed
duration, the part of that a collection took (``gc_s``), their stats summed
(for ``gc``: the collections of each generation, ``generation<g>``), and the
part of the first chip's idle time that lies inside them. A
collection takes precedence over the span it interrupted: idle time inside a
``gc`` span is the collector's and no other span's, by intervals and not by
nesting, because the collection may have run on another thread.

A trace that holds NONE of the six names is an older program's: ``reduce``
returns None and every reader built on it leaves its metric out. Nothing
here imports the program.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

from . import program_trace
from .trace_reduce import subtract, total, union

PREFIX = "paddle_serving."
ADMIT, GC = PREFIX + "admit", PREFIX + "gc"
PEEK, LOOKUP = PREFIX + "prefix_peek", PREFIX + "prefix_lookup"
INSERT, EVICT = PREFIX + "prefix_insert", PREFIX + "prefix_evict"
#: the prefix index on the host, whichever phase it ran in
WALKS = (PEEK, LOOKUP, INSERT, EVICT)
NAMES = (ADMIT,) + WALKS + (GC,)
LONGEST = 10

_CACHE: Dict[str, Optional[Dict]] = {}


def _inside(idle: List, intervals: List) -> float:
    """Of the merged ``idle`` intervals, the time that ``intervals``
    (merged) cover."""
    return total(subtract(idle, subtract(idle, intervals)))


def reduce(trace: Dict) -> Optional[Dict]:
    """Times in seconds. ``blocks_walked`` is the work the walks were asked
    for: the radix nodes the two lookups matched plus the blocks of the
    sequences inserted (``tokens`` over the page size the program's
    ``cbe.dispatch`` records state); None where the trace states no page
    size."""
    every = trace.get("spans", [])
    mine = [s for s in every if s[0] in NAMES]
    if not mine:
        return None
    ns = 1e-9
    ops = trace.get("ops", [])
    window = trace.get("window")
    if not window:          # left out: the extent of what the trace holds
        events = [e[:3] for e in ops] + [s[:3] for s in every]
        window = (min(s for _, s, _ in events),
                  max(s + d for _, s, d in events))
    window = tuple(window)
    mine = [s for s in mine if window[0] <= s[1] < window[1]]
    busy = union([(max(s, window[0]), min(s + d, window[1]))
                  for _, s, d in ops])
    idle = subtract([window], busy)
    collecting = union([(s, s + d) for name, s, d, _ in mine if name == GC])

    by_name = {}
    for name in NAMES:
        spans = [s for s in mine if s[0] == name]
        held = union([(s, s + d) for _, s, d, _ in spans])
        own = held if name == GC else subtract(held, collecting)
        stats: Dict[str, int] = {}
        for *_, found in spans:
            for key, value in found.items():
                if name == GC:          # collections by generation
                    key, value = f"{key}{int(value)}", 1
                stats[key] = stats.get(key, 0) + int(value)
        by_name[name] = {
            "count": len(spans),
            "s": sum(d for _, _, d, _ in spans) * ns,
            "gc_s": total(subtract(held, own)) * ns,
            "idle_s": _inside(idle, own) * ns,
            "stats": stats}
    page = next((int(s[3]["page_size"]) for s in reversed(every)
                 if s[0] == program_trace.DISPATCH and "page_size" in s[3]),
                None)
    walked = [by_name[name]["stats"].get(key, 0) for name, key in
              ((PEEK, "blocks"), (LOOKUP, "blocks"), (INSERT, "tokens"))]
    longest = sorted(mine, key=lambda s: -s[2])[:LONGEST]
    return {
        "window_s": (window[1] - window[0]) * ns,
        "idle_s": total(idle) * ns,
        "by_name": by_name,
        "walk_s": sum(by_name[name]["s"] for name in WALKS),
        "page_size": page,
        "blocks_walked": None if not page else (
            walked[0] + walked[1] + walked[2] / page),
        "longest": [{"name": name, "at_s": (s - window[0]) * ns,
                     "s": d * ns,
                     "stats": {k: int(v) for k, v in found.items()}}
                    for name, s, d, found in longest],
    }


def for_obs(obs) -> Optional[Dict]:
    """``reduce`` of the run's trace with ``dispatches`` (the trace's own
    count of the window's dispatches, a cut one by its share, as
    ``program_trace.for_obs`` divides); None when the run has no device
    trace, the window holds no dispatch or the program wrote none of the
    six spans. Keeps ``host_spans.json`` beside the trace and prints the
    summary as one line of stdout (before the result line)."""
    if obs.trace is None or not obs.trace.dispatches:
        return None
    path = program_trace.find_xplane(obs.cell.name)
    if path is None:
        return None
    if path in _CACHE:
        return _CACHE[path]
    out = _CACHE[path] = reduce(program_trace.load(path))
    if out is None:
        return None
    out["dispatches"] = obs.trace.dispatches
    per = 1e3 / out["dispatches"]
    summary = {
        "xplane": os.path.relpath(path, program_trace.ROOT),
        "window_s": out["window_s"], "idle_s": out["idle_s"],
        "dispatches_in_window": out["dispatches"],
        "page_size": out["page_size"],
        "per_dispatch": {
            name: {"count": v["count"] / out["dispatches"],
                   "ms": per * v["s"], "gc_ms": per * v["gc_s"],
                   "idle_ms": per * v["idle_s"],
                   "stats": {k: x / out["dispatches"]
                             for k, x in v["stats"].items()}}
            for name, v in out["by_name"].items()},
        "longest": out["longest"],
    }
    run_dir = path.split(os.sep + "trace" + os.sep)[0]
    with open(os.path.join(run_dir, "host_spans.json"), "w") as f:
        json.dump(dict(summary, totals=out["by_name"]), f, indent=1)
    print(json.dumps({"host_spans": summary}), flush=True)
    return out


def uses_prefix_cache(obs) -> bool:
    """A cell whose configuration turns the prefix cache off has no prefix
    index to read: its ``prefix.*`` metrics are left out, not read as 0."""
    return bool(obs.cell.config.get("serving", {}).get("prefix_cache"))
