"""From a profiler trace to numbers: device busy time (the union of the
intervals in which an operation ran), idle gaps and what the host was doing
in them, self time by operation name, collective time and its exposed part.

Two stages, so that the arithmetic is testable without a chip:

  ``load_xplane(path)`` reads the ``.xplane.pb`` the JAX profiler wrote (with
  ``jax.profiler.ProfileData``, nothing else) into plain lists;
  ``reduce_trace(trace)`` does the arithmetic on those lists.

A trace is ``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
"modules": {plane: [[name, start_ns, dur_ns], ...]},
"host": [[name, start_ns, dur_ns], ...]}``: ``devices`` holds one event per
executed operation, ``modules`` one per executed program (a dispatch), and
``host`` the benchmark's own spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``), which the profiler puts on the same clock
as the device events.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: device planes and the line of each that holds one event per executed op
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."
#: HLO names of operations that move data between chips
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler.start_trace`` dir."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def op_name(event_name: str) -> str:
    """The profiler names a device event by its whole HLO instruction
    (``%fusion.3 = bf16[...] fusion(...)``); keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> Dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List] = {}
    modules: Dict[str, List] = {}
    host: List = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                into = {OPS_LINE: devices, MODULES_LINE: modules}.get(
                    line.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        [op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX))
    return {"devices": devices, "modules": modules, "host": host}


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``a`` (merged) that ``b`` (merged) does not cover."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(events: Sequence[Sequence]) -> List[Tuple]:
    """(name, start, end, self time, is_leaf) of each event of ONE line.
    Events of a line nest (a ``while`` spans the ops of its body): an
    event's self time is its duration less its direct children's, so that
    times by name add up to the busy time and nothing is counted twice."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[List] = []
    stack: List[Tuple[float, int]] = []     # (end, index into out)
    for name, start, dur in order:
        end = start + dur
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[3] -= dur
            parent[4] = False
        out.append([name, start, end, dur, True])
        stack.append((end, len(out) - 1))
    return [(n, a, b, max(t, 0.0), leaf) for n, a, b, t, leaf in out]


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class TraceSummary:
    window_s: float
    chips: int
    busy_s: float                        # mean over chips
    idle_share: float                    # mean over chips
    idle_share_worst: float
    op_seconds: Dict[str, float]         # self time by name, mean over chips
    collective_s: float                  # mean over chips
    collective_exposed_s: float
    idle_gaps: List[Tuple[str, float]]   # longest gaps of the first chip,
    #                                      labelled by the host span around
    dispatch_s: float = 0.0              # device time of one execution of
    #                                      the program that took most time
    dispatches: float = 0.0              # how many of them the window holds
    #                                      (a cut one counts by its share)

    def top_ops(self, n: int = 10) -> List[List]:
        ranked = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ranked[:n]]

    def seconds_of(self, pattern: str) -> float:
        """Self time of the operations whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_seconds.items() if rx.search(k))


def _label(gap: Interval, spans: Sequence[Sequence]) -> str:
    """The innermost benchmark span around the gap's midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    best = None
    for name, start, dur in spans:
        if start <= mid <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else "outside any bench span"


def _dispatches(modules: Dict, window: Interval) -> Tuple[float, float]:
    """Of the first chip's programs, the one that took most time: the mean
    duration of its executions that lie whole inside the window, and how many
    executions the window holds, one cut by an edge counted by the share of
    it that is inside."""
    events = next((v for _, v in sorted(modules.items()) if v), [])
    by_name: Dict[str, List] = {}
    for name, s, d in events:
        name = name.split("(", 1)[0]            # jit_run(<fingerprint>)
        inside = min(s + d, window[1]) - max(s, window[0])
        if inside > 0 and d > 0:
            by_name.setdefault(name, []).append((d, inside))
    if not by_name:
        return 0.0, 0.0
    runs = max(by_name.values(), key=lambda r: sum(i for _, i in r))
    whole = [d for d, inside in runs if inside >= d] or [d for d, _ in runs]
    return sum(whole) / len(whole), sum(inside / d for d, inside in runs)


def reduce_trace(trace: Dict, max_gaps: int = 10) -> Optional[TraceSummary]:
    """None when no operation ran on a device in the trace."""
    devices = {k: v for k, v in sorted(trace["devices"].items()) if v}
    if not devices:
        return None
    host = trace.get("host", [])
    if host:
        window = (min(s for _, s, _ in host),
                  max(s + d for _, s, d in host))
    else:
        window = (min(e[1] for ev in devices.values() for e in ev),
                  max(e[1] + e[2] for ev in devices.values() for e in ev))
    ns = 1e-9
    busy, idle, coll, exposed = [], [], [], []
    ops: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for i, events in enumerate(devices.values()):
        inside = [[n, max(s, window[0]),
                   min(s + d, window[1]) - max(s, window[0])]
                  for n, s, d in events
                  if min(s + d, window[1]) > max(s, window[0])]
        merged = union([(s, s + d) for _, s, d in inside])
        busy.append(total(merged) * ns)
        idle.append(1.0 - total(merged) / (window[1] - window[0]))
        timed = self_times(inside)
        for name, _, _, t, _ in timed:
            ops[name] = ops.get(name, 0.0) + t * ns
        leaves = [(a, b, bool(COLLECTIVE.match(name)))
                  for name, a, b, _, leaf in timed if leaf]
        c = union([(a, b) for a, b, is_c in leaves if is_c])
        compute = union([(a, b) for a, b, is_c in leaves if not is_c])
        coll.append(total(c) * ns)
        exposed.append(total(subtract(c, compute)) * ns)
        if i == 0:
            idle_iv = subtract([window], merged)
            longest = sorted(idle_iv, key=lambda g: g[0] - g[1])[:max_gaps]
            gaps = [(_label(g, host), (g[1] - g[0]) * ns) for g in longest]
    dispatch_s, dispatches = _dispatches(trace.get("modules", {}), window)
    n = len(devices)
    return TraceSummary(
        window_s=(window[1] - window[0]) * ns, chips=n,
        busy_s=sum(busy) / n, idle_share=sum(idle) / n,
        idle_share_worst=max(idle),
        op_seconds={k: v / n for k, v in ops.items()},
        collective_s=sum(coll) / n, collective_exposed_s=sum(exposed) / n,
        idle_gaps=gaps, dispatch_s=dispatch_s * ns,
        dispatches=dispatches)
