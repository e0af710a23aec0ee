"""The PROGRAM'S own spans in the profiler trace, and what they say about the
gaps the device idles through and the work each dispatch asked for.

The serving engine wraps every stretch of its host loop in a span written
with ``jax.profiler.TraceAnnotation`` (``paddle_serving.step`` > ``cbe.step``
> ``cbe.admit`` ``plan`` ``upload`` ``dispatch`` ``fence`` ``unpack``
``audit``), so any profiler session holds them on the device trace's clock;
``cbe.dispatch`` carries ten integers, the dispatch's work record (``n``
``rounds`` ``token_slots`` ``prefill_tokens`` ``decode_tokens`` ``live_rows``
``attended_pages`` ``grid_steps`` ``causal_pairs`` ``page_size``). A program
that lacks them (an older commit, the train step) gives a trace without
``cbe.dispatch``, and every reader built on this file returns None.

Two stages, as in ``trace_reduce`` (whose interval arithmetic this imports):

  ``load(path)`` reads the ``.xplane.pb`` into plain lists;
  ``reduce(trace)`` does the arithmetic on those lists.

A trace is ``{"ops": [[name, start_ns, dur_ns], ...], "spans": [[name,
start_ns, dur_ns, {stat: value}], ...], "window": [start_ns, end_ns]}``:
``ops`` the FIRST chip's executed operations, ``spans`` every host event
named ``cbe.*`` or ``paddle_serving.*``, ``window`` the extent of the
benchmark's own ``bench.*`` spans — the window ``trace_reduce`` measures idle
time in (left out: the extent of ops and spans).

``for_obs(obs)`` finds the run's trace, reduces it once, adds the ragged
kernel's required work, keeps ``program_spans.json`` beside the trace and
prints the summary as one line of stdout (before the result line).
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional, Sequence

from . import metric_math, trace_reduce
from .metric_math import median
from .trace_reduce import subtract, total, union

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "perfbench_out")

PROGRAM_PREFIXES = ("cbe.", "paddle_serving.")
ROUND, STEP = "paddle_serving.step", "cbe.step"
DISPATCH, FENCE = "cbe.dispatch", "cbe.fence"
#: the record's keys that add up over dispatches
RECORD_SUMS = ("token_slots", "prefill_tokens", "decode_tokens", "live_rows",
               "attended_pages", "grid_steps", "causal_pairs")


def load(path: str) -> Dict:
    from jax.profiler import ProfileData
    ops: List = []
    spans: List = []
    bench: List = []
    first_chip = None
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            if first_chip is not None and plane.name >= first_chip:
                continue
            first_chip, ops = plane.name, [
                [trace_reduce.op_name(e.name), float(e.start_ns),
                 float(e.duration_ns)]
                for line in plane.lines if line.name == trace_reduce.OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIXES):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns), dict(e.stats)])
                    elif e.name.startswith(trace_reduce.HOST_SPAN_PREFIX):
                        bench.append((float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    trace = {"ops": ops, "spans": spans}
    if bench:
        trace["window"] = [min(a for a, _ in bench), max(b for _, b in bench)]
    return trace


def exclusive(spans: Sequence[Sequence]) -> Dict[str, List]:
    """``{name: merged intervals}`` in which a span of that name is the
    INNERMOST one. The spans are one thread's, so they nest."""
    order = sorted(spans, key=lambda e: (e[1], -e[2]))
    kids: List[List] = [[] for _ in order]
    stack: List = []                            # (end, index into order)
    for i, (_, start, dur, *_rest) in enumerate(order):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            kids[stack[-1][1]].append((start, start + dur))
        stack.append((start + dur, i))
    out: Dict[str, List] = {}
    for (name, start, dur, *_rest), inner in zip(order, kids):
        out.setdefault(name, []).extend(
            subtract([(start, start + dur)], union(inner)))
    return {name: union(iv) for name, iv in out.items()}


def reduce(trace: Dict) -> Optional[Dict]:
    """None when no operation ran on a device in the trace, or the program
    dispatched nothing in it. Times in seconds; ``dispatches`` holds one
    entry per COMPLETE dispatch: a ``cbe.step`` inside the window with its
    ``cbe.dispatch`` and ``cbe.fence`` (one cut by an edge of the session
    leaves phases without a ``cbe.step``, or a step without its fence, and
    is dropped)."""
    ops = trace.get("ops", [])
    # the engine's phases and the scheduler's round: one thread's, nested
    # (a capture window's other ``paddle_serving.*`` spans overlap freely)
    spans = [s for s in trace.get("spans", [])
             if s[0] == ROUND or s[0].startswith("cbe.")]
    if not ops or not any(s[0] == DISPATCH for s in spans):
        return None
    ns = 1e-9
    events = [e[:3] for e in ops] + [s[:3] for s in spans]
    window = tuple(trace.get("window") or (
        min(s for _, s, _ in events), max(s + d for _, s, d in events)))

    dispatches = []
    for _, start, dur, *_ in sorted((s for s in spans if s[0] == STEP),
                                    key=lambda s: s[1]):
        end = start + dur
        phases, record = {}, None
        for name, s, d, stats in spans:
            if name in (STEP, ROUND) or not start <= s < end:
                continue
            phases[name] = phases.get(name, 0.0) + d * ns
            if name == DISPATCH:
                record = {key: int(v) for key, v in stats.items()}
        if (record is None or FENCE not in phases
                or start < window[0] or end > window[1]):
            continue
        dispatches.append({"step_s": dur * ns,
                           "host_s": dur * ns - phases[FENCE],
                           "phases_s": phases, "record": record})
    if not dispatches:
        return None

    busy = union([(max(s, window[0]), min(s + d, window[1]))
                  for _, s, d in ops])
    idle = subtract([window], busy)
    by_name = {
        name: total(subtract(idle, subtract(idle, iv))) * ns
        for name, iv in exclusive(spans).items()}
    in_round = by_name.pop(ROUND, 0.0)
    idle_s = total(idle) * ns
    n = len(dispatches)
    return {
        "window_s": (window[1] - window[0]) * ns,
        "idle_s": idle_s,
        "idle_by_phase_s": dict(sorted(by_name.items())),
        "idle_in_round_outside_engine_s": in_round,
        "idle_outside_program_s": max(
            idle_s - in_round - sum(by_name.values()), 0.0),
        "dispatches": dispatches,
        "record_mean": {
            key: sum(d["record"].get(key, 0) for d in dispatches) / n
            for key in RECORD_SUMS},
        "page_size": dispatches[-1]["record"].get("page_size"),
    }


def required_work(record: Dict, config: Dict, chips: int,
                  itemsize: int = 2) -> Dict:
    """What the ragged paged-attention kernel HAS to do for one dispatch
    with this record (means will do: everything is linear), per chip of a
    ``chips``-way head-sharded mesh: read every attended K and V page once,
    read q and write o for every token-slot of the packed axis (bf16), and
    QK^T and PV (2 FLOPs each per element of ``head_dim``) for every
    query-key pair the causal mask lets through, in every layer."""
    layers = config["num_hidden_layers"]
    heads = config["num_attention_heads"] / chips
    kv_heads = config["num_key_value_heads"] / chips
    d = config.get("head_dim") or (config["hidden_size"]
                                   // config["num_attention_heads"])
    kv = (record["attended_pages"] * record["page_size"] * kv_heads * d
          * itemsize * 2)
    qo = record["token_slots"] * heads * d * itemsize * 2
    return {"bytes": layers * (kv + qo),
            "flops": layers * 4.0 * record["causal_pairs"] * heads * d}


# ---------------------------------------------------------------------------
_CACHE: Dict[str, Optional[Dict]] = {}


def find_xplane(cell_name: str) -> Optional[str]:
    """The newest ``.xplane.pb`` of the cell's traced runs (``Observations``
    carries no path; a run's own trace is the newest when its readers
    run)."""
    found = glob.glob(os.path.join(
        OUT_DIR, cell_name, "*-trace1", "trace", "plugins", "profile", "*",
        "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def for_obs(obs) -> Optional[Dict]:
    """``reduce`` of the run's trace plus ``required`` (the kernel's
    required bytes, FLOPs and least seconds for the mean record, per
    dispatch and chip); None when the run has no device trace or the
    program wrote no ``cbe.dispatch`` into it."""
    if obs.trace is None:
        return None
    path = find_xplane(obs.cell.name)
    if path is None:
        return None
    if path in _CACHE:
        return _CACHE[path]
    out = _CACHE[path] = reduce(load(path))
    if out is None:
        return None
    mean = dict(out["record_mean"], page_size=out["page_size"])
    work = required_work(mean, obs.cell.config, obs.cell.chips)
    out["required"] = dict(work, **metric_math.roofline_seconds(
        work["flops"], work["bytes"], obs.peaks))
    done = out["dispatches"]
    names = sorted({p for d in done for p in d["phases_s"]})
    # the window's idle time is every round's, cut ones too: per dispatch
    # by the trace's own count (a cut dispatch counts by its share)
    per = 1e3 / (obs.trace.dispatches or len(done))
    kernel_s = obs.trace.seconds_of("ragged_paged_attention")
    summary = {
        "xplane": os.path.relpath(path, ROOT),
        "window_s": out["window_s"], "idle_s": out["idle_s"],
        "dispatches_in_window": obs.trace.dispatches,
        "dispatches": len(done),
        "idle_ms_per_dispatch_by_phase": {
            k: per * v for k, v in out["idle_by_phase_s"].items()},
        "idle_in_round_outside_engine_ms_per_dispatch":
            per * out["idle_in_round_outside_engine_s"],
        "idle_outside_program_ms_per_dispatch":
            per * out["idle_outside_program_s"],
        "phase_ms_p50": {
            p: 1e3 * median(d["phases_s"].get(p, 0.0) for d in done)
            for p in names},
        "step_ms_p50": 1e3 * median(d["step_s"] for d in done),
        "host_ms_p50": 1e3 * median(d["host_s"] for d in done),
        "record_mean": mean, "required": out["required"],
        "kernel_ms_per_dispatch": per * kernel_s,
    }
    run_dir = path.split(os.sep + "trace" + os.sep)[0]
    with open(os.path.join(run_dir, "program_spans.json"), "w") as f:
        json.dump(dict(summary, per_dispatch=done), f, indent=1)
    print(json.dumps({"program_spans": summary}), flush=True)
    return out
