"""Scheduler: time to first token, median over the requests whose origin
lies in the window — first token's arrival minus the time the request was
DUE (open loop) or sent (batch). A request still without a first token when
the window closes counts as slower than any other; failed requests are in no
median. From the benchmark's own per-request record.

A per-layer reading, not an end-to-end metric yet: at ~35 requests a window
and ~1 s dispatches it moves by 2-10% between runs of one schedule (PERF.md,
findings of PR 22)."""

from perfbench import metric_math


def read(obs, q=50.0):
    values = [r.first - r.origin for r in obs.requests
              if obs.in_window(r.origin) and r.outcome in (None, "ok")]
    if not values:
        return None
    value = metric_math.percentile(values, q)
    return None if value == float("inf") else value * 1e3
