"""Engine host loop: median wall time of ``sched.step`` (plan, dispatch,
the one fence, unpack) from the benchmark's span around it."""

from perfbench import metric_math


def read(obs):
    d = obs.spans.durations("bench.sched_step", obs.window)
    return metric_math.median(d) * 1e3 if d else None
