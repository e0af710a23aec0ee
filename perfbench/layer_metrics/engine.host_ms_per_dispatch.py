"""Engine host loop: median over the traced window's dispatches of the
program's ``cbe.step`` span less its ``cbe.fence`` span — the host's own
work in one engine step (admit, plan, upload, enqueue, unpack, audit), the
wait for the device taken out."""

from perfbench import metric_math, program_trace


def read(obs):
    t = program_trace.for_obs(obs)
    if t is None:
        return None
    return 1e3 * metric_math.median(d["host_s"] for d in t["dispatches"])
