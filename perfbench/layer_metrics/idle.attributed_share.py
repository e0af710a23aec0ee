"""Engine host loop: of the first chip's idle time in the traced window, the
share that lies inside a span of the program's own (``cbe.*`` or
``paddle_serving.step``) — whether the program's spans cover the gaps."""

from perfbench import program_trace


def read(obs):
    t = program_trace.for_obs(obs)
    if t is None or not t["idle_s"]:
        return None
    return 100.0 * (1.0 - t["idle_outside_program_s"] / t["idle_s"])
