"""Kernels: device time of the ``moe_grouped_matmul`` kernel's events (the
expert layer's three grouped products) over the device's busy time, from
the trace."""


def read(obs):
    if obs.trace is None or not obs.trace.busy_s:
        return None
    t = obs.trace.seconds_of("moe_grouped_matmul")
    return 100.0 * t / obs.trace.busy_s if t else None
