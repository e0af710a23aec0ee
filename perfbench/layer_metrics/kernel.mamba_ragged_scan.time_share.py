"""Kernels: device time of the ``mamba_ragged_scan`` kernel's events (the
selective scan of a model with Mamba layers, over the packed token axis and
each row's state) over the device's busy time, from the trace."""


def read(obs):
    if obs.trace is None or not obs.trace.busy_s:
        return None
    t = obs.trace.seconds_of("mamba_ragged_scan")
    return 100.0 * t / obs.trace.busy_s if t else None
