"""Kernels: device time of the ``ragged_paged_attention`` kernel's events
over the device's busy time, from the trace. (Its roofline share needs the
attended pages and packed tokens per dispatch, which only the engine knows:
the ``tracing`` issue's.)"""


def read(obs):
    if obs.trace is None or not obs.trace.busy_s:
        return None
    t = obs.trace.seconds_of("ragged_paged_attention")
    return 100.0 * t / obs.trace.busy_s if t else None
