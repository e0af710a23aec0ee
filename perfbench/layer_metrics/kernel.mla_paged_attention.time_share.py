"""Kernels: device time of the ``mla_paged_attention`` kernel's events (the
latent ragged paged-attention kernel of a model with multi-head latent
attention) over the device's busy time, from the trace."""


def read(obs):
    if obs.trace is None or not obs.trace.busy_s:
        return None
    t = obs.trace.seconds_of("mla_paged_attention")
    return 100.0 * t / obs.trace.busy_s if t else None
