"""Kernels: the ragged paged-attention kernel's share of its roofline. The
least time the chip could take for the mean work record of the traced
window's dispatches (``program_trace.required_work``: attended K/V pages
read once, q in and o out, QK^T and PV over the causal pairs; memory-bound
at serving shapes), over the trace time of ``ragged_paged_attention`` per
dispatch."""

from perfbench import program_trace


def read(obs):
    t = program_trace.for_obs(obs)
    if t is None or not obs.trace.dispatches:
        return None
    kernel_s = obs.trace.seconds_of("ragged_paged_attention")
    if not kernel_s:
        return None
    return 100.0 * t["required"]["seconds"] / (kernel_s / obs.trace.dispatches)
