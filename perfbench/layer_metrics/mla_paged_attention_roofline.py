"""Kernels: the latent ragged paged-attention kernel's share of its
roofline. The least time the chip could take for the mean work record of
the traced window's dispatches (``latent_work.required_work``: every
attended page's latent entries read once, q in and o out at the model's
head sizes, the expanded form's FLOPs over the causal pairs; memory-bound
at decode shapes), over the trace time of ``mla_paged_attention`` per
dispatch."""

from perfbench import latent_work, metric_math, program_trace


def read(obs):
    t = program_trace.for_obs(obs)
    if t is None or not obs.trace.dispatches:
        return None
    kernel_s = obs.trace.seconds_of("mla_paged_attention")
    if not kernel_s:
        return None
    work = latent_work.required_work(
        dict(t["record_mean"], page_size=t["page_size"]), obs.cell.config)
    least = metric_math.roofline_seconds(work["flops"], work["bytes"],
                                         obs.peaks)["seconds"]
    return 100.0 * least / (kernel_s / obs.trace.dispatches)
