"""Kernels: the grouped expert product's share of its roofline. The least
time the chip could take for the routing the traced window's dispatches
recorded (``expert_work.required_work``: the weights of the experts hit
once, every assignment's rows in and out, 2 FLOPs a multiply-add;
memory-bound at serving shapes), per dispatch, over the trace time of
``moe_grouped_matmul`` per dispatch."""

from perfbench import expert_work, metric_math


def read(obs):
    t = expert_work.for_obs(obs)
    if t is None or not obs.trace.dispatches:
        return None
    kernel_s = obs.trace.seconds_of("moe_grouped_matmul")
    if not kernel_s:
        return None
    work = expert_work.required_work(t, obs.cell.config)
    least = metric_math.roofline_seconds(work["flops"], work["bytes"],
                                         obs.peaks)["seconds"]
    return 100.0 * (least / t["dispatches"]) / (
        kernel_s / obs.trace.dispatches)
