"""Kernels: the selective scan's share of its roofline. The least time the
chip could take for the state traffic the traced window's dispatches
recorded (``state_work.required_work``: each advanced row's state once in
and once out in every Mamba layer at the precision the configuration
states, every scanned token's inputs in and output out, 2 FLOPs a
multiply-add; memory-bound at decode), per dispatch, over the trace time of
``mamba_ragged_scan`` per dispatch."""

from perfbench import metric_math, state_work


def read(obs):
    t = state_work.for_obs(obs)
    if t is None or not obs.trace.dispatches:
        return None
    kernel_s = obs.trace.seconds_of("mamba_ragged_scan")
    if not kernel_s:
        return None
    work = state_work.required_work(t, obs.cell.config)
    least = metric_math.roofline_seconds(work["flops"], work["bytes"],
                                         obs.peaks)["seconds"]
    return 100.0 * (least / t["dispatches"]) / (
        kernel_s / obs.trace.dispatches)
