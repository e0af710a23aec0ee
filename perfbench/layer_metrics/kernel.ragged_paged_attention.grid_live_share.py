"""Kernels: of the steps the ragged paged-attention kernel's grid takes
(rounds x rows x block-table width, per layer), the share that has a page to
read: ``100 x sum(attended_pages) / sum(grid_steps)`` over the work records
on the program's ``cbe.dispatch`` spans of the traced window."""

from perfbench import program_trace


def read(obs):
    t = program_trace.for_obs(obs)
    if t is None or not t["record_mean"]["grid_steps"]:
        return None
    mean = t["record_mean"]
    return 100.0 * mean["attended_pages"] / mean["grid_steps"]
