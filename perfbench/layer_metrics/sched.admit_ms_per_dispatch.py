"""Scheduler: what the scheduler's own admission costs a round: the summed
duration of the program's ``paddle_serving.admit`` spans in the traced
window (``ServingScheduler._admit`` over a queue that holds something: its
sizing walk of the prefix index and any eviction included) over the
window's dispatches (``perfbench/host_spans.py``). 0.0 where the program
writes the span and the window held none; left out on a program that does
not write it."""

from perfbench import host_spans


def read(obs):
    t = host_spans.for_obs(obs)
    if t is None:
        return None
    return 1e3 * t["by_name"][host_spans.ADMIT]["s"] / t["dispatches"]
