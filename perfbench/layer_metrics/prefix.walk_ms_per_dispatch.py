"""Scheduler: the prefix index on the host, whichever phase it ran in: the
summed duration of the program's ``paddle_serving.prefix_peek`` (the
scheduler's sizing walk), ``prefix_lookup`` (the engine's admission walk),
``prefix_insert`` (a retired sequence's conversion and insertion) and
``prefix_evict`` spans in the traced window over the window's dispatches
(``perfbench/host_spans.py``). Left out where the configuration serves
without a prefix cache, and on a program that writes no such span."""

from perfbench import host_spans


def read(obs):
    t = host_spans.for_obs(obs)
    if t is None or not host_spans.uses_prefix_cache(obs):
        return None
    return 1e3 * t["walk_s"] / t["dispatches"]
