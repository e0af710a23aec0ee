"""Scheduler: the work the prefix index's walks were asked for, in blocks of
one page a dispatch: the summed ``blocks`` (radix nodes matched) of the
program's ``paddle_serving.prefix_peek`` and ``prefix_lookup`` spans plus
the summed ``tokens`` of its ``prefix_insert`` spans over the page size its
``cbe.dispatch`` records state, over the traced window's dispatches
(``perfbench/host_spans.py``). Beside ``prefix.walk_ms_per_dispatch`` it
gives the ms a block, so that a faster walk is told from fewer walks. Left
out where the configuration serves without a prefix cache."""

from perfbench import host_spans


def read(obs):
    t = host_spans.for_obs(obs)
    if (t is None or t["blocks_walked"] is None
            or not host_spans.uses_prefix_cache(obs)):
        return None
    return t["blocks_walked"] / t["dispatches"]
