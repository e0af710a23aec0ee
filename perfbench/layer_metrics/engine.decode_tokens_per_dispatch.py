"""Engine host loop: decode rounds planned per dispatch (one token each) —
the mean of ``decode_tokens`` in the work record on the program's
``cbe.dispatch`` spans of the traced window."""

from perfbench import program_trace


def read(obs):
    t = program_trace.for_obs(obs)
    return None if t is None else t["record_mean"]["decode_tokens"]
