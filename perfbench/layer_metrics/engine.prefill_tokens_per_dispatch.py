"""Engine host loop: prompt tokens fed per dispatch — the mean of
``prefill_tokens`` in the work record on the program's ``cbe.dispatch``
spans of the traced window."""

from perfbench import program_trace


def read(obs):
    t = program_trace.for_obs(obs)
    return None if t is None else t["record_mean"]["prefill_tokens"]
